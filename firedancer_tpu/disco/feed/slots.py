"""Staging-slot arenas for the fd_feed ingest runtime.

A Slot is one preallocated host arena in the exact layout
native/verify_drain.cc stages and ops.verify.verify_batch /
ballet.ed25519.native.verify_arrays consume: row-major msgs/lens/sigs/
pubs plus the packed payload sidecar (offs/lens/sigs/lanes/tsorig/tspub)
the completion path publishes from. Nothing is allocated per frag — the
stager writes into the slot via one C call per drain round.

The SlotPool is the handoff between the stager thread (fills slots) and
the dispatch thread (ships READY slots to the device): a bounded ring of
slots in FREE -> FILLING -> READY -> (dispatched) -> FREE rotation, the
software analog of wiredancer's DMA slot table (wd_f1.c:327-408 — the
request queue the FPGA drains while the host stages the next request).
Backpressure is structural: when every slot is FILLING/READY the stager
blocks in acquire() (counted in slot_stall / stall_ns) until the
dispatcher releases one, which in turn only happens as device batches
retire — so host-side staging can never run unboundedly ahead of the
device.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

FREE = 0
FILLING = 1
READY = 2

_MTU = 1232  # FD_TPU_MTU (kept literal: tiles.py imports from here)


class Slot:
    """One staging arena + the per-txn bookkeeping the completion path
    needs. Arrays are preallocated once and reused for the pool's whole
    lifetime; reset() only rewinds the cursors (rows are overwritten and
    row tails zeroed by the native drain, so stale bytes cannot leak
    between incarnations of the slot)."""

    __slots__ = (
        "idx", "state", "msgs", "lens", "sigs", "pubs", "pay", "offs",
        "plens", "psigs", "tlanes", "tsorigs", "tspubs", "hashes",
        "ha_mask", "n_txn", "n_lane", "pay_fill", "t_first", "t_commit",
        "drain_end", "flush_verdict", "rung", "rung_depth",
    )

    def __init__(self, idx: int, batch: int, max_msg_len: int):
        self.idx = idx
        self.state = FREE
        self.msgs = np.zeros((batch, max_msg_len), np.uint8)
        self.lens = np.zeros(batch, np.uint32)
        self.sigs = np.zeros((batch, 64), np.uint8)
        self.pubs = np.zeros((batch, 32), np.uint8)
        self.pay = np.zeros(batch * _MTU, np.uint8)
        # Per-txn sidecars, accumulated ACROSS drain rounds at txn index
        # (offs are converted to absolute pay offsets as rounds land):
        # the completion path publishes straight out of these arrays via
        # fd_frag_publish_bulk — no per-txn Python objects anywhere.
        self.offs = np.zeros(batch, np.uint32)
        self.plens = np.zeros(batch, np.uint32)
        self.psigs = np.zeros(batch, np.uint64)
        self.tlanes = np.zeros(batch, np.uint32)
        self.tsorigs = np.zeros(batch, np.uint32)
        self.tspubs = np.zeros(batch, np.uint32)
        self.hashes = np.zeros(batch, np.uint64)   # FNV HA tags (drain)
        # True = HA-duplicate at staging time: lanes verify (they are
        # already staged) but the result must not publish.
        self.ha_mask = np.zeros(batch, np.bool_)
        self.n_txn = 0
        self.n_lane = 0
        self.pay_fill = 0
        self.t_first = 0       # deadline anchor (tickcount ns)
        self.t_commit = 0      # FILLING -> READY (tickcount ns)
        self.drain_end = 0     # in-ring seq after the last drain round
                               # (the batch's ack target once verified)
        # Why this slot shipped ("full" / "capacity" / "deadline" /
        # "starved" / "ring_starved" / "halt") — stamped at commit so
        # fd_xray's exemplar batch context can attribute the flush
        # decision per dispatched batch.
        self.flush_verdict = "full"
        # fd_engine rung context: the scheduler's target B for this
        # batch and the queue depth it decided from (0/0 = scheduler
        # off) — stamped by the stager, read by the dispatcher's
        # exemplar capture.
        self.rung = 0
        self.rung_depth = 0

    def reset(self) -> None:
        self.ha_mask[: max(self.n_txn, 1)] = False
        self.n_txn = 0
        self.n_lane = 0
        self.pay_fill = 0
        self.t_first = 0
        self.t_commit = 0
        self.drain_end = 0
        self.flush_verdict = "full"
        self.rung = 0
        self.rung_depth = 0


class SlotPool:
    """Bounded FREE/FILLING/READY rotation between one stager thread and
    one dispatcher thread. READY order is commit order (FIFO), so device
    batches retire in the order their txns were drained — the property
    VerifyTile's ack cursor relies on."""

    def __init__(self, n_slots: int, batch: int, max_msg_len: int):
        if n_slots < 2:
            # 1 slot cannot overlap fill with dispatch — the whole point
            # of the pool; a typo'd FD_FEED_SLOTS=1 must not silently
            # serialize the feeder.
            raise ValueError(f"SlotPool needs >= 2 slots, got {n_slots}")
        self.batch = batch
        self.slots: List[Slot] = [
            Slot(i, batch, max_msg_len) for i in range(n_slots)
        ]
        self._free: List[Slot] = list(self.slots)
        self._ready: List[Slot] = []
        self._lock = threading.Lock()
        self._free_cv = threading.Condition(self._lock)
        # Feeder stats (read by VerifyTile into verify_stats/cnc diag).
        # Batch/lane/fill accounting lives on the TILE (stat_batches /
        # stat_lanes, counted at dispatch) — one authority, not two.
        self.slot_stall = 0          # acquires that had to wait
        self.stall_ns = 0            # total time the stager spent waiting

    # -- stager side -----------------------------------------------------

    def acquire(self, timeout_s: float) -> Optional[Slot]:
        """FREE -> FILLING. Blocks up to timeout_s when no slot is free
        (counted once per wait in slot_stall, wall time in stall_ns) so
        the stager stays interruptible for HALT."""
        import time

        with self._free_cv:
            if not self._free:
                self.slot_stall += 1
                t0 = time.perf_counter_ns()
                self._free_cv.wait(timeout_s)
                self.stall_ns += time.perf_counter_ns() - t0
            if not self._free:
                return None
            slot = self._free.pop(0)
            slot.state = FILLING
            return slot

    def commit(self, slot: Slot) -> None:
        """FILLING -> READY (FIFO): hand a filled slot to the dispatcher."""
        with self._lock:
            if slot.state != FILLING:
                raise ValueError(
                    f"commit of slot {slot.idx} in state {slot.state} "
                    "(want FILLING) — slot lifecycle violated"
                )
            slot.state = READY
            self._ready.append(slot)

    # -- dispatcher side -------------------------------------------------

    def pop_ready(self) -> Optional[Slot]:
        with self._lock:
            if not self._ready:
                return None
            return self._ready.pop(0)

    def release(self, slot: Slot) -> None:
        """Dispatched slot back to FREE (arenas reusable)."""
        slot.reset()
        with self._free_cv:
            slot.state = FREE
            self._free.append(slot)
            self._free_cv.notify()

    # -- shared observers ------------------------------------------------

    def ready_cnt(self) -> int:
        with self._lock:
            return len(self._ready)

    def outstanding(self) -> int:
        """Slots not currently FREE (FILLING + READY + dispatched). At
        post-halt quiescence this must be 0 — the chaos smoke's "no
        slot is permanently lost from the pool" gate: every fault path
        (quarantine, failover, stager restart) must return its slot."""
        with self._lock:
            return len(self.slots) - len(self._free)

    def idle(self) -> bool:
        """True when no slot holds staged-but-undispatched txns (no
        READY backlog, and the stager's FILLING slot — if any — is
        empty). A popped-but-undispatched slot keeps its n_txn until the
        dispatcher has recorded the batch in flight, so there is no
        window where staged work is invisible to both this check and
        the tile's _inflight list. Quiescence checks read this from
        another thread."""
        with self._lock:
            if self._ready:
                return False
            return all(s.n_txn == 0 for s in self.slots)

