"""disco tiles — long-running actors over tango rings.

Role parity with the reference's disco/frank layer: the generic tile
run-loop blueprint (housekeeping / backpressure / frag drain, modeled on
/root/reference/src/disco/mux/fd_mux.h:56-175 and
app/frank/fd_frank_verify.c:140-207), plus the concrete tiles of the hot
path: replay (pcap/synthetic source, disco/replay/), verify (sigverify —
the TPU offload point, app/frank/load/fd_frank_verify_synth_load.c),
dedup (tcache on meta sig, disco/dedup/), pack (account-lock scheduling
into bank lanes, app/frank/fd_frank_pack.c), and a sink (bank stub).

Tiles here are Python threads/processes joined to the same native
shared-memory rings (native/tango.cc via tango.rings); the hot math is
batched onto the device inside VerifyTile. Frag payloads on the
replay->verify link are whole Solana transaction wire bytes; the verify
tile parses in-tile exactly like the reference quic tile does
(fd_quic_tile.c:492 fd_txn_parse into the dcache slot).
"""

from __future__ import annotations

import os
import time
from hashlib import sha256 as _sha256
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from firedancer_tpu import flags
from firedancer_tpu.ballet import ed25519 as oracle
from firedancer_tpu.ballet.txn import MAX_SIG_CNT, TxnParseError, parse_txn
from firedancer_tpu.disco import chaos, flight, xray
from firedancer_tpu.disco.feed.policy import (
    FLUSH_DEADLINE,
    FLUSH_FULL,
    FLUSH_STARVED,
    AdaptiveFlush,
    CircuitBreaker,
    respawn_backoff_s,
)
from firedancer_tpu.tango import tempo
from firedancer_tpu.tango.fctl import make_fctl_for_fseqs
from firedancer_tpu.tango.rings import (
    CNC_BOOT,
    CNC_HALT,
    CNC_RUN,
    CTL_ERR,
    DIAG_FILT_CNT,
    DIAG_FILT_SZ,
    DIAG_OVRNR_CNT,
    DIAG_PUB_CNT,
    DIAG_PUB_SZ,
    DIAG_SLOW_CNT,
    POLL_EMPTY,
    POLL_FRAG,
    POLL_OVERRUN,
    Cnc,
    DCache,
    FSeq,
    Frag,
    MCache,
    Workspace,
)
from firedancer_tpu.tango.tcache import TCache
from firedancer_tpu.utils.rng import Rng

# cnc diag slots (frank/fd_frank.h:20-36 ABI analog)
CNC_DIAG_IN_BACKP = 0
CNC_DIAG_BACKP_CNT = 1
CNC_DIAG_HA_FILT_CNT = 2
CNC_DIAG_HA_FILT_SZ = 3
CNC_DIAG_SV_FILT_CNT = 4
CNC_DIAG_SV_FILT_SZ = 5
# Gauge (not a counter): consumed-but-unverified frags a verify tile is
# holding its ack cursor back for. Supervisors/tests read it to know,
# deterministically, when staged device work exists (the crash window
# the held-back fseq protects).
CNC_DIAG_UNACKED = 6
# Fault-injection hold entries (FD_VERIFY_HOLD_AFTER_DISPATCH_S): the
# deterministic kill trigger for crash tests — UNACKED counts txns
# while batches fill by signature LANES, so a "staged >= batch" gauge
# test can miss the hold window on multisig-bearing corpora.
CNC_DIAG_HOLDS = 7
# fd_feed feeder gauges (verify tiles; cnc diag slots 8..15). Counters
# mirror the in-process verify_stats so monitors/supervisors see the
# feeder across process boundaries: batches dispatched, lanes in them
# (fill_ratio = lanes / (batches * batch)), deadline vs starved partial
# flushes, stager slot-acquire stalls, and the dispatcher's
# device-idle-estimate ns.
CNC_DIAG_FEED_BATCHES = 8
CNC_DIAG_FEED_LANES = 9
CNC_DIAG_FEED_DEADLINE = 10
CNC_DIAG_FEED_STARVED = 11
CNC_DIAG_FEED_SLOT_STALL = 12
CNC_DIAG_FEED_IDLE_NS = 13
# Supervisor respawn accounting (written by the SUPERVISOR, read by
# monitor.py): crash-only restarts of this tile, and the current
# respawn backoff in ms (a gauge, delta-published). 16-slot ABI only.
CNC_DIAG_RESTARTS = 14
CNC_DIAG_BACKOFF_MS = 15

CTL_SOM_EOM = 3

# Cap on the stager-thread restart backoff (thread-scale supervision: a
# stager outage past ~2 s blows the flush deadline regardless, so the
# exponential decay stops here; the process supervisor's analogous cap
# is flag-tunable via FD_SUP_BACKOFF_MAX_MS).
_STAGER_BACKOFF_CAP_S = 2.0

FD_TPU_MTU = 1232  # disco/quic/fd_quic.h:46-47

_U64 = (1 << 64) - 1


def meta_sig(payload: bytes) -> int:
    """Frag meta sig: first 8 bytes of the txn's first Ed25519 signature
    (the dedup identity) — the layout every publisher and the dedup tile
    must agree on (byte 0 is the compact signature count)."""
    return int.from_bytes(payload[1:9], "little") if len(payload) > 8 else 0


@dataclass
class LinkNames:
    """Workspace object names for one mcache/dcache/fseq link."""

    mcache: str
    dcache: str
    fseq: str


class InLink:
    """Consumer side of a link: poll frags in seq order, detect overruns."""

    def __init__(self, wksp: Workspace, names: LinkNames,
                 edge: Optional[str] = None):
        self.mcache = MCache(wksp, names.mcache)
        self.dcache = DCache(wksp, names.dcache)
        self.fseq = FSeq(wksp, names.fseq)
        # Resume from the published consumer progress: 0 on a fresh
        # fseq, the last-acknowledged seq after a crash-restart (the
        # supervisor's crash-only recovery relies on this).
        self.seq = self.fseq.query()
        # fd_xray consumer-side queue telemetry for this edge (sampled
        # dwell = producer tspub -> drain, depth, consumer idle): None
        # when the link has no edge name (direct test construction) or
        # FD_XRAY=0 — hot paths gate on the handle's None-ness.
        self.edge = edge
        self.xq: Optional[xray.EdgeRx] = (
            xray.edge_rx(wksp, edge) if edge else None)
        self.xq_cnt = 0
        # Clamped to >= 1: the stride is a modulus on the hot drain
        # path, and a 0 from the environment must tighten sampling to
        # every frag, never divide-by-zero a consuming tile.
        self.xq_every = (max(1, flags.get_int("FD_XRAY_QUEUE_SAMPLE"))
                         if self.xq is not None else 0)

    def dwell_sample(self, tspub: int, now: int = 0) -> None:
        """Sampled queue-dwell observe (every FD_XRAY_QUEUE_SAMPLE'th
        drained frag): the queue-wait half of the xray waterfall. The
        stride check runs FIRST so non-sampled frags cost one counter
        increment — callers without a hoisted clock pass now=0 and the
        tick is read only on the sampled Nth frag."""
        self.xq_cnt += 1
        if tspub and self.xq_cnt % self.xq_every == 0:
            if not now:
                now = tempo.tickcount() & 0xFFFFFFFF
            self.xq.observe_dwell((now - tspub) & 0xFFFFFFFF)

    def poll(self):
        """Returns (status, frag, payload_bytes_or_None)."""
        r, f = self.mcache.poll(self.seq)
        if r == POLL_EMPTY:
            return r, None, None
        if r == POLL_OVERRUN:
            # Jump forward to the oldest frag still in the ring; only the
            # frags actually skipped over count as lost.
            new_seq = self.mcache.seq_next()
            new_pos = max(new_seq - self.mcache.depth + 1, self.seq + 1)
            self.fseq.diag_add(DIAG_OVRNR_CNT, new_pos - self.seq)
            self.seq = new_pos
            return r, None, None
        payload = self.dcache.read(f.chunk, f.sz)
        return r, f, payload

    def advance(self):
        self.seq += 1

    def housekeep(self):
        self.fseq.update(self.seq)


class OutLink:
    """Producer side: dcache chunk walk + mcache publish + credit control."""

    def __init__(
        self,
        wksp: Workspace,
        names: LinkNames,
        mtu: int = FD_TPU_MTU,
        reliable_fseqs: Optional[Sequence[FSeq]] = None,
        edge: Optional[str] = None,
    ):
        self.mcache = MCache(wksp, names.mcache)
        self.dcache = DCache(wksp, names.dcache)
        self.mtu = mtu
        self.seq = self.mcache.seq_next()
        # Restart-safe chunk resume: a respawned producer must continue
        # the dcache walk where the dead incarnation stopped, or it
        # would overwrite the payload bytes of still-unconsumed frags
        # (whose mcache entries remain valid — silent corruption, not an
        # overrun). The last published frag's own meta records where the
        # walk was.
        self.chunk = 0
        if self.seq > 0:
            r, last = self.mcache.poll(self.seq - 1)
            if r == POLL_FRAG and last is not None:
                self.chunk = self.dcache.next_chunk(
                    last.chunk, last.sz, mtu
                )
        self.fctl = make_fctl_for_fseqs(
            self.mcache.depth, reliable_fseqs or [], cr_burst=1
        )
        self.cr_avail = 0
        # Per-stage latency reservoir (docs/LATENCY.md): tsorig -> tspub
        # of every frag published on THIS link, i.e. source-stamp to
        # this-stage-complete. publish() already computes both stamps,
        # so the sample is one subtraction on a path that costs ~40 us —
        # bounded reservoir (algorithm R) keeps long soaks at constant
        # memory. The replay artifacts report p50/p99 per stage.
        self.lat_ns: list = []
        self.lat_cap = 16384
        self._lat_seen = 0
        self._lat_rng = Rng(seq=0x1a7)
        # fd_flight trace span: this link's ALWAYS-ON log2 latency
        # histogram (full population, unlike the sampled reservoir) in
        # the shared registry. None when the link has no edge name
        # (direct test construction) or spans are hatched off.
        self.span: Optional[flight.EdgeHist] = None
        if (edge and flight.enabled()
                and flags.get_bool("FD_TRACE_SPANS")):
            self.span = flight.edge_hist(wksp, edge)
        # fd_xray producer-side handles: exemplar sampler (head/tail
        # capture riding the same publish-latency computation) and the
        # credit-stall/credits tx row. Both None when xray is off.
        self.xspan: Optional[xray.SpanCtx] = (
            xray.span_ctx(edge) if edge else None)
        self.xq_tx: Optional[xray.EdgeTx] = (
            xray.edge_tx(wksp, edge) if edge else None)

    def _reservoir_insert(self, lat: int) -> None:
        """Algorithm-R insert: every publish-latency sample in the
        link's lifetime has equal selection probability, so a long
        soak's percentiles reflect the whole run, not the warmup
        window. ONE body, shared by both sampling entry points."""
        self._lat_seen += 1
        if len(self.lat_ns) < self.lat_cap:
            self.lat_ns.append(lat)
        else:
            j = self._lat_rng.roll(self._lat_seen)
            if j < self.lat_cap:
                self.lat_ns[j] = lat

    def lat_sample(self, lat: int, tsorig: int = 0, tspub: int = 0) -> None:
        """Per-frag sample: always-on span histogram + reservoir +
        (when xray is armed and the caller passed the stamps) the
        deterministic exemplar head/tail capture."""
        if self.span is not None:
            self.span.observe(lat)
        if self.xspan is not None and tsorig:
            self.xspan.observe(tsorig, tspub, lat)
        self._reservoir_insert(lat)

    def lat_sample_many(self, lats, tsorigs=None) -> None:
        """Bulk-completion variant: one vectorized histogram update for
        the whole batch, reservoir inserts per sample as before; the
        exemplar capture is one vectorized mask over the trace ids."""
        if self.span is not None:
            self.span.observe_many(lats)
        if self.xspan is not None and tsorigs is not None:
            self.xspan.observe_many(tsorigs, lats)
        for lat in lats.tolist():
            self._reservoir_insert(lat)

    def housekeep(self):
        self.cr_avail = self.fctl.tx_cr_update(self.cr_avail, self.seq)

    def can_publish(self) -> bool:
        if self.cr_avail > 0:
            return True
        self.housekeep()
        return self.cr_avail > 0

    def publish(self, payload: bytes, sig: int, tsorig: int = 0,
                ctl: int = CTL_SOM_EOM) -> None:
        """Copy payload into the dcache and publish its frag meta.
        `ctl` defaults to SOM|EOM; the quarantine/audit paths publish
        offending txns with CTL_ERR set so the fault is visible on the
        ring instead of silently vanishing."""
        if len(payload) > self.mtu:
            # Not an assert: python -O would strip it, and an oversized
            # payload published past the MTU tramples the next frag's
            # dcache chunk (shared-memory corruption, not a local bug).
            raise ValueError(
                f"payload of {len(payload)} bytes exceeds the link MTU "
                f"({self.mtu}): refusing to publish past the dcache "
                "chunk walk"
            )
        self.dcache.write(self.chunk, payload)
        tspub = tempo.tickcount() & 0xFFFFFFFF
        if tsorig:
            self.lat_sample((tspub - tsorig) & 0xFFFFFFFF,
                            tsorig=tsorig, tspub=tspub)
        self.mcache.publish(
            self.seq, sig, self.chunk, len(payload), ctl, tsorig, tspub
        )
        self.chunk = self.dcache.next_chunk(self.chunk, len(payload), self.mtu)
        self.seq += 1
        self.cr_avail = max(0, self.cr_avail - 1)


class Tile:
    """Generic run loop: housekeeping on jittered intervals + frag drain.

    Subclasses implement on_frag(frag, payload) and optionally on_idle().
    """

    name = "tile"

    def __init__(
        self,
        wksp: Workspace,
        cnc_name: str,
        in_link: Optional[InLink] = None,
        out_link: Optional[OutLink] = None,
        in_links: Optional[List[InLink]] = None,
        lazy_ns: Optional[int] = None,
        seed: int = 0,
    ):
        if in_link is not None and in_links is not None:
            raise ValueError("pass in_link or in_links, not both")
        self.wksp = wksp
        self.cnc_name = cnc_name  # stable tile identity (chaos hb ordinals)
        # fd_flight identity: the cnc name minus its ".cnc" suffix is
        # the registry row label AND the flight-recorder name.
        self.flight_label = (
            cnc_name[:-4] if cnc_name.endswith(".cnc") else cnc_name
        )
        self.flightrec = flight.recorder(self.flight_label)
        self.cnc = Cnc(wksp, cnc_name)
        # Multi-input tiles (the mux pattern, mux/fd_mux.h:56-175) poll
        # every in-link round-robin; in_link stays as the first for the
        # common single-input case.
        self.in_links: List[InLink] = (
            list(in_links) if in_links is not None
            else ([in_link] if in_link is not None else [])
        )
        self.in_link = self.in_links[0] if self.in_links else None
        self.in_cur = self.in_link  # link of the frag being processed
        self.out_link = out_link
        self.rng = Rng(seq=seed)
        depth = self.in_links[0].mcache.depth if self.in_links else (
            out_link.mcache.depth if out_link else 128
        )
        lazy = lazy_ns if lazy_ns is not None else tempo.lazy_default(depth)
        self._async_min = tempo.async_min(lazy)
        self._last_in_backp = 0
        self.halted = False
        # Optional core pin (fd_tile's dedicated-core model, fd_tile.h:13;
        # set by the pipeline from the layout.tile_cpus config). Python
        # threads share the GIL, but pinning still removes migration
        # jitter from the hot poll loops and matches the reference's
        # affinity contract for the native drain path.
        self.cpu_idx: Optional[int] = None
        # fd_xray consumer-idle accounting: ns this tile spent in its
        # idle naps, accumulated locally and flushed to the in-edge rx
        # row at housekeep (single-writer: this tile's thread).
        self._xq_idle_ns = 0
        self._xq_on = any(il.xq is not None for il in self.in_links)

    # -- overridables ----------------------------------------------------

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        raise NotImplementedError

    def on_idle(self) -> None:
        """Called when the input is empty (flush partial batches etc.)."""

    def on_housekeep(self) -> None:
        """Extra per-tile housekeeping."""

    def done(self) -> bool:
        """Source tiles return True when exhausted."""
        return False

    # Bulk-drain batch per in-link per round (native fd_frag_drain): one
    # C call replaces ~18 us of per-frag Python ring hop. Bounded so the
    # crash-replay window (frags consumed but not yet fseq-published)
    # stays small — the pipeline is crash-only and dedup absorbs
    # replays, exactly as with the 1-frag window of the Python poll.
    BULK_FRAGS = 64

    def _bulk_state(self, il):
        st = getattr(il, "_bulk", None)
        if st is None:
            import ctypes as _ct

            from firedancer_tpu.tango.rings import lib as _rings_lib

            n = self.BULK_FRAGS
            # The staging buffer is sized so ANY frag fits it alone
            # (frag sz is u16, max 65535 < n * FD_TPU_MTU) and the
            # per-frag cap passed to the C side is the u16 ceiling —
            # the drain must never truncate a payload (it defers frags
            # that don't fit the REMAINING room instead).
            st = {
                "lib": _rings_lib(),
                "ct": _ct,
                "pay": np.zeros(n * FD_TPU_MTU, np.uint8),
                "offs": np.zeros(n, np.uint32),
                "lens": np.zeros(n, np.uint32),
                "sigs": np.zeros(n, np.uint64),
                "ts": np.zeros(n, np.uint32),
                "seqs": np.zeros(n, np.uint64),
                "ctls": np.zeros(n, np.uint16),
                "tspubs": np.zeros(n, np.uint32),
                "ctr": np.zeros(2, np.uint64),
                "cap": 0xFFFF,
            }
            il._bulk = st
        return st

    def poll_inputs(self):
        """One drain round over the in-links. Returns (progressed,
        overrun). Tiles with their own native drain override this."""
        progressed = False
        overrun = False
        for il in self.in_links:
            st = self._bulk_state(il)
            ct = st["ct"]
            seq = ct.c_uint64(il.seq)
            ovr0 = int(st["ctr"][1])
            args = [
                il.mcache._mem, ct.addressof(il.dcache._buf),
                ct.byref(seq), self.BULK_FRAGS, st["cap"],
                st["pay"].ctypes.data, st["pay"].nbytes,
                st["offs"].ctypes.data, st["lens"].ctypes.data,
                st["sigs"].ctypes.data, st["ts"].ctypes.data,
                st["seqs"].ctypes.data, st["ctls"].ctypes.data,
                st["tspubs"].ctypes.data, st["ctr"].ctypes.data,
            ]
            n = st["lib"].fd_frag_drain(*args)
            d_ovr = int(st["ctr"][1]) - ovr0
            if d_ovr:
                il.fseq.diag_add(DIAG_OVRNR_CNT, d_ovr)
                overrun = True
            if n > 0:
                self.in_cur = il
                pay = st["pay"]
                offs, lens = st["offs"], st["lens"]
                sigs, tss, seqs = st["sigs"], st["ts"], st["seqs"]
                ctls, tspubs = st["ctls"], st["tspubs"]
                xq_now = (tempo.tickcount() & 0xFFFFFFFF
                          if il.xq is not None else 0)
                for i in range(n):
                    off = int(offs[i])
                    ln = int(lens[i])
                    # Propagate the producer's ctl word (ADVICE r5 low
                    # #3): a CTL_ERR frag must reach on_frag as an
                    # error frag.
                    ctl = int(ctls[i])
                    tspub = int(tspubs[i])
                    if xq_now:
                        # fd_xray queue-dwell (producer publish -> this
                        # drain), sampled every Nth frag per edge.
                        il.dwell_sample(tspub, xq_now)
                    frag = Frag(seq=int(seqs[i]), sig=int(sigs[i]),
                                chunk=0, sz=ln, ctl=ctl,
                                tsorig=int(tss[i]), tspub=tspub)
                    self.on_frag(frag, pay[off:off + ln].tobytes())
                progressed = True
            # Publish-cursor semantics match the per-frag path: il.seq
            # advances only after the batch is fully processed (housekeep
            # publishes from il.seq, so a crash mid-batch replays it).
            il.seq = seq.value
        return progressed, overrun

    # -- run loop --------------------------------------------------------

    def _housekeep_out(self) -> None:
        """Out-link credit refresh + backpressure diag mirror — shared
        by the base housekeep and overrides that replace only the
        in-link fseq publication (VerifyTile's verified cursor)."""
        if self.out_link:
            self.out_link.housekeep()
            if self.out_link.xq_tx is not None:
                self.out_link.xq_tx.sample_credits(self.out_link.cr_avail)
            # Mirror the fctl backpressure gauge into the cnc diag
            # (IN_BACKP slot, frank/fd_frank.h:20-36 semantics).
            backp = 1 if self.out_link.fctl.in_backpressure else 0
            if backp != self._last_in_backp:
                self.cnc.diag_add(
                    CNC_DIAG_IN_BACKP, (backp - self._last_in_backp) & _U64
                )
                self._last_in_backp = backp

    def _beat(self, now: int) -> None:
        """Publish the cnc heartbeat — unless a chaos hb_stall window is
        open (the supervised wedge detector is the intended observer of
        a stalled heartbeat; healing is the kill + respawn)."""
        c = chaos.active()
        if c is not None and c.hb_stalled(self.cnc_name):
            return
        self.cnc.heartbeat(now)

    def _xq_housekeep(self) -> None:
        """fd_xray queue telemetry at housekeeping rate: sampled ring
        depth per in-edge + the idle-ns flush (both cheap; the depth
        probe is one ns-scale PyDLL call per link). Runs on the tile
        thread — the same thread that drains the in-links — so every
        rx-row write stays single-threaded. VerifyTile's overridden
        housekeep does NOT route here: in feed mode the STAGER thread
        drains (and owns the row — see _stager_drain), and the legacy
        native path books its telemetry at the drain site too."""
        if not self._xq_on:
            return
        first = True
        for il in self.in_links:
            if il.xq is None:
                continue
            il.xq.sample_depth(il.mcache.seq_next() - il.seq)
            if first and self._xq_idle_ns:
                il.xq.add_idle(self._xq_idle_ns)
                self._xq_idle_ns = 0
                first = False

    def housekeep(self, now: int) -> None:
        self._beat(now)
        for il in self.in_links:
            il.housekeep()
        self._xq_housekeep()
        self._housekeep_out()
        self.on_housekeep()

    def run(self, max_ns: int = 30_000_000_000) -> None:
        """Run until HALT signal, done(), or max_ns wall time."""
        if self.cpu_idx is not None and hasattr(os, "sched_setaffinity"):
            # NB Linux inherits the affinity mask into threads created
            # FROM this thread — lazily-spawned pools (XLA's intra-op
            # pool, etc.) must already exist. VerifyTile guarantees this
            # by pre-warming its jit on the constructing (main) thread.
            try:
                os.sched_setaffinity(0, {self.cpu_idx})  # calling thread
            except OSError:
                pass  # affinity is best-effort (cpuset may forbid it)
        try:
            self._run_loop(max_ns)
        except BaseException as e:
            # Postmortem BEFORE re-raising: the flight dump is the
            # record of what the tile was doing when it died (no-op
            # unless FD_FLIGHT_DUMP names a directory), and the xray
            # autopsy bundles the window's exemplars + waterfall +
            # suspects (no-op unless FD_XRAY_DIR names a directory).
            self.flightrec.record("crash", err=repr(e)[:200])
            flight.maybe_dump(f"crash:{self.flight_label}", wksp=self.wksp)
            xray.maybe_autopsy(f"crash:{self.flight_label}",
                               wksp=self.wksp)
            raise
        finally:
            # teardown must happen even if step()/on_frag() raised, or
            # sockets leak and the supervisor spins until its timeout;
            # on_halt() runs first so a failing final housekeep (broken
            # shared state) can't skip the socket teardown
            try:
                self.on_halt()
            finally:
                self.halted = True
                self.flightrec.record("halt")
                try:
                    self.housekeep(tempo.tickcount())
                finally:
                    self.cnc.signal(CNC_BOOT)

    def _run_loop(self, max_ns: int) -> None:
        self.cnc.signal(CNC_RUN)
        start = tempo.tickcount()
        then = start
        idle_spins = 0
        while True:
            now = tempo.tickcount()
            if now >= then:
                self.housekeep(now)
                if self.cnc.signal_query() == CNC_HALT:
                    break
                if now - start > max_ns:
                    break
                then = now + tempo.async_reload(self.rng, self._async_min)
            if self.done():
                if self.cnc.signal_query() == CNC_HALT:
                    break
                time.sleep(50e-6)
                continue
            if not self.in_links:
                self.step()
                continue
            progressed, overrun = self.poll_inputs()
            if progressed or overrun:
                idle_spins = 0
            else:
                self.on_idle()
                idle_spins += 1
                if idle_spins > 64:
                    time.sleep(20e-6)  # FD_SPIN_PAUSE analog
                    if self._xq_on:
                        self._xq_idle_ns += 20_000

    def on_halt(self) -> None:
        """Tile-specific teardown (close sockets etc)."""

    def publish_backp(self, payload: bytes, sig: int, tsorig: int = 0,
                      count_diag: bool = True) -> bool:
        """Publish downstream, spinning through backpressure (counted in
        the cnc BACKP diag) until credits arrive or HALT. Returns False if
        the frag was dropped because HALT arrived first."""
        t_stall = 0
        while not self.out_link.can_publish():
            if self.cnc.signal_query() == CNC_HALT:
                return False
            if not t_stall:
                t_stall = tempo.tickcount()
            self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
            time.sleep(20e-6)
        if t_stall and self.out_link.xq_tx is not None:
            # fd_xray producer credit-stall: the wall time this publish
            # spent blocked on downstream credits (the backpressure
            # half of the waterfall attribution).
            self.out_link.xq_tx.add_stall(tempo.tickcount() - t_stall)
        self.out_link.publish(payload, sig, tsorig=tsorig)
        if count_diag and self.in_cur is not None:
            self.in_cur.fseq.diag_add(DIAG_PUB_CNT, 1)
            self.in_cur.fseq.diag_add(DIAG_PUB_SZ, len(payload))
        return True

    def step(self) -> None:
        """Source tiles (no in_link) override or rely on done()."""
        time.sleep(50e-6)


class MuxTile(Tile):
    """N-in -> 1-out frag multiplexer (disco/mux/fd_mux.c analog): forwards
    every input frag downstream in arrival order, preserving sig/tsorig.
    The generic multi-input run loop in Tile *is* the mux blueprint; this
    tile is the identity instance of it."""

    name = "mux"

    def __init__(self, wksp, cnc_name, in_links: List[InLink], out_link, **kw):
        super().__init__(wksp, cnc_name, in_links=in_links, out_link=out_link,
                         **kw)

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        self.publish_backp(payload, frag.sig, tsorig=frag.tsorig)


class ReplayTile(Tile):
    """Source: publishes a list of payloads downstream with flow control
    (disco/replay/fd_replay.c analog; feed it utils.pcap.read_all(path)).
    With several out_links (one per verify lane) payloads round-robin
    across lanes — the data-parallel ingest fan-out the reference gets
    from N flow-steered quic+verify tile pairs (config verify_tile_count,
    configure/frank.c:215-224)."""

    name = "replay"

    def __init__(self, wksp, cnc_name, out_link=None, payloads: List[bytes] = (),
                 out_links: Optional[List[OutLink]] = None, **kw):
        if (out_link is None) == (out_links is None):
            raise ValueError("pass exactly one of out_link / out_links")
        self.out_links = list(out_links) if out_links else [out_link]
        super().__init__(wksp, cnc_name, out_link=self.out_links[0], **kw)
        self.payloads = payloads
        self.pos = 0
        self.pub_cnt = 0
        self.pub_sz = 0

    def done(self) -> bool:
        return self.pos >= len(self.payloads)

    def housekeep(self, now: int) -> None:
        super().housekeep(now)
        for ol in self.out_links[1:]:
            ol.housekeep()

    def step(self) -> None:
        lane = self.out_links[self.pos % len(self.out_links)]
        c = chaos.active()
        if c is not None and c.source_starved():
            # Injected credit starvation: behave exactly like real
            # backpressure (count + back off) until the window closes.
            self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
            if lane.xq_tx is not None:
                lane.xq_tx.add_stall(20_000)
            time.sleep(20e-6)
            return
        if not lane.can_publish():
            self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
            if lane.xq_tx is not None:
                # fd_xray: source-side credit stall (one 20 us backoff
                # per refused attempt) — a credit_starve chaos window
                # shows up as stall_ns on the replay_verify edge.
                lane.xq_tx.add_stall(20_000)
            time.sleep(20e-6)
            return
        if c is not None:
            # Ring-level injection keyed to the upcoming payload ordinal
            # (1-based): may publish a CTL_ERR frag ahead of it. Re-check
            # credits afterward — the err frag spent one.
            c.source_inject(lane, self.pos + 1)
            if not lane.can_publish():
                return
        payload = self.payloads[self.pos]
        lane.publish(payload, meta_sig(payload),
                     tsorig=tempo.tickcount() & 0xFFFFFFFF)
        self.pos += 1
        self.pub_cnt += 1
        self.pub_sz += len(payload)


def _txn_batch_arrays(items, max_len: int):
    """Pack (sig, pub, msg) tuples into padded arrays for verify_batch."""
    n = len(items)
    msgs = np.zeros((n, max_len), np.uint8)
    lens = np.zeros(n, np.int32)
    sigs = np.zeros((n, 64), np.uint8)
    pubs = np.zeros((n, 32), np.uint8)
    for i, (sig, pub, msg) in enumerate(items):
        m = np.frombuffer(msg, np.uint8)[:max_len]
        msgs[i, : len(m)] = m
        lens[i] = len(m)
        sigs[i] = np.frombuffer(sig, np.uint8)
        pubs[i] = np.frombuffer(pub, np.uint8)
    return msgs, lens, sigs, pubs


@dataclass
class _InflightBatch:
    """One dispatched device batch awaiting completion (the software analog
    of a wiredancer DMA slot, wd_f1.c:327-408: request pushed async, result
    later completed into the consumer mcache keyed by seq)."""

    out: object                    # jax.Array of statuses, dispatched async
    todo: list                     # [(payload, n_items, tsorig)] whole txns
    oversize: list                 # per-lane True if msg exceeded staging
    t_dispatch: int                # tickcount at dispatch (diag)
    # fd_feed cpu path: the staging slot the verify executor is still
    # reading from; released back to the pool when the batch retires.
    slot: object = None
    # True when the batch went to the PRIMARY verify lane (device, or
    # the feed cpu executor): its outcome feeds the failover circuit
    # breaker. CPU-failover and quarantine re-verifies set False.
    device: bool = False
    # fd_engine accounting: the dispatch rung (B the engine ran at; 0 =
    # scheduler off / legacy path) and the registry entry whose service
    # EMA the completion feeds.
    rung: int = 0
    entry: object = None
    # fd_drain: the dedup pre-filter aux dispatch riding the same
    # round trip — (novel jax.Array, novel_cnt jax.Array) or None when
    # the drain stage is off / disarmed for this batch.
    drain: object = None


def book_retire(fl, ib: _InflightBatch, t_ready: int,
                t_retired: int) -> None:
    """Book one retired fd_feed batch's lifecycle into the verify
    tile's flight lane, from tickcount stamps taken in order: t_first
    (first staged txn), t_commit (READY), t_dispatch (dispatch
    returned), t_ready (the completion found it ready), t_retired
    (after its publish). stage + ready_wait + inflight + retire is
    exactly t_retired - t_first. The ring wait is the oldest frag's
    dwell by the drain's wrap-safe rule (0 where it rejects one);
    fallback_wait only for batches whose per-lane pass ran."""
    slot = ib.slot
    fl.inc("retired")
    ring = xray.dwell32(slot.t_first, int(slot.tspubs[0]))
    if ring > 0:
        fl.inc("ring_wait_ns", ring)
    # A slot handed to _feed_dispatch without a commit (a caller that
    # stages and dispatches itself, as tests do) waited for none.
    t_commit = slot.t_commit or ib.t_dispatch
    fl.inc("stage_ns", t_commit - slot.t_first)
    fl.inc("ready_wait_ns", ib.t_dispatch - t_commit)
    fl.inc("inflight_ns", t_ready - ib.t_dispatch)
    t_fallback = getattr(ib.out, "t_fallback", 0)
    if t_fallback:
        fl.inc("fallback_wait_ns", t_fallback - ib.t_dispatch)
    fl.inc("retire_ns", t_retired - t_ready)


class _ReadyBatch:
    """Completed-synchronously result with the async-batch surface
    (_complete polls .is_ready() and np.asarray's the result)."""

    def __init__(self, statuses):
        self._s = statuses

    def is_ready(self) -> bool:
        return True

    def __array__(self, dtype=None):
        import numpy as _np

        return _np.asarray(self._s, dtype=dtype)


# Verify-mode resolution lives in the fd_engine registry module since
# PR 13 (ONE owner for every engine-resolution decision); re-exported
# here because the tile construction sites and a decade of tests spell
# it tiles.resolve_verify_mode.
from firedancer_tpu.disco.engine import resolve_verify_mode  # noqa: E402


class _FutureBatch:
    """concurrent.futures result with the async-batch surface — the
    fd_feed cpu dispatch path, where a verify executor thread runs the
    GIL-releasing fd_ed25519_cpu_verify_batch call concurrently with
    staging (the host-verifier analog of an async device dispatch)."""

    def __init__(self, fut):
        self._f = fut

    def is_ready(self) -> bool:
        return self._f.done()

    def __array__(self, dtype=None):
        import numpy as _np

        return _np.asarray(self._f.result(), dtype=dtype)


class VerifyTile(Tile):
    """Sigverify: parse txn in-tile, ha-dedup, verify signatures, forward.

    backend='cpu' verifies per-txn on the host — the native C++
    verifier when built, else the Python oracle. backend='oracle' PINS
    the pure-Python reference implementation (differential tests rely
    on it being the bit-exact oracle, never an out-of-band .so).
    backend='tpu' accumulates a batch and dispatches the fused
    verify_batch XLA program ASYNCHRONOUSLY (the wiredancer offload shim,
    wd_f1.c:327-408): up to `inflight` batches are in flight on the device
    while the tile keeps draining its in-ring; completions are polled
    non-blockingly (jax async dispatch + Array.is_ready) and published
    into the out mcache in dispatch order. Partial batches are governed
    by the ADAPTIVE flush policy (disco/feed/policy.py): hard latency
    deadline (max_wait_us if passed, else FD_FEED_DEADLINE_US), plus a
    fast starved-input flush when the device is idle — at steady state
    batches fill and flush_timeout stays ~0 (the ROADMAP round-6 gate).
    Failed/parse-error/duplicate txns are dropped and counted in the cnc
    diag (SV/HA filter slots).

    feed=True (the fd_feed ingest runtime) moves the whole ring-drain /
    parse / HA-dedup / staging path onto a dedicated STAGER thread that
    fills preallocated SlotPool arenas (disco/feed/slots.py) while this
    tile's run loop becomes a pure dispatcher: pop READY slots, ship
    them to the device (or the native CPU verifier — a GIL-releasing C
    call, so staging genuinely overlaps it), publish completions. Feeder
    stats (fill_ratio, slot_stall, device_idle_est) land in verify_stats
    and — on the 16-slot cnc ABI — in the CNC_DIAG_FEED_* gauges that
    monitors and supervisors read across process boundaries.
    """

    name = "verify"

    def __init__(
        self,
        wksp,
        cnc_name,
        in_link,
        out_link,
        backend: str = "cpu",
        batch: int = 128,
        max_msg_len: int = FD_TPU_MTU,
        tcache_depth: int = 4096,
        inflight: int = 2,
        max_wait_us: Optional[int] = None,
        native_drain: bool = True,
        verify_mode: str = "auto",
        mesh_devices: int = 0,
        feed: bool = False,
        feed_slots: Optional[int] = None,
        **kw,
    ):
        super().__init__(wksp, cnc_name, in_link=in_link, out_link=out_link, **kw)
        # Typed raises, not asserts (python -O strips asserts, and a
        # typo'd config here silently verifies on the wrong engine):
        if backend not in ("oracle", "cpu", "tpu"):
            raise ValueError(
                f"unknown verify backend {backend!r} (want oracle|cpu|tpu)"
            )
        # Production default (round-6 un-park, round-10 mesh
        # composition): RLC batch verify is the PRIMARY device mode —
        # one Pippenger MSM pass per clean batch (sharded across
        # mesh_devices when configured), exact per-lane fallback on
        # batch-equation failure or fill overflow (ops/verify_rlc.py).
        # Resolution + validation live in resolve_verify_mode above.
        verify_mode = resolve_verify_mode(backend, verify_mode,
                                          mesh_devices)
        self.backend = backend
        self.verify_mode = verify_mode
        self.batch = batch
        self.max_msg_len = max_msg_len
        self.ha_tcache = TCache(tcache_depth)
        self.inflight_max = max(1, inflight)
        # Partial-batch flush: deadline-based adaptive policy (replaces
        # the round-2 fixed max-wait timer). An explicit max_wait_us
        # still pins the deadline; otherwise FD_FEED_DEADLINE_US.
        deadline_us = (
            max_wait_us if max_wait_us is not None
            else flags.get_int("FD_FEED_DEADLINE_US")
        )
        self.max_wait_ns = deadline_us * 1_000  # kept: tests/monitors read it
        self.flush_policy = AdaptiveFlush(self.max_wait_ns)
        self._pending: list = []       # [(payload, items, tsorig, seq_end)]
        self._pending_lanes = 0
        self._pending_since = 0        # tickcount of oldest pending txn
        self._inflight: list = []      # FIFO of _InflightBatch
        # Crash-consistency cursor: the fseq published to the producer is
        # held back to the last seq whose txn is FULLY verified (not just
        # consumed), so a SIGKILL between consume and verify-complete
        # cannot lose staged txns — the respawned worker re-reads them
        # (duplicates are healed by the downstream dedup tile).
        self._acked_seq = self.in_link.seq if self.in_link else 0
        # The delta mirror for the UNACKED gauge must seed from the
        # SHARED slot, not 0: the cnc diag survives a worker crash while
        # this process-local mirror does not, and a zero seed would make
        # the respawned incarnation re-add the dead one's last gauge
        # value forever (phantom staged work — the exact crash this
        # gauge exists to instrument).
        self._last_unacked = int(self.cnc.diag(CNC_DIAG_UNACKED))
        # Fault-injection knob (the reference's synth-load style): hold
        # the tile once, right after its first dispatch, with the
        # UNACKED gauge freshly published — a deterministic window for
        # crash tests to SIGKILL a tile that provably holds staged
        # batches (tests/test_supervisor.py). 0 = disabled (production).
        self._hold_s = flags.get_float("FD_VERIFY_HOLD_AFTER_DISPATCH_S")
        # A respawned incarnation (nonzero crash-surviving gauge) must
        # not hold again: the knob freezes only the first incarnation,
        # so the post-crash re-read path runs at full speed.
        self._held = self._last_unacked > 0
        self._verify_batch_fn = None
        # fd_flight: dispatch/completion/healing stats live in the
        # tile's registry LANE (one typed metric row, shared-memory
        # backed when the workspace carries the flight region) — the
        # stat_* names below are read-only VIEWS over it, so monitors,
        # verify_stats, and the replay/bench artifacts all read one
        # authority instead of hand-mirrored attributes.
        self.fl = flight.tile_lane(wksp, self.flight_label)
        # fd_xray: the tile's trigger/batch-context exemplar ring (one
        # span per sampled txn of every dispatched batch — batch id,
        # engine key, flush verdict, shard lane — plus quarantine /
        # breaker / CTL_ERR trigger events), and the cached sampling
        # threshold so the per-batch mask costs one vectorized hash.
        self._xr_on = xray.enabled()
        self.xr = xray.ring(f"tile:{self.flight_label}")
        self._xr_thr = xray.sample_threshold() if self._xr_on else 0
        # fd_engine identity: the registry spec this tile's dispatches
        # are keyed by (mode x B x shards x frontend — the flight
        # engine_key, now a typed registry key).
        from firedancer_tpu.disco import engine as fd_engine

        self._engine_spec = fd_engine.EngineSpec.for_tile(
            backend, verify_mode, batch, mesh_devices)
        self._engine_key = self._engine_spec.key
        # The registry record exists for host engines too (cpu/oracle
        # have nothing to compile, but their dispatch/service
        # accounting keys the same way); the tpu branch below replaces
        # this with the acquire()'d (built + warmed) entry.
        self._registry = fd_engine.registry()
        self._engine_entry = self._registry.entry(self._engine_spec)
        # Per-mesh-shard metric lanes (round-12 distributed aggregation:
        # populated only when mesh_devices > 1 — one row per shard,
        # booked at dispatch with the lanes that shard's slice of the
        # batch actually carries, so flight.merge_tile_metrics over them
        # reproduces this tile's own row; shared-memory backed when
        # build_topology(verify_shards=N) pre-labeled the rows).
        self.fl_shards: list = []
        # device id -> batch rows of the last mesh dispatch's input
        # (_note_shard_rows; {} off-mesh).
        self.stat_shard_rows: dict = {}
        self.stat_ring_dwell_ns: list = []  # publish->drain backlog samples
        self._dwell_span: Optional[flight.EdgeHist] = None
        self._breaker_pub = (None, 0, 0)   # last published breaker view
        # Device->CPU failover circuit (fd_feed mode; None elsewhere).
        self._breaker: Optional[CircuitBreaker] = None
        # Feeder gauge mirror (CNC_DIAG_FEED_*): published by EVERY
        # verify tile — legacy tiles report batches/lanes/flush buckets
        # too, so the supervisor's cross-process verify_stats are never
        # blind.
        self._feed_diag_mirror = [0] * 6
        # Native bulk drain (native/verify_drain.cc): one C call per batch
        # round replaces the per-frag Python poll/parse/copy loop (~30 us
        # per txn measured; the loop is the host-side throughput cap,
        # microbench.py ring_tile_hop). Requires the single-in-link tpu
        # path; per-frag semantics (parse errors, HA dedup, diag
        # counters) are preserved — parse is differentially fuzz-tested
        # against ballet/txn.py.
        self._nd = False
        self._jnp = None
        self._feed = False
        from firedancer_tpu.ballet.txn import MAX_SIG_CNT

        nd_ok = (backend in ("tpu", "cpu") and native_drain
                 and in_link is not None and batch >= MAX_SIG_CNT)
        if nd_ok:
            # batch >= MAX_SIG_CNT guarantees every parseable txn fits a
            # fresh batch; smaller batches fall back to the Python path,
            # which oracles outsized multisig txns instead of dropping.
            # backend='cpu' additionally needs the native verifier: the
            # drained staging layout feeds fd_ed25519_cpu_verify_batch
            # directly (one C call per batch — the per-frag Python loop
            # was the replay gate's 30x cap).
            from firedancer_tpu.ballet.ed25519 import native as _ed_native

            nd_ok = backend == "tpu" or _ed_native.available()
        if feed:
            if not nd_ok:
                # A feeder that silently fell back to the per-frag loop
                # would report legacy throughput as fd_feed numbers;
                # run_pipeline's routing checks the same preconditions
                # and picks the legacy runner instead of ever hitting
                # this.
                raise ValueError(
                    "feed=True requires the native drain path (cpu|tpu "
                    "backend, a single in_link, batch >= MAX_SIG_CNT, "
                    "and the native verifier for backend='cpu')"
                )
            self._feed_setup(feed_slots)
        elif nd_ok:
            self._nd_setup()
        if backend == "tpu":
            import jax.numpy as jnp

            self._jnp = jnp
            if mesh_devices:
                # Data-parallel verify over a device mesh: the ring
                # pipeline stays host-side, the batch axis shards over
                # 'dp' (parallel/mesh.py) — XLA inserts the collectives.
                # The shim is unchanged: the sharded step returns one
                # global statuses array whose .is_ready()/np.asarray
                # surface matches the single-device path.
                self.fl_shards = [
                    flight.tile_lane(wksp,
                                     f"{self.flight_label}.shard{i}")
                    for i in range(mesh_devices)
                ]
            # fd_engine registry resolution: build + pre-warm the
            # engine (compile the fixed (batch, max_msg_len) shape now
            # so the run loop never stalls on first-flush compilation;
            # rlc additionally warms its per-lane fallback graph). The
            # warm can take minutes (cold jit, or even a compile-cache
            # LOAD on a small host); in the supervised path worker.py's
            # boot-heartbeat thread keeps the cnc alive throughout, so
            # the wedge detector does not fire on a compiling tile.
            # Per-engine compile accounting (mode x B x shards x
            # frontend impl) is booked by the registry into the flight
            # compile records and mirrored into this tile's lane below:
            # the respawn-storm class of failure is a COMPILE-TIME
            # pathology, and before fd_flight it was invisible until it
            # had destroyed throughput.
            entry, warmed_now = self._registry.acquire(
                self._engine_spec, warm=True, max_msg_len=max_msg_len)
            self._engine_entry = entry
            self._verify_batch_fn = entry.fn
            if warmed_now:
                self._account_compile(entry.key, entry.compile_s)
                if verify_mode == "rlc":
                    self._account_compile(entry.key + ":fallback",
                                          entry.fallback_compile_s)
        # fd.* host spans on the profiler's clock (no-ops without JAX in
        # the process or with FD_FLIGHT=0): resolved here, once JAX is
        # loaded on the device path.
        self._span = flight.span_maker()
        # fd_engine rung scheduler (feed mode): pick the dispatch B from
        # the FD_ENGINE_LADDER rungs by queue depth + deadline slack
        # (disco/engine.py). Needs >= 2 usable rungs at or below the
        # staging batch (arenas are sized to the batch, which always
        # tops the ladder); anything else — including every
        # legacy/non-feed topology — keeps the fixed-B behavior, and
        # FD_ENGINE_SCHED=0 is the bisection hatch.
        self.rung_sched = None
        self.stat_rung_hist: dict = {}
        self._rung_entries: dict = {}
        self._rung_last = 0
        if self._feed and flags.get_bool("FD_ENGINE_SCHED"):
            rungs = fd_engine.rung_ladder(cap=batch, floor=MAX_SIG_CNT)
            if mesh_devices:
                # A rung that does not divide the mesh cannot build its
                # sharded engine (the same check the tile's own batch
                # passed) — drop it rather than letting prewarm crash
                # the boot (sync) or silently fail the rung (background).
                rungs = [r for r in rungs if r % mesh_devices == 0]
            if batch not in rungs:
                rungs.append(batch)
                rungs.sort()
            if len(rungs) >= 2:
                cost = None
                if backend == "tpu":
                    # Per-rung engines: registry entries (cost model =
                    # each rung's measured service EMA) + background
                    # prewarm of the non-primary rungs, so a rung
                    # switch picks up a WARM engine instead of paying
                    # a mid-run compile (a cold rung falls back to the
                    # primary engine at dispatch).
                    self._rung_entries = {
                        r: self._registry.entry(
                            self._engine_spec.with_batch(r))
                        for r in rungs
                    }
                    ents = self._rung_entries

                    def cost(r, _e=ents):
                        return _e[r].service_est_ns()

                    self._registry.prewarm_ladder(
                        [self._engine_spec.with_batch(r)
                         for r in rungs if r != batch],
                        max_msg_len=max_msg_len)
                self.rung_sched = fd_engine.RungScheduler(
                    rungs, self.max_wait_ns, cost_ns=cost,
                    shards=mesh_devices or 1)
                # ONE flush policy object: the stager's verdict calls
                # go through the scheduler's embedded AdaptiveFlush, so
                # the property-tested decide()/due() surface and the
                # shipped wiring share state (hwm clock hardening
                # included) instead of drifting as two instances.
                self.flush_policy = self.rung_sched.flush
                self.fl.set_gauge("rung_cur", rungs[0])
                self._rung_last = rungs[0]
                self.flightrec.record(
                    "rung_ladder", rungs=list(rungs),
                    prewarm=flags.get_str("FD_ENGINE_PREWARM"))
        # fd_soak zero-downtime live reconfig: request_reconfig()
        # validates + parks ONE pending request; _feed_poll drains the
        # inflight window to a barrier and _apply_reconfig swaps the
        # engine/ladder in the dispatch gap — per inflight window,
        # never per pipeline (staging keeps running throughout).
        import threading

        self.mesh_devices = mesh_devices
        self._reconfig_lock = threading.Lock()
        self._reconfig_pending: Optional[dict] = None
        self._reconfig_seq = 0

    # -- fd_flight views: the registry lane is the ONE authority for
    # dispatch/healing stats; these read-only properties keep the
    # long-standing stat_* read surface for monitors and tests. --------

    @property
    def stat_batches(self) -> int:
        return self.fl.get("batches")

    @property
    def stat_lanes(self) -> int:
        return self.fl.get("lanes")

    @property
    def stat_flush_timeout(self) -> int:
        return self.fl.get("flush_timeout")

    @property
    def stat_flush_starved(self) -> int:
        return self.fl.get("flush_starved")

    @property
    def stat_inflight_stall(self) -> int:
        return self.fl.get("inflight_stall")

    @property
    def stat_rlc_fallback(self) -> int:
        return self.fl.get("rlc_fallback")

    @property
    def stat_feed_idle_ns(self) -> int:
        return self.fl.get("feed_idle_ns")

    @property
    def stat_stager_restarts(self) -> int:
        return self.fl.get("stager_restarts")

    @property
    def stat_cpu_failover(self) -> int:
        return self.fl.get("cpu_failover")

    @property
    def stat_quarantined(self) -> int:
        return self.fl.get("quarantined")

    @property
    def stat_quarantine_err_txn(self) -> int:
        return self.fl.get("quarantine_err_txn")

    @property
    def stat_ctl_err(self) -> int:
        return self.fl.get("ctl_err_drop")

    def _xr_batch(self, tsorigs, n: int, verdict: str, device: bool,
                  slot_idx=None, tlanes=None, rung=None,
                  rung_target: int = 0, rung_depth: int = 0) -> None:
        """fd_xray batch-context exemplars: one span per HEAD-SAMPLED
        txn of a dispatched batch, carrying the batch ordinal, engine
        key (mode x B x shards x frontend), flush verdict, and — on a
        sharded mesh — the shard lane the txn's signatures land on.
        One vectorized hash per batch; Python only for the hits."""
        if not self._xr_on or n <= 0:
            return
        ids = np.asarray(tsorigs[:n], np.uint64)
        idxs = np.nonzero(xray.sampled_mask(ids, self._xr_thr))[0]
        if idxs.size == 0:
            return
        now = tempo.tickcount() & 0xFFFFFFFF
        batch_no = self.stat_batches
        shards = len(self.fl_shards)
        lane_start = None
        if shards and tlanes is not None:
            lane_start = np.zeros(n, np.int64)
            np.cumsum(np.asarray(tlanes[:n], np.int64)[:-1],
                      out=lane_start[1:])
        # Shard attribution partitions the DISPATCHED shape: a reduced
        # rung on a mesh engine splits `rung` lanes over the shards,
        # not the tile's staging batch.
        per = ((rung or self.batch) // shards) if shards else 0
        for i in idxs[:16]:
            extra = {
                "batch": batch_no,
                "engine": self._engine_key,
                "verdict": verdict,
                "device": device,
            }
            if slot_idx is not None:
                extra["slot"] = slot_idx
            if rung is not None:
                # fd_engine rung context: the B this batch actually
                # dispatched at, plus the stager's TARGET rung and the
                # queue depth behind that decision — a deadline/starved
                # flush or a cold-rung fallback can dispatch a B other
                # than the target, and the exemplar must not pair one
                # rung with the other's inputs.
                extra["rung"] = rung
                extra["rung_target"] = rung_target
                extra["rung_depth"] = rung_depth
            if lane_start is not None:
                extra["shard"] = int(lane_start[i]) // per
            t = int(ids[i])
            self.xr.record(t, t, now, "head", extra)

    def _xr_trigger(self, trigger: str, tsorigs=None, **extra) -> None:
        """fd_xray tail-trigger event (quarantine / breaker / ctl_err):
        recorded with up to 8 of the affected trace ids so the
        autopsy's exemplar section names transactions, not just
        counters."""
        if not self._xr_on:
            return
        ids = []
        if tsorigs is not None:
            ids = [int(t) for t in np.asarray(tsorigs).ravel()[:8]]
        now = tempo.tickcount() & 0xFFFFFFFF
        self.xr.record(ids[0] if ids else 0, ids[0] if ids else 0, now,
                       trigger,
                       dict(extra, traces=ids, engine=self._engine_key))

    def _account_compile(self, engine: str, seconds: float) -> None:
        """Book one engine (pre)compile into the tile lane. The
        process-level flight compile record was already appended by the
        fd_engine registry's warm pass — this mirror is the per-tile
        accounting (compile counters + the boot flight event)."""
        hit = flight.compile_cache_hit_est(seconds)
        self.fl.inc("compile_cnt")
        self.fl.inc("compile_ns", int(seconds * 1e9))
        if hit:
            self.fl.inc("compile_cache_hit")
        self.flightrec.record("compile", engine=engine,
                              s=round(seconds, 3))

    def _with_live_heartbeat(self, fn):
        """Run a blocking host-side operation inside the RUN loop (where
        worker.py's boot beat no longer covers us) while a daemon thread
        keeps the cnc heartbeat fresh, so supervision can tell 'held /
        busy' from 'wedged'. Used by the fault-injection hold."""
        import threading

        stop = threading.Event()

        def beat():
            while not stop.is_set():
                self.cnc.heartbeat(tempo.tickcount())
                stop.wait(1.0)

        t = threading.Thread(target=beat, daemon=True)
        t.start()
        try:
            return fn()
        finally:
            stop.set()
            t.join(timeout=5.0)

    def _nd_bindings(self) -> None:
        """ctypes bindings + drain scratch shared by the legacy native
        staging path and the fd_feed stager."""
        import ctypes

        from firedancer_tpu.tango.rings import lib as rings_lib

        self._nd_lib = rings_lib()
        self._nd_ct = ctypes
        # 8 slots; {ctl_err, ctl_err_bytes} at [6]/[7].
        self._nd_counters = np.zeros(8, np.uint64)
        self._nd_prev = np.zeros(8, np.uint64)

    def _nd_setup(self) -> None:
        self._nd_bindings()
        b, mtu = self.batch, self.max_msg_len
        self._nd_msgs = np.zeros((b, mtu), np.uint8)
        self._nd_lens = np.zeros(b, np.uint32)
        self._nd_sigs = np.zeros((b, 64), np.uint8)
        self._nd_pubs = np.zeros((b, 32), np.uint8)
        self._nd_pay = np.zeros(b * FD_TPU_MTU, np.uint8)
        self._nd_offs = np.zeros(b, np.uint32)
        self._nd_plens = np.zeros(b, np.uint32)
        self._nd_psigs = np.zeros(b, np.uint64)
        self._nd_tlanes = np.zeros(b, np.uint32)
        self._nd_tsorig = np.zeros(b, np.uint32)
        self._nd_tspub = np.zeros(b, np.uint32)
        self._nd_hash = np.zeros(b, np.uint64)
        self._nd_pay_fill = 0
        self._nd = True

    def _feed_setup(self, feed_slots: Optional[int]) -> None:
        """fd_feed mode: staging slots + stager-thread state. The slot
        arenas replace the single _nd_* staging buffer; the stager is
        started lazily by the first dispatcher poll (construction must
        stay side-effect-free for tiles that are built but never run)."""
        import threading as _threading

        from firedancer_tpu.disco.feed.slots import SlotPool

        self._nd_bindings()
        n_slots = feed_slots or flags.get_int("FD_FEED_SLOTS")
        self.feed_pool = SlotPool(n_slots, self.batch, self.max_msg_len)
        self._feed_exec = None
        if self.backend == "cpu":
            # Concurrent GIL-releasing native verify calls: the cpu
            # "device" is every core the host can spare, not one
            # serialized C call (the wiredancer shim's multiple DMA
            # slots, in host form).
            from concurrent.futures import ThreadPoolExecutor

            n_thr = flags.get_int("FD_FEED_VERIFY_THREADS")
            if n_thr <= 0:
                n_thr = min(2, os.cpu_count() or 1)
            self._feed_exec = ThreadPoolExecutor(
                max_workers=n_thr,
                thread_name_prefix=f"{self.name}.verify",
            )
            self.inflight_max = max(self.inflight_max, n_thr)
        self._feed = True
        self._feed_started = False
        self._feed_stop = _threading.Event()
        self._feed_thread: Optional[_threading.Thread] = None
        self._feed_slot = None          # current FILLING slot (stager-owned)
        self._feed_idle_mark = 0        # dispatcher idle-window anchor
        # Device->CPU verify failover circuit: consecutive primary-lane
        # errors trip it, the CPU oracle lane serves while it is open,
        # and a half-open probe restores the primary path once the
        # device recovers — device loss costs throughput, not liveness.
        if flags.get_bool("FD_VERIFY_BREAKER"):
            self._breaker = CircuitBreaker(
                threshold=flags.get_int("FD_VERIFY_BREAKER_THRESHOLD"),
                cooldown_ns=flags.get_int(
                    "FD_VERIFY_BREAKER_COOLDOWN_MS") * 1_000_000,
            )
        # Feeder-internal thread supervision (crash-only, like the
        # process supervisor one level up): a dead stager is restarted
        # with exponential backoff instead of taking the whole feeder
        # down; staged slots (READY backlog + the FILLING arena) are
        # preserved across the restart. Beyond the restart budget the
        # feeder fails loudly — a permanently crashing stager is a bug,
        # not an operational fault.
        self._stager_restart_max = flags.get_int("FD_FEED_STAGER_RESTART_MAX")
        self._stager_backoff_ns = flags.get_int(
            "FD_FEED_STAGER_BACKOFF_MS") * 1_000_000
        self._stager_restart_at = 0     # 0 = no restart pending
        self._stager_err_cls: Optional[str] = None
        # Ring-dwell trace span (source publish -> stager drain): the
        # feeder's input-backlog distribution, always-on in the flight
        # registry next to the publish edges.
        if flight.enabled() and flags.get_bool("FD_TRACE_SPANS"):
            self._dwell_span = flight.edge_hist(self.wksp, "verify_drain")
        self._drain_setup()

    def _drain_setup(self) -> None:
        """fd_drain arming (feed mode only): the dedup pre-filter graph
        rides every verify dispatch on the same queue, and its
        novel-mask (+ pack colors under FD_DRAIN_PACK) travels
        downstream in the frag ctl word. Disarms silently — behavior
        then bit-identical to FD_DRAIN=off — when jax is unavailable."""
        from firedancer_tpu.disco import engine as fd_engine

        self._drain = None
        self._drain_fn = None
        self._drain_pack_fn = None
        self._drain_block = 0
        if fd_engine.drain_mode() == "off" or self.out_link is None:
            return
        try:
            import jax.numpy as jnp

            from firedancer_tpu.disco import drain as drain_mod
        except Exception:
            return
        self._drain_jnp = jnp
        self._drain_mod = drain_mod
        quota = flags.get_int("FD_DRAIN_ROT_QUOTA")
        if quota <= 0:
            # Auto quota: the disco/drain.py eviction proof with the
            # DEFAULT downstream tcache depth. Operators running a
            # deeper dedup tcache must set FD_DRAIN_ROT_QUOTA.
            quota = drain_mod.rot_quota(
                4096, self.out_link.mcache.depth, self.batch)
        self._drain = drain_mod.DrainWindow(
            flags.get_int("FD_DRAIN_FILTER_BITS"), quota)
        self._drain_fn = drain_mod.make_filter_fn()
        if flags.get_bool("FD_DRAIN_PACK"):
            from firedancer_tpu.ballet.pack import CuEstimator
            from firedancer_tpu.ops.pack_gc import (
                H_BITS_DEFAULT,
                MAX_COLORS_DEFAULT,
            )

            self._drain_est = CuEstimator()
            self._drain_pack_fn = drain_mod.make_pack_fn(
                n_colors=min(MAX_COLORS_DEFAULT,
                             drain_mod.MAX_CTL_COLORS),
                h_bits=H_BITS_DEFAULT, cu_cap=12_000_000)

    def _drain_pack_arrays(self, slot):
        """Hashed account-lock arrays for the FD_DRAIN_PACK coloring
        graph, straight off the slot's payload sidecar. Unparseable /
        budget-less rows become lock-free zero-score placeholders (they
        color freely and their colors are ignored downstream — PackTile
        re-parses and validates, so a hint here is never authority)."""
        from firedancer_tpu.ballet.compute_budget import (
            estimate_rewards_and_compute,
        )
        from firedancer_tpu.ballet.pack import PackTxn
        from firedancer_tpu.ballet.txn import MAX_ACCT_CNT
        from firedancer_tpu.ops.pack_gc import PackTxnPad, build_arrays

        txns: list = [PackTxnPad] * self.batch
        for t in range(slot.n_txn):
            off = int(slot.offs[t])
            ln = int(slot.plens[t])
            payload = slot.pay[off:off + ln].tobytes()
            try:
                txn = parse_txn(payload)
            except TxnParseError:
                continue
            rce = estimate_rewards_and_compute(
                txn, payload, lamports_per_signature=5000,
                estimator=self._drain_est)
            if rce is None:
                continue
            rewards, est_cus, _cu_limit = rce
            txns[t] = PackTxn(
                txn_id=t, rewards=rewards, est_cus=est_cus,
                writable=frozenset(
                    txn.account(payload, i)
                    for i in range(txn.acct_cnt) if txn.is_writable(i)),
                readonly=frozenset(
                    txn.account(payload, i)
                    for i in range(txn.acct_cnt)
                    if not txn.is_writable(i)),
            )
        return build_arrays(txns, max_w=MAX_ACCT_CNT, max_r=MAX_ACCT_CNT)

    def _drain_dispatch(self, slot):
        """Ship the fd_drain aux graph for a staged slot right behind
        its verify dispatch (same device queue, one completion sync —
        the PR-13 split-pair discipline). Banks commit immediately: jax
        chains the still-in-flight bank array, so consecutive batches
        filter against each other's inserts with no host sync. Returns
        (novel, colors, block) device handles, or None on any failure —
        which disarms THIS batch only (no claims = all maybe-dup =
        exactly the drain-off behavior) and resets the window to empty
        banks (safe: emptier banks only widen maybe-dup)."""
        jnp = self._drain_jnp
        drain_mod = self._drain_mod
        try:
            from firedancer_tpu.ops.dedup_filter import split_tags

            hi, lo = split_tags(slot.psigs)
            valid = np.zeros(self.batch, np.bool_)
            valid[: slot.n_txn] = True
            bits_a, bits_b = self._drain.banks()
            colors = None
            block = 0
            if self._drain_pack_fn is not None:
                w_idx, r_idx, scores, cus = self._drain_pack_arrays(slot)
                novel, bits_new, _cnt, colors = self._drain_pack_fn(
                    jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(valid),
                    bits_a, bits_b, jnp.asarray(w_idx),
                    jnp.asarray(r_idx), jnp.asarray(scores),
                    jnp.asarray(cus))
                block = self._drain_block
                self._drain_block = (self._drain_block + 1) \
                    % (drain_mod.CTL_BLOCK_MASK + 1)
            else:
                novel, bits_new, _cnt = self._drain_fn(
                    jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(valid),
                    bits_a, bits_b)
            self._drain.commit(bits_new)
            return novel, colors, block
        except Exception:
            # A failed aux dispatch may have poisoned the chained bank
            # array: start a fresh window (empty banks claim nothing,
            # so the one-sided contract holds trivially).
            try:
                self._drain = drain_mod.DrainWindow(
                    self._drain.h_bits, self._drain.rot_quota)
            except Exception:
                self._drain = None  # jax gone entirely: stay disarmed
            return None

    def _nd_account(self, il) -> bool:
        """Fold one native drain round's counter deltas into the diag
        counters (parse errors, oversize, CTL_ERR drops, overruns) and
        the chaos audit; returns True when the round crossed an
        overrun. Shared by the legacy staging path and the stager."""
        d = self._nd_counters - self._nd_prev
        self._nd_prev = self._nd_counters.copy()
        if d[1] or d[3]:  # parse errors + oversize -> sv filter diag
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, int(d[1] + d[3]))
            self.cnc.diag_add(CNC_DIAG_SV_FILT_SZ, int(d[4] + d[5]))
        c = chaos.active()
        if d[6]:
            # Producer-flagged CTL_ERR frags dropped at the ctl word
            # (never staged): filtered traffic, and the detection+heal
            # of the chaos ring_ctl_err class.
            self.fl.inc("ctl_err_drop", int(d[6]))
            self.flightrec.record("ctl_err_drop", n=int(d[6]))
            self._xr_trigger("ctl_err", n=int(d[6]))
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, int(d[6]))
            self.cnc.diag_add(CNC_DIAG_SV_FILT_SZ, int(d[7]))
            if c is not None:
                c.on_ctl_err_drop(int(d[6]))
        overrun = False
        if d[2]:
            il.fseq.diag_add(DIAG_OVRNR_CNT, int(d[2]))
            overrun = True
            if c is not None:
                c.on_overrun_observed()
        return overrun

    def poll_inputs(self):
        if self._feed:
            return self._feed_poll()
        if not self._nd:
            return super().poll_inputs()
        il = self.in_link
        ct = self._nd_ct
        room_lanes = self.batch - self._pending_lanes
        if room_lanes <= 0:
            self._dispatch()
            self._complete(block=False)
            return False, False
        lane0 = self._pending_lanes
        seq = ct.c_uint64(il.seq)
        n = self._nd_lib.fd_verify_drain(
            il.mcache._mem, ct.addressof(il.dcache._buf),
            ct.byref(seq),
            self.batch - len(self._pending), room_lanes,
            self.batch, self.max_msg_len,
            self._nd_msgs.ctypes.data + lane0 * self.max_msg_len,
            self._nd_lens.ctypes.data + lane0 * 4,
            self._nd_sigs.ctypes.data + lane0 * 64,
            self._nd_pubs.ctypes.data + lane0 * 32,
            self._nd_pay.ctypes.data + self._nd_pay_fill,
            self._nd_pay.nbytes - self._nd_pay_fill,
            self._nd_offs.ctypes.data, self._nd_plens.ctypes.data,
            self._nd_psigs.ctypes.data,
            self._nd_tlanes.ctypes.data, self._nd_tsorig.ctypes.data,
            self._nd_tspub.ctypes.data, self._nd_hash.ctypes.data,
            self._nd_counters.ctypes.data,
        )
        overrun = self._nd_account(il)
        if n <= 0:
            il.seq = seq.value
            if not self._pending and not self._inflight:
                self._acked_seq = il.seq  # everything consumed is done
            return False, overrun
        if not self._pending:
            self._pending_since = tempo.tickcount()
        drain_end = seq.value  # ack target once this round's txns verify
        base = self._nd_pay_fill
        for i in range(n):
            off = base + int(self._nd_offs[i])
            ln = int(self._nd_plens[i])
            payload = self._nd_pay[off : off + ln].tobytes()
            cnt = int(self._nd_tlanes[i])
            # Ack granularity is the drain round: only the round's LAST
            # entry carries the post-round seq — a batch boundary inside
            # the round must not let the ack run past unverified txns.
            seq_end = drain_end if i == n - 1 else 0
            if self.ha_tcache.insert(hash(payload)):
                self.cnc.diag_add(CNC_DIAG_HA_FILT_CNT, 1)
                self.cnc.diag_add(CNC_DIAG_HA_FILT_SZ, ln)
                # Lanes stay staged; completion skips publish (None).
                self._pending.append((None, cnt, 0, seq_end))
            else:
                self._pending.append(
                    (payload, cnt, int(self._nd_tsorig[i]), seq_end)
                )
            self._nd_pay_fill = off + ln
            self._pending_lanes += cnt
        # Advance the consumed-seq marker only AFTER the txns are visible
        # in _pending: the supervisor's quiescence check reads both from
        # another thread, and seq-first would open a consumed-but-unqueued
        # window where the pipeline looks drained and HALT races in.
        il.seq = seq.value
        if self._pending_lanes >= self.batch:
            self._dispatch()
        elif self._ring_starved():
            self._dispatch(force=True)
        self._complete(block=False)
        return True, overrun

    # -- fd_feed: stager thread + slot dispatcher ------------------------

    def _feed_start(self) -> None:
        import threading as _threading

        self._feed_started = True
        self._feed_stager_err: Optional[BaseException] = None

        def _guarded():
            try:
                self._stager_loop()
            except BaseException as e:  # propagate to the dispatcher
                self._feed_stager_err = e

        t = _threading.Thread(
            target=_guarded, name=f"{self.name}.stager", daemon=True
        )
        self._feed_thread = t
        t.start()

    def _stager_supervise(self) -> None:
        """Feeder-internal crash-only supervision of the stager thread:
        a raise out of the stager loop is DETECTED here (the dispatcher
        keeps running — it still retires in-flight batches and ships the
        READY backlog), and the stager is restarted after an
        exponential backoff with jitter. Nothing staged is lost across
        the restart: the READY queue lives in the SlotPool, the FILLING
        arena stays parked in self._feed_slot, and the in-ring cursor
        plus held-back ack cover anything the dead incarnation had
        consumed (the property tests/test_chaos.py pins). Past the
        restart budget the original error is re-raised — the old
        fail-loudly behavior for genuinely broken code."""
        err = self._feed_stager_err
        if err is not None:
            self._feed_stager_err = None
            self.fl.inc("stager_restarts")
            self.flightrec.record("stager_restart",
                                  n=self.stat_stager_restarts,
                                  err=repr(err)[:120])
            c = chaos.active()
            if c is not None and isinstance(err, chaos.ChaosFault):
                c.note(err.cls, "detected")
                self._stager_err_cls = err.cls
            if self.stat_stager_restarts > self._stager_restart_max:
                raise RuntimeError(
                    f"fd_feed stager died {self.stat_stager_restarts} times "
                    f"(> FD_FEED_STAGER_RESTART_MAX="
                    f"{self._stager_restart_max}); giving up"
                ) from err
            # Same backoff law as the process supervisor's tile respawn
            # (feed/policy.respawn_backoff_s), with a thread-scale cap:
            # a stager outage beyond 2 s would blow the flush deadline
            # anyway, so decaying further buys nothing.
            backoff_s = respawn_backoff_s(
                self.stat_stager_restarts,
                self._stager_backoff_ns / 1e9,
                _STAGER_BACKOFF_CAP_S,
                self.rng,
            )
            self._stager_restart_at = (
                tempo.tickcount() + int(backoff_s * 1e9))
            import logging

            logging.getLogger("firedancer_tpu.disco.feed").warning(
                "fd_feed stager died (%r); restart %d/%d in %.1f ms",
                err, self.stat_stager_restarts, self._stager_restart_max,
                backoff_s * 1e3,
            )
            return
        if (
            self._stager_restart_at
            and not self._feed_stop.is_set()
            and (self._feed_thread is None
                 or not self._feed_thread.is_alive())
            and tempo.tickcount() >= self._stager_restart_at
        ):
            self._stager_restart_at = 0
            self._feed_start()
            if self._stager_err_cls is not None:
                c = chaos.active()
                if c is not None:
                    c.note(self._stager_err_cls, "healed")
                self._stager_err_cls = None

    def _stager_drain(self, slot) -> int:
        """One fd_verify_drain round into `slot` at its current fill
        cursors. Per-txn bookkeeping stays in the slot's numpy sidecar
        arrays (offs converted to absolute arena offsets) — the only
        per-txn Python here is the HA-tcache insert of the drain's FNV
        tag. Returns staged txn count; updates diag counters."""
        il = self.in_link
        c = chaos.active()
        if c is not None:
            # Injection points, both state-clean (before the C call, so
            # a raise leaves no half-booked slot): scheduled stager
            # death, and the consumer-side cursor rewind that produces
            # a deterministic overrun on the next poll.
            c.stager_round_hook()
            c.overrun_rewind(il)
        if il.xq is not None:
            # The STAGER thread is the one writer of the feeder's
            # in-edge rx row (VerifyTile.housekeep deliberately skips
            # the base _xq_housekeep — a tile-thread write here would
            # break the row's single-writer contract), so depth is
            # sampled per drain round alongside the dwell below.
            il.xq.sample_depth(il.mcache.seq_next() - il.seq)
        ct = self._nd_ct
        k0 = slot.n_txn
        seq = ct.c_uint64(il.seq)
        n = self._nd_lib.fd_verify_drain(
            il.mcache._mem, ct.addressof(il.dcache._buf),
            ct.byref(seq),
            self.batch - k0, self.batch - slot.n_lane,
            self.batch, self.max_msg_len,
            slot.msgs.ctypes.data + slot.n_lane * self.max_msg_len,
            slot.lens.ctypes.data + slot.n_lane * 4,
            slot.sigs.ctypes.data + slot.n_lane * 64,
            slot.pubs.ctypes.data + slot.n_lane * 32,
            slot.pay.ctypes.data + slot.pay_fill,
            slot.pay.nbytes - slot.pay_fill,
            slot.offs.ctypes.data + k0 * 4,
            slot.plens.ctypes.data + k0 * 4,
            slot.psigs.ctypes.data + k0 * 8,
            slot.tlanes.ctypes.data + k0 * 4,
            slot.tsorigs.ctypes.data + k0 * 4,
            slot.tspubs.ctypes.data + k0 * 4,
            slot.hashes.ctypes.data + k0 * 8,
            self._nd_counters.ctypes.data,
        )
        self._nd_account(il)
        if n <= 0:
            il.seq = seq.value  # consumed-but-unstageable (errors) frags
            if (
                slot.n_txn == 0 and not self._inflight
                and self.feed_pool.idle()
            ):
                # Everything consumed is fully handled: nothing staged in
                # this slot, no READY backlog, nothing on the device. New
                # staged work can only come from THIS thread, so the ack
                # fast-path cannot race a dispatch.
                self._acked_seq = il.seq
            return 0
        with self._span("fd.feed.drain"):
            return self._stage_round(slot, k0, n, seq.value, c)

    def _stage_round(self, slot, k0: int, n: int, seq_end: int, c) -> int:
        """The bookkeeping of a drain round that staged n txns into
        `slot` from txn index k0: stamps, ring dwell, HA dedup, cursors.
        Returns n."""
        il = self.in_link
        now = tempo.tickcount()
        if k0 == 0:
            slot.t_first = now  # deadline anchor: first STAGED txn
        # Ring dwell (producer publish -> this drain) of the round's
        # oldest frag: the feeder's input-backlog gauge (reported as
        # stage latency). tspub is a 32-bit tick; xray.dwell32 recovers
        # the modular difference (exact across any number of 2^32 ns
        # clock wraps — tests/test_clock_wrap.py) and rejects absurd
        # dwells (> ~4 s) as wrap artifacts. Dwell is NOT folded into
        # the flush deadline: with a backlog the next round fills the
        # batch in O(ms) anyway, and turning old-but-plentiful input
        # into partial flushes would trade fill ratio for nothing.
        dwell = xray.dwell32(now, int(slot.tspubs[k0]))
        if dwell >= 0:
            if len(self.stat_ring_dwell_ns) < 65536:
                self.stat_ring_dwell_ns.append(dwell)
            if self._dwell_span is not None:
                self._dwell_span.observe(dwell)
            if il.xq is not None:
                # fd_xray queue row for the feeder's in-edge: the same
                # round-oldest dwell the verify_drain stage reports.
                il.xq.observe_dwell(dwell)
        # Offsets came back relative to the round's arena base; make
        # them absolute so the completion's bulk publish can read every
        # round of this slot with one base pointer.
        slot.offs[k0 : k0 + n] += slot.pay_fill
        # HA dedup on the drain's whole-payload FNV tags — the only
        # per-txn Python in the feeder (~1 us/txn); duplicates keep
        # their staged lanes but are masked out of the publish.
        ha_filt_cnt = 0
        ha_filt_sz = 0
        hashes = slot.hashes[k0 : k0 + n].tolist()
        insert = self.ha_tcache.insert
        for i, h in enumerate(hashes):
            if insert(h):
                k = k0 + i
                slot.ha_mask[k] = True
                ha_filt_cnt += 1
                ha_filt_sz += int(slot.plens[k])
        if ha_filt_cnt:
            self.cnc.diag_add(CNC_DIAG_HA_FILT_CNT, ha_filt_cnt)
            self.cnc.diag_add(CNC_DIAG_HA_FILT_SZ, ha_filt_sz)
        if c is not None:
            # slot_corrupt injection: flip one staged MESSAGE byte of a
            # txn from this round (lanes started at the pre-round
            # n_lane). The sidecar payload stays pristine — sigverify
            # must fail exactly that txn and the pool must carry on.
            c.post_stage_hook(slot, k0, n, lane0=slot.n_lane)
        last = k0 + n - 1
        slot.pay_fill = int(slot.offs[last]) + int(slot.plens[last])
        slot.n_lane += int(slot.tlanes[k0 : k0 + n].sum())
        slot.n_txn += n
        slot.drain_end = seq_end
        # Consumed-seq marker only AFTER the txns are visible in the
        # slot (n_txn above): the quiescence check and the ack fast
        # path read both from other threads, and seq-first would open a
        # consumed-but-invisible window where the pipeline looks
        # drained while staged txns exist.
        il.seq = seq_end
        return n

    def _stager_loop(self) -> None:
        """fd_feed stager: drain the in-ring into slot arenas and hand
        full (or flush-due partial) slots to the dispatcher. Everything
        per-frag — seqlock'd ring drain, parse, payload copy, HA dedup —
        lives here, OFF the dispatch thread; the drain itself is one
        GIL-releasing C call per round."""
        pool = self.feed_pool
        il = self.in_link
        idle_spins = 0
        while not self._feed_stop.is_set():
            slot = self._feed_slot
            if slot is None:
                slot = pool.acquire(0.05)  # stalls counted by the pool
                if slot is None:
                    continue
                self._feed_slot = slot
            seq_before = il.seq
            n = self._stager_drain(slot)
            # fd_engine rung target: the scheduler's pick (staged lanes
            # + ring backlog + deadline slack) bounds the batch this
            # slot fills toward; self.batch with the scheduler off. Low
            # offered load makes a small rung "full" early (small-rung
            # latency); a deep backlog targets the top rung (big-rung
            # fill efficiency).
            rung = self._sched_rung(slot)
            if slot.n_lane >= rung:
                self._feed_commit(slot, FLUSH_FULL)
                idle_spins = 0
                continue
            if n > 0:
                idle_spins = 0
                continue
            if (slot.n_txn and il.seq == seq_before
                    and self.batch - slot.n_lane < MAX_SIG_CNT
                    and il.mcache.seq_next() > il.seq):
                # Capacity-blocked, not starved: the ring head is a
                # multisig txn that cannot fit the remaining lane room.
                # Ship the slot as effectively-full instead of letting
                # the deadline timer misbook a 25 ms stall per batch.
                self._feed_commit(slot, "capacity")
                idle_spins = 0
                continue
            if slot.n_txn:
                if self._ring_starved():
                    # Held-back acks are about to exhaust the producer's
                    # credits: a partial batch beats a stalled pipeline
                    # (uncounted force, matching the legacy path).
                    self._feed_commit(slot, "ring_starved")
                    continue
                verdict = self.flush_policy.due(
                    tempo.tickcount(), slot.n_lane, rung,
                    slot.t_first, starved=True,
                    device_idle=(not self._inflight
                                 and pool.ready_cnt() == 0),
                    backpressured=self.out_link.fctl.probe(
                        self.out_link.seq) <= 0,
                )
                if verdict is not None:
                    if verdict == FLUSH_DEADLINE:
                        self.fl.inc("flush_timeout")
                    elif verdict == FLUSH_STARVED:
                        self.fl.inc("flush_starved")
                    self.flightrec.record("flush", verdict=verdict,
                                          lanes=slot.n_lane)
                    self._feed_commit(slot, verdict)
                    continue
            # Empty drain round: sleep IMMEDIATELY rather than hot-spin.
            # The feeder works at batch granularity (a cpu batch is
            # ~20 ms of verify), so a 100 us reaction lag is free — while
            # a spinning stager holds the GIL in ~5 ms scheduler quanta
            # and starves the in-process source publisher, which was
            # measured to cost more end-to-end than the device idle it
            # was trying to avoid.
            idle_spins += 1
            time.sleep(20e-6 if idle_spins <= 8 else 100e-6)

    def _sched_rung(self, slot) -> int:
        """Target rung for the batch being staged (stager thread): the
        fd_engine scheduler's pick from staged lanes + ring backlog +
        deadline slack, stamped on the slot for the xray batch-context
        exemplars; self.batch with the scheduler off. Rung changes book
        a flight `rung` event (with the decision inputs) and the
        rung_switches counter, so a sentinel p99 win or regression can
        be attributed to scheduling from the event trail alone."""
        if self.rung_sched is None:
            return self.batch
        il = self.in_link
        backlog = max(0, il.mcache.seq_next() - il.seq)
        # Saturation signal: the ring backlog at half its structural
        # cap means the producer is ahead as fast as the depth-bounded
        # ring can express it — the scheduler drops its latency
        # protections and goes for big-rung fill efficiency.
        rung = self.rung_sched.pick(
            tempo.tickcount(), slot.n_lane, slot.t_first, backlog,
            backlog_full=backlog * 2 >= il.mcache.depth)
        if rung != self._rung_last:
            depth, slack, lanes = self.rung_sched.last_inputs
            self.fl.inc("rung_switches")
            self.fl.set_gauge("rung_cur", rung)
            self.flightrec.record("rung", b=rung, prev=self._rung_last,
                                  depth=depth, slack_ns=slack,
                                  lanes=lanes)
            self._rung_last = rung
        slot.rung = rung
        slot.rung_depth = self.rung_sched.last_inputs[0]
        return rung

    def _feed_commit(self, slot, verdict: str = FLUSH_FULL) -> None:
        slot.flush_verdict = verdict  # fd_xray batch-context exemplars
        slot.t_commit = tempo.tickcount()
        self._feed_slot = None
        self.feed_pool.commit(slot)

    def _feed_dispatch(self, slot) -> None:
        """Ship one READY slot to the verify engine and record the
        in-flight batch. The slot stays attached to the batch until it
        retires — the completion publishes straight out of its sidecar
        arrays (fd_frag_publish_bulk) — so the stager refills OTHER
        slots while this one verifies."""
        # fd_engine dispatch rung: the smallest rung covering the
        # staged lanes (engines are compiled per rung; a partial pads
        # up to the rung's shape). A rung whose engine is not WARM yet
        # falls back to the always-warm primary engine rather than
        # stalling the dispatcher on a compile.
        rung = self.batch
        entry = self._engine_entry
        fn = self._verify_batch_fn
        if self.rung_sched is not None:
            rung = self.rung_sched.dispatch_rung(slot.n_lane)
            if self.backend == "tpu" and rung != self.batch:
                e = self._registry.warm_entry(
                    self._engine_spec.with_batch(rung))
                if e is None:
                    rung = self.batch
                else:
                    entry, fn = e, e.fn
        if slot.n_lane < rung:
            # Zero the stale tail rows exactly like _dispatch_py's pad
            # lanes (zero sig/pub/len): a previous batch's leftovers in
            # the arena must never verify — and under rlc they would
            # poison the batch equation into a permanent fallback.
            # Only the rows the chosen rung's engine reads need it.
            slot.lens[slot.n_lane:rung] = 0
            slot.sigs[slot.n_lane:rung] = 0
            slot.pubs[slot.n_lane:rung] = 0
        out = None
        via_device = False
        c = chaos.active()
        now = tempo.tickcount()
        allow = self._breaker is None or self._breaker.allow_device(now)
        fault_cls = None
        if allow:
            try:
                if c is not None:
                    c.verify_dispatch_hook()  # may raise ChaosDeviceLost
                with self._span("fd.verify.dispatch"):
                    if self.backend == "cpu":
                        from firedancer_tpu.ballet.ed25519 import (
                            native as ed_native,
                        )

                        out = _FutureBatch(self._feed_exec.submit(
                            ed_native.verify_arrays,
                            slot.msgs, slot.lens, slot.sigs, slot.pubs,
                            slot.n_lane,
                        ))
                    else:
                        put = entry.put
                        msgs = put(slot.msgs[:rung])
                        if entry.in_sharding is not None:
                            self._note_shard_rows(msgs)
                        out = fn(
                            msgs,
                            put(slot.lens[:rung].astype(np.int32)),
                            put(slot.sigs[:rung]),
                            put(slot.pubs[:rung]),
                        )
                via_device = True
            except Exception as e:
                # Device unavailable at dispatch (or the executor
                # refused the batch): feed the breaker and fall through
                # to the CPU oracle lane — the slot is NEVER lost to a
                # dispatch failure, and the loop keeps running.
                if self._breaker is not None:
                    self._breaker.record_error(now)
                if c is not None and isinstance(e, chaos.ChaosFault):
                    c.note(e.cls, "detected")
                    fault_cls = e.cls
        if out is None:
            out = _ReadyBatch(self._verify_slot_cpu(slot))
            self.fl.inc("cpu_failover")
            self.flightrec.record("cpu_failover", lanes=slot.n_lane)
            if fault_cls is not None and c is not None:
                c.note(fault_cls, "healed")
        # fd_drain: the dedup pre-filter (+ optional pack coloring) aux
        # dispatch rides the same round trip — even behind a CPU
        # failover verify, the filter verdict is orthogonal to the
        # verify result.
        drain_out = None
        if self._drain is not None and slot.n_txn:
            drain_out = self._drain_dispatch(slot)
            if drain_out is not None:
                self.fl.inc("drain_batches")
        self._inflight.append(_InflightBatch(
            out=out, todo=[], oversize=[False] * self.batch,
            t_dispatch=tempo.tickcount(), slot=slot, device=via_device,
            rung=rung if self.rung_sched is not None else 0,
            entry=entry if via_device else None,
            drain=drain_out,
        ))
        self.fl.inc("batches")
        self.fl.inc("lanes", slot.n_lane)
        # fd_pod occupancy: the feed path books per-shard lanes too
        # (the legacy dispatchers always did), over the DISPATCHED
        # shape — a reduced rung splits `rung` lanes over the mesh,
        # not the tile's staging batch. The shard rows are what the
        # sentinel's shard-balance SLO and the smoke's 1.5x occupancy
        # gate read.
        self._book_shard_lanes(slot.n_lane, shape=rung)
        ev = {"lanes": slot.n_lane, "device": via_device}
        if self.rung_sched is not None:
            # Per-rung dispatch accounting: the histogram the replay
            # artifact carries (verify_stats.rung_hist) + the registry
            # entry's own dispatch counters.
            self.stat_rung_hist[rung] = self.stat_rung_hist.get(rung, 0) + 1
            if entry is not None:
                entry.note_dispatch(slot.n_lane)
            ev["b"] = rung
        self.flightrec.record("dispatch", **ev)
        self._xr_batch(slot.tsorigs, slot.n_txn, slot.flush_verdict,
                       via_device, slot_idx=slot.idx, tlanes=slot.tlanes,
                       rung=rung if self.rung_sched is not None else None,
                       rung_target=getattr(slot, "rung", 0),
                       rung_depth=getattr(slot, "rung_depth", 0))

    def _verify_slot_cpu(self, slot):
        """The CPU oracle lane over a staged slot: the failover target
        when the device (or verify executor) is gone, and the re-verify
        engine of the poisoned-batch quarantine. Bisection ladder: the
        native batch verifier first; if IT raises, per-lane through the
        pure-Python RFC 8032 oracle — the lane of last resort cannot
        itself be an offload."""
        from firedancer_tpu.ballet.ed25519 import native as ed_native

        if ed_native.available():
            try:
                return np.asarray(ed_native.verify_arrays(
                    slot.msgs, slot.lens, slot.sigs, slot.pubs,
                    slot.n_lane,
                ))
            except Exception:
                pass  # bisect further: per-lane oracle below
        from firedancer_tpu.ballet.ed25519 import oracle as ed_oracle

        out = np.ones(self.batch, np.int32)
        for lane in range(slot.n_lane):
            ln = int(slot.lens[lane])
            out[lane] = ed_oracle.verify(
                slot.msgs[lane, :ln].tobytes(),
                slot.sigs[lane].tobytes(),
                slot.pubs[lane].tobytes(),
            )
        return out

    def _oracle_verify_payload(self, payload: bytes) -> bool:
        """Whole-txn CPU oracle verdict (quarantine lane for batches
        staged outside slot arenas)."""
        try:
            txn = parse_txn(payload)
            items = list(txn.verify_items(payload))
        except TxnParseError:
            return False
        from firedancer_tpu.ballet.ed25519 import native as ed_native

        if ed_native.available():
            try:
                return all(st == 0 for st in ed_native.verify_items(items))
            except Exception:
                pass
        from firedancer_tpu.ballet.ed25519 import oracle as ed_oracle

        return all(
            ed_oracle.verify(msg, sig, pub) == 0 for (sig, pub, msg) in items
        )

    def _quarantine_statuses(self, ib):
        """Poisoned-batch quarantine: the batch's verify raised, so its
        result is untrusted — bisect to the CPU oracle lane and produce
        per-lane statuses in the batch's own layout. Clean txns go on
        to publish normally (an injected/transient backend error loses
        nothing); genuinely bad txns fail here and are published with
        CTL_ERR by the completion path."""
        if ib.slot is not None:
            return self._verify_slot_cpu(ib.slot)
        return self._oracle_statuses_todo(ib.todo)

    def _oracle_statuses_todo(self, todo):
        """Per-lane statuses for a todo-list batch (legacy staging
        layout) from whole-txn CPU oracle verdicts — the quarantine
        re-verify for batches staged outside slot arenas."""
        statuses = np.ones(self.batch, np.int32)
        off = 0
        for payload, cnt, _tsorig, _seq_end in todo:
            ok = payload is None or self._oracle_verify_payload(payload)
            statuses[off:off + cnt] = 0 if ok else 1
            off += cnt
        return statuses

    def _publish_err(self, payload: bytes, sig: int) -> None:
        """Quarantine audit trail: an offending txn goes downstream as a
        CTL_ERR frag — visible on the ring (dedup counts + drops it
        without letting it shadow a valid same-sig txn) instead of
        silently vanishing. Same HALT/backpressure discipline as
        publish_backp."""
        t_stall = 0
        while not self.out_link.can_publish():
            if self.cnc.signal_query() == CNC_HALT:
                return
            if not t_stall:
                t_stall = tempo.tickcount()
            self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
            time.sleep(20e-6)
        if t_stall and self.out_link.xq_tx is not None:
            self.out_link.xq_tx.add_stall(tempo.tickcount() - t_stall)
        self.out_link.publish(payload, sig, ctl=CTL_SOM_EOM | CTL_ERR)
        self.fl.inc("quarantine_err_txn")

    def _publish_feed_batch(self, slot, statuses,
                            quarantined: bool = False,
                            drain=None) -> int:
        """Completion half of the feeder: fold per-lane statuses to
        per-txn verdicts (numpy reduceat over the slot's lane counts)
        and publish every passing, non-HA-duplicate txn downstream with
        ONE bulk native call per credit window. Returns the batch's ack
        target (the in-ring seq after the slot's last drain round).
        quarantined=True (the batch's verify raised and these statuses
        came from the CPU oracle lane) additionally publishes each
        offending txn with CTL_ERR — the audit trail of the quarantine."""
        n = slot.n_txn
        if n == 0:
            return slot.drain_end
        lanes = slot.tlanes[:n].astype(np.int64)
        starts = np.zeros(n, np.int64)
        np.cumsum(lanes[:-1], out=starts[1:])
        bad = (np.asarray(statuses)[: slot.n_lane] != 0).astype(np.int32)
        anybad = np.add.reduceat(bad, starts) > 0
        ha = slot.ha_mask[:n]
        ok = ~anybad & ~ha
        sv = anybad & ~ha
        sv_cnt = int(sv.sum())
        if sv_cnt:
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, sv_cnt)
            self.cnc.diag_add(
                CNC_DIAG_SV_FILT_SZ, int(slot.plens[:n][sv].sum()))
            c = chaos.active()
            if c is not None:
                # slot_corrupt audit: consume corruption records whose
                # txn just failed sigverify (the drop IS the heal).
                c.on_sv_drop(slot.psigs[:n][sv])
            if quarantined:
                for t in np.nonzero(sv)[0]:
                    off_b = int(slot.offs[t])
                    ln = int(slot.plens[t])
                    self._publish_err(
                        slot.pay[off_b:off_b + ln].tobytes(),
                        int(slot.psigs[t]),
                    )
        n_ok = int(ok.sum())
        if not n_ok:
            return slot.drain_end
        # fd_drain claims: fetch the aux graph's novel-mask (+ colors)
        # at completion — dispatched alongside the verify, so this is a
        # ready device array, not a sync. Any fetch failure simply
        # publishes claim-free (all maybe-dup — the off behavior).
        novel_t = None
        colors_t = None
        ctls = None
        block = 0
        if drain is not None:
            try:
                novel_dev, colors_dev, block = drain
                novel_t = np.asarray(novel_dev)[:n]
                if colors_dev is not None:
                    colors_t = np.asarray(colors_dev)[:n].astype(np.int32)
                ctls = self._drain_mod.encode_ctl(
                    CTL_SOM_EOM, novel_t, colors_t, block)
            except Exception:
                novel_t = None
                ctls = None
        mask8 = ok.astype(np.uint8)
        ol = self.out_link
        ct = self._nd_ct
        seqv = ct.c_uint64(ol.seq)
        chunkv = ct.c_uint32(ol.chunk)
        cursor = ct.c_uint32(0)
        bytes_out = np.zeros(1, np.uint64)
        now32 = tempo.tickcount() & 0xFFFFFFFF
        published = 0
        halted = False
        novel_pub = 0
        maybe_pub = 0
        while published < n_ok and not halted:
            # Credit-windowed bulk publish: same fctl discipline as
            # publish_backp (spin through backpressure, drop on HALT),
            # amortized over the window instead of paid per frag.
            t_stall = 0
            while not ol.can_publish():
                if self.cnc.signal_query() == CNC_HALT:
                    halted = True  # drop the rest, like publish_backp
                    break
                if not t_stall:
                    t_stall = tempo.tickcount()
                self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
                time.sleep(20e-6)
            if t_stall and ol.xq_tx is not None:
                ol.xq_tx.add_stall(tempo.tickcount() - t_stall)
            if halted:
                break
            cur0 = cursor.value
            if ctls is not None:
                pub = self._nd_lib.fd_frag_publish_bulk_ctl(
                    ol.mcache._mem, ct.addressof(ol.dcache._buf),
                    ol.dcache.chunk_cnt, ol.mtu,
                    ct.byref(seqv), ct.byref(chunkv),
                    slot.pay.ctypes.data,
                    slot.offs.ctypes.data, slot.plens.ctypes.data,
                    slot.psigs.ctypes.data, slot.tsorigs.ctypes.data,
                    ctls.ctypes.data,
                    mask8.ctypes.data, ct.byref(cursor), n,
                    min(ol.cr_avail, n_ok - published), now32,
                    bytes_out.ctypes.data,
                )
            else:
                pub = self._nd_lib.fd_frag_publish_bulk(
                    ol.mcache._mem, ct.addressof(ol.dcache._buf),
                    ol.dcache.chunk_cnt, ol.mtu,
                    ct.byref(seqv), ct.byref(chunkv),
                    slot.pay.ctypes.data,
                    slot.offs.ctypes.data, slot.plens.ctypes.data,
                    slot.psigs.ctypes.data, slot.tsorigs.ctypes.data,
                    mask8.ctypes.data, ct.byref(cursor), n,
                    min(ol.cr_avail, n_ok - published), now32,
                    bytes_out.ctypes.data,
                )
            ol.seq = seqv.value
            ol.chunk = chunkv.value
            ol.cr_avail = max(0, ol.cr_avail - pub)
            published += pub
            if novel_t is not None:
                # Per-window claim accounting over the cursor range the
                # C call actually examined: only mask-selected lanes in
                # [cur0, cursor) were published (HALT-dropped tails
                # never count — the rotation quota is over PUBLISHES).
                w = slice(cur0, cursor.value)
                novel_pub += int((novel_t[w] & ok[w]).sum())
                maybe_pub += int((~novel_t[w] & ok[w]).sum())
            if pub <= 0:
                break  # defensive: cursor exhausted without publishes
        if novel_t is not None:
            self.fl.inc("drain_novel", novel_pub)
            self.fl.inc("drain_maybe", maybe_pub)
            if self._drain is not None:
                self._drain.note_published(novel_pub)
                if self._drain.maybe_rotate(
                        blocked=chaos.active() is not None):
                    self.fl.inc("drain_rot")
        il = self.in_link
        il.fseq.diag_add(DIAG_PUB_CNT, published)
        il.fseq.diag_add(DIAG_PUB_SZ, int(bytes_out[0]))
        # Stage-latency reservoir (OutLink.publish is bypassed on the
        # bulk path): same Algorithm-R insert per sample, so long-soak
        # percentiles stay run-representative, not warmup-biased.
        ts = slot.tsorigs[:n][ok]
        ts = ts[ts != 0]
        if ts.size:
            lats = (now32 - ts.astype(np.int64)) & 0xFFFFFFFF
            ol.lat_sample_many(lats, ts)
        return slot.drain_end

    # -- fd_soak zero-downtime live reconfig -----------------------------

    def request_reconfig(self, req: dict) -> tuple:
        """Validate + park ONE live-reconfig request (callable from any
        thread); the dispatcher applies it at the next inflight-window
        barrier (_feed_poll -> _apply_reconfig). Returns
        (accepted, detail).

        The request dict: 'ladder' (optional list of rung batch sizes
        — the staging batch is always appended: arenas are sized to
        it, so a swap replaces the ladder BELOW it), 'verify_mode'
        (optional rlc|direct re-resolution), 'env' (optional FD_* flag
        flips the controller has ALREADY exported — FD_FRONTEND_IMPL /
        FD_DECOMPRESS_IMPL / FD_DRAIN — which the barrier apply
        re-resolves through the registry). A request that cannot
        produce a dispatchable configuration is REFUSED here,
        atomically, with the running config untouched: an invalid
        mode/backend combination (rlc on a host backend), a ladder
        with fewer than 2 usable rungs, or a swap already pending (the
        double-swap race — one barrier, one swap)."""
        from firedancer_tpu.disco import engine as fd_engine

        def refuse(reason: str) -> tuple:
            self.fl.inc("reconfig_refused")
            self.flightrec.record("reconfig_refused", reason=reason)
            return False, reason

        if not self._feed:
            return refuse("reconfig requires the fd_feed staging path")
        mode = req.get("verify_mode") or self.verify_mode
        try:
            mode = fd_engine.resolve_verify_mode(
                self.backend, mode, self.mesh_devices)
        except ValueError as e:
            return refuse(str(e))
        ladder = req.get("ladder")
        rungs = None
        if ladder is not None:
            if not flags.get_bool("FD_ENGINE_SCHED"):
                return refuse("ladder swap with FD_ENGINE_SCHED=0")
            try:
                rungs = sorted({int(r) for r in ladder})
            except (TypeError, ValueError):
                return refuse(f"unparseable ladder {ladder!r}")
            rungs = [r for r in rungs if MAX_SIG_CNT <= r <= self.batch]
            if self.mesh_devices:
                rungs = [r for r in rungs
                         if r % self.mesh_devices == 0]
            if self.batch not in rungs:
                rungs.append(self.batch)
                rungs.sort()
            if len(rungs) < 2:
                return refuse(
                    f"ladder {ladder!r} leaves < 2 usable rungs under "
                    f"staging batch {self.batch}")
        with self._reconfig_lock:
            if self._reconfig_pending is not None:
                return refuse(
                    "a reconfig is already pending (one barrier, one "
                    "swap)")
            self._reconfig_seq += 1
            pend = {"seq": self._reconfig_seq, "verify_mode": mode,
                    "env": dict(req.get("env") or {})}
            if rungs is not None:
                pend["ladder"] = rungs
            self._reconfig_pending = pend
        self.flightrec.record("reconfig_request", seq=pend["seq"],
                              mode=mode,
                              ladder=list(rungs) if rungs else None)
        return True, f"pending (seq {pend['seq']})"

    def _apply_reconfig(self) -> None:
        """Swap the engine configuration in the dispatch gap: called by
        the dispatcher ONLY at the inflight-window barrier (zero
        batches in flight), so no dispatch holds an engine across the
        swap and sink continuity is digest-exact by construction —
        staged/READY slots are untouched and simply dispatch on the
        new engines. Old rung engines unreachable under the new
        configuration are retired from the registry."""
        from firedancer_tpu.disco import engine as fd_engine

        with self._reconfig_lock:
            req = self._reconfig_pending
        if req is None:
            return
        t0 = time.perf_counter()
        old_specs = {self._engine_spec}
        if self.rung_sched is not None and self.backend == "tpu":
            old_specs |= {self._engine_spec.with_batch(r)
                          for r in self.rung_sched.rungs}
        mode = req["verify_mode"]
        spec = fd_engine.EngineSpec.for_tile(
            self.backend, mode, self.batch, self.mesh_devices)
        cold_primary = False
        if self.backend == "tpu":
            e = self._registry.warm_entry(spec)
            if e is None:
                # Unwarmed target (the controller prewarms before
                # requesting; this is the cold-swap fallback): one
                # blocking acquire — the barrier already paused
                # dispatch, and stalling here beats dispatching on a
                # half-built engine.
                cold_primary = True
                e, warmed_now = self._registry.acquire(
                    spec, warm=True, max_msg_len=self.max_msg_len)
                if warmed_now:
                    self._account_compile(e.key, e.compile_s)
                    if mode == "rlc":
                        self._account_compile(
                            e.key + ":fallback", e.fallback_compile_s)
            self._engine_entry = e
            self._verify_batch_fn = e.fn
        else:
            self._engine_entry = self._registry.entry(spec)
        self._engine_spec = spec
        self._engine_key = spec.key
        self.verify_mode = mode
        rungs = req.get("ladder")
        if rungs is None and self.rung_sched is not None:
            # Flag-flip-only reconfig under an active scheduler: keep
            # the rung list, rebuild the per-rung engines on the new
            # spec below.
            rungs = list(self.rung_sched.rungs)
        new_specs = {spec}
        if rungs is not None and len(rungs) >= 2:
            cost = None
            if self.backend == "tpu":
                self._rung_entries = {
                    r: self._registry.entry(spec.with_batch(r))
                    for r in rungs
                }
                ents = self._rung_entries

                def cost(r, _e=ents):
                    return _e[r].service_est_ns()

                self._registry.prewarm_ladder(
                    [spec.with_batch(r) for r in rungs
                     if r != self.batch],
                    max_msg_len=self.max_msg_len)
                new_specs |= {spec.with_batch(r) for r in rungs}
            self.rung_sched = fd_engine.RungScheduler(
                rungs, self.max_wait_ns, cost_ns=cost,
                shards=self.mesh_devices or 1)
            self.flush_policy = self.rung_sched.flush
            self.fl.set_gauge("rung_cur", rungs[0])
            self._rung_last = rungs[0]
        retired = 0
        if self.backend == "tpu":
            retired = self._registry.retire(
                [s for s in old_specs if s not in new_specs])
        drain_flip = "FD_DRAIN" in (req.get("env") or {})
        if drain_flip:
            # _drain_setup re-reads drain_mode() and rebuilds (or
            # tears down) the aux graph from scratch — it is the one
            # FD_DRAIN resolution point, so the flip routes through it.
            self._drain_setup()
        with self._reconfig_lock:
            self._reconfig_pending = None
        self.fl.inc("reconfigs")
        self.flightrec.record(
            "reconfig", seq=req["seq"], mode=mode, engine=spec.key,
            rungs=list(rungs) if rungs else None, retired=retired,
            cold_primary=cold_primary, drain=drain_flip,
            barrier_acked=self._acked_seq,
            apply_ms=round((time.perf_counter() - t0) * 1e3, 3))

    def _feed_poll(self):
        """Dispatcher round (the feed-mode poll_inputs): retire one
        completion, ship every READY slot up to the in-flight cap, and
        account device idleness (nothing in flight AND nothing READY =
        the engine is starving — the gauge this subsystem exists to
        drive to zero)."""
        if not self._feed_started:
            self._feed_start()
        self._stager_supervise()
        self._complete(block=False)
        if self._reconfig_pending is not None and not self._inflight:
            # fd_soak live-reconfig barrier: with a swap pending, new
            # dispatches hold until the inflight WINDOW drains (the
            # stager keeps staging — READY slots queue and upstream
            # rings absorb offered load), then the swap happens in the
            # gap. Never a whole-pipeline drain.
            self._apply_reconfig()
        progressed = False
        if self._reconfig_pending is None:
            while len(self._inflight) < self.inflight_max:
                slot = self.feed_pool.pop_ready()
                if slot is None:
                    break
                self._feed_dispatch(slot)
                progressed = True
        now = tempo.tickcount()
        if self.stat_batches and not self._inflight \
                and self.feed_pool.ready_cnt() == 0:
            if self._feed_idle_mark:
                self.fl.inc("feed_idle_ns", now - self._feed_idle_mark)
            self._feed_idle_mark = now
        else:
            self._feed_idle_mark = 0
        if not progressed:
            # Same GIL-citizenship as the stager: the dispatcher has
            # nothing until a slot commits (>= one drain round away) or
            # a device batch completes — don't hot-spin the run loop at
            # the source publisher's expense. Completions of an ALREADY
            # in-flight batch are polled on a shorter nap.
            time.sleep(50e-6 if self._inflight else 100e-6)
        return progressed, False

    def _publish_feed_diag(self) -> None:
        """Publish the tile's flight-registry lane (breaker gauges,
        slot stalls, and every dispatch/healing counter) to shared
        memory, and keep the legacy CNC_DIAG_FEED_* mirror for the
        16-slot cnc ABI (crash-surviving, read by old tooling)."""
        if self._feed:
            # Pool-owned stat: fold into the lane so the shared row is
            # the one authority (delta via counter semantics: the lane
            # value tracks the pool's monotonically).
            stall = self.feed_pool.slot_stall
            have = self.fl.get("slot_stall")
            if stall > have:
                self.fl.inc("slot_stall", stall - have)
        b = self._breaker
        bstate = b.state if b is not None else "disabled"
        self.fl.set_gauge("breaker_state",
                          flight.BREAKER_STATE_CODE.get(bstate, 3))
        if b is not None:
            self.fl.set_gauge("breaker_trips", b.trips)
            self.fl.set_gauge("breaker_reprobes", b.reprobes)
            cur = (b.state, b.trips, b.reprobes)
            if cur != self._breaker_pub and self._breaker_pub[0] is not None:
                self.flightrec.record("breaker", state=b.state,
                                      trips=b.trips, reprobes=b.reprobes)
                self._xr_trigger("breaker", state=b.state, trips=b.trips,
                                 reprobes=b.reprobes)
            self._breaker_pub = cur
        self.fl.publish()
        for shard in self.fl_shards:
            shard.publish()
        vals = (
            self.stat_batches, self.stat_lanes, self.stat_flush_timeout,
            self.stat_flush_starved,
            self.feed_pool.slot_stall if self._feed else 0,
            self.stat_feed_idle_ns,
        )
        for i, (slot_idx, v) in enumerate(zip(
            (CNC_DIAG_FEED_BATCHES, CNC_DIAG_FEED_LANES,
             CNC_DIAG_FEED_DEADLINE, CNC_DIAG_FEED_STARVED,
             CNC_DIAG_FEED_SLOT_STALL, CNC_DIAG_FEED_IDLE_NS),
            vals,
        )):
            if v != self._feed_diag_mirror[i]:
                self.cnc.diag_add(
                    slot_idx, (v - self._feed_diag_mirror[i]) & _U64
                )
                self._feed_diag_mirror[i] = v

    def _dispatch_native(self, force: bool = False) -> None:
        jnp = self._jnp
        if not self._pending:
            return
        if not force and self._pending_lanes < self.batch:
            return
        while len(self._inflight) >= self.inflight_max:
            self.fl.inc("inflight_stall")
            self._complete(block=True)
        via_device = False
        if self.backend == "cpu":
            # Host path: one synchronous C call over the staged rows —
            # no copies (the buffers are free to reuse once it returns).
            from firedancer_tpu.ballet.ed25519 import native as ed_native

            try:
                out = _ReadyBatch(ed_native.verify_arrays(
                    self._nd_msgs, self._nd_lens, self._nd_sigs,
                    self._nd_pubs, self._pending_lanes,
                ))
            except Exception:
                # Verifier raised mid-batch: quarantine inline (per-txn
                # CPU oracle verdicts) instead of killing the tile.
                self.fl.inc("quarantined")
                self.flightrec.record("quarantine",
                                      lanes=self._pending_lanes)
                out = _ReadyBatch(self._oracle_statuses_todo(self._pending))
        else:
            if self._pending_lanes < self.batch:
                # Stale rows from the previous batch must verify as pad
                # lanes (zero sig/pub/len — _dispatch_py's padding), not
                # as leftover signatures: under rlc a stale lane poisons
                # the whole-batch equation into a permanent fallback.
                self._nd_lens[self._pending_lanes:] = 0
                self._nd_sigs[self._pending_lanes:] = 0
                self._nd_pubs[self._pending_lanes:] = 0
            out = self._verify_batch_fn(
                jnp.asarray(self._nd_msgs.copy()),
                jnp.asarray(self._nd_lens.astype(np.int32)),
                jnp.asarray(self._nd_sigs.copy()),
                jnp.asarray(self._nd_pubs.copy()),
            )
            via_device = True
        todo = self._pending
        lanes0 = self._pending_lanes
        self.fl.inc("lanes", self._pending_lanes)
        self._book_shard_lanes(self._pending_lanes)
        self._pending = []
        self._pending_lanes = 0
        self._nd_pay_fill = 0
        self._inflight.append(_InflightBatch(
            out=out, todo=todo, oversize=[False] * self.batch,
            t_dispatch=tempo.tickcount(), device=via_device,
        ))
        self.fl.inc("batches")
        if self._xr_on:
            self._xr_batch(
                np.array([t[2] for t in todo], np.uint64), len(todo),
                FLUSH_FULL if lanes0 >= self.batch else "partial",
                via_device)

    def _ack_inline(self, frag: Frag) -> None:
        """A frag handled to completion inside on_frag (filtered or
        oracle-verified) is ackable immediately — but only when nothing
        older is still staged on the device."""
        if not self._pending and not self._inflight:
            self._acked_seq = frag.seq + 1

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        if frag.ctl & CTL_ERR:
            # Producer-flagged error frag (the Python-path analog of the
            # native drain's ctl word drop): filter, never verify.
            self.fl.inc("ctl_err_drop")
            self.flightrec.record("ctl_err_drop", n=1)
            self._xr_trigger("ctl_err", tsorigs=[frag.tsorig], n=1)
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, 1)
            self.cnc.diag_add(CNC_DIAG_SV_FILT_SZ, len(payload))
            c = chaos.active()
            if c is not None:
                c.on_ctl_err_drop(1)
            self._ack_inline(frag)
            self._flush_if_due()
            return
        try:
            txn = parse_txn(payload)
        except TxnParseError:
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, 1)
            self.cnc.diag_add(CNC_DIAG_SV_FILT_SZ, len(payload))
            self._ack_inline(frag)
            # A stream of filtered frags keeps the drain loop hot (no
            # on_idle): the staged batch's max-wait must be checked here
            # too, or a flood of junk would strand a partial batch.
            self._flush_if_due()
            return
        # High-availability dup filter before paying for the verify
        # (synth-load FD_TCACHE_INSERT ha_tag analog). The tag covers the
        # WHOLE payload, not the signature prefix: this filter runs before
        # sigverify, so a corrupted copy of a pending txn (same signature
        # bytes, flipped payload byte — or vice versa) must not shadow the
        # valid original out of the tcache. Signature-keyed dedup is safe
        # only post-verify (the dedup tile's meta_sig).
        ha_tag = hash(payload)
        if self.ha_tcache.insert(ha_tag):
            self.cnc.diag_add(CNC_DIAG_HA_FILT_CNT, 1)
            self.cnc.diag_add(CNC_DIAG_HA_FILT_SZ, len(payload))
            self._ack_inline(frag)
            self._flush_if_due()  # see TxnParseError path
            return
        items = list(txn.verify_items(payload))
        if self.backend in ("cpu", "oracle"):
            if self.backend == "cpu":
                # Bulk path: the native C++ verifier (>=10k/s/core) when
                # built, else the Python oracle — same status contract,
                # differentially pinned in tests/test_ed25519_cpu.py.
                from firedancer_tpu.ballet.ed25519 import native as ed_native

                statuses = ed_native.verify_items(items)
            else:
                # 'oracle' pins the pure-Python reference — a
                # cross-check lane must never silently dispatch to an
                # out-of-band .so (round-4 advisor finding).
                from firedancer_tpu.ballet.ed25519 import oracle as ed_oracle

                statuses = [ed_oracle.verify(msg, sig, pub)
                            for (sig, pub, msg) in items]
            ok = all(st == 0 for st in statuses)
            self._finish(payload, ok, tsorig=frag.tsorig)
            self._ack_inline(frag)
            return
        if len(items) > self.batch or any(
            len(msg) > self.max_msg_len for (_, _, msg) in items
        ):
            # A txn with more sigs than device lanes, or a message longer
            # than the staging width (can't happen when max_msg_len is
            # the MTU, but don't trust the wire — and never silently
            # truncate a message into a false reject): verify on the
            # CPU fallback, like the native drain's oversize path.
            from firedancer_tpu.ballet.ed25519 import native as ed_native

            ok = all(st == 0 for st in ed_native.verify_items(items))
            self._finish(payload, ok, tsorig=frag.tsorig)
            self._ack_inline(frag)
            return
        if not self._pending:
            self._pending_since = tempo.tickcount()
        self._pending.append((payload, items, frag.tsorig, frag.seq + 1))
        self._pending_lanes += len(items)
        self._flush_if_due()
        self._complete(block=False)

    def _note_shard_rows(self, arr) -> None:
        """Record where a mesh dispatch's batch rows landed: device id
        -> rows of the array actually handed to the engine (read from
        its addressable shards, not recomputed from the sharding)."""
        self.stat_shard_rows = {
            int(sh.device.id): sh.index[0].stop - sh.index[0].start
            for sh in arr.addressable_shards
        }

    def _book_shard_lanes(self, n_lane: int, shape: int = 0) -> None:
        """Per-mesh-shard dispatch accounting: shard_map partitions the
        batch axis contiguously over 'dp', so shard i owns lanes
        [i*per, (i+1)*per) — book each shard's slice of the real (non-
        pad) lanes into its flight row. The slices sum to n_lane by
        construction, so the merged (sum-of-shards) snapshot equals
        this tile's own lanes counter (test-pinned). `shape` is the
        dispatched batch when it differs from the staging batch (a
        reduced fd_engine rung on the feed path)."""
        if not self.fl_shards:
            return
        per = (shape or self.batch) // len(self.fl_shards)
        for i, lane in enumerate(self.fl_shards):
            lane.inc("batches")
            lane.inc("lanes", min(max(n_lane - i * per, 0), per))

    def _ring_starved(self) -> bool:
        """The held-back ack cursor is about to exhaust the producer's
        credits: flush now rather than letting max-wait decide — a
        partial batch beats a stalled pipeline."""
        il = self.in_link
        return il is not None and (
            il.seq - self._acked_seq >= max(1, il.mcache.depth - 64)
        )

    def _flush_if_due(self, starved: bool = False) -> None:
        """Dispatch a staged batch when it is full, when the held-back
        ack cursor is about to starve the producer's credits, or when
        the adaptive policy says so (deadline expiry, or starved input
        with an idle device — disco/feed/policy.py). Called from every
        path that can make progress without going idle (frag drain,
        filtered frags, housekeeping), so a continuous input stream can
        never strand a partial batch (round-2 ADVICE finding). In feed
        mode the stager owns flushing; this is a no-op."""
        if self._feed or not self._pending:
            return
        if self._pending_lanes >= self.batch:
            self._dispatch()
            return
        if self._ring_starved():
            self._dispatch(force=True)
            return
        verdict = self.flush_policy.due(
            tempo.tickcount(), self._pending_lanes, self.batch,
            self._pending_since, starved=starved,
            device_idle=not self._inflight,
            # The housekeep-refreshed gauge, not a fresh fseq probe:
            # this runs per frag on the Python path.
            backpressured=bool(self.out_link.fctl.in_backpressure)
            if self.out_link else False,
        )
        if verdict == FLUSH_DEADLINE:
            self.fl.inc("flush_timeout")
            self.flightrec.record("flush", verdict=verdict,
                                  lanes=self._pending_lanes)
            self._dispatch(force=True)
        elif verdict == FLUSH_STARVED:
            self.fl.inc("flush_starved")
            self.flightrec.record("flush", verdict=verdict,
                                  lanes=self._pending_lanes)
            self._dispatch(force=True)
        # FLUSH_FULL is unreachable here: the lanes >= batch case
        # dispatched above, and this method is single-threaded.

    def on_idle(self) -> None:
        if self._inflight:
            self._complete(block=False)
        self._flush_if_due(starved=True)

    def housekeep(self, now: int) -> None:
        # Publish the VERIFIED cursor, not the consumed one: a crash
        # between consume and verify-complete must leave the frags
        # re-readable for the respawned worker (crash-only recovery).
        # Flow control self-heals: held-back credits return as batches
        # complete, and the max-wait flush bounds how long a partial
        # batch can hold them. Everything else (out-link credit refresh,
        # backpressure diag mirror, on_housekeep's max-wait backstop)
        # must still run — the base housekeep minus the in-link fseq
        # publication, which is replaced by the verified cursor above.
        self._beat(now)
        for il in self.in_links:
            il.fseq.update(min(self._acked_seq, il.seq))
        self._publish_unacked()
        self._publish_feed_diag()
        self._housekeep_out()
        self.on_housekeep()

    def _publish_unacked(self) -> None:
        unacked = 0
        for il in self.in_links:
            unacked += max(0, il.seq - self._acked_seq)
        if unacked != self._last_unacked:
            self.cnc.diag_add(
                CNC_DIAG_UNACKED, (unacked - self._last_unacked) & _U64
            )
            self._last_unacked = unacked

    def on_housekeep(self) -> None:
        # The housekeeping interval is the latency backstop when the tile
        # sits in the frag-drain fast path and never goes idle.
        if self._inflight:
            self._complete(block=False)
        self._flush_if_due()

    def on_halt(self) -> None:
        # Drain device work so no async computation outlives the tile;
        # results are published best-effort (publish_backp drops on HALT).
        if self._feed:
            # Stop the stager first (it owns the in-ring cursor), then
            # flush everything it staged: the leftover FILLING slot,
            # every READY slot, and all in-flight batches.
            self._feed_stop.set()
            if self._feed_thread is not None:
                self._feed_thread.join(timeout=10.0)
            slot = self._feed_slot
            if slot is not None:
                if slot.n_txn:
                    self._feed_commit(slot, "halt")
                else:
                    # An empty FILLING slot must return to FREE, or the
                    # pool-integrity audit (slots_leaked) reads a
                    # phantom leak at every shutdown.
                    self._feed_slot = None
                    self.feed_pool.release(slot)
            while True:
                s = self.feed_pool.pop_ready()
                if s is None:
                    break
                self._feed_dispatch(s)
            self._complete(block=True, drain_all=True)
            if self._feed_exec is not None:
                self._feed_exec.shutdown(wait=True)
            self._publish_feed_diag()
            return
        if self._pending and (self.backend == "tpu" or self._nd):
            self._dispatch(force=True)
        self._complete(block=True, drain_all=True)

    # -- async offload shim ----------------------------------------------

    def _dispatch(self, force: bool = False) -> None:
        if self._nd:
            self._dispatch_native(force)
        else:
            self._dispatch_py(force)
        if self._hold_s and not self._held and self._inflight:
            # Fault-injection hold (see __init__): gauge first, so the
            # supervisor-side observer is guaranteed to see the staged
            # work before the window closes. Heartbeats stay live so
            # the wedge detector doesn't race the test's fault hook for
            # the kill.
            self._held = True
            self._publish_unacked()
            self.cnc.diag_add(CNC_DIAG_HOLDS, 1)
            self._with_live_heartbeat(lambda: time.sleep(self._hold_s))

    def _dispatch_py(self, force: bool = False) -> None:
        """Ship pending txns to the device as fixed-shape batches without
        waiting for results (jax dispatches asynchronously). Whole txns
        only per batch — a txn's sigs never straddle two batches, so each
        completion is self-contained. Unless force, a trailing partial
        batch stays pending (it ships on batch-full or max-wait)."""
        jnp = self._jnp
        while self._pending and (force or self._pending_lanes >= self.batch):
            # Txns stay in _pending until the in-flight record exists: the
            # supervisor's quiescence check reads `_pending or _inflight`
            # from another thread, and a batch held only in locals would be
            # invisible to it — HALT could race in and drop the batch.
            take = 0
            flat = []
            for _, items, _, _ in self._pending:
                if len(flat) + len(items) > self.batch:
                    break
                flat.extend(items)
                take += 1
            todo = [
                (payload, len(items), tsorig, seq_end)
                for payload, items, tsorig, seq_end in self._pending[:take]
            ]
            # Back-pressure the shim, not the device: cap in-flight batches
            # (wiredancer polls the DMA fill level, wd_f1.c:352-358).
            while len(self._inflight) >= self.inflight_max:
                self.fl.inc("inflight_stall")
                self._complete(block=True)
            pad = [(b"\x00" * 64, b"\x00" * 32, b"")] * (self.batch - len(flat))
            msgs, lens, sigs, pubs = _txn_batch_arrays(
                flat + pad, self.max_msg_len
            )
            out = self._verify_batch_fn(
                jnp.asarray(msgs), jnp.asarray(lens), jnp.asarray(sigs),
                jnp.asarray(pubs),
            )
            # A message longer than the staging width cannot be verified on
            # device; fail it rather than trusting a truncated hash.
            oversize = [len(msg) > self.max_msg_len for (_, _, msg) in flat]
            self._inflight.append(_InflightBatch(
                out=out, todo=todo, oversize=oversize,
                t_dispatch=tempo.tickcount(), device=True,
            ))
            self.fl.inc("batches")
            self.fl.inc("lanes", len(flat))
            self._book_shard_lanes(len(flat))
            if self._xr_on:
                self._xr_batch(
                    np.array([t[2] for t in todo], np.uint64), len(todo),
                    FLUSH_FULL if len(flat) >= self.batch else "partial",
                    True,
                    tlanes=np.array([t[1] for t in todo], np.int64))
            del self._pending[:take]
            self._pending_lanes -= len(flat)
            if self._pending:
                self._pending_since = tempo.tickcount()

    def _complete(self, block: bool, drain_all: bool = False) -> None:
        """Retire finished device batches in dispatch order, publishing
        results downstream (the completion half of the wiredancer shim)."""
        while self._inflight:
            ib = self._inflight[0]
            if not block and not ib.out.is_ready():
                return
            # Found ready (a blocking retire books its wait as retire).
            t_ready = tempo.tickcount()
            c = chaos.active()
            quarantined = False
            try:
                if c is not None:
                    c.verify_complete_hook()  # may raise ChaosBackendError
                with self._span("fd.verify.readback"):
                    statuses = np.asarray(ib.out)  # blocks only if not ready
            except Exception as e:
                # Poisoned batch: the verify raised instead of returning
                # statuses. Quarantine — re-verify the whole batch on
                # the CPU oracle lane (offenders will publish CTL_ERR,
                # clean txns publish normally) — so a backend error
                # fails at most the txns that deserve it and the slot
                # always returns to the pool. Device-lane failures also
                # feed the failover breaker.
                quarantined = True
                self.fl.inc("quarantined")
                self.flightrec.record("quarantine",
                                      err=repr(e)[:120])
                if self._xr_on:
                    ids = (ib.slot.tsorigs[:ib.slot.n_txn]
                           if ib.slot is not None
                           else np.array([t[2] for t in ib.todo],
                                         np.uint64))
                    self._xr_trigger("quarantine", ids,
                                     err=repr(e)[:80])
                if ib.device and self._breaker is not None:
                    self._breaker.record_error(tempo.tickcount())
                fault_cls = (e.cls if isinstance(e, chaos.ChaosFault)
                             else None)
                if c is not None and fault_cls is not None:
                    c.note(fault_cls, "detected")
                statuses = self._quarantine_statuses(ib)
                if c is not None and fault_cls is not None:
                    c.note(fault_cls, "healed")
            if not quarantined:
                if ib.device and self._breaker is not None:
                    self._breaker.record_success()
                if getattr(ib.out, "used_fallback", False):
                    self.fl.inc("rlc_fallback")
                if ib.entry is not None:
                    # fd_engine cost model: feed the engine's service
                    # EMA (dispatch -> clean completion wall time) so
                    # the rung scheduler's slack capping tracks the
                    # device instead of a guess.
                    ib.entry.note_service(
                        tempo.tickcount() - ib.t_dispatch)
            if ib.slot is not None:
                # fd_feed batch: verdicts + publishes straight off the
                # slot's sidecar arrays (one bulk native call). A
                # quarantined batch publishes claim-free: its drain aux
                # dispatch shares the poisoned queue, so its claims are
                # untrusted too (all maybe-dup = exact downstream).
                with self._span("fd.verify.publish"):
                    batch_ack = self._publish_feed_batch(
                        ib.slot, statuses, quarantined=quarantined,
                        drain=None if quarantined else ib.drain)
                book_retire(self.fl, ib, t_ready, tempo.tickcount())
            else:
                off = 0
                batch_ack = 0
                for payload, cnt, tsorig, seq_end in ib.todo:
                    batch_ack = max(batch_ack, seq_end)
                    if payload is None:  # HA-filtered post-staging
                        off += cnt
                        continue
                    lane = statuses[off : off + cnt]
                    over = any(ib.oversize[off : off + cnt])
                    ok = cnt > 0 and not over and bool((lane == 0).all())
                    self._finish(payload, ok, tsorig=tsorig)
                    if quarantined and not ok:
                        self._publish_err(payload, meta_sig(payload))
                    off += cnt
            # Pop only AFTER the batch's results are published: the
            # supervisor's quiescence check reads _inflight from another
            # thread, and popping first opens a window where the
            # pipeline looks drained, HALT lands, and publish_backp
            # drops this batch's output.
            self._inflight.pop(0)
            if ib.slot is not None:
                self.feed_pool.release(ib.slot)
            # Batches retire in dispatch order, so the newest seq carried
            # by this batch is now fully verified and ackable; with the
            # device idle, everything consumed is. In feed mode "device
            # idle" must also mean the STAGER holds nothing: frags
            # consumed into a slot but not yet dispatched are exactly
            # the crash window the held-back ack protects (the stager
            # makes staged txns visible — slot.n_txn — BEFORE advancing
            # il.seq, so this check cannot race past them).
            self._acked_seq = max(self._acked_seq, batch_ack)
            if (not self._pending and not self._inflight and self.in_link
                    and (not self._feed or self.feed_pool.idle())):
                self._acked_seq = self.in_link.seq
            if not drain_all:
                return  # retire at most one per call; keep the loop hot

    def _finish(self, payload: bytes, ok: bool, tsorig: int = 0) -> None:
        if not ok:
            self.cnc.diag_add(CNC_DIAG_SV_FILT_CNT, 1)
            self.cnc.diag_add(CNC_DIAG_SV_FILT_SZ, len(payload))
            return
        self.publish_backp(payload, meta_sig(payload), tsorig=tsorig)


class DedupTile(Tile):
    """tcache dedup on the frag meta sig (disco/dedup/fd_dedup.c).

    The hot path is VECTORIZED over the bulk fd_frag_drain rounds
    (round-18, the REPLAY_CPU lever): one C drain call per round, the
    membership test batched through TCache.insert_batch (numpy
    unique/scatter instead of a per-frag Python probe), the CTL_ERR
    and duplicate masks folded with numpy, diag counters published as
    per-round sums, and every surviving frag forwarded with ONE
    fd_frag_publish_bulk call per credit window — the per-frag Python
    (Frag construction, on_frag dispatch, per-frag dcache/mcache
    ctypes round-trips) that made dedup the host pipeline's widest
    per-frag stage is gone from the steady state. on_frag keeps the
    exact legacy semantics for the pure-Python poll path (no native
    .so) and is the behavior oracle the bulk path is content-pinned
    against."""

    name = "dedup"

    def __init__(self, wksp, cnc_name, in_link=None, out_link=None,
                 tcache_depth: int = 4096, in_links=None, **kw):
        # The reference dedup is mux+tcache (dedup/fd_dedup.h:57-80):
        # several verify lanes fan in here via in_links.
        super().__init__(wksp, cnc_name, in_link=in_link, out_link=out_link,
                         in_links=in_links, **kw)
        self.tcache = TCache(tcache_depth)
        # fd_drain consumption: device novel claims arrive in the ctl
        # word (CTL_NOVEL); a claimed frag's dup verdict is owed to the
        # filter (probe skip), the map lookup downgrades to a contract
        # tripwire, and the claim bit is stripped before forwarding
        # (pack color/block bits pass through untouched). The lane rows
        # here are the dedup half of the smoke's probe parity gate:
        # drain_probe_skip + drain_probed == verify's novel + maybe.
        self.fl = flight.tile_lane(wksp, self.flight_label)

    def poll_inputs(self):
        if self.out_link is None:
            return super().poll_inputs()
        progressed = False
        overrun = False
        for il in self.in_links:
            st = self._bulk_state(il)
            ct = st["ct"]
            seq = ct.c_uint64(il.seq)
            ovr0 = int(st["ctr"][1])
            args = [
                il.mcache._mem, ct.addressof(il.dcache._buf),
                ct.byref(seq), self.BULK_FRAGS, st["cap"],
                st["pay"].ctypes.data, st["pay"].nbytes,
                st["offs"].ctypes.data, st["lens"].ctypes.data,
                st["sigs"].ctypes.data, st["ts"].ctypes.data,
                st["seqs"].ctypes.data, st["ctls"].ctypes.data,
                st["tspubs"].ctypes.data, st["ctr"].ctypes.data,
            ]
            n = st["lib"].fd_frag_drain(*args)
            d_ovr = int(st["ctr"][1]) - ovr0
            if d_ovr:
                il.fseq.diag_add(DIAG_OVRNR_CNT, d_ovr)
                overrun = True
            if n > 0:
                self.in_cur = il
                self._dedup_round(il, st, n)
                progressed = True
            # Cursor semantics match the base bulk path: il.seq
            # advances only after the round is fully processed, so a
            # crash mid-round replays it (dedup itself absorbs the
            # replays downstream of a respawn).
            il.seq = seq.value
        return progressed, overrun

    def _dedup_round(self, il, st, n: int) -> None:
        """One vectorized dedup round: masks + counters + bulk publish
        — per-frag semantics (CTL_ERR drop before the tcache insert,
        whole-payload order preserved, tsorig carried through, sampled
        xray dwell) exactly as on_frag, minus the per-frag Python."""
        lens = st["lens"][:n]
        sigs = st["sigs"][:n]
        err = (st["ctls"][:n] & CTL_ERR) != 0
        # CTL_ERR frags (quarantine audit trail) are counted + dropped
        # BEFORE the tcache probe — a poisoned copy must never shadow
        # the valid same-sig txn out of the dedup window — so only the
        # clean frags' sigs enter the batched membership test.
        from firedancer_tpu.disco.drain import CTL_NOVEL

        clean = ~err
        novel = ((st["ctls"][:n] & CTL_NOVEL) != 0) & clean
        dup = np.zeros(n, np.bool_)
        if clean.any():
            fn0 = self.tcache.false_novel_cnt
            dup[clean] = self.tcache.insert_batch(
                sigs[clean],
                novel=novel[clean] if novel.any() else None)
            n_novel = int(novel.sum())
            if n_novel:
                self.fl.inc("drain_probe_skip", n_novel)
            self.fl.inc("drain_probed", int(clean.sum()) - n_novel)
            d_fn = self.tcache.false_novel_cnt - fn0
            if d_fn:
                # One-sided contract breach: ledger it loudly (the
                # offending frags already got the exact dup verdict, so
                # correctness held — but a nonzero here means the
                # filter/rotation proof is broken upstream).
                self.fl.inc("drain_false_novel", d_fn)
                self.flightrec.record("drain_false_novel", n=d_fn)
        filt = err | dup
        n_filt = int(filt.sum())
        if n_filt:
            il.fseq.diag_add(DIAG_FILT_CNT, n_filt)
            il.fseq.diag_add(DIAG_FILT_SZ, int(lens[filt].sum()))
        if il.xq is not None:
            # Stride-sampled queue dwell, same cadence as the per-frag
            # path (every xq_every'th drained frag).
            now32 = tempo.tickcount() & 0xFFFFFFFF
            sel = np.nonzero((il.xq_cnt + 1 + np.arange(n))
                             % il.xq_every == 0)[0]
            il.xq_cnt += n
            for i in sel.tolist():
                tspub = int(st["tspubs"][i])
                if tspub:
                    il.xq.observe_dwell((now32 - tspub) & 0xFFFFFFFF)
        mask8 = (~filt).astype(np.uint8)
        n_ok = int(mask8.sum())
        self.fl.publish()
        if not n_ok:
            return
        # Forward ctl: strip the consumed NOVEL claim, pass the pack
        # color/block hints through to PackTile.
        ctls_fwd = st["ctls"][:n] & np.uint16(0xFFFF ^ CTL_NOVEL)
        ol = self.out_link
        ct = st["ct"]
        seqv = ct.c_uint64(ol.seq)
        chunkv = ct.c_uint32(ol.chunk)
        cursor = ct.c_uint32(0)
        bytes_out = np.zeros(1, np.uint64)
        now32 = tempo.tickcount() & 0xFFFFFFFF
        published = 0
        halted = False
        while published < n_ok and not halted:
            # Credit-windowed bulk publish: publish_backp's fctl
            # discipline (spin through backpressure, drop on HALT),
            # amortized over the window instead of paid per frag.
            t_stall = 0
            while not ol.can_publish():
                if self.cnc.signal_query() == CNC_HALT:
                    halted = True  # drop the rest, like publish_backp
                    break
                if not t_stall:
                    t_stall = tempo.tickcount()
                self.cnc.diag_add(CNC_DIAG_BACKP_CNT, 1)
                time.sleep(20e-6)
            if t_stall and ol.xq_tx is not None:
                ol.xq_tx.add_stall(tempo.tickcount() - t_stall)
            if halted:
                break
            pub = st["lib"].fd_frag_publish_bulk_ctl(
                ol.mcache._mem, ct.addressof(ol.dcache._buf),
                ol.dcache.chunk_cnt, ol.mtu,
                ct.byref(seqv), ct.byref(chunkv),
                st["pay"].ctypes.data,
                st["offs"].ctypes.data, st["lens"].ctypes.data,
                st["sigs"].ctypes.data, st["ts"].ctypes.data,
                ctls_fwd.ctypes.data,
                mask8.ctypes.data, ct.byref(cursor), n,
                min(ol.cr_avail, n_ok - published), now32,
                bytes_out.ctypes.data,
            )
            ol.seq = seqv.value
            ol.chunk = chunkv.value
            ol.cr_avail = max(0, ol.cr_avail - pub)
            published += pub
            if pub <= 0:
                break  # defensive: cursor exhausted without publishes
        il.fseq.diag_add(DIAG_PUB_CNT, published)
        il.fseq.diag_add(DIAG_PUB_SZ, int(bytes_out[0]))
        # Stage-latency samples (OutLink.publish is bypassed on the
        # bulk path): vectorized histogram + reservoir, the
        # _publish_feed_batch pattern.
        ts = st["ts"][:n][~filt]
        ts = ts[ts != 0]
        if ts.size:
            lats = (now32 - ts.astype(np.int64)) & 0xFFFFFFFF
            ol.lat_sample_many(lats, ts)

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        from firedancer_tpu.disco.drain import CTL_NOVEL

        if frag.ctl & CTL_ERR:
            # Quarantine audit frags (verify's CTL_ERR offenders) end
            # here: counted + dropped BEFORE the tcache insert — a
            # poisoned copy must never shadow the valid same-sig txn
            # out of the dedup window.
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            self.in_cur.fseq.diag_add(DIAG_FILT_SZ, frag.sz)
            return
        if frag.ctl & CTL_NOVEL:
            # fd_drain claim on the per-frag path: verdict owed to the
            # device filter; the insert keeps exact ring order and the
            # tripwire restores the dup verdict on a contract breach.
            self.fl.inc("drain_probe_skip")
            breach = self.tcache.insert_novel_batch([frag.sig])
            if not breach[0]:
                self.publish_backp(payload, frag.sig, tsorig=frag.tsorig)
                return
            self.fl.inc("drain_false_novel")
            self.flightrec.record("drain_false_novel", n=1)
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            self.in_cur.fseq.diag_add(DIAG_FILT_SZ, frag.sz)
            return
        self.fl.inc("drain_probed")
        if self.tcache.insert(frag.sig):
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            self.in_cur.fseq.diag_add(DIAG_FILT_SZ, frag.sz)
            return
        self.publish_backp(payload, frag.sig, tsorig=frag.tsorig)


class PackTile(Tile):
    """Account-lock conflict scheduling into bank lanes
    (app/frank/fd_frank_pack.c + ballet/pack semantics). Scheduled txns
    are published downstream with the bank index in the high sig bits;
    completion is immediate (the sink stands in for bank execution)."""

    name = "pack"

    def __init__(self, wksp, cnc_name, in_link, out_link, bank_cnt: int = 4,
                 scheduler: str = "greedy", gc_block: int = 1024, **kw):
        from firedancer_tpu.ballet.pack import CuEstimator, Pack

        super().__init__(wksp, cnc_name, in_link=in_link, out_link=out_link, **kw)
        if scheduler not in ("greedy", "gc"):
            raise ValueError(f"unknown pack scheduler {scheduler!r}")
        self.pack = Pack(bank_cnt=bank_cnt)
        self.est = CuEstimator()
        self.bank_cnt = bank_cnt
        # scheduler="gc": block-batched XLA graph coloring (ops/pack_gc,
        # the BASELINE stretch) instead of the streaming CPU greedy heap.
        # Waves are conflict-free parallel batches; txns within a wave
        # spread round-robin over banks. gc_block bounds batching latency.
        self.scheduler = scheduler
        self.gc_block = gc_block
        self._gc_pending: list = []
        self._next_txn_id = 0
        self._payloads: dict = {}
        self._tsorig: dict = {}
        self._rr_bank = 0
        # fd_drain device wave schedules: txns arriving with a ctl
        # color hint accumulate per device block id and publish as the
        # device's waves once the block closes — IF the block passes
        # ballet.pack.validate_schedule AND beats CPU greedy rewards/CU
        # (else exact ledgered fallback to the greedy waves). The lane
        # rows carry the accounting gate: pack_block_device +
        # pack_sched_fallback == blocks scheduled.
        self._dev_block: list = []          # [(color, PackTxn)]
        self._dev_block_id: Optional[int] = None
        self.fl = flight.tile_lane(wksp, self.flight_label)

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        from firedancer_tpu.ballet.pack import PackTxn

        try:
            txn = parse_txn(payload)
        except TxnParseError:
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            return
        writable = frozenset(
            txn.account(payload, i)
            for i in range(txn.acct_cnt)
            if txn.is_writable(i)
        )
        readonly = frozenset(
            txn.account(payload, i)
            for i in range(txn.acct_cnt)
            if not txn.is_writable(i)
        )
        from firedancer_tpu.ballet.compute_budget import (
            estimate_rewards_and_compute,
        )

        rce = estimate_rewards_and_compute(
            txn, payload, lamports_per_signature=5000, estimator=self.est
        )
        if rce is None:
            # Malformed ComputeBudgetProgram instruction: whole txn fails
            # (fd_pack.c:298-299 drops it at insert time).
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            return
        rewards, est_cus, _cu_limit = rce
        if est_cus > self.pack.max_cu_per_bank:
            # Can never fit any bank/wave budget: no scheduler ever picks
            # it (the greedy heap would hold it forever; the GC rounds
            # would re-color it forever). The reference similarly bounds
            # insertable cost. Drop + count.
            self.in_cur.fseq.diag_add(DIAG_FILT_CNT, 1)
            self.in_cur.fseq.diag_add(DIAG_FILT_SZ, len(payload))
            return
        tid = self._next_txn_id
        self._next_txn_id += 1
        pt = PackTxn(
            txn_id=tid,
            rewards=rewards,
            est_cus=est_cus,
            writable=writable,
            readonly=readonly,
        )
        self._payloads[tid] = payload
        self._tsorig[tid] = frag.tsorig
        if self.scheduler == "gc":
            from firedancer_tpu.disco.drain import ctl_block, ctl_color

            color = ctl_color(frag.ctl)
            if color >= 0:
                # fd_drain device color: collect into the current
                # device block (block id changes close the previous
                # one — frags arrive in publish order, so a block's
                # txns are contiguous).
                blk = ctl_block(frag.ctl)
                if self._dev_block_id is not None \
                        and blk != self._dev_block_id:
                    self._close_dev_block()
                self._dev_block_id = blk
                self._dev_block.append((color, pt))
                if len(self._dev_block) >= self.gc_block:
                    self._close_dev_block()
                return
            self._gc_pending.append(pt)
            if len(self._gc_pending) >= self.gc_block:
                self._drain_gc()
            return
        self.pack.insert(pt)
        self._drain()

    def on_idle(self) -> None:
        if self.scheduler == "gc":
            if self._dev_block:
                self._close_dev_block()
            if self._gc_pending:
                self._drain_gc()
            return
        self._drain()

    def _drain_gc(self) -> None:
        """Schedule the pending block on the device scheduler and publish
        wave by wave (waves are admissible parallel batches; the CPU
        Pack/validate_schedule semantics are pinned by tests/test_pack_gc
        and the bench's admissibility gate)."""
        from firedancer_tpu.ops.pack_gc import schedule_block

        # _gc_pending stays populated through the (slow: possible XLA
        # compile) device call and the publishes — the supervisor's
        # quiescence check reads it from another thread, and a batch held
        # only in locals would let HALT race in and drop it (same
        # invariant _dispatch_py documents).
        from firedancer_tpu.ballet.txn import MAX_ACCT_CNT

        txns = list(self._gc_pending)
        # Pinned shapes: one compiled program serves every block size in
        # [1, gc_block] x any account mix (review finding: per-block
        # shape drift recompiled the scan in the hot path).
        waves, leftover = schedule_block(
            txns, pad_to=self.gc_block,
            max_w=MAX_ACCT_CNT, max_r=MAX_ACCT_CNT)
        waves, leftover = self._gate_device_waves(txns, waves, leftover)
        self._publish_waves(waves)
        # CU-capped leftovers stay pending; the next round has fresh wave
        # budgets, so the set strictly shrinks (unschedulably large txns
        # were rejected at insert time).
        self._gc_pending = list(leftover)
        self.fl.publish()

    def _gate_device_waves(self, txns, dev_waves, dev_left):
        """The fd_drain schedule gate: a device-emitted wave schedule
        publishes only if it is ADMISSIBLE (ballet.pack.
        validate_schedule — the exact lock-set authority, immune to the
        device's hash collisions) and at least matches the CPU greedy
        baseline on rewards/CU; otherwise the block falls back to the
        greedy waves with exact accounting (pack_block_device +
        pack_sched_fallback == blocks)."""
        from firedancer_tpu.ballet.pack import validate_schedule
        from firedancer_tpu.disco import drain as drain_mod
        from firedancer_tpu.ops.pack_gc import MAX_COLORS_DEFAULT

        cpu_waves, cpu_left = drain_mod.greedy_waves(
            txns, MAX_COLORS_DEFAULT, 12_000_000)
        if validate_schedule(dev_waves) and drain_mod.device_beats_greedy(
                dev_waves, dev_left, cpu_waves, cpu_left):
            self.fl.inc("pack_block_device")
            self.fl.inc("pack_wave_device", len(dev_waves))
            return dev_waves, dev_left
        self.fl.inc("pack_sched_fallback")
        self.flightrec.record("pack_sched_fallback",
                              txns=len(txns), waves=len(dev_waves))
        return cpu_waves, cpu_left

    def _close_dev_block(self) -> None:
        """Close the current fd_drain device block: reassemble its ctl
        colors into waves, gate them exactly like a locally-scheduled
        block, and publish. Subset safety: a block's waves were colored
        over the whole verify batch, and any subset of an admissible
        wave is still admissible (locks and CU only shrink) — but the
        gate re-validates the arrived subset anyway, never the hint."""
        entries = self._dev_block
        self._dev_block = []
        self._dev_block_id = None
        if not entries:
            return
        waves_map: dict = {}
        for color, pt in entries:
            waves_map.setdefault(color, []).append(pt)
        dev_waves = [waves_map[c] for c in sorted(waves_map)]
        txns = [pt for _color, pt in entries]
        waves, leftover = self._gate_device_waves(txns, dev_waves, [])
        self._publish_waves(waves)
        self._gc_pending.extend(leftover)
        self.fl.publish()

    def _publish_waves(self, waves) -> None:
        for wave in waves:
            for txn in wave:
                # Persistent round-robin: within a wave txns may run in
                # parallel (no conflicts), across waves banks just take
                # the next slot — trickle arrivals (1-txn waves) still
                # spread over all banks.
                bank = self._rr_bank
                self._rr_bank = (self._rr_bank + 1) % self.bank_cnt
                payload = self._payloads.pop(txn.txn_id)
                sig = (bank << 48) | (txn.txn_id & 0xFFFFFFFFFFFF)
                self.publish_backp(payload, sig, count_diag=False,
                                   tsorig=self._tsorig.pop(txn.txn_id, 0))

    def _drain(self) -> None:
        """Schedule as many non-conflicting txns as possible, rotating
        banks after each success; stop after a full cycle of failures."""
        misses = 0
        block_ended = False
        while misses < self.bank_cnt:
            bank = self._rr_bank
            self._rr_bank = (self._rr_bank + 1) % self.bank_cnt
            txn = self.pack.schedule(bank)
            if txn is None:
                misses += 1
                if misses >= self.bank_cnt and not block_ended:
                    # All banks refused. With nothing in flight the only
                    # cause is exhausted per-block CU budgets: in the
                    # reference a new PoH slot resets them; the slice has
                    # no PoH clock, so end the block here to avoid a
                    # permanent scheduling wedge.
                    if (
                        self.pack.pending_cnt() > 0
                        and self.pack.inflight_cnt() == 0
                    ):
                        self.pack.end_block()
                        block_ended = True
                        misses = 0
                continue
            block_ended = False
            misses = 0
            payload = self._payloads.pop(txn.txn_id)
            sig = (bank << 48) | (txn.txn_id & 0xFFFFFFFFFFFF)
            self.publish_backp(payload, sig, count_diag=False,
                               tsorig=self._tsorig.pop(txn.txn_id, 0))
            # Bank execution is immediate in the slice: release locks.
            self.pack.complete(bank, txn.txn_id)


class SinkTile(Tile):
    """Terminal consumer (bank stub): counts everything it receives."""

    name = "sink"

    def __init__(self, wksp, cnc_name, in_link, record_digests: bool = False,
                 **kw):
        super().__init__(wksp, cnc_name, in_link=in_link, **kw)
        self.recv_cnt = 0
        self.recv_sz = 0
        self.bank_hist: dict = {}
        # Optional content audit: sha256 of every received payload, so
        # replay gates can assert the sink saw EXACTLY the expected txns
        # (count equality alone would let compensating errors cancel).
        self.record_digests = record_digests
        self.digests: list = []
        # End-to-end latency samples (ns, 32-bit wrap-safe under ~4.29 s):
        # source tsorig stamp -> sink arrival. Feeds the p50/p99 the bench
        # and replay gate report. Bounded reservoir (algorithm R) so a
        # long soak stays at constant memory.
        self.latencies_ns: list = []
        self.latency_sample_cap = 65536
        self._latency_seen = 0
        # Trace-id audit trail: with record_digests on, the tsorig
        # stamp (the txn's trace id, minted once at source publish) of
        # every received frag — the propagation tests assert these
        # survive the pipeline bit-exactly.
        self.trace_ids: list = []
        # End-to-end trace span: the "sink" edge of the flight registry
        # (always-on log2 histogram; the reservoir below stays for
        # fine-grained percentiles).
        self._e2e_span: Optional[flight.EdgeHist] = None
        if flight.enabled() and flags.get_bool("FD_TRACE_SPANS"):
            self._e2e_span = flight.edge_hist(wksp, "sink")
        # fd_xray e2e exemplar sampler: the sink's head/tail capture
        # closes every sampled txn's span chain (correlated by the
        # deterministic trace-id hash — no coordination with upstream).
        self._xr_ctx: Optional[xray.SpanCtx] = xray.span_ctx("sink")

    def on_frag(self, frag: Frag, payload: bytes) -> None:
        self.recv_cnt += 1
        self.recv_sz += frag.sz
        bank = frag.sig >> 48
        self.bank_hist[bank] = self.bank_hist.get(bank, 0) + 1
        if self.record_digests:
            self.digests.append(_sha256(payload).digest())
            self.trace_ids.append(frag.tsorig)
        if frag.tsorig:
            lat = (tempo.tickcount() - frag.tsorig) & 0xFFFFFFFF
            if self._e2e_span is not None:
                self._e2e_span.observe(lat)
            if self._xr_ctx is not None:
                self._xr_ctx.observe(frag.tsorig,
                                     (frag.tsorig + lat) & 0xFFFFFFFF, lat)
            self._latency_seen += 1
            if len(self.latencies_ns) < self.latency_sample_cap:
                self.latencies_ns.append(lat)
            else:
                j = self.rng.roll(self._latency_seen)
                if j < self.latency_sample_cap:
                    self.latencies_ns[j] = lat
        self.in_cur.fseq.diag_add(DIAG_PUB_CNT, 1)
        self.in_cur.fseq.diag_add(DIAG_PUB_SZ, frag.sz)
        # Checkpoint the cursor WITH the count: if the fseq only moved on
        # housekeep, a sink crash would make the respawned incarnation
        # re-read (and re-count) every frag since the last housekeep —
        # the delivery counters would over-count the unpublished window
        # (round-2 ADVICE finding). Publishing per frag shrinks the
        # replay window to at most the single in-flight frag (a crash
        # between the diag_add above and this store); counting before
        # publishing means the counters can only ever over-count by that
        # one frag, never under-count.
        self.in_cur.fseq.update(frag.seq + 1)
