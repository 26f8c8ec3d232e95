"""fd_flight — unified metrics registry, trace spans, flight recorder.

The observability layer the round-6 gates (>=400k verifies/s,
>=20k txn/s replay) and the ROOFLINE.md falsifiable predictions are
attributed THROUGH. Before this module the numbers were assembled by
hand from three disjoint sources — verify_stats dicts built in
feed/runtime.py, 16-slot cnc diag counters mirrored per gauge, and
sampled stage_latency_ms — with no per-transaction trace and no
postmortem record (the PR 3 compile-stall respawn storm was invisible
until it had destroyed throughput). fd_flight replaces that with:

  REGISTRY   typed central metric specs (the flags.py pattern: name /
             kind / doc declared ONCE, below) backed by preallocated
             shared-memory rows in the tango workspace. build_topology
             creates two regions — ``flight.metrics`` (one row of u64
             slots per tile) and ``flight.edges`` (one log2 histogram
             row per link edge + the e2e span) — with self-describing
             label headers, so tiles, the feeder stager/dispatcher,
             the supervisor, and worker processes all attach by label
             and write through one API. verify_stats / replay / bench
             artifacts are VIEWS assembled from these rows, not
             hand-rolled dicts. Every row has exactly one writer (the
             owning tile; a crash-respawned incarnation resumes
             delta-exact because counters only ever accumulate), so no
             cross-process atomics are needed.

  SPANS      the trace id of a txn is its 32-bit ``tsorig`` stamp,
             minted exactly once at source publish (replay/quic tile)
             and propagated bit-exactly through parse -> dedup ->
             verify (stage/flush/dispatch/complete — the feed slot
             sidecars carry it through staging, quarantine re-verify
             and the bulk completion) -> pack -> sink. Every OutLink
             publish observes (tspub - tsorig) into its edge's
             ALWAYS-ON log2 histogram — full-population latency per
             edge, replacing the sampling-only p50/p99 as the
             docs/LATENCY.md budget surface (the reservoirs remain for
             fine-grained percentiles). Host spans on the profiler's
             clock (span_maker: fd.feed.drain, fd.verify.dispatch,
             fd.rlc.*, fd.verify.readback/publish) name the verify
             batch's host work in a jax.profiler trace, and the
             verify lane's lifecycle counters (retired, *_ns) split
             each retired batch into its phases, always on.

  RECORDER   a per-tile ring buffer of the last N structured events
             (dispatches, adaptive-flush verdicts, breaker
             transitions, quarantines, chaos injections, stager /
             worker respawns, HALT) that dumps to a JSON artifact on
             crash, HALT, or signal when FD_FLIGHT_DUMP names a
             directory — the postmortem the respawn-storm class of
             failure requires.

Deliberately stdlib+numpy only: host-side tiles must stay
jax-import-free (disco/tiles.py's dispatch contract); span_maker
reaches for jax.profiler only where JAX is already loaded.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from firedancer_tpu import flags

# Artifact schema (BENCH/REPLAY/PACK artifacts + BENCH_LOG.jsonl lines
# + flight dumps). 2 = the fd_flight era: schema_version itself,
# stage_hist, engine_key/compile accounting. 3 = the fdgraph era:
# verify/engine artifacts carry a graph_cert block (sha256 of the
# committed lint_graph_cert.json + per-rung MSM cost-drift pct), so a
# bench number is always attributable to the proved graph contract set
# it ran under.
ARTIFACT_SCHEMA_VERSION = 3

_U64 = (1 << 64) - 1


# --------------------------------------------------------------------------
# Metric specs — the typed central registry. Declared once, like flags.py:
# a metric that is not specced here cannot be written (IndexError at the
# lane), so names/semantics cannot drift per call site.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Metric:
    name: str
    kind: str          # "counter" (monotonic, delta-accumulated across
                       # tile incarnations) | "gauge" (last-write-wins)
    doc: str


# One row of these per tile in the ``flight.metrics`` region. The verify
# tile is the main writer; other tiles leave unused slots at 0.
TILE_METRICS: Tuple[Metric, ...] = (
    Metric("batches", "counter", "verify batches dispatched"),
    Metric("lanes", "counter",
           "signature lanes in dispatched batches (fill_ratio = lanes / "
           "(batches * batch))"),
    Metric("flush_timeout", "counter",
           "partial batches flushed by deadline expiry (ROADMAP round-6 "
           "gate: ~0 at steady state)"),
    Metric("flush_starved", "counter",
           "partial batches flushed by the starved-input early-out"),
    Metric("inflight_stall", "counter",
           "dispatches that blocked on the in-flight batch cap"),
    Metric("rlc_fallback", "counter",
           "batches that took the per-lane fallback after the RLC batch "
           "equation failed"),
    Metric("cpu_failover", "counter",
           "batches served by the CPU oracle lane (breaker open or "
           "dispatch error)"),
    Metric("quarantined", "counter",
           "poisoned batches re-verified on the CPU oracle lane at "
           "completion"),
    Metric("quarantine_err_txn", "counter",
           "quarantine offenders published downstream as CTL_ERR audit "
           "frags"),
    Metric("ctl_err_drop", "counter",
           "producer-flagged CTL_ERR frags dropped at the ctl word"),
    Metric("stager_restarts", "counter",
           "fd_feed stager-thread supervision respawns"),
    Metric("slot_stall", "counter",
           "stager slot acquires that had to wait for a FREE slot"),
    Metric("feed_idle_ns", "counter",
           "dispatcher host estimate of device idle, ns: counts only "
           "stretches with nothing in flight AND nothing READY, so it "
           "misses the device waiting on a per-lane launch (the device "
           "trace is the authority)"),
    Metric("compile_cnt", "counter",
           "verify-engine (pre)compiles paid by this tile"),
    Metric("compile_ns", "counter",
           "total wall ns spent in verify-engine (pre)compiles"),
    Metric("compile_cache_hit", "counter",
           "(pre)compiles that resolved fast enough to be persistent-"
           "cache hits (< 1 s heuristic)"),
    # fd_engine rung scheduler (disco/engine.py): target-B changes and
    # the current target, mirrored from the stager's decisions; the
    # per-rung dispatch histogram lives in verify_stats.rung_hist (the
    # ladder is config-sized, so it cannot be a fixed metric row).
    Metric("rung_switches", "counter",
           "fd_engine rung-scheduler target-B changes (ladder moves "
           "between the 8k/16k/32k-style rungs)"),
    Metric("rung_cur", "gauge",
           "current fd_engine scheduler target B (0 = scheduler off)"),
    Metric("breaker_state", "gauge",
           "verify failover breaker state: 0 closed, 1 open, 2 half_open, "
           "3 disabled/absent"),
    Metric("breaker_trips", "gauge",
           "times the failover circuit opened from closed"),
    Metric("breaker_reprobes", "gauge",
           "half-open device re-probes attempted"),
    # fd_siege QUIC front-door defense counters (written by the quic
    # tile's lane; zero everywhere else). Shed work is ACCOUNTED, never
    # silent: admitted + shed == offered is a siege-smoke gate.
    Metric("admit_shed", "counter",
           "txns shed by per-connection token-bucket admission at the "
           "QUIC tile (FD_QUIC_ADMIT_RATE/_BURST)"),
    Metric("queue_shed", "counter",
           "txns shed by credit-aware lowest-priority load shedding "
           "when the front-door ready queue exceeds FD_QUIC_SHED_DEPTH"),
    Metric("conn_quarantine", "counter",
           "abusive peers quarantined by the connection-level circuit "
           "breaker (FD_QUIC_ABUSE_THRESHOLD trips within 1 s)"),
    Metric("quarantine_drop", "counter",
           "datagrams dropped at the socket from quarantined peers "
           "(cooldown window; half-open re-admit after it)"),
    # fd_drain device-resident post-verify pipeline (disco/drain.py).
    # Verify-tile rows: the filter aux dispatch + novel/maybe claim
    # split over PUBLISHED clean txns (CTL_ERR and quarantine-dropped
    # lanes excluded, so at quiescence drain_novel + drain_maybe ==
    # drain_probe_skip + drain_probed on the dedup lane).
    Metric("drain_batches", "counter",
           "verify batches dispatched with the fused fd_drain dedup "
           "pre-filter aux graph"),
    Metric("drain_novel", "counter",
           "published clean txns the device filter claimed DEFINITELY "
           "novel (ctl CTL_NOVEL set)"),
    Metric("drain_maybe", "counter",
           "published clean txns left maybe-dup (host TCache stays the "
           "authority)"),
    Metric("drain_rot", "counter",
           "fd_drain filter window rotations (bank B <- A after the "
           "eviction-covering publish quota)"),
    # Dedup-tile rows: what the novel claims bought downstream.
    Metric("drain_probe_skip", "counter",
           "clean frags whose dup verdict came from the device novel "
           "claim — the TCache probe skipped as decision authority"),
    Metric("drain_probed", "counter",
           "clean frags probed against the host TCache (maybe-dup "
           "lanes)"),
    Metric("drain_false_novel", "counter",
           "tripwire: novel claims the TCache contradicted (one-sided "
           "contract breach; frag dropped as duplicate, ~0 always)"),
    # Pack-tile rows: device pack_gc wave schedules vs the CPU greedy
    # oracle. Exact accounting gate: pack_block_device +
    # pack_sched_fallback == blocks scheduled.
    Metric("pack_wave_device", "counter",
           "pack waves published from device pack_gc wave colors"),
    Metric("pack_block_device", "counter",
           "pack blocks whose device schedule validated and beat (or "
           "tied) CPU greedy rewards/CU"),
    Metric("pack_sched_fallback", "counter",
           "pack blocks that fell back to the exact CPU greedy "
           "schedule (validation miss or losing rewards/CU)"),
    # fd_soak live-reconfig rows: ladder/flag swaps applied at the
    # inflight-window barrier vs requests refused at validation.
    Metric("reconfigs", "counter",
           "live reconfigs applied at the inflight-window barrier "
           "(ladder swap / engine-flag flip / drain-mode change, zero "
           "dropped txns by construction)"),
    Metric("reconfig_refused", "counter",
           "live reconfig requests refused at validation (invalid "
           "mode/backend combo, unusable ladder, or a swap already "
           "pending)"),
    # Verify-batch lifecycle (fd_feed): every phase of a retired batch,
    # booked in one place when the batch retires, after its publish, so
    # any window's deltas share the one denominator `retired`. The
    # per-batch mean of a phase is d(<phase>_ns) / d(retired).
    Metric("retired", "counter",
           "fd_feed verify batches retired (published and released)"),
    Metric("ring_wait_ns", "counter",
           "ring dwell of each retired batch's oldest frag: source "
           "publish -> the stager's drain of it, ns"),
    Metric("stage_ns", "counter",
           "first staged txn -> slot commit (READY), summed over retired "
           "batches, ns"),
    Metric("ready_wait_ns", "counter",
           "slot commit -> dispatch returned (READY queue wait plus the "
           "host's put and launch), summed over retired batches, ns"),
    Metric("inflight_ns", "counter",
           "dispatch -> the completion found the batch ready (the "
           "device programs, the per-lane launch, the wait for an older "
           "batch to retire first), summed over retired batches, ns"),
    Metric("fallback_wait_ns", "counter",
           "dispatch -> per-lane fallback launch, summed over retired "
           "batches whose rlc batch equation failed, ns"),
    Metric("retire_ns", "counter",
           "found ready -> retired (readback, verdict fold, publish), "
           "summed over retired batches, ns"),
)

TILE_IDX: Dict[str, int] = {m.name: i for i, m in enumerate(TILE_METRICS)}
_TILE_KIND: Tuple[str, ...] = tuple(m.kind for m in TILE_METRICS)

BREAKER_STATE_CODE = {"closed": 0, "open": 1, "half_open": 2, "disabled": 3}
BREAKER_STATE_NAME = {v: k for k, v in BREAKER_STATE_CODE.items()}

# Log2 latency histogram shape per edge: bucket b counts samples with
# bit_length(ns) == b, i.e. ns in [2^(b-1), 2^b). 40 buckets cover up
# to ~18 minutes; everything larger clamps into the last bucket. Row
# layout: [sum_ns, bucket_0 .. bucket_{N-1}]  (count = sum of buckets).
N_BUCKETS = 40
EDGE_SLOTS = 1 + N_BUCKETS

# Region names + header layout. Header: [magic, n_rows, n_slots, 0];
# each row: 4 u64 of utf-8 label (32 bytes, NUL-padded) + n_slots u64.
_METRICS_REGION = "flight.metrics"
_EDGES_REGION = "flight.edges"
_SLO_REGION = "flight.slo"
_MAGIC_TILES = 0xF11687_0001
_MAGIC_EDGES = 0xF11687_0002
_MAGIC_SLO = 0xF11687_0003
_LABEL_U64 = 4   # 32-byte label field

# fd_sentinel SLO rows (disco/sentinel.py is the single writer — one
# sentinel per run, in the runner process). Slot layout per SLO:
# [evals, alerts, breach_polls, burn_milli, state]; evals/alerts/
# breach_polls are counters, burn_milli (current burn rate x1000, or
# stall ms for liveness SLOs) and state (0 ok / 1 alert) are gauges.
SLO_SLOTS = 5
SLO_EVALS, SLO_ALERTS, SLO_BREACH_POLLS, SLO_BURN_MILLI, SLO_STATE = range(5)


def _region_footprint(n_rows: int, n_slots: int) -> int:
    return 8 * (4 + n_rows * (_LABEL_U64 + n_slots))


def _pack_label(label: str) -> bytes:
    b = label.encode()[: _LABEL_U64 * 8 - 1]
    return b + b"\x00" * (_LABEL_U64 * 8 - len(b))


def create_regions(wksp, tile_labels, edge_labels, slo_labels=()) -> None:
    """Allocate + initialize the shared-memory registry regions (called
    by build_topology; every row is pre-labeled so attachers never
    race a claim). slo_labels pre-labels the fd_sentinel SLO rows
    (sentinel.SLO_NAMES); empty skips the region — old callers keep
    working and the sentinel degrades to process-local rows."""
    regions = [
        (_METRICS_REGION, _MAGIC_TILES, tile_labels, len(TILE_METRICS)),
        (_EDGES_REGION, _MAGIC_EDGES, edge_labels, EDGE_SLOTS),
    ]
    if slo_labels:
        regions.append((_SLO_REGION, _MAGIC_SLO, slo_labels, SLO_SLOTS))
    for region, magic, labels, n_slots in regions:
        labels = list(labels)
        wksp.alloc(region, _region_footprint(len(labels), n_slots))
        a = np.frombuffer(wksp.view(region), np.uint64)
        a[:] = 0
        a[0] = magic
        a[1] = len(labels)
        a[2] = n_slots
        for i, label in enumerate(labels):
            row = 4 + i * (_LABEL_U64 + n_slots)
            a[row: row + _LABEL_U64] = np.frombuffer(
                _pack_label(label), np.uint64)


def _region_rows(wksp, region: str, magic: int, n_slots: int):
    """[(label, u64_row_view)] of a registry region, or None when the
    region is absent / from a different schema (old workspace: callers
    degrade to process-local arrays)."""
    try:
        view = wksp.view(region)
    except KeyError:
        return None
    a = np.frombuffer(view, np.uint64)
    if a.size < 4 or int(a[0]) != magic or int(a[2]) != n_slots:
        return None
    out = []
    n_rows = int(a[1])
    for i in range(n_rows):
        row = 4 + i * (_LABEL_U64 + n_slots)
        label = a[row: row + _LABEL_U64].tobytes().split(b"\x00")[0]
        out.append((label.decode("utf-8", "replace"),
                    a[row + _LABEL_U64: row + _LABEL_U64 + n_slots]))
    return out


def _attach_row(wksp, region: str, magic: int, n_slots: int, label: str):
    rows = _region_rows(wksp, region, magic, n_slots)
    if rows is None:
        return None
    for lab, row in rows:
        if lab == label:
            return row
    return None


# --------------------------------------------------------------------------
# Writer handles.
# --------------------------------------------------------------------------


class TileLane:
    """One tile's metric row. ``inc``/``set_gauge`` write the LOCAL
    array (allocation-free: a preallocated u64 vector, one indexed
    add); ``publish`` mirrors it into the shared row — counters as
    deltas (so a crash-respawned incarnation accumulates instead of
    rewinding the shared view), gauges as last-write-wins."""

    __slots__ = ("label", "a", "_shm", "_last")

    def __init__(self, label: str, shm_row=None):
        self.label = label
        self.a = np.zeros(len(TILE_METRICS), np.uint64)
        self._shm = shm_row
        self._last = np.zeros(len(TILE_METRICS), np.uint64)

    def inc(self, name: str, n: int = 1) -> None:
        self.a[TILE_IDX[name]] += np.uint64(n)

    def set_gauge(self, name: str, v: int) -> None:
        self.a[TILE_IDX[name]] = np.uint64(v)

    def get(self, name: str) -> int:
        return int(self.a[TILE_IDX[name]])

    def publish(self) -> None:
        if self._shm is None:
            return
        # SNAPSHOT the live array first: in fd_feed mode the stager
        # thread incs this lane while the dispatcher publishes, and
        # computing deltas against the live view would fold a
        # concurrent increment into _last without ever mirroring it
        # (a permanently lost count). With the snapshot, an inc that
        # lands mid-publish is simply carried by the NEXT publish.
        cur = self.a.copy()
        last = self._last
        if np.array_equal(cur, last):
            return
        for i, kind in enumerate(_TILE_KIND):
            if kind == "counter":
                d = int(cur[i]) - int(last[i])
                if d:
                    self._shm[i] += np.uint64(d & _U64)
            elif cur[i] != self._shm[i]:
                self._shm[i] = cur[i]
        self._last = cur

    def as_dict(self) -> Dict[str, int]:
        return {m.name: int(self.a[i]) for i, m in enumerate(TILE_METRICS)}


class EdgeHist:
    """Always-on log2 latency histogram for one pipeline edge. The row
    (shared-memory when the workspace carries the registry region, a
    process-local array otherwise) is written directly — each edge has
    exactly one producing tile, so the writes are single-writer."""

    __slots__ = ("label", "row")

    def __init__(self, label: str, row=None):
        self.label = label
        self.row = row if row is not None else np.zeros(EDGE_SLOTS, np.uint64)

    def observe(self, ns: int) -> None:
        b = min(int(ns).bit_length(), N_BUCKETS - 1)
        # sum_ns wraps mod 2^64 by design (a counter, not a gauge);
        # int-side math avoids numpy's overflow warning on the wrap.
        self.row[0] = np.uint64((int(self.row[0]) + ns) & _U64)
        self.row[1 + b] += np.uint64(1)

    def observe_many(self, ns_arr) -> None:
        """Vectorized observe (the fd_feed bulk completion path)."""
        a = np.asarray(ns_arr, np.int64)
        if a.size == 0:
            return
        # bit_length via log2: bucket b holds [2^(b-1), 2^b).
        b = np.zeros(a.shape, np.int64)
        pos = a > 0
        b[pos] = np.floor(np.log2(a[pos])).astype(np.int64) + 1
        np.clip(b, 0, N_BUCKETS - 1, out=b)
        counts = np.bincount(b, minlength=N_BUCKETS).astype(np.uint64)
        self.row[1:] += counts
        self.row[0] = np.uint64((int(self.row[0]) + int(a.sum())) & _U64)

    # -- read side --------------------------------------------------------

    def count(self) -> int:
        return int(self.row[1:].sum())

    def percentile_ns(self, q: float) -> int:
        """Upper bucket bound of the q-quantile (q in [0,1]): the
        histogram's conservative estimate of p50/p99 — coarse (factor
        2) by construction, but over the FULL population, always on."""
        buckets = self.row[1:]
        n = int(buckets.sum())
        if n == 0:
            return 0
        target = q * n
        acc = 0
        for b in range(N_BUCKETS):
            acc += int(buckets[b])
            if acc >= target:
                return (1 << b) if b else 0
        return 1 << (N_BUCKETS - 1)

    def summary(self) -> Dict[str, int]:
        return {
            "n": self.count(),
            "p50_ns_le": self.percentile_ns(0.50),
            "p99_ns_le": self.percentile_ns(0.99),
            "sum_ns": int(self.row[0]),
        }


def tile_lane(wksp, label: str) -> TileLane:
    """The one write API for tile metrics: attaches the tile's shared
    row when the workspace carries the registry (build_topology
    workspaces do), else degrades to a process-local lane (raw test
    workspaces, direct tile construction)."""
    row = None
    if wksp is not None:
        try:
            row = _attach_row(wksp, _METRICS_REGION, _MAGIC_TILES,
                              len(TILE_METRICS), label)
        except Exception:
            row = None
    return TileLane(label, row)


def edge_hist(wksp, label: str) -> EdgeHist:
    row = None
    if wksp is not None:
        try:
            row = _attach_row(wksp, _EDGES_REGION, _MAGIC_EDGES,
                              EDGE_SLOTS, label)
        except Exception:
            row = None
    return EdgeHist(label, row)


# --------------------------------------------------------------------------
# Read side — snapshot views assembled FROM the registry.
# --------------------------------------------------------------------------


def read_tiles(wksp) -> Optional[Dict[str, Dict[str, int]]]:
    """{tile_label: {metric: value}} from the shared region (None when
    the workspace predates fd_flight)."""
    rows = _region_rows(wksp, _METRICS_REGION, _MAGIC_TILES,
                        len(TILE_METRICS))
    if rows is None:
        return None
    return {
        label: {m.name: int(row[i]) for i, m in enumerate(TILE_METRICS)}
        for label, row in rows
    }


def read_edges(wksp) -> Optional[Dict[str, Dict[str, int]]]:
    """{edge_label: histogram summary} from the shared region."""
    rows = _region_rows(wksp, _EDGES_REGION, _MAGIC_EDGES, EDGE_SLOTS)
    if rows is None:
        return None
    return {label: EdgeHist(label, row).summary() for label, row in rows}


def read_edges_raw(wksp) -> Optional[Dict[str, np.ndarray]]:
    """{edge_label: COPY of the raw [sum_ns, bucket_0..] row} — the
    form fd_sentinel's windowed burn-rate deltas and the cross-shard
    histogram merge need (summaries cannot be merged; log2 bucket rows
    merge by elementwise add)."""
    rows = _region_rows(wksp, _EDGES_REGION, _MAGIC_EDGES, EDGE_SLOTS)
    if rows is None:
        return None
    return {label: np.array(row, dtype=np.uint64) for label, row in rows}


def slo_row(wksp, label):
    """The shared row for one SLO (sentinel is the single writer), or
    None when the workspace predates the region / lacks the label —
    callers degrade to a process-local array."""
    if wksp is None:
        return None
    try:
        return _attach_row(wksp, _SLO_REGION, _MAGIC_SLO, SLO_SLOTS, label)
    except Exception:
        return None


def read_slos(wksp) -> Optional[Dict[str, Dict[str, int]]]:
    """{slo_name: {evals, alerts, breach_polls, burn_milli, state}}
    from the shared region (None when absent)."""
    rows = _region_rows(wksp, _SLO_REGION, _MAGIC_SLO, SLO_SLOTS)
    if rows is None:
        return None
    keys = ("evals", "alerts", "breach_polls", "burn_milli", "state")
    return {label: {k: int(row[i]) for i, k in enumerate(keys)}
            for label, row in rows}


# --------------------------------------------------------------------------
# Cross-process / cross-shard aggregation (fd_sentinel part 3): roll
# per-process and per-shard registry rows into ONE snapshot. Counters
# sum (they delta-accumulate, so the sum over rows IS the pod total);
# log2 histogram rows merge by elementwise add (bucketing is identical
# everywhere, so the merged histogram is exactly the histogram of the
# concatenated samples); gauges need a policy — breaker_state merges
# most-severe (an open breaker anywhere must not be averaged away),
# every other gauge sums (trips/reprobes are per-row totals whose pod
# aggregate is their sum).
# --------------------------------------------------------------------------

# Severity order for merging breaker_state codes: open > half_open >
# closed > disabled (codes 1, 2, 0, 3 — see BREAKER_STATE_CODE).
_BREAKER_SEVERITY = {1: 3, 2: 2, 0: 1, 3: 0}


def merge_tile_metrics(rows) -> Dict[str, int]:
    """Aggregate several tile metric dicts (as read_tiles values /
    TileLane.as_dict) into one rollup row."""
    out = {m.name: 0 for m in TILE_METRICS}
    breaker = 3  # disabled until any row says otherwise
    for row in rows:
        for m in TILE_METRICS:
            v = int(row.get(m.name, 0))
            if m.name == "breaker_state":
                if (_BREAKER_SEVERITY.get(v, 0)
                        > _BREAKER_SEVERITY.get(breaker, 0)):
                    breaker = v
            else:
                out[m.name] += v
    out["breaker_state"] = breaker
    return out


def merge_edge_rows(rows) -> np.ndarray:
    """Elementwise-add several raw edge rows into one (sum_ns wraps
    mod 2^64 like the per-row counter it is)."""
    out = np.zeros(EDGE_SLOTS, np.uint64)
    sum_ns = 0
    for row in rows:
        a = np.asarray(row, np.uint64)
        out[1:] += a[1:]
        sum_ns = (sum_ns + int(a[0])) & _U64
    out[0] = np.uint64(sum_ns)
    return out


def snapshot_raw(wksp) -> Dict[str, dict]:
    """One registry snapshot in mergeable form: {"metrics": {tile:
    {metric: value}}, "edges": {edge: raw row}}."""
    return {
        "metrics": read_tiles(wksp) or {},
        "edges": read_edges_raw(wksp) or {},
    }


def merge_snapshots(snaps) -> Dict[str, dict]:
    """Merge several snapshot_raw() results (one per process workspace
    / verify shard) into ONE: per-label counter sums and histogram
    adds, plus summaries of the merged edges. The contract the pod-
    scale verify service stands on: counters of the merge equal the
    sum of the per-source rows (test-pinned in tests/test_sentinel.py)."""
    snaps = list(snaps)
    metric_rows: Dict[str, List[dict]] = {}
    edge_rows: Dict[str, List[np.ndarray]] = {}
    for s in snaps:
        for label, row in (s.get("metrics") or {}).items():
            metric_rows.setdefault(label, []).append(row)
        for label, row in (s.get("edges") or {}).items():
            edge_rows.setdefault(label, []).append(row)
    edges_raw = {label: merge_edge_rows(rows)
                 for label, rows in edge_rows.items()}
    return {
        "metrics": {label: merge_tile_metrics(rows)
                    for label, rows in metric_rows.items()},
        "edges_raw": edges_raw,
        "edges": {label: EdgeHist(label, row).summary()
                  for label, row in edges_raw.items()},
    }


def verify_stats_view(wksp, label: str, batch: int) -> Optional[dict]:
    """The verify_stats record for one tile, assembled from the shared
    registry — the cross-process view the supervisor publishes (the
    in-process runners read the richer tile-object view via
    feed/runtime.verify_tile_stats; both carry the same keys)."""
    tiles = read_tiles(wksp)
    if tiles is None or label not in tiles:
        return None
    t = tiles[label]
    batches = t["batches"]
    return {
        "batches": batches,
        "lanes": t["lanes"],
        "fill_ratio": round(t["lanes"] / float(batches * batch), 4)
        if batches else 0.0,
        "flush_timeout": t["flush_timeout"],
        "flush_starved": t["flush_starved"],
        "inflight_stall": t["inflight_stall"],
        "rlc_fallback": t["rlc_fallback"],
        "slot_stall": t["slot_stall"],
        "device_idle_est_ms": round(t["feed_idle_ns"] / 1e6, 2),
        "stager_restarts": t["stager_restarts"],
        "cpu_failover": t["cpu_failover"],
        "quarantined": t["quarantined"],
        "quarantine_err_txn": t["quarantine_err_txn"],
        "ctl_err_drop": t["ctl_err_drop"],
        "breaker_state": BREAKER_STATE_NAME.get(
            t["breaker_state"], "disabled"),
        "breaker_trips": t["breaker_trips"],
        "breaker_reprobes": t["breaker_reprobes"],
        "compile_cnt": t["compile_cnt"],
        "compile_ms": round(t["compile_ns"] / 1e6, 1),
        "compile_cache_hit": t["compile_cache_hit"],
        # fd_engine rung scheduler: the shared lane carries the switch
        # counter + current-target gauge; the per-rung histogram is
        # tile-object state (config-sized), so the cross-process view
        # reports the same keys with the shape the artifact schema
        # allows for "unknown" ({}).
        "rung_switches": t["rung_switches"],
        "rung_cur": t["rung_cur"],
        "rung_hist": {},
        "rung_ladder": [],
        # fd_drain: filter claim split over published clean txns.
        "drain_batches": t["drain_batches"],
        "drain_novel": t["drain_novel"],
        "drain_maybe": t["drain_maybe"],
        "drain_rot": t["drain_rot"],
    }


def render_prom(wksp) -> str:
    """Prometheus-style text snapshot of the shared registry (+ this
    process's compile records). Exposition-format compatible enough
    for promtool/scrapers; the schema gate in scripts/obs_smoke.py
    pins the metric families."""
    lines: List[str] = []
    tiles = read_tiles(wksp) or {}
    for m in TILE_METRICS:
        prom_kind = "gauge" if m.kind == "gauge" else "counter"
        lines.append(f"# HELP fd_flight_{m.name} {m.doc}")
        lines.append(f"# TYPE fd_flight_{m.name} {prom_kind}")
        for label, t in sorted(tiles.items()):
            lines.append(
                f'fd_flight_{m.name}{{tile="{label}"}} {t[m.name]}')
    edges = _region_rows(wksp, _EDGES_REGION, _MAGIC_EDGES, EDGE_SLOTS) or []
    lines.append("# HELP fd_flight_edge_latency_ns trace-span latency "
                 "(tsorig -> tspub) per pipeline edge, log2 buckets")
    lines.append("# TYPE fd_flight_edge_latency_ns histogram")
    for label, row in edges:
        acc = 0
        for b in range(N_BUCKETS):
            acc += int(row[1 + b])
            lines.append(
                f'fd_flight_edge_latency_ns_bucket{{edge="{label}",'
                f'le="{1 << b}"}} {acc}')
        lines.append(
            f'fd_flight_edge_latency_ns_bucket{{edge="{label}",'
            f'le="+Inf"}} {acc}')
        lines.append(
            f'fd_flight_edge_latency_ns_sum{{edge="{label}"}} {int(row[0])}')
        lines.append(
            f'fd_flight_edge_latency_ns_count{{edge="{label}"}} {acc}')
    # fd_sentinel SLO rows (the fl_slo_* families): evaluation counts,
    # alert transitions, breach polls, current burn (x1000) and state
    # per declared SLO — scrapers alert on fd_flight_slo_state.
    slos = _region_rows(wksp, _SLO_REGION, _MAGIC_SLO, SLO_SLOTS) or []
    if slos:
        fams = (
            ("evals", SLO_EVALS, "counter", "sentinel evaluation passes"),
            ("alerts", SLO_ALERTS, "counter",
             "ok->alert transitions (burn-rate breaches)"),
            ("breach_polls", SLO_BREACH_POLLS, "counter",
             "evaluation passes spent in breach"),
            ("burn_milli", SLO_BURN_MILLI, "gauge",
             "current burn rate x1000 (stall/heartbeat-age ms for "
             "liveness SLOs)"),
            ("state", SLO_STATE, "gauge", "0 ok, 1 alerting"),
        )
        for name, slot, kind, doc in fams:
            lines.append(f"# HELP fd_flight_slo_{name} {doc}")
            lines.append(f"# TYPE fd_flight_slo_{name} {kind}")
            for label, row in slos:
                lines.append(
                    f'fd_flight_slo_{name}{{slo="{label}"}} '
                    f"{int(row[slot])}")
    with _compile_lock:
        recs = list(_compiles)
    lines.append("# HELP fd_flight_compile_seconds verify-engine compile "
                 "wall time per engine key (mode x B x shards x frontend)")
    lines.append("# TYPE fd_flight_compile_seconds gauge")
    for r in recs:
        lines.append(
            f'fd_flight_compile_seconds{{engine="{r["engine"]}",'
            f'cache_hit_est="{str(r["cache_hit_est"]).lower()}"}} '
            f'{r["seconds"]}')
    lines.append("")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# Compile accounting (process-local; mirrored into the tile lane by the
# caller when one exists). Engine keys are mode x B x shards x frontend
# — the engine-registry refactor (ROADMAP direction 3) made observable
# before it lands.
# --------------------------------------------------------------------------

_compiles: List[dict] = []
_compile_lock = threading.Lock()
_COMPILE_CAP = 256
_CACHE_HIT_S = 1.0   # persistent-cache loads come back well under this


def engine_key(mode: str, batch: int, shards: int, frontend: str,
               msm: str = "auto") -> str:
    """mode:B<batch>:shards<n>:fe<impl>[:msm<plan>] — the msm segment
    (fd_msm2 schedule token, e.g. s7l3) appears ONLY when a non-auto
    plan is pinned, so every pre-fd_msm2 key (and every auto-plan
    engine) keeps its exact historical spelling and compile records
    stay comparable across rounds."""
    key = f"{mode}:B{batch}:shards{shards}:fe{frontend}"
    if msm and msm != "auto":
        key += f":msm{msm}"
    return key


def compile_cache_hit_est(seconds: float) -> bool:
    """THE persistent-cache-hit heuristic: one predicate shared by the
    compile records, the bench artifacts, and the fd_engine registry
    entries, so 'cache hit' can never mean two different thresholds at
    two dispatch sites (the PR-13 bench/prewarm consistency fix)."""
    return seconds < _CACHE_HIT_S


def record_compile(engine: str, seconds: float) -> dict:
    rec = {
        "engine": engine,
        "seconds": round(seconds, 3),
        "cache_hit_est": compile_cache_hit_est(seconds),
        "ts": time.time(),
    }
    with _compile_lock:
        _compiles.append(rec)
        del _compiles[:-_COMPILE_CAP]
    return rec


def compile_records() -> List[dict]:
    with _compile_lock:
        return list(_compiles)


# --------------------------------------------------------------------------
# Flight recorder — per-tile ring of structured events, dumpable.
# --------------------------------------------------------------------------

_recorders: Dict[str, "FlightRecorder"] = {}
_recorders_lock = threading.Lock()


def enabled() -> bool:
    """FD_FLIGHT=0 is the overhead-bisection hatch: event recording and
    span histograms off; metric lanes stay on (artifacts need them).
    Read per construction site, never per frag — the hot paths gate on
    the None-ness of the handles this decides."""
    return flags.get_bool("FD_FLIGHT")


_NO_SPAN = contextlib.nullcontext()   # stateless: one shared instance


def _no_span(name: str) -> contextlib.nullcontext:
    return _NO_SPAN


def span_maker():
    """The host-span maker for one construction site: ``maker(name)``
    is a fresh ``jax.profiler.TraceAnnotation(name)`` (a TraceMe event
    on the profiler's timeline, in the same trace as the device ops)
    when JAX is already loaded and FD_FLIGHT is on, else the shared
    no-op context. Resolved once per site, like enabled(); a span
    object records only once, so the maker builds one per use. This
    never imports JAX into a process that has not: host-backend tiles
    stay JAX-free. No span may sit inside a jitted function."""
    if enabled() and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation
    return _no_span


class FlightRecorder:
    """Bounded ring of (tick, kind, fields) events. record() is a
    locked list store + int math — events are per-batch / per-fault
    (never per-frag), and recorders ARE written from several threads
    (the chaos injector's note() fires from the source, stager, and
    dispatcher threads), so an unlocked pos++ would drop events."""

    __slots__ = ("name", "buf", "pos", "n", "_lock")

    def __init__(self, name: str, cap: int):
        self.name = name
        self.buf: List[Optional[tuple]] = [None] * max(cap, 8)
        self.pos = 0
        self.n = 0
        self._lock = threading.Lock()

    def record(self, kind: str, **fields) -> None:
        from firedancer_tpu.tango import tempo

        t = tempo.tickcount()
        with self._lock:
            self.buf[self.pos] = (t, kind, fields or None)
            self.pos = (self.pos + 1) % len(self.buf)
            self.n += 1

    def events(self) -> List[dict]:
        """Chronological events currently held (oldest first)."""
        with self._lock:
            buf = list(self.buf)
            pos, n = self.pos, self.n
        cap = len(buf)
        start = pos if n >= cap else 0
        out = []
        for i in range(min(n, cap)):
            e = buf[(start + i) % cap]
            if e is None:
                continue
            t, kind, fields = e
            d = {"t": t, "kind": kind}
            if fields:
                d.update(fields)
            out.append(d)
        return out


class _NullRecorder:
    __slots__ = ()
    name = "null"
    n = 0

    def record(self, kind: str, **fields) -> None:
        pass

    def events(self) -> List[dict]:
        return []


_NULL = _NullRecorder()


def recorder(name: str):
    """A FRESH recorder registered under `name` (latest wins — each
    tile incarnation / chaos injector gets its own ring; the dump shows
    the current run's). Returns a no-op recorder when FD_FLIGHT=0."""
    if not enabled():
        return _NULL
    rec = FlightRecorder(name, flags.get_int("FD_FLIGHT_EVENTS"))
    with _recorders_lock:
        _recorders[name] = rec
    return rec


def dump(reason: str, wksp=None) -> dict:
    """The postmortem artifact: every live recorder's ring + the
    registry snapshot (when a workspace is given) + compile records."""
    with _recorders_lock:
        recs = dict(_recorders)
    out: dict = {
        "schema_version": ARTIFACT_SCHEMA_VERSION,
        "kind": "fd_flight_dump",
        "reason": reason,
        "pid": os.getpid(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "recorders": {
            name: {"n_total": r.n, "events": r.events()}
            for name, r in sorted(recs.items())
        },
        "compiles": compile_records(),
    }
    # fd_xray exemplar rings + queue telemetry ride in the SAME dump
    # envelope (one postmortem artifact per trigger; lazy import —
    # xray imports this module). Readers that predate the section
    # ignore the key; sentinel.evaluate_edges_summary explicitly
    # accepts-and-ignores non-edge sections.
    try:
        from firedancer_tpu.disco import xray as _xray

        out["xray"] = {"spans": _xray.dump_spans()}
    except Exception:
        pass
    # A left workspace (leave() nulls the handle) must be skipped, not
    # dereferenced: fd_wksp_* with a NULL handle is a crash, not an
    # exception — and the signal handler can outlive the run that
    # registered the workspace.
    if wksp is not None and getattr(wksp, "_h", None):
        try:
            out["metrics"] = read_tiles(wksp)
            out["edges"] = read_edges(wksp)
            out["slos"] = read_slos(wksp)
            if "xray" in out:
                from firedancer_tpu.disco import xray as _xray

                out["xray"]["queue"] = _xray.read_queue(wksp)
        except Exception:
            pass
    return out


def maybe_dump(reason: str, wksp=None) -> Optional[str]:
    """Write the dump as a JSON artifact when FD_FLIGHT_DUMP names a
    directory (crash / HALT / signal triggers all route here); returns
    the path or None. Never raises — a failing postmortem writer must
    not mask the fault it is documenting."""
    try:
        d = flags.get_raw("FD_FLIGHT_DUMP")
        if not d or not enabled():
            return None
        os.makedirs(d, exist_ok=True)
        slug = "".join(c if c.isalnum() else "_" for c in reason)[:48]
        path = os.path.join(
            d, f"flight_{os.getpid()}_{int(time.time() * 1e3)}_{slug}.json")
        with open(path, "w") as f:
            json.dump(dump(reason, wksp=wksp), f, indent=1)
        return path
    except Exception:
        return None


_signal_installed = False
_dump_wksp = None


def install_dump_signal(wksp=None) -> None:
    """SIGUSR1 -> flight dump (live postmortem of a running pipeline).
    Main-thread only; a no-op off the main thread. Re-invocation
    REBINDS the dumped workspace (each run calls this, so the handler
    always reads the CURRENT run's registry, not the first run's
    long-left mapping)."""
    global _signal_installed, _dump_wksp
    if not enabled():
        return
    _dump_wksp = wksp  # rebind every call; dump() skips left handles
    if _signal_installed:
        return
    import signal

    def _h(signum, frame):
        maybe_dump("signal", wksp=_dump_wksp)

    try:
        signal.signal(signal.SIGUSR1, _h)
        _signal_installed = True
    except (ValueError, OSError):
        pass  # not the main thread / restricted environment
