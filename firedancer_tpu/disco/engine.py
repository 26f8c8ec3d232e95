"""fd_engine — verify-graph engine registry + latency-adaptive rung
scheduler (ROADMAP direction 3: continuous batching).

Two halves, both pure host-side (stdlib + the flight/msm_plan helpers;
jax is imported lazily only when a device graph is actually built, so
disco/tiles.py's jax-import-free contract for host-backend tiles
holds):

  REGISTRY   every verify graph is a typed, cached EngineEntry keyed by
             the flight ``engine_key`` (mode x B x shards x frontend).
             The entry carries the built async verify callable (and the
             per-lane fallback graph for rlc), its prewarm state
             (cold/warming/warm/failed), the measured compile cost
             (booked through flight.record_compile — the same per-engine
             compile accounting fd_flight introduced), the analytic
             fill-efficiency / executed-madds cost from msm_plan, and a
             measured service-time EMA. Before fd_engine this dispatch
             logic was smeared across disco/tiles.py (VerifyTile's
             backend=='tpu' branch), ops/backend.py
             (default_verify_mode) and bench.py (the worker's
             jit+rlc-wrap block); all three now resolve through the
             registry, so the compile-cache-hit accounting between
             bench workers and VerifyTile prewarm comes from ONE
             heuristic instead of three hand-rolled copies.
             ``prewarm_ladder`` warms the configured rung ladder on a
             background thread (FD_ENGINE_PREWARM policy) so a tile can
             switch rungs without paying a mid-run compile.

  SCHEDULER  RungScheduler promotes AdaptiveFlush (disco/feed/policy.py)
             into an ONLINE continuous-batching scheduler, inference-
             serving style: pick the dispatch B from the FD_ENGINE_LADDER
             rung ladder using queue depth (staged lanes + ring
             backlog), deadline slack, and each rung's registry-attached
             cost model. Low offered load takes the small-rung latency
             (the batch "fills" at the small rung and ships early);
             saturation takes the big-rung throughput (fill efficiency
             is monotone in B — msm_plan, BENCH r05: 0.63 -> 0.76 from
             8k to 32k). Pure decision logic, AdaptiveFlush pattern:
             the caller passes now_ns, no clock reads, so the policy is
             property-testable without a device — the deadline
             invariant (a partial batch is never starved past the
             deadline) is inherited verbatim because the flush verdict
             still comes from the embedded AdaptiveFlush, just with the
             chosen rung as the batch bound.

Thread discipline (docs/OWNERSHIP.md, fdlint pass 6): the registry's
entry map is lock-guarded; per-entry builds/warms serialize on the
entry's own build lock (never the registry lock — compiles take
minutes); the prewarm thread only calls the same lock-guarded acquire
path. A RungScheduler instance is single-threaded by contract (the
feed stager owns the tile's instance).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple

from firedancer_tpu import flags, msm_plan
from firedancer_tpu.disco import flight
from firedancer_tpu.disco.feed.policy import AdaptiveFlush

# EngineEntry prewarm states.
ENGINE_COLD = "cold"         # record exists; no graph built yet
ENGINE_WARMING = "warming"   # a warm pass (compile) is in flight
ENGINE_WARM = "warm"         # compiled + warmed: dispatchable now
ENGINE_FAILED = "failed"     # last warm attempt raised (err recorded)

# Host engines (no device graph to compile): the registry still tracks
# them so every dispatch site keys its accounting the same way.
_HOST_MODES = ("cpu", "oracle")


def current_frontend() -> str:
    """The frontend half of the engine key (FD_FRONTEND_IMPL)."""
    return flags.get_str("FD_FRONTEND_IMPL") or "auto"


@dataclass(frozen=True)
class EngineSpec:
    """The typed engine identity behind flight.engine_key — mode x B x
    shards x frontend x msm plan. Hashable, so it is also the registry
    map key. ``msm`` is the fd_msm2 schedule token ("auto" = resolve
    active_plan() from the FD_MSM_* flags at build time); a pinned
    token forces that exact schedule into the built verify graph, so
    two engines at the same rung but different MSM plans are DISTINCT
    registry entries with separate compile/service accounting."""

    mode: str            # rlc | direct (device) or cpu | oracle (host)
    batch: int
    shards: int = 0      # mesh_devices of the sharded verify step
    frontend: str = "auto"
    msm: str = "auto"    # fd_msm2 plan token (msm_plan.parse_plan)

    @property
    def key(self) -> str:
        return flight.engine_key(self.mode, self.batch, self.shards,
                                 self.frontend, self.msm)

    def with_batch(self, batch: int) -> "EngineSpec":
        return replace(self, batch=batch)

    def with_msm(self, msm: str) -> "EngineSpec":
        return replace(self, msm=msm)

    def resolved_msm(self) -> str:
        """The plan token this spec's graph would bake in NOW: the
        pinned token verbatim, else the FD_MSM_* flag resolution
        (msm_plan.plan_from_flags — jax-free, so host-side registry
        bookkeeping can call this; meaningful for rlc engines only,
        direct/host engines run no Pippenger MSM)."""
        if self.msm != "auto":
            return self.msm
        return msm_plan.plan_token(msm_plan.plan_from_flags())

    @classmethod
    def for_tile(cls, backend: str, verify_mode: str, batch: int,
                 mesh_devices: int) -> "EngineSpec":
        """The spec a VerifyTile's dispatches are keyed by: device
        backends key on the resolved verify mode, host backends on the
        backend name (the long-standing engine_key convention). The
        msm field comes from the registry's per-rung plan table
        (msm_search winners), falling back to "auto" (the FD_MSM_*
        flags) for rungs no search has certified."""
        mode = verify_mode if backend == "tpu" else backend
        msm = "auto"
        if mode == "rlc":
            msm = registry().rung_plan(batch)
        return cls(mode, batch, mesh_devices, current_frontend(), msm)


def parse_key(key: str) -> EngineSpec:
    """Inverse of EngineSpec.key
    ("mode:B<batch>:shards<n>:fe<impl>[:msm<plan>]") for
    artifact/readback tooling; raises ValueError on junk. The msm
    segment is optional — every pre-fd_msm2 key parses to msm="auto",
    so old artifacts keep round-tripping."""
    parts = key.split(":")
    if (len(parts) not in (4, 5) or not parts[1].startswith("B")
            or not parts[2].startswith("shards")
            or not parts[3].startswith("fe")):
        raise ValueError(f"not an engine key: {key!r}")
    msm = "auto"
    if len(parts) == 5:
        if not parts[4].startswith("msm") or len(parts[4]) <= 3:
            raise ValueError(f"not an engine key: {key!r}")
        msm = parts[4][3:]
    return EngineSpec(parts[0], int(parts[1][1:]), int(parts[2][6:]),
                      parts[3][2:], msm)


# --------------------------------------------------------------------------
# Mode resolution — moved here from disco/tiles.py + ops/backend.py so
# ONE module owns every engine-resolution decision (the dispatch sites
# are registry lookups).
# --------------------------------------------------------------------------


def drain_mode() -> str:
    """FD_DRAIN resolution: 'auto' arms the device-resident post-verify
    drain (dedup pre-filter + optional pack coloring fused behind
    verify) wherever the substrate supports it — the fd_feed staging
    path; anywhere else it degrades to exactly the 'off' behavior,
    never to an error. 'off' disables the
    drain stage outright (the A/B and bisection hatch). An unknown
    value raises — a typo'd force must never masquerade as a
    measurement of either arm."""
    mode = flags.get_str("FD_DRAIN") or "auto"
    if mode not in ("auto", "off"):
        raise ValueError(f"unknown FD_DRAIN {mode!r} (want auto|off)")
    return mode


def default_verify_mode() -> str:
    """Verify-tile mode when the config says 'auto' (round-6 RLC
    promotion): 'rlc' — batch RLC verification over the VMEM Pallas
    Pippenger MSM (ops/verify_rlc.py) — on TPU platforms; 'direct'
    per-lane on host-jax backends (no VMEM engine to amortize, and the
    CPU-jax RLC graph is a CI/parity path, not a production one).
    FD_VERIFY_MODE forces either explicitly; an unrecognized value is
    an error, not a silent fall-through to the platform default (a
    typo'd force must never masquerade as a measurement of the mode
    the operator asked for)."""
    forced = flags.get_raw("FD_VERIFY_MODE")
    if forced:
        if forced not in ("rlc", "direct"):
            raise ValueError(
                f"unknown FD_VERIFY_MODE {forced!r} (want rlc|direct)"
            )
        return forced
    from firedancer_tpu.ops.backend import _platform_is_tpu

    return "rlc" if _platform_is_tpu() else "direct"


def resolve_verify_mode(backend: str, verify_mode: str,
                        mesh_devices: int) -> str:
    """Resolve a VerifyTile's verify mode (module-level so the
    contract is unit-testable without a workspace).

    'auto' resolves by the ATTACHED PLATFORM (default_verify_mode
    above): rlc on TPU families — including mesh_devices, now that the
    Pippenger MSM shards across the mesh (round-10) — direct on
    host-jax backends. FD_VERIFY_MODE forces either explicitly; an
    unknown value raises. The GENUINELY unsupported combination is rlc
    on a non-jax backend ('cpu'/'oracle' host verifiers have no batch
    engine for the RLC graph to run on) — that is the only remaining
    blanket rejection. FD_MSM_SHARD=0 is the bisection hatch that
    restores the pre-round-10 rlc+mesh rejection (a silent downgrade
    to direct would masquerade as a measurement of the sharded path).

    The env force is validated HERE as well as at the platform default:
    host-backend tiles must stay jax-import-free, so they cannot probe
    the platform, but an explicit force — or a typo'd one — must still
    fail loudly instead of being silently dropped."""
    if verify_mode not in ("auto", "direct", "rlc"):
        raise ValueError(
            f"unknown verify_mode {verify_mode!r} (want auto|direct|rlc)"
        )
    shard_ok = flags.get_bool("FD_MSM_SHARD")
    if verify_mode == "auto":
        forced = flags.get_raw("FD_VERIFY_MODE")
        if forced and forced not in ("rlc", "direct"):
            raise ValueError(
                f"unknown FD_VERIFY_MODE {forced!r} (want rlc|direct)"
            )
        if backend != "tpu":
            if forced == "rlc":
                raise ValueError(
                    "FD_VERIFY_MODE=rlc requires backend='tpu' (the "
                    "host cpu|oracle verifiers have no batch engine "
                    "for the RLC graph — the one genuinely "
                    "unsupported combination)"
                )
            return "direct"
        verify_mode = default_verify_mode()
        if verify_mode == "rlc" and mesh_devices and not shard_ok:
            # The FD_MSM_SHARD=0 hatch: a platform auto-pick quietly
            # stays direct, but an EXPLICIT FD_VERIFY_MODE=rlc force
            # must fail loudly, not be silently dropped.
            if forced == "rlc":
                raise ValueError(
                    "FD_VERIFY_MODE=rlc with mesh_devices needs the "
                    "sharded MSM, which FD_MSM_SHARD=0 disabled"
                )
            verify_mode = "direct"
        return verify_mode
    if verify_mode == "rlc" and backend != "tpu":
        # Silently running the oracle path while the operator believes
        # RLC is on would be indistinguishable from "no fallbacks".
        raise ValueError(
            "verify_mode='rlc' requires backend='tpu' (the host "
            "cpu|oracle verifiers have no batch engine for the RLC "
            "graph — the one genuinely unsupported combination)"
        )
    if verify_mode == "rlc" and mesh_devices and not shard_ok:
        raise ValueError(
            "verify_mode='rlc' with mesh_devices needs the sharded "
            "MSM, which FD_MSM_SHARD=0 disabled"
        )
    return verify_mode


def engine_kernels(mode: str, batch: int, shards: int = 0) -> Dict[str, str]:
    """Which engine serves each stage of a device verify graph at this
    batch (per-shard lanes on a mesh) under the current flags: the
    front end ('fused' | 'interpret' | 'staged', plus the SHA engine
    when staged), the decompress, the rlc MSM and the per-lane DSM
    (direct mode, and rlc's fallback). The dispatchers decide with the
    same functions at trace time, so a forced FD_*_IMPL=pallas that
    cannot serve the shape raises here, at build, before any compile."""
    from firedancer_tpu.ops.decompress_pallas import decompress_choice
    from firedancer_tpu.ops.frontend_pallas import frontend_choice
    from firedancer_tpu.ops.sha512 import sha_choice
    from firedancer_tpu.ops.verify import dsm_choice

    lanes = batch // shards if shards else batch
    out = {"frontend": frontend_choice(lanes)}
    if out["frontend"] == "staged":
        out["sha"] = sha_choice(lanes)
    # A and R decompress stacked: 2*lanes rows.
    out["decompress"] = decompress_choice(2 * lanes)
    if mode == "rlc":
        from firedancer_tpu.ops.verify_rlc import msm_engine

        out["msm"] = msm_engine()
    out["dsm"] = dsm_choice()
    return out


# --------------------------------------------------------------------------
# Engine entries + registry.
# --------------------------------------------------------------------------


class EngineEntry:
    """One prepared verify engine. Mutation discipline: ``state`` /
    ``fn`` / compile fields change only under the entry's build lock
    (held by whichever thread builds or warms it — a tile constructor,
    a bench worker, or the registry prewarm thread); the dispatch-side
    counters (dispatches/lanes/service EMA) are written by the single
    dispatching tile thread that owns the engine at runtime."""

    __slots__ = (
        "spec", "key", "state", "fn", "direct_fn", "compile_s",
        "fallback_compile_s", "cache_hit_est", "err", "dispatches",
        "lanes", "service_ns", "fill_efficiency", "madds_per_lane",
        "msm_token", "built_ts", "_warmed", "_build_lock",
        # fd_pod split-step pair (mesh rlc engines under FD_POD_SPLIT):
        # the two separately-jitted graphs + their own service EMAs, so
        # the cost model can be overlap-aware (combine_tail hides
        # behind the next batch's local_fill when double-buffered).
        "fn_local", "fn_tail", "service_local_ns", "service_tail_ns",
        # fd_drain post-verify stage (None unless FD_DRAIN armed this
        # build): the dedup-filter aux graph, dispatched back-to-back
        # with fn on the same device queue so statuses + novel-mask
        # come home in one completion.
        "fn_drain",
        # Which engine serves each verify stage at this batch
        # (engine_kernels): recorded at build, carried in verify_stats.
        "kernels",
        # Mesh engines: the batch-axis NamedSharding that put() places
        # host inputs with, so each chip receives only its own rows.
        "in_sharding",
        # Host counters: the rlc weights' draw and placement (booked by
        # the async verifier per batch), and on a mesh engine the lanes
        # with a message that put() placed on each shard.
        "rlc_weights_ns", "rlc_weights_calls", "shard_lanes",
    )

    def __init__(self, spec: EngineSpec):
        self.spec = spec
        self.key = spec.key
        self.state = ENGINE_WARM if spec.mode in _HOST_MODES \
            else ENGINE_COLD
        self.fn: Optional[Callable] = None        # async verify callable
        self.direct_fn: Optional[Callable] = None  # rlc per-lane fallback
        # fd_pod split-step graphs (None unless spec.shards + rlc +
        # FD_POD_SPLIT built this engine as a local/tail pair).
        self.fn_local: Optional[Callable] = None
        self.fn_tail: Optional[Callable] = None
        # fd_drain aux stage (None unless FD_DRAIN armed this build).
        self.fn_drain: Optional[Callable] = None
        self.kernels: Dict[str, str] = {}
        self.in_sharding = None
        self.rlc_weights_ns = 0
        self.rlc_weights_calls = 0
        self.shard_lanes: List[int] = [0] * spec.shards
        self.service_local_ns = 0   # EMA: dispatch -> local_fill ready
        self.service_tail_ns = 0    # EMA: local ready -> combine ready
        self.compile_s = 0.0
        self.fallback_compile_s = 0.0
        self.cache_hit_est = False
        self.err: Optional[str] = None
        self.dispatches = 0
        self.lanes = 0
        self.service_ns = 0        # EMA of dispatch->complete wall ns
        # Analytic cost model (msm_plan): meaningful for the rlc MSM
        # engine; the direct/host engines scale ~linearly in lanes, so
        # their per-lane proxy is flat.
        if spec.mode == "rlc":
            self.fill_efficiency = msm_plan.fill_efficiency(
                spec.batch)["total"]
            self.madds_per_lane = msm_plan.executed_madds_per_lane(
                spec.batch)
            # fd_msm2: the schedule token this engine's graph bakes in
            # (re-resolved at _build, where the bake actually happens).
            self.msm_token = spec.resolved_msm()
        else:
            self.fill_efficiency = None
            self.madds_per_lane = None
            self.msm_token = None
        self.built_ts = 0.0
        self._warmed: set = set()   # (batch, max_msg_len) shapes warmed
        self._build_lock = threading.Lock()

    def put(self, a):
        """Place one host batch input for this engine: on a mesh
        engine, each chip receives its own rows straight from the host
        array. The warm pass and every dispatcher place inputs through
        here, so the graph the warm compiled is the one the dispatches
        hit (a different input sharding is a different jit entry: a
        second trace and compile mid-run)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.in_sharding is None:
            return jnp.asarray(a)
        if isinstance(a, np.ndarray) and a.ndim == 1:
            # The batch's one 1-D input is its lane lengths: book the
            # lanes with a message that each shard receives.
            live = np.count_nonzero(a.reshape(len(self.shard_lanes), -1),
                                    axis=1)
            for i, n in enumerate(live.tolist()):
                self.shard_lanes[i] += n
        return jax.device_put(a, self.in_sharding)

    def note_weights(self, ns: int) -> None:
        """One batch's rlc weights drawn and placed, in ns of host
        time (the async verifier's on_weights)."""
        self.rlc_weights_ns += ns
        self.rlc_weights_calls += 1

    def note_dispatch(self, lanes: int) -> None:
        self.dispatches += 1
        self.lanes += lanes

    def note_service(self, ns: int) -> None:
        """Measured dispatch->complete wall time: EMA(1/8) so the cost
        model tracks the device without chasing single-batch noise."""
        self.service_ns = (ns if not self.service_ns
                           else (7 * self.service_ns + ns) // 8)

    def note_service_split(self, local_ns: int, tail_ns: int) -> None:
        """fd_pod split-step cost samples: separate EMAs for the two
        graphs, same 1/8 smoothing as note_service. The whole-batch
        EMA is fed too (local + tail) so consumers that predate the
        split keep reading a sane number."""
        self.service_local_ns = (local_ns if not self.service_local_ns
                                 else (7 * self.service_local_ns
                                       + local_ns) // 8)
        self.service_tail_ns = (tail_ns if not self.service_tail_ns
                                else (7 * self.service_tail_ns
                                      + tail_ns) // 8)
        self.note_service(local_ns + tail_ns)

    def service_est_ns(self) -> int:
        """Best service-time estimate for one batch on this engine:
        the measured EMA, 0 while unmeasured (callers treat 0 as "no
        cost information — do not cap on it").

        OVERLAP-AWARE when the split EMAs are populated: a
        double-buffered dispatcher retires one batch per
        max(local_fill, combine_tail) at steady state — the classic
        two-stage pipeline bound — because batch k's tail executes
        while batch k+1's fill is already dispatched. The estimate is
        that bound, never less than either stage (a scheduler capping
        deadline slack on the serialized sum would step down exactly
        when pipelining has already hidden the tail)."""
        if self.service_local_ns and self.service_tail_ns:
            return max(self.service_local_ns, self.service_tail_ns)
        return self.service_ns

    def overlap_hidden_est(self) -> float:
        """Fraction of combine_tail the double-buffer hides at steady
        state, per the measured EMAs: 1.0 while the tail fits inside
        the next fill entirely, shrinking as the tail dominates. 0.0
        until both split EMAs are measured (monolithic engines stay
        0.0 — nothing is split, nothing hides)."""
        lo, tl = self.service_local_ns, self.service_tail_ns
        if not lo or not tl:
            return 0.0
        return min(1.0, lo / tl)

    def account_first_call(self, seconds: float,
                           msg_len: int = 0) -> None:
        """Book a caller-measured first-call compile (the bench worker
        path: it warms on its REAL inputs so the timed reps stay
        one-execution-per-rep) through the same flight accounting the
        warm path uses. Pass the executed msg_len so the shape is
        registered as warmed — a later acquire(warm=True) at the SAME
        shape must not re-warm and double-book the compile record
        (jit retraces genuinely different shapes, so those still
        warm). Takes the build lock: these fields are build-phase
        state."""
        with self._build_lock:
            rec = flight.record_compile(self.key, seconds)
            self.compile_s = seconds
            self.cache_hit_est = bool(rec["cache_hit_est"])
            self.state = ENGINE_WARM
            self.built_ts = time.time()
            if msg_len:
                self._warmed.add((self.spec.batch, msg_len))

    def snapshot(self) -> dict:
        return {
            "key": self.key,
            "mode": self.spec.mode,
            "batch": self.spec.batch,
            "shards": self.spec.shards,
            "frontend": self.spec.frontend,
            "state": self.state,
            "compile_s": round(self.compile_s, 3),
            "fallback_compile_s": round(self.fallback_compile_s, 3),
            "cache_hit_est": self.cache_hit_est,
            "dispatches": self.dispatches,
            "lanes": self.lanes,
            "service_est_ns": self.service_est_ns(),
            # fd_pod split-step accounting ({} = monolithic engine):
            "split": ({
                "service_local_ns": self.service_local_ns,
                "service_tail_ns": self.service_tail_ns,
                "overlap_hidden_est": round(self.overlap_hidden_est(), 3),
            } if self.fn_local is not None else {}),
            "fill_efficiency": (round(self.fill_efficiency, 4)
                                if self.fill_efficiency is not None
                                else None),
            # fd_msm2: the MSM schedule token the graph bakes in
            # (None = not an MSM engine). "auto" never appears here —
            # the entry records the RESOLVED plan, so an artifact
            # reader can tell which schedule a service EMA measured
            # even when the spec deferred to the FD_MSM_* flags.
            "msm": self.msm_token,
            # fd_drain: whether this build attached the post-verify
            # drain stage (FD_DRAIN at build time).
            "drain": self.fn_drain is not None,
            "kernels": dict(self.kernels),
            "err": self.err,
        }


class EngineRegistry:
    """The process-wide map engine_key -> EngineEntry. ``acquire`` is
    the ONE dispatch-site API: get-or-create the entry, build its
    graph, optionally warm (compile) it — idempotent per (spec, warm
    shape), so N call sites resolving the same engine pay one compile
    and share one accounting record."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[EngineSpec, EngineEntry] = {}
        # fd_msm2: per-rung MSM schedule winners (batch -> plan token),
        # installed by scripts/msm_search.py's certify+parity+bench
        # pipeline; EngineSpec.for_tile consults this so a tile's rlc
        # dispatches pick up the searched plan without env plumbing.
        self._rung_plans: Dict[int, str] = {}
        self._prewarm_q: deque = deque()   # (spec, max_msg_len)
        self._prewarm_wake = threading.Event()
        self._prewarm_stop = threading.Event()
        self._prewarm_thread: Optional[threading.Thread] = None
        # Guarded by _lock: True while a prewarm thread has committed
        # to draining the queue. The exit decision and this flag flip
        # happen under ONE lock hold, so a producer appending specs
        # either sees running=False (and starts a fresh thread) or is
        # seen by the draining loop before it breaks — is_alive() alone
        # races a thread that decided to exit but hasn't died yet.
        self._prewarm_running = False

    # -- per-rung MSM plans (fd_msm2) ------------------------------------

    def set_rung_plan(self, batch: int, token: str) -> None:
        """Install the msm_search winner for a B rung. The token is
        validated through msm_plan.parse_plan — a plan the grammar
        rejects (and so the certifier never admitted) cannot be
        registered, which is the registry half of the negative-control
        contract. "auto" clears the pin (the rung falls back to the
        FD_MSM_* flags)."""
        if token != "auto":
            msm_plan.parse_plan(token)   # raises on junk/unshippable
        with self._lock:
            if token == "auto":
                self._rung_plans.pop(int(batch), None)
            else:
                self._rung_plans[int(batch)] = token

    def rung_plan(self, batch: int) -> str:
        """The pinned MSM schedule token for a B rung ("auto" when no
        search winner is installed)."""
        with self._lock:
            return self._rung_plans.get(int(batch), "auto")

    # -- entry map -------------------------------------------------------

    def entry(self, spec: EngineSpec) -> EngineEntry:
        """Get-or-create the record WITHOUT building anything (cost
        model / accounting handles for schedulers and artifacts)."""
        with self._lock:
            e = self._entries.get(spec)
            if e is None:
                e = EngineEntry(spec)
                self._entries[spec] = e
            return e

    def entries(self) -> List[EngineEntry]:
        with self._lock:
            return list(self._entries.values())

    def snapshot(self) -> List[dict]:
        """Artifact view of every known engine (bench/replay records,
        flight dumps)."""
        return [e.snapshot() for e in self.entries()]

    # -- build + warm ----------------------------------------------------

    def acquire(self, spec: EngineSpec, warm: bool = True,
                max_msg_len: int = 1232) -> Tuple[EngineEntry, bool]:
        """Resolve an engine for dispatch. Returns (entry, warmed_now):
        warmed_now is True when THIS call paid a warm pass (the caller
        books it into its own tile lane; flight.record_compile is
        already booked by the registry). warm=False builds the callable
        without compiling — the bench worker warms on its real inputs
        and books via entry.account_first_call."""
        e = self.entry(spec)
        if spec.mode in _HOST_MODES:
            return e, False
        with e._build_lock:
            if e.fn is None:
                try:
                    self._build(e)
                except BaseException as exc:
                    # Build failures must be observable too (a rung
                    # whose shape can't build, e.g. not divisible over
                    # the mesh): state=failed + err, like a failed warm
                    # — snapshot readers can tell "broken" from "never
                    # attempted", and warm_entry keeps returning None.
                    e.state = ENGINE_FAILED
                    e.err = repr(exc)[:200]
                    raise
            warmed_now = False
            if warm:
                warmed_now = self._warm_locked(e, max_msg_len)
            return e, warmed_now

    def _build(self, e: EngineEntry) -> None:
        """Construct the async verify callable(s) for a device engine —
        the dispatch logic formerly inlined in VerifyTile.__init__ and
        bench.worker. Cheap (graph wrapping only); the compile happens
        at the warm pass / first call."""
        spec = e.spec
        import jax

        from firedancer_tpu.ops.verify import verify_batch

        e.kernels = engine_kernels(spec.mode, spec.batch, spec.shards)

        # fd_msm2: the MSM schedule this graph bakes in. A pinned spec
        # token forces that exact plan; "auto" passes plan=None so the
        # builders resolve active_plan() from the FD_MSM_* flags at
        # trace time (the pre-fd_msm2 behavior when all flags default).
        plan = None
        if spec.mode == "rlc":
            if spec.msm != "auto":
                plan = msm_plan.parse_plan(spec.msm)
            e.msm_token = spec.resolved_msm()

        rlc_sharded = None
        weights_sharding = None
        if spec.shards:
            if spec.batch % spec.shards:
                raise ValueError(
                    f"batch {spec.batch} must divide over {spec.shards} "
                    "mesh devices"
                )
            from firedancer_tpu.parallel.mesh import (
                make_mesh,
                verify_step_sharded,
            )

            from jax.sharding import NamedSharding, PartitionSpec

            mesh = make_mesh(spec.shards)
            axis = mesh.axis_names[0]
            e.in_sharding = NamedSharding(mesh, PartitionSpec(axis))
            # The rlc weights go where put() sends the lanes: z by
            # lane, u in its (K, 2, B) block layout by lane.
            weights_sharding = (e.in_sharding, NamedSharding(
                mesh, PartitionSpec(None, None, axis)))
            _sharded = verify_step_sharded(mesh)

            def direct_fn(msgs, lens, sigs, pubs):
                return _sharded(msgs, lens, sigs, pubs)[0]

            if spec.mode == "rlc":
                if flags.get_bool("FD_POD_SPLIT"):
                    # fd_pod split-step pair: local_fill + combine_tail
                    # as two jitted graphs, composed here into the
                    # rlc_fn contract (status, definite, batch_ok).
                    # Dispatching through the composition enqueues BOTH
                    # graphs asynchronously, so with inflight >= 2 the
                    # tile's dispatcher already double-buffers: batch
                    # k+1's local_fill is on the queue while batch k's
                    # combine_tail executes.
                    from firedancer_tpu.parallel.mesh import (
                        verify_rlc_split_sharded,
                    )

                    local_fn, tail_fn = verify_rlc_split_sharded(
                        mesh, plan=plan)
                    e.fn_local = local_fn
                    e.fn_tail = tail_fn
                    span = flight.span_maker()

                    def rlc_sharded(msgs, lens, sigs, pubs, z, u):
                        with span("fd.pod.local_launch"):
                            status, definite, parts = local_fn(
                                msgs, lens, sigs, pubs, z, u)
                        with span("fd.pod.tail_launch"):
                            ok = tail_fn(parts)
                        return status, definite, ok
                else:
                    from firedancer_tpu.parallel.mesh import (
                        verify_rlc_step_sharded,
                    )

                    rlc_sharded = verify_rlc_step_sharded(mesh, plan=plan)
        else:
            direct_fn = jax.jit(verify_batch)
        fn = direct_fn
        if spec.mode == "rlc":
            # RLC batch-verify fast pass with lazy per-lane fallback
            # (ops/verify_rlc.py); clean batches cost one MSM pass.
            from firedancer_tpu.ops.verify_rlc import (
                make_async_verifier,
                verify_batch_rlc,
            )

            if rlc_sharded is None and plan is not None:
                # Single-device engine with a pinned plan: bake it into
                # the jitted RLC graph here (make_async_verifier's
                # default jit would re-resolve from the flags).
                import functools

                rlc_sharded = jax.jit(
                    functools.partial(verify_batch_rlc, plan=plan))
            fn = make_async_verifier(direct_fn, rlc_fn=rlc_sharded,
                                     weights_sharding=weights_sharding,
                                     on_weights=e.note_weights)
        e.direct_fn = direct_fn
        e.fn = fn
        # fd_drain: attach the dedup-filter aux graph (built like the
        # FD_POD_SPLIT pair — a separately-jitted stage the dispatcher
        # enqueues right behind fn, so the novel-mask rides home in the
        # same completion sync). Gated at build, like FD_POD_SPLIT.
        if drain_mode() != "off":
            from firedancer_tpu.disco import drain as drain_mod

            e.fn_drain = drain_mod.make_filter_fn()

    def _warm_locked(self, e: EngineEntry, max_msg_len: int) -> bool:
        """Warm (compile) the engine at (batch, max_msg_len) — caller
        holds the entry build lock. Returns True when a warm pass ran.
        The rlc fallback graph is warmed too: the zero-lane warm batch
        resolves on the RLC pass alone, and the per-lane fallback would
        otherwise compile mid-run on the first salted batch."""
        shape = (e.spec.batch, max_msg_len)
        if shape in e._warmed:
            return False
        import jax.numpy as jnp
        import numpy as np

        e.state = ENGINE_WARMING
        warm_args = tuple(e.put(np.zeros(s, dt)) for s, dt in (
            (shape, np.uint8),
            ((e.spec.batch,), np.int32),
            ((e.spec.batch, 64), np.uint8),
            ((e.spec.batch, 32), np.uint8),
        ))
        try:
            t0 = time.perf_counter()
            np.asarray(e.fn(*warm_args))
            e.compile_s = time.perf_counter() - t0
            rec = flight.record_compile(e.key, e.compile_s)
            e.cache_hit_est = bool(rec["cache_hit_est"])
            if e.spec.mode == "rlc":
                t0 = time.perf_counter()
                np.asarray(e.direct_fn(*warm_args))
                e.fallback_compile_s = time.perf_counter() - t0
                flight.record_compile(e.key + ":fallback",
                                      e.fallback_compile_s)
            if e.fn_drain is not None:
                # fd_drain aux graph: warm at the same batch shape so
                # the first drain dispatch never compiles mid-run.
                from firedancer_tpu.ops.dedup_filter import filter_words

                w = filter_words(flags.get_int("FD_DRAIN_FILTER_BITS"))
                for out in e.fn_drain(
                        jnp.zeros((e.spec.batch,), jnp.uint32),
                        jnp.zeros((e.spec.batch,), jnp.uint32),
                        jnp.zeros((e.spec.batch,), jnp.bool_),
                        jnp.zeros((w,), jnp.uint32),
                        jnp.zeros((w,), jnp.uint32)):
                    np.asarray(out)
        except BaseException as exc:
            e.state = ENGINE_FAILED
            e.err = repr(exc)[:200]
            raise
        e._warmed.add(shape)
        e.state = ENGINE_WARM
        e.err = None
        e.built_ts = time.time()
        return True

    def warm_entry(self, spec: EngineSpec) -> Optional[EngineEntry]:
        """The dispatch-time lookup for a rung switch: the entry iff it
        is WARM and dispatchable right now, else None (the caller keeps
        the engine it already holds — a rung switch must never stall a
        hot loop on a compile)."""
        with self._lock:
            e = self._entries.get(spec)
        if e is not None and e.state == ENGINE_WARM and e.fn is not None:
            return e
        return None

    def entry_count(self) -> int:
        """Registered engine entries (every state) — the fd_soak
        compile-cache tripwire samples this: a flat count over hours
        means the ladder is closed; monotone growth means shapes are
        leaking past the prewarmed rungs."""
        with self._lock:
            return len(self._entries)

    def retire(self, specs) -> int:
        """Drop the given specs from the registry (live-reconfig
        cleanup after a ladder swap: the OLD rungs' engines become
        unreachable and their jitted callables can be collected).
        Specs not present are ignored; returns how many were dropped.
        Callers must not retire the engine a tile still dispatches on
        — the reconfig barrier guarantees no inflight batch holds one.
        """
        dropped = 0
        with self._lock:
            for spec in specs:
                if self._entries.pop(spec, None) is not None:
                    dropped += 1
        return dropped

    # -- background prewarm ---------------------------------------------

    def prewarm_ladder(self, specs, max_msg_len: int = 1232,
                       policy: Optional[str] = None) -> None:
        """Warm a rung ladder per the FD_ENGINE_PREWARM policy:
        'background' queues the specs for the registry prewarm thread
        (started on first use; rung switches pick each engine up as it
        turns WARM), 'sync' warms inline before returning, 'off' does
        nothing (every rung but the primary stays cold — the scheduler
        then effectively pins the primary engine)."""
        policy = policy or flags.get_str("FD_ENGINE_PREWARM")
        if policy not in ("background", "sync", "off"):
            raise ValueError(
                f"unknown FD_ENGINE_PREWARM {policy!r} "
                "(want background|sync|off)"
            )
        if policy == "off":
            return
        if policy == "sync":
            for spec in specs:
                self.acquire(spec, warm=True, max_msg_len=max_msg_len)
            return
        with self._lock:
            for spec in specs:
                self._prewarm_q.append((spec, max_msg_len))
            if not self._prewarm_running:
                self._prewarm_running = True
                self._prewarm_stop.clear()
                t = threading.Thread(
                    target=self._prewarm_loop, name="fd_engine.prewarm",
                    daemon=True,
                )
                self._prewarm_thread = t
                t.start()
        self._prewarm_wake.set()

    def _prewarm_loop(self) -> None:
        # Single consumer of the prewarm queue; every mutation it
        # performs goes through the same lock-guarded acquire path the
        # foreground callers use (docs/OWNERSHIP.md row). A failed warm
        # is recorded on the entry (state=failed, err) and the loop
        # moves on — a broken rung must not kill prewarm for the rest
        # of the ladder.
        while not self._prewarm_stop.is_set():
            with self._lock:
                item = (self._prewarm_q.popleft()
                        if self._prewarm_q else None)
            if item is None:
                self._prewarm_wake.wait(timeout=0.2)
                self._prewarm_wake.clear()
                with self._lock:
                    if not self._prewarm_q:
                        # Exit decision + running-flag flip under ONE
                        # lock hold (see _prewarm_running): a producer
                        # can never enqueue into a thread that already
                        # chose to die.
                        self._prewarm_running = False
                        break
                continue
            spec, max_msg_len = item
            try:
                self.acquire(spec, warm=True, max_msg_len=max_msg_len)
            except BaseException:
                pass  # entry carries state=failed + err for observers
        with self._lock:
            self._prewarm_running = False  # stop-Event exits too

    def prewarm_idle(self) -> bool:
        """True when no background prewarm work is queued or running
        (tests + the engine smoke synchronize on this)."""
        with self._lock:
            return not self._prewarm_q and not self._prewarm_running

    def stop_prewarm(self, timeout: float = 10.0) -> None:
        """Stop background prewarm: the queue is DROPPED (stop means
        stop — leaving specs queued would strand them behind a dead
        thread) and the thread joined. A later prewarm_ladder call
        starts fresh (the running flag flips off at thread exit)."""
        with self._lock:
            self._prewarm_q.clear()
        self._prewarm_stop.set()
        self._prewarm_wake.set()
        t = self._prewarm_thread
        if t is not None:
            t.join(timeout=timeout)


_registry: Optional[EngineRegistry] = None
_registry_lock = threading.Lock()


def registry() -> EngineRegistry:
    """The process-wide registry (tiles, bench workers and smokes all
    resolve through this one instance, so engine accounting has one
    authority per process)."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = EngineRegistry()
        return _registry


# --------------------------------------------------------------------------
# Rung ladder + scheduler.
# --------------------------------------------------------------------------


def rung_ladder(cap: Optional[int] = None, floor: int = 0) -> List[int]:
    """The FD_ENGINE_LADDER rung list: parsed, deduped, ascending.
    `cap` drops rungs above the tile's staging batch (arenas are sized
    to the largest rung); `floor` drops rungs too small to stage a
    whole txn (MAX_SIG_CNT). A malformed entry raises — a typo'd
    ladder must never silently schedule on the wrong rungs."""
    raw = flags.get_str("FD_ENGINE_LADDER")
    rungs = set()
    for part in (raw or "").split(","):
        part = part.strip()
        if not part:
            continue
        try:
            b = int(part)
        except ValueError:
            raise ValueError(
                f"bad FD_ENGINE_LADDER entry {part!r} (want a "
                "comma-separated list of batch sizes)"
            ) from None
        if b <= 0:
            raise ValueError(
                f"FD_ENGINE_LADDER rung {b} must be positive")
        rungs.add(b)
    out = sorted(r for r in rungs
                 if r >= floor and (cap is None or r <= cap))
    return out


class RungScheduler:
    """Latency-adaptive rung selection over a B ladder — AdaptiveFlush
    promoted into an online continuous-batching scheduler.

    Decision shape (all pure in the passed clock; single caller thread
    by contract — the feed stager):

      pick(now_ns, lanes, first_ns, backlog)  ->  target rung
          the largest rung the present queue depth (staged lanes +
          ring backlog) can fill — monotone rung-up in depth, the
          property test pins it — capped by deadline slack: a rung
          whose measured service estimate exceeds the staged batch's
          remaining latency budget cannot meet the deadline, so the
          pick steps down (floor: the smallest rung). Unmeasured rungs
          (cost 0) are never capped — prewarm hasn't seen them yet and
          guessing would pin the ladder small forever.

      due(...)  ->  AdaptiveFlush verdict with the CURRENT rung as the
          batch bound: the deadline/starve invariants are inherited
          verbatim (same policy object, same hwm clock hardening).

      dispatch_rung(lanes)  ->  the smallest rung that covers a staged
          lane count (engines are compiled per rung; a partial pads up
          to the chosen rung's shape).

    `cost_ns(rung)` is the registry-attached service model (EngineEntry
    service EMA); None disables slack capping (host engines, whose
    service scales with lanes rather than the padded rung).

    `shards` (fd_pod): on a mesh engine every rung is a GLOBAL batch
    split contiguously over the shards, so rungs must divide the mesh
    (a non-dividing rung raises — the tile drops them before
    construction) and `shard_rung` exposes the per-shard lane count a
    feeder lane should stage toward for a given global rung."""

    def __init__(self, rungs, deadline_ns: int,
                 cost_ns: Optional[Callable[[int], int]] = None,
                 shards: int = 1):
        rungs = sorted(set(int(r) for r in rungs))
        if not rungs:
            raise ValueError("RungScheduler needs at least one rung")
        if any(r <= 0 for r in rungs):
            raise ValueError(f"rungs must be positive, got {rungs}")
        self.shards = max(1, int(shards))
        bad = [r for r in rungs if r % self.shards]
        if bad:
            raise ValueError(
                f"rungs {bad} do not divide over {self.shards} mesh "
                "shards (every rung is a global batch split "
                "contiguously across the mesh)"
            )
        self.rungs = rungs
        self.deadline_ns = deadline_ns
        self.cost_ns = cost_ns
        self.flush = AdaptiveFlush(deadline_ns)
        self.cur = rungs[0]
        self.switches = 0
        self.decisions = 0
        self.last_inputs: Tuple[int, int, int] = (0, 0, 0)

    def shard_rung(self, rung: int) -> int:
        """Per-shard lane count of a global rung (the commit threshold
        one fd_pod feeder lane stages toward)."""
        return max(1, rung // self.shards)

    # -- pure selection --------------------------------------------------

    def pick_rung(self, depth: int, slack_ns: Optional[int] = None) -> int:
        """Stateless rung choice: largest rung fully coverable by
        `depth`, capped by the deadline slack via the cost model.
        Monotone non-decreasing in depth for fixed slack."""
        i = 0
        for j, rung in enumerate(self.rungs):
            if depth >= rung:
                i = j
        if slack_ns is not None and self.cost_ns is not None:
            while i > 0:
                c = self.cost_ns(self.rungs[i])
                if not c or c <= slack_ns:
                    break
                i -= 1
        return self.rungs[i]

    def dispatch_rung(self, lanes: int) -> int:
        """Smallest rung that covers `lanes` staged lanes (a multisig
        txn can overshoot the commit threshold); the top rung bounds
        everything by construction (arenas are sized to it)."""
        for rung in self.rungs:
            if lanes <= rung:
                return rung
        return self.rungs[-1]

    # -- online decision (stateful: switch tracking) ---------------------

    def pick(self, now_ns: int, lanes: int, first_ns: int,
             backlog: int, backlog_full: bool = False) -> int:
        """The stager-facing decision: target rung for the batch being
        staged. Queue depth = staged lanes + ring backlog (backlog is
        in txns — a lower bound on lanes, so depth under-counts and the
        rung-up errs toward latency, never toward a padded monster
        batch). Slack = the staged batch's remaining deadline budget
        (full budget while nothing is staged).

        ``backlog_full`` is the caller's saturation signal: the in-ring
        backlog is at (half of) its structural cap, i.e. the producer
        is ahead of the stager as fast as the ring can express it —
        the ring is depth-bounded, so raw backlog alone cannot reach
        big-rung territory. Saturation means the pipeline is
        queueing-bound and NO rung meets the deadline: depth is lifted
        to the top rung and the slack cap is dropped, because capping
        by service cost there shrinks batches exactly when big-rung
        fill efficiency matters most (the small-rung death spiral the
        engine smoke pins: worse throughput -> deeper backlog -> still
        capped). Monotonicity survives: backlog_full only ever lifts
        the pick."""
        depth = max(0, lanes) + max(0, backlog)
        if backlog_full or backlog >= self.rungs[-1]:
            depth = max(depth, self.rungs[-1])
            slack = None
        elif lanes > 0 and first_ns:
            slack = max(0, self.deadline_ns - max(0, now_ns - first_ns))
        else:
            slack = self.deadline_ns
        rung = self.pick_rung(depth, slack_ns=slack)
        self.decisions += 1
        self.last_inputs = (depth, slack, lanes)
        if rung != self.cur:
            self.switches += 1
            self.cur = rung
        return rung

    def due(self, now_ns: int, lanes: int, first_ns: int, *,
            starved: bool = False, device_idle: bool = False,
            backpressured: bool = False):
        """AdaptiveFlush verdict at the current rung (FLUSH_FULL when
        lanes filled the rung, FLUSH_DEADLINE at deadline expiry — the
        invariant the property test pins — FLUSH_STARVED on the idle
        early-out), or None to keep filling."""
        return self.flush.due(
            now_ns, lanes, self.cur, first_ns, starved=starved,
            device_idle=device_idle, backpressured=backpressured,
        )

    def decide(self, now_ns: int, lanes: int, first_ns: int,
               backlog: int, *, starved: bool = False,
               device_idle: bool = False, backpressured: bool = False,
               backlog_full: bool = False):
        """pick + due in one call (the property-test surface): returns
        (verdict_or_None, rung)."""
        rung = self.pick(now_ns, lanes, first_ns, backlog,
                         backlog_full=backlog_full)
        verdict = None
        if lanes > 0:
            verdict = self.due(
                now_ns, lanes, first_ns, starved=starved,
                device_idle=device_idle, backpressured=backpressured,
            )
        return verdict, rung


# --------------------------------------------------------------------- #
# fdlint pass 7 (graph-audit) contracts — literals, read with
# ast.literal_eval by firedancer_tpu/lint/graphs.py, never imported.
# These cover the registry's engine classes: the direct (non-RLC)
# verify graph, its psum-carrying sharded wrapper, and the fused
# frontend / batched decompress front-end engines.  RLC and MSM stage
# contracts live next to their builders in ops/verify_rlc.py and
# ops/msm.py.
# --------------------------------------------------------------------- #

def fabric_split_pair(mesh, batch: int, plan=None):
    """fd_fabric entry: the split rlc pair (local_fill + combine_tail)
    on a caller-provided MULTI-AXIS mesh, plus its compile-ledger key.

    The registry cannot serve this: EngineSpec keys on a flat shard
    count and _build constructs its own single-axis 'dp' mesh via
    make_mesh, but a fabric's (host, dp) topology comes from
    jax.distributed — the mesh is the caller's. So the fabric builds
    the pair here and books its own warm pass via
    flight.record_compile(key, seconds), the same ledger every
    registry engine books into (fd_report's compile table and the
    fd_soak compile tripwires see fabric compiles like any other).

    Returns (local_jit, combine_jit, key): the u3-native pair
    (parallel/mesh.verify_rlc_split_global — u is the global (K, 2, B)
    block layout, no host-side reshape, because a (K, 2B) reshape
    cannot cross processes) and the key
    "rlc:B<batch>:fabric<hosts>x<dp>:fe<frontend>:msm<plan>".
    """
    from firedancer_tpu.parallel.mesh import verify_rlc_split_global

    if plan is not None:
        token = msm_plan.plan_token(plan)
    else:
        token = EngineSpec("rlc", batch).resolved_msm()
    shape = "x".join(str(int(s)) for s in mesh.devices.shape)
    key = (f"rlc:B{batch}:fabric{shape}:fe{current_frontend()}"
           f":msm{token}")
    local_jit, combine_jit = verify_rlc_split_global(mesh, plan=plan)
    return local_jit, combine_jit, key


GRAPH_CONTRACTS = {
    "direct": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
    },
    "direct_sharded": {
        "collectives": {"psum": 3},
        "axes": ["dp"],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "derived_from": ["direct"],
    },
    "frontend": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
    },
    "decompress": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
    },
}
