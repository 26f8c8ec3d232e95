"""flags — the typed central registry for FD_* environment flags.

Role parity with the reference's compile-time configuration discipline
(fd_util_base.h FD_HAS_* capability macros + the make profiles): every
tunable the reference bakes in at compile time, this port reads from
the environment — which is strictly more dangerous, because a typo'd
name, a stale default duplicated across call sites, or a read at the
wrong time (trace time vs run time) all fail silently at runtime.

This module is the single source of truth for every FD_* flag:

  - name, type, typed default, and a doc string (docs/FLAGS.md is
    generated from here via `scripts/fdlint.py --dump-flags`);
  - the `trace_time` marker: a flag whose value is captured while a
    jax/pallas computation TRACES (baked into the compiled graph, NOT
    re-read per step). fdlint's trace-safety pass allows registry reads
    inside traced code only for flags carrying this marker — a raw
    os.environ read there is flagged (the value silently pins without
    the registry's paper trail, and jit caching does not key on it);
  - optional `choices` for enum-shaped flags.

fdlint's flag-registry pass flags any os.environ/getenv read of an
FD_* name outside this module, so defaults and semantics cannot drift
back into call sites. Deliberately stdlib-only: host-side tiles must
stay jax-import-free (disco/tiles.py's dispatch contract), and the
bench orchestrator reads budgets before any backend import.

Read accessors preserve the call-site semantics the registry replaced:
an UNSET or EMPTY environment value yields the default (`get_raw`
returns None so `if flags.get_raw("FD_VERIFY_MODE"):` behaves exactly
like the `os.environ.get(...)` truthiness checks it replaced).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

_UNSET = object()


@dataclass(frozen=True)
class Flag:
    name: str
    type: type                              # str | int | float | bool
    default: Any                            # typed default (None = unset)
    doc: str
    trace_time: bool = False                # baked into traced graphs
    choices: Optional[Tuple[str, ...]] = None


REGISTRY: Dict[str, Flag] = {}


def _register(
    name: str,
    type_: type,
    default: Any,
    doc: str,
    *,
    trace_time: bool = False,
    choices: Optional[Tuple[str, ...]] = None,
) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate flag registration: {name}")
    if not doc:
        raise ValueError(f"flag {name} registered without a doc string")
    REGISTRY[name] = Flag(
        name=name, type=type_, default=default, doc=doc,
        trace_time=trace_time, choices=choices,
    )


# --------------------------------------------------------------------------
# Kernel / backend implementation selectors (ALL trace-time: the chosen
# implementation is baked into the traced graph; changing the env after
# a graph compiled does nothing until a fresh trace).
# --------------------------------------------------------------------------

_register(
    "FD_MUL_IMPL", str, "schoolbook",
    "In-kernel field-multiply schedule: schoolbook int32 (r3 baseline), "
    "karatsuba, f32 (exact-f32-product convolution; |limb| <= 512), "
    "rolled (7-rotation), or factored. A/B'd by the bench ladder.",
    trace_time=True,
    choices=("schoolbook", "karatsuba", "f32", "rolled", "factored"),
)
_register(
    "FD_SQ_IMPL", str, "sq",
    "In-kernel squaring: 'sq' = the specialized half-triangle fe_sq; "
    "'mul' swaps in a plain multiply — the escape hatch if a Mosaic "
    "version rejects fe_sq's slice/concat construction.",
    trace_time=True, choices=("sq", "mul"),
)
_register(
    "FD_SC_IMPL", str, None,
    "Scalar (mod-L) arithmetic backend: 'pallas' opts into the VMEM "
    "Barrett kernels; default is the XLA graph (round-4 v5e measurement: "
    "XLA wins ~3x on these short scalar chains).",
    trace_time=True, choices=("pallas",),
)
_register(
    "FD_CANON_IMPL", str, None,
    "Kernel canonicalize form: 'seq' rolls back to the sequential-ripple "
    "version should a Mosaic update reject the Kogge-Stone construction.",
    trace_time=True, choices=("seq",),
)
_register(
    "FD_MSM_IMPL", str, "auto",
    "MSM engine for the RLC batch-verify pass: 'pallas' (VMEM Pippenger "
    "kernels, the production TPU engine), 'xla' (graph MSM), 'interpret' "
    "(the Pallas kernels under the interpreter — CPU CI parity-tests the "
    "exact shipping engine), 'auto' = pallas iff the backend is a TPU "
    "family. An unrecognized value raises (a typo'd force must never "
    "quietly test the wrong engine).",
    trace_time=True, choices=("auto", "xla", "pallas", "interpret"),
)
_register(
    "FD_MSM_PLAN", str, "auto",
    "fd_msm2 Pippenger schedule token: sign char ('u' unsigned / 's' "
    "signed-digit), window width w in {6,7,8}, optional 'l3' suffix "
    "for the lazy-reduction-depth-3 niels-madd fill (signed REQUIRES "
    "l3 — the balanced recode only exists on that engine; lazy plans "
    "require Z==1 points, which every production call site feeds). "
    "'auto' composes a plan from FD_MSM_SIGNED/FD_MSM_WINDOW (all-"
    "default == the historical u7 engine, bit-identical). A concrete "
    "token here OVERRIDES both. Candidates are certifier-gated: only "
    "tokens that pass scripts/msm_search.py's cert+parity gate are "
    "registrable per rung (see build/msm_search.json).",
    trace_time=True,
    choices=("auto", "u6", "u7", "u8", "u6l3", "u7l3", "u8l3",
             "s6l3", "s7l3", "s8l3"),
)
_register(
    "FD_MSM_WINDOW", int, 7,
    "fd_msm2 window width in bits (6, 7, or 8) when FD_MSM_PLAN is "
    "'auto'. Non-default widths imply the lazy niels fill (the only "
    "engine with width-generic grids). 7 + FD_MSM_SIGNED unset == the "
    "historical engine.",
    trace_time=True,
)
_register(
    "FD_MSM_SIGNED", bool, False,
    "fd_msm2 signed-digit (balanced w-NAF-style) recoding when "
    "FD_MSM_PLAN is 'auto': halves live buckets per window (magnitude "
    "grid 2^(w-1)+1 wide, sign folded into the gather as a niels "
    "yp<->ym swap + t2d negation), shrinking the Poisson static-round "
    "bound and the reduction width. Implies the lazy fill; the borrow "
    "recode is certified int32-wrap-free (ops/msm_recode.py).",
    trace_time=True,
)
_register(
    "FD_DSM_IMPL", str, "auto",
    "Double-scalar-mult backend: 'pallas' forces the VMEM kernel, 'xla' "
    "the graph; 'auto' = pallas iff the backend is a TPU family.",
    trace_time=True, choices=("auto", "xla", "pallas"),
)
_register(
    "FD_POW_IMPL", str, "auto",
    "Field power-chain (invert / pow22523) backend: pallas | xla | auto "
    "(pallas iff TPU; the VMEM chains measure ~5x the XLA graph's "
    "per-mul rate on v5e).",
    trace_time=True, choices=("auto", "xla", "pallas"),
)
_register(
    "FD_SHA_IMPL", str, "auto",
    "Batch SHA-512 backend: pallas (VMEM compression kernel) | xla | "
    "auto (pallas iff TPU).",
    trace_time=True, choices=("auto", "xla", "pallas"),
)
_register(
    "FD_DECOMPRESS_IMPL", str, "auto",
    "Point-decompress backend: pallas (Montgomery-batched VMEM kernel "
    "with niels emission) | xla (the cache-blocked batched host "
    "graph) | interpret (the kernels under the Pallas interpreter — "
    "CPU CI parity) | auto (pallas iff TPU). Shapes an engine cannot "
    "serve fall back bit-exactly to the staged per-lane-chain "
    "composition: the host graph needs whole 1024-lane blocks, the "
    "kernel path folds whole padded 512-lane tiles (sub-tile batches "
    "take the staged chain).",
    trace_time=True, choices=("auto", "xla", "pallas", "interpret"),
)
_register(
    "FD_DECOMPRESS_BATCH", int, 6,
    "log2 of the Montgomery inversion group in the batched decompress "
    "(lanes per fe_invert chain; 6 = one chain per 64 lanes, the "
    "2B -> 2B/64 analytic inversion-count drop recorded in bench "
    "artifacts). 0 disables the batched math entirely — the staged "
    "per-lane power-chain path runs (the A/B bisection hatch).",
    trace_time=True,
)
_register(
    "FD_DECOMPRESS_SQ_SCHED", str, "auto",
    "Squaring schedule for the decompress ladder's 252 repeated "
    "squarings on the XLA path: l3 (lean scatter-add construction, "
    "lazy-reduction depth 3), l4 (lean, full 4-pass carry), f32 "
    "(exact-f32-product half-triangle). auto = l3, the certifier-"
    "gated search winner (scripts/fe_schedule_search.py); every "
    "choice here is fdcert-proved int32-wrap-free — rejected "
    "candidates (int32x2 wraps, f32fold leaves the mantissa-exact "
    "window) are not registrable.",
    trace_time=True, choices=("auto", "l3", "l4", "f32"),
)
_register(
    "FD_DECOMPRESS_CHUNK", int, 1024,
    "Lane-block width the batched decompress host graph serializes "
    "through lax.map (cache-blocking: the ~252-squaring ladder's "
    "working set stays L2-resident — measured 2.9x the flat graph's "
    "per-squaring rate on the CI host). 0 = one block over the whole "
    "batch. Kernel path ignores this (VMEM tiles are the blocks).",
    trace_time=True,
)
_register(
    "FD_FRONTEND_IMPL", str, "auto",
    "Fused verify front-end engine (ops/frontend_pallas.py: SHA-512 -> "
    "Barrett mod-L -> RLC coefficient muls as ONE VMEM kernel): "
    "'pallas' forces the fused kernels, 'xla' pins the staged "
    "composition (per-stage FD_SHA_IMPL / FD_SC_IMPL dispatch), "
    "'interpret' runs the fused kernels under the Pallas interpreter "
    "(CPU CI parity-tests the exact shipping engine), 'auto' = pallas "
    "iff the backend is a TPU family. Ineligible shapes (batch not a "
    "multiple of 1024, VMEM overflow) always take the staged "
    "composition regardless. An unrecognized value raises.",
    trace_time=True, choices=("auto", "xla", "pallas", "interpret"),
)
_register(
    "FD_COMPRESS_IMPL", str, "auto",
    "Point-compress / point-equality backend: pallas | xla | auto "
    "(pallas iff TPU).",
    trace_time=True, choices=("auto", "xla", "pallas"),
)
_register(
    "FD_DSM_LANES", int, 1024,
    "DSM kernel batch tile per program (v5e r3: 512 ~9% slower than "
    "1024; VMEM headroom allows 2048). The rolled multiply caps its "
    "default at 512 unless this is set explicitly.",
    trace_time=True,
)
_register(
    "FD_DSM_DEBUG", str, "",
    "DSM timing attribution ONLY (results are WRONG): 'doubles_only' "
    "drops both table adds+lookups, 'no_badd' drops the B-side "
    "lookup+add. Used by scripts/dsm_attrib.py; never set in production.",
    trace_time=True, choices=("doubles_only", "no_badd"),
)
_register(
    "FD_POW_BLOCK", int, 10,
    "Squarings unrolled per fori_loop iteration in the pow chains "
    "(round-5 hedge: 1 reproduces the round-4 per-squaring loop shape; "
    ">= chain length fully unrolls).",
    trace_time=True,
)
_register(
    "FD_FE_DEBUG_BOUNDS", bool, False,
    "Debug guard for the NARROWER f32 kernel-multiply contract "
    "(|limb| <= 512 vs the generic 1024): checks concrete operands at "
    "fe_mul_f32/fe_sq_f32 dispatch in eager/interpret runs.",
    trace_time=True,
)
_register(
    "FD_RLC_TORSION_K", int, 64,
    "Trial count for the RLC torsion subgroup certification "
    "(soundness <= 2^-K for torsion defects per accepted batch).",
    trace_time=True,
)
_register(
    "FD_MSM_SHARD", bool, True,
    "Allow the RLC verify mode to compose with mesh_devices via the "
    "mesh-sharded Pippenger MSM (per-device bucket fills, one "
    "cross-mesh window-partial combine; parallel/mesh."
    "verify_rlc_step_sharded). '0' is the bisection hatch that "
    "restores the pre-round-10 behavior: auto mode quietly resolves "
    "rlc+mesh to direct, while an EXPLICIT rlc force with mesh_devices "
    "raises (a silent downgrade would masquerade as a sharded-path "
    "measurement). Read at tile construction, not inside traced code.",
)
_register(
    "FD_POD_SPLIT", bool, True,
    "fd_pod split-step dispatch for the mesh-sharded RLC pass: build "
    "the verify engine as TWO jitted graphs — local_fill (per-shard "
    "SHA/decompress/bucket fill, no collectives) and combine_tail "
    "(the window-partial all_gather + unified adds + doubling-chain "
    "tails) — so the dispatcher double-buffers batch k's combine_tail "
    "against batch k+1's local_fill (parallel/mesh."
    "verify_rlc_split_sharded). '0' is the bisection hatch that keeps "
    "the monolithic single-graph sharded step (bit-exact either way). "
    "Read at engine build, not inside traced code.",
)
_register(
    "FD_DRAIN", str, "auto",
    "fd_drain device-resident post-verify pipeline: 'auto' dispatches "
    "the dedup pre-filter graph back-to-back with every feed verify "
    "batch (novel-mask + optional pack colors ride home in the same "
    "completion and travel downstream in the frag ctl word via "
    "fd_frag_publish_bulk_ctl); 'off' is the bisection hatch — the "
    "pipeline is then bit-identical to pre-drain. Silently disarms "
    "(== off) when the native .so predates the ctl bulk publisher. "
    "Read at engine build / tile construction, not inside traced code.",
)
_register(
    "FD_DRAIN_FILTER_BITS", int, 131072,
    "fd_drain filter bank size (buckets per bank; power of two). Two "
    "banks of h_bits/8 device bytes each; larger banks lower the "
    "false-maybe (hash collision) rate and so raise the probe-skip "
    "fraction. 131072 holds ~2 full default TCache windows at <6% "
    "collision occupancy.",
)
_register(
    "FD_DRAIN_ROT_QUOTA", int, 0,
    "fd_drain filter rotation quota: confirmed-novel PUBLISHES before "
    "the window rotates (bank B dropped). 0 = auto: downstream tcache "
    "depth assumed 4096 + out-ring depth + batch (the disco/drain.py "
    "eviction proof). Set explicitly when the dedup tile runs a "
    "non-default tcache_depth — the quota must be >= its depth plus "
    "in-flight frags or rotation breaks the one-sided contract.",
)
_register(
    "FD_DRAIN_PACK", bool, False,
    "fd_drain pack fusion: also run the pack_gc wave-coloring graph in "
    "the drain dispatch (account indices hashed host-side at dispatch) "
    "and carry wave colors + block ids downstream in the ctl word. "
    "PackTile validates every device block (ballet.pack."
    "validate_schedule) and compares rewards/CU against CPU greedy, "
    "falling back with exact accounting — colors are hints, never "
    "authority. Off by default: the dispatch-side account parse costs "
    "host CPU per txn.",
)
_register(
    "FD_POD_INFLIGHT", int, 2,
    "fd_pod dispatcher depth: how many (local_fill, combine_tail) "
    "batch pairs may be in flight before the pod service blocks on "
    "the oldest completion — 2 is classic double-buffering "
    "(wiredancer's DMA slot pair).",
)
_register(
    "FD_POD_SMOKE_N", int, 140,
    "pod_smoke corpus size (txns). The default keeps the forced "
    "8-device CPU-mesh lane's wall time bounded on 1-core CI hosts "
    "while still dispatching several full global batches.",
)
_register(
    "FD_POD_SMOKE_BATCH", int, 32,
    "pod_smoke global batch (lanes; must be divisible by "
    "FD_MESH_DEVICES so it splits over the shards): the "
    "sharded verify graphs compile at this shape, so the smoke keeps "
    "it small — production rungs come from FD_ENGINE_LADDER instead.",
)
_register(
    "FD_MESH_DEVICES", int, 8,
    "Virtual host-platform device count for CPU mesh runs: the value "
    "patched into XLA_FLAGS --xla_force_host_platform_device_count by "
    "worker boot and parallel/multihost when no explicit count is "
    "given. Must match across processes sharing a persistent compile "
    "cache (the compile key covers the device topology). Real TPU "
    "hosts ignore it (the plugin enumerates hardware).",
)
_register(
    "FD_GRAPH_SHARDS", int, 8,
    "Shard count fdlint pass 7 (graph-audit) traces the mesh graphs "
    "at: the virtual CPU device count for the shard_map combine-tail "
    "and sharded-wrapper traces. Matches FD_MESH_DEVICES' default so "
    "the audited topology is the one CI's pod lanes actually run.",
)
_register(
    "FD_GRAPH_TIMING", bool, False,
    "Print per-graph trace wall time to stderr during fdlint pass 7 "
    "(graph-audit) — the knob for re-budgeting the <60s CI lane when "
    "the graph set grows.",
)
_register(
    "FD_GRAPH_RUNGS", str, None,
    "Comma-separated batch rungs for fdlint pass 7's per-rung MSM "
    "cost-reconciliation traces. Unset = the FD_ENGINE_LADDER rungs, "
    "so the audit covers exactly the registry's prewarmed graph "
    "shapes; the smallest rung doubles as the structural audit rung.",
)
_register(
    "FD_VERIFY_MODE", str, None,
    "Force the verify tile's device mode: 'rlc' (batch RLC over the "
    "Pippenger MSM) or 'direct' (per-lane). Unset = platform auto "
    "(rlc on TPU families, direct on host-jax backends). An "
    "unrecognized value raises rather than falling through.",
    trace_time=True, choices=("rlc", "direct"),
)

# --------------------------------------------------------------------------
# Host-side runtime knobs (read per run, not baked into graphs).
# --------------------------------------------------------------------------

_register(
    "FD_VERIFY_HOLD_AFTER_DISPATCH_S", float, 0.0,
    "Fault injection: hold the verify tile once, right after its first "
    "dispatch, with the UNACKED gauge freshly published — the "
    "deterministic SIGKILL window for crash tests. 0 disables "
    "(production).",
)
_register(
    "FD_SUP_KEEP_LOGS", str, None,
    "Supervisor post-mortem dir: run out of this directory and keep "
    "per-tile logs + pod + result files after the run (normally "
    "everything is ephemeral).",
)

# --------------------------------------------------------------------------
# fd_feed ingest-runtime knobs (disco/feed/ — the host-side feeder that
# overlaps parse/dedup/staging with device verify; all read per run).
# --------------------------------------------------------------------------

_register(
    "FD_FEED", bool, True,
    "Route run_pipeline through the fd_feed ingest runtime (staging-slot "
    "feeder + downstream worker process) when the topology supports it "
    "(single verify lane, cpu|tpu backend, batch >= MAX_SIG_CNT, native "
    "drain built). '0' pins the legacy in-process step loop for "
    "bisection; unsupported topologies fall back to it automatically.",
)
_register(
    "FD_FEED_SLOTS", int, 4,
    "Staging slots per verify lane: preallocated host arenas filled by "
    "the stager thread while earlier batches are on the device. 2 is "
    "the minimum for fill/dispatch overlap; cpu-backend batches hold "
    "their slot until the verify call retires, so the default leaves "
    "FD_FEED_VERIFY_THREADS in flight plus one filling plus one ready. "
    "Cost: (batch x MTU) host bytes per slot.",
)
_register(
    "FD_FEED_DEADLINE_US", int, 25_000,
    "Partial-batch latency deadline for the adaptive flush policy "
    "(VerifyTile default when the caller does not pass max_wait_us): a "
    "staged partial batch is ALWAYS dispatched within this bound, "
    "anchored at the oldest txn's STAGING time (ring dwell is reported "
    "separately as the verify_drain stage latency, not charged to the "
    "flush deadline). Steady-state traffic fills batches long before "
    "the deadline, so deadline flushes ~= 0 (the ROADMAP round-6 "
    "flush_timeout gate); an input-starved partial with an idle device "
    "flushes after deadline/16 instead of waiting the full budget.",
)
_register(
    "FD_RINGS_PYDLL", bool, True,
    "Route the nanosecond-scale ring ops (mcache publish/poll, fseq, "
    "cnc, next_chunk) through a GIL-HOLDING ctypes handle (PyDLL). The "
    "seed's CDLL handle released the GIL around every ring op, costing "
    "a scheduler handoff (~100-700 us under thread contention) per "
    "~100 ns op — the dominant host-pipeline cost before round 8. '0' "
    "restores the seed behavior for A/B and bisection; bulk drains and "
    "batch verifies always release the GIL regardless.",
)
_register(
    "FD_FEED_VERIFY_THREADS", int, 0,
    "CPU-backend verify executor width for the fd_feed dispatcher: N "
    "concurrent GIL-releasing fd_ed25519_cpu_verify_batch calls over "
    "READY slots (the host-verifier analog of keeping several device "
    "batches in flight). 0 = auto (min(2, cpu_count)); 1 pins the "
    "serial dispatch.",
)
_register(
    "FD_FEED_PROC", str, "auto",
    "fd_feed worker-pool placement: '1' runs source + dedup/pack/sink "
    "in worker processes (tango shm rings across process boundaries), "
    "'0' keeps them on in-process threads, 'auto' picks processes only "
    "when the host has >= 4 cores (on a 2-core host the extra "
    "interpreters cost more in boot + oversubscription than the GIL "
    "they dodge — measured 3401 vs 753 txn/s at n=2180). The feeder "
    "slots and adaptive flush are active either way.",
    choices=("auto", "1", "0"),
)

# --------------------------------------------------------------------------
# fd_engine — verify-graph engine registry + latency-adaptive rung
# scheduler (disco/engine.py; all read per run at tile/registry
# construction, never inside traced code).
# --------------------------------------------------------------------------

_register(
    "FD_ENGINE_LADDER", str, "8192,16384,32768",
    "fd_engine B rung ladder (comma-separated batch sizes): the rungs "
    "the continuous-batching scheduler picks between and the prewarm "
    "set the registry warms. Rungs above a tile's staging batch are "
    "dropped (arenas are sized to the batch, which always tops the "
    "ladder); a malformed entry raises. The default matches the bench "
    "B-sweep (fill efficiency 0.63 -> 0.76 from 8k to 32k).",
)
_register(
    "FD_ENGINE_SCHED", bool, True,
    "Latency-adaptive rung scheduler on the fd_feed verify path: pick "
    "the dispatch B from the FD_ENGINE_LADDER rungs by queue depth + "
    "deadline slack + the registry's per-rung cost model, so low "
    "offered load takes the small-rung latency and saturation takes "
    "the big-rung throughput. '0' is the bisection hatch that pins "
    "the fixed staging batch (the pre-PR-13 behavior); topologies "
    "with fewer than two usable rungs pin it automatically.",
)
_register(
    "FD_ENGINE_PREWARM", str, "background",
    "Registry prewarm policy for the non-primary ladder rungs: "
    "'background' compiles them on the fd_engine prewarm thread "
    "(rung switches pick each engine up as it turns warm; a cold "
    "rung dispatches on the primary engine meanwhile), 'sync' warms "
    "inline at tile construction (boot pays every compile up front), "
    "'off' skips prewarm (the scheduler effectively pins the primary "
    "engine on device backends).",
    choices=("background", "sync", "off"),
)

# --------------------------------------------------------------------------
# fd_siege QUIC front-door defenses + scenario-suite knobs (disco/
# quic_tile.py admission/shedding/quarantine, disco/siege.py swarm; all
# read per run — the quic tile resolves them at construction).
# --------------------------------------------------------------------------

_register(
    "FD_QUIC_DEFENSES", bool, True,
    "Master switch for the QUIC front-door overload defenses: per-"
    "connection token-bucket admission, credit-aware lowest-priority "
    "load shedding, and the per-peer abuse circuit breaker (connection "
    "quarantine). On by default — the fd_siege suite proves the "
    "pipeline stays inside its SLOs under attack BECAUSE of these; "
    "'0' is the A/B hatch the siege smoke uses for the overhead gate.",
)
_register(
    "FD_QUIC_ADMIT_RATE", int, 5000,
    "Per-connection token-bucket admission rate at the QUIC tile, "
    "transactions/second: streams completing beyond the bucket are "
    "SHED (counted in the quic tile's admit_shed flight metric, sha256 "
    "recorded in the shed ledger so replay gates stay bit-exact) "
    "instead of ever reaching the feed. A single hostile connection "
    "cannot monopolize the front door.",
)
_register(
    "FD_QUIC_ADMIT_BURST", int, 256,
    "Per-connection admission bucket depth (burst allowance). A fresh "
    "connection may land this many transactions at wire speed before "
    "the FD_QUIC_ADMIT_RATE refill governs it.",
)
_register(
    "FD_QUIC_SHED_DEPTH", int, 4096,
    "Ready-queue depth at the QUIC tile above which credit-aware load "
    "shedding engages: the LOWEST-priority queued transaction (compute-"
    "budget fee order, the same order fd_pack maximizes) is dropped "
    "and counted in queue_shed — overload degrades by shedding the "
    "cheapest work instead of backpressuring the feed into an SLO burn.",
)
_register(
    "FD_QUIC_ABUSE_THRESHOLD", int, 32,
    "Per-peer abuse events (malformed datagrams, oversized streams, "
    "slowloris reassembly pressure — admission sheds deliberately do "
    "NOT score: a NAT'd address full of honest users sheds without "
    "malice) within a 1 s window that "
    "trip the connection-level circuit breaker: the peer's connections "
    "are closed and its datagrams dropped at the socket for the "
    "quarantine cooldown (fd_chaos breaker pattern: trip -> quarantine "
    "-> half-open re-admit, cooldown doubling per consecutive trip).",
)
_register(
    "FD_QUIC_QUARANTINE_COOLDOWN_MS", int, 250,
    "Base quarantine cooldown for a tripped abusive peer before the "
    "half-open re-admit; doubles per consecutive re-trip (capped 8x).",
)
_register(
    "FD_QUIC_SLOW_MAX_BUF", int, 262144,
    "Per-connection cap on buffered bytes of INCOMPLETE streams "
    "(slowloris posture): a connection dribbling partial streams past "
    "this reassembly budget is an abuse event and gets quarantined — "
    "held-open streams cannot grow server state unboundedly.",
)
_register(
    "FD_QUIC_HS_TIMEOUT_S", float, 3.0,
    "Server-side handshake deadline: a connection that has not "
    "completed its handshake within this window is reaped (the half-"
    "open-connection flood defense; a junk Initial buys an attacker "
    "at most this much state lifetime). 0 disables.",
)
_register(
    "FD_SIEGE_N", int, 1200,
    "fd_siege corpus size per adversarial profile (unique valid txns; "
    "disco/corpus.py mainnet shape, so expected sink digests stay "
    "computable by construction).",
)
_register(
    "FD_SIEGE_SEED", int, 0,
    "fd_siege determinism seed: corpus generation, swarm connection "
    "schedules, and junk payloads all derive from it — a failing "
    "profile replays bit-identically.",
)
_register(
    "FD_SIEGE_PROFILES", str, None,
    "Comma-separated adversarial profile names for scripts/fd_siege.py "
    "(conn_churn, dup_storm, malformed_flood, slowloris, "
    "oversize_abuse, keyupdate_churn). Unset = the full suite.",
)
_register(
    "FD_SIEGE_OUT", str, None,
    "Directory for the per-profile SIEGE_r*.json artifacts (default: "
    "the repo root, next to the BENCH_r* family fd_report ingests).",
)

# --------------------------------------------------------------------------
# fd_fabric — the multi-host, multi-tenant verify fabric
# (disco/fabric.py + parallel/multihost.ensure_multihost). The four
# FD_FABRIC_{COORD,PROCS,PROC_ID,DIR} flags are per-PROCESS: the
# fd_fabric launcher sets them differently in each child's environment.
# --------------------------------------------------------------------------

_register(
    "FD_FABRIC_COORD", str, None,
    "jax.distributed coordinator address (host:port of process 0) for "
    "the fd_fabric multi-process mesh. Unset = single-process operation "
    "(ensure_multihost records fallback_reason instead of failing).",
)
_register(
    "FD_FABRIC_PROCS", int, 1,
    "Number of processes in the fd_fabric mesh (the 'host' axis, DCN). "
    "1 (default) = single-process: worker boot skips jax.distributed "
    "entirely and behaves exactly as before fd_fabric existed.",
)
_register(
    "FD_FABRIC_PROC_ID", int, 0,
    "This process's rank in the fd_fabric mesh, 0-based; process 0 is "
    "both the jax.distributed coordinator and the cross-host judgment "
    "coordinator (merges per-process flight dumps into FABRIC_r*.json).",
)
_register(
    "FD_FABRIC_DIR", str, None,
    "Shared directory for per-process fabric dumps (flight snapshots, "
    "tenant ledgers, sink digests): every process writes "
    "fabric_proc<id>.json here at drain and process 0 collects them. "
    "Required when FD_FABRIC_PROCS > 1; a shared filesystem path on "
    "real pods.",
)
_register(
    "FD_FABRIC_RUN", str, None,
    "JSON run config for a scripts/fd_fabric.py --child process "
    "(corpus size/seed, per_shard, tenant profile/rate/burst, dump "
    "dir): the launcher serializes ONE dict into every child's "
    "environment so all processes regenerate identical corpus bytes "
    "and tenant plans from the same seed. Unset outside child mode.",
)
_register(
    "FD_FABRIC_LOCAL_DEVICES", int, 1,
    "Virtual CPU devices per fabric process (the 'dp' axis, ICI). "
    "Routed through init_multihost's mismatch check: a stale "
    "XLA_FLAGS count that disagrees raises DeviceCountMismatchError "
    "instead of silently diverging the compile-cache key across the "
    "fabric. Real TPU hosts ignore it.",
)
_register(
    "FD_TENANT_RATE", int, 2000,
    "Per-TENANT token-bucket admission rate at the fabric front door, "
    "transactions/second of the (virtual) arrival clock: a tenant "
    "offering beyond its bucket is shed at admission, sha256-ledgered, "
    "and counted per tenant — the multi-tenant analog of the per-"
    "connection FD_QUIC_ADMIT_RATE (same policy.TokenBucket).",
)
_register(
    "FD_TENANT_BURST", int, 64,
    "Per-tenant admission bucket depth (burst allowance) at the fabric "
    "front door; FD_QUIC_ADMIT_BURST's tenant-level analog.",
)

# --------------------------------------------------------------------------
# fd_chaos fault injection + the self-healing machinery it proves out
# (disco/chaos.py; all read per run).
# --------------------------------------------------------------------------

_register(
    "FD_CHAOS", bool, False,
    "Arm the fd_chaos deterministic fault-injection layer for the run: "
    "every pipeline runner (and worker process) installs a fresh "
    "ChaosInjector from FD_CHAOS_SEED + FD_CHAOS_SCHEDULE at boot. "
    "Off (default) in production — the healing machinery it exercises "
    "(stager supervision, verify breaker, quarantine) is always on.",
)
_register(
    "FD_CHAOS_SEED", int, 0,
    "Seed for the chaos injector's counter-based Rng (byte/position "
    "choices of corrupting faults). Same seed + schedule + corpus "
    "replays the same faults bit-identically.",
)
_register(
    "FD_CHAOS_SCHEDULE", str, None,
    "Chaos schedule: 'class@N[,class@N:M,...]' with 1-based ordinals "
    "of each class's hook site (publish attempt, stager drain round, "
    "dispatch, completion, housekeep pass, monitor pass). Classes: "
    "ring_ctl_err, ring_overrun, credit_starve, stager_kill, "
    "slot_corrupt, backend_raise, device_lost, hb_stall, worker_kill; "
    "windowed classes (credit_starve, device_lost, hb_stall) take N:M. "
    "Unknown classes or malformed ordinals raise — a typo'd schedule "
    "must never silently inject nothing.",
)
_register(
    "FD_VERIFY_BREAKER", bool, True,
    "Device->CPU verify failover circuit breaker in the fd_feed "
    "dispatcher: consecutive primary-lane verify errors trip it, the "
    "CPU oracle lane serves while open, and a half-open probe restores "
    "the device path once it recovers (device loss degrades throughput, "
    "not liveness). '0' disables — a dispatch error then falls back "
    "per-batch without tripping.",
)
_register(
    "FD_VERIFY_BREAKER_THRESHOLD", int, 3,
    "Consecutive device verify errors (while the breaker is closed) "
    "that trip it open. One transient error followed by a success "
    "resets the count — that is the quarantine path's job.",
)
_register(
    "FD_VERIFY_BREAKER_COOLDOWN_MS", int, 100,
    "Open-circuit cooldown before a half-open re-probe of the device "
    "path. A failed probe re-opens with the cooldown doubled (up to "
    "8x), so a dead device is re-probed at a decaying rate.",
)
_register(
    "FD_FEED_STAGER_RESTART_MAX", int, 5,
    "fd_feed stager-thread supervision budget: restarts allowed before "
    "the feeder gives up and re-raises the stager's error (a "
    "permanently crashing stager is a bug, not an operational fault). "
    "Staged slots survive each restart.",
)
_register(
    "FD_FEED_STAGER_BACKOFF_MS", int, 10,
    "Base delay before a crashed stager thread is restarted; doubles "
    "per consecutive restart (capped at 2 s) with +0-25% jitter.",
)
_register(
    "FD_SUP_BACKOFF_MS", int, 200,
    "Supervisor respawn backoff base per tile: a crashed tile is "
    "respawned after base * 2^(restarts-1) ms (+0-25% jitter, capped "
    "by FD_SUP_BACKOFF_MAX_MS), so a crash-looping tile cannot drive "
    "a respawn storm (the round-8 boot-grace fix papered over exactly "
    "that). 0 restores the seed's immediate-respawn behavior.",
)
_register(
    "FD_SUP_BACKOFF_MAX_MS", int, 5000,
    "Cap on the supervisor's per-tile exponential respawn backoff.",
)

# --------------------------------------------------------------------------
# fd_flight observability (disco/flight.py — unified metrics registry,
# per-txn trace spans, crash-dumpable flight recorder; all read per run).
# --------------------------------------------------------------------------

_register(
    "FD_FLIGHT", bool, True,
    "fd_flight event recording + always-on trace-span histograms. '0' "
    "is the overhead-bisection hatch: flight recorders become no-ops "
    "and OutLink publishes skip the edge-histogram observe; the metric "
    "LANES stay on regardless (verify_stats and the replay/bench "
    "artifacts are views over them).",
)
_register(
    "FD_FLIGHT_EVENTS", int, 256,
    "Ring capacity of each flight recorder (events kept per tile / "
    "per subsystem for the crash dump). Memory is O(cap) tuples.",
)
_register(
    "FD_FLIGHT_DUMP", str, None,
    "Directory for flight-recorder JSON dumps. When set, a dump is "
    "written on tile crash, pipeline HALT, and SIGUSR1 (see "
    "docs/RUNBOOK.md 'reading a flight-recorder dump'). Unset (the "
    "default) writes nothing — recording still runs, so an operator "
    "can flip this on and signal a live process.",
)
_register(
    "FD_TRACE_SPANS", bool, True,
    "Per-frag trace spans: every OutLink publish (and the fd_feed bulk "
    "completion) observes tspub - tsorig into the edge's always-on "
    "log2 histogram in the flight registry. '0' disables the observes "
    "only (A/B hatch); the trace id (the tsorig stamp minted at source "
    "publish) propagates regardless — it is the latency stamp.",
)
_register(
    "FD_METRICS_PROM", str, None,
    "File path: the pipeline runners write a Prometheus-style text "
    "snapshot of the flight registry here after each run (the pull-"
    "less export for scrapers/CI; scripts/fd_top.py --prom renders "
    "the same text live).",
)

# --------------------------------------------------------------------------
# fd_sentinel — the judgment layer over fd_flight (disco/sentinel.py):
# in-pipeline SLO evaluation with multi-window burn-rate detection,
# the perf-regression tracker, and the prediction ledger. All read per
# run; budgets are stated ONCE here + in sentinel.SLO_TABLE and
# rendered into docs/SLO.md (test-pinned, like docs/FLAGS.md).
# --------------------------------------------------------------------------

_register(
    "FD_SENTINEL", bool, True,
    "Run the fd_sentinel SLO evaluator inside every pipeline run: a "
    "low-rate poller over the fd_flight registry (edge histograms, "
    "heartbeats, progress) that turns docs/SLO.md budget breaches into "
    "flight-recorder events, fd_flight_slo_* prom metrics, and the "
    "PipelineResult.slo summary. '0' is the overhead-bisection hatch.",
)
_register(
    "FD_SENTINEL_INTERVAL_MS", int, 250,
    "fd_sentinel evaluation interval. Each pass is a handful of "
    "shared-memory reads + integer math; the burn-rate windows "
    "(FD_SLO_FAST_S/FD_SLO_SLOW_S) are measured in wall time, so a "
    "coarser interval only coarsens detection latency, not the math.",
)
_register(
    "FD_SLO_E2E_BUDGET_MS", int, 2500,
    "p99 budget for the queue-inclusive trace-span latency SLOs (sink "
    "end-to-end and the cumulative verify/dedup/pack/drain stages), ms "
    "— the docs/LATENCY.md gate-corpus budget. Enforced in log2-bucket "
    "space with one bucket of slack (a sample counts against the error "
    "budget only when it is provably > 2x this). Smoke lanes with "
    "smaller corpora pin it to their corpus budget (slo_smoke's clean "
    "half: 1500).",
)
_register(
    "FD_SLO_SOURCE_BUDGET_MS", int, 10,
    "p99 budget for the source-publish span (replay_verify edge), ms. "
    "The stage is queue-free (tsorig is minted in the same call that "
    "stamps tspub), so breaching 2x this means pathological scheduling "
    "— GIL monopolization, a blocked dcache write — not offered load.",
)
_register(
    "FD_SLO_STALL_MS", int, 2000,
    "pipeline_progress liveness SLO: alert when NO pipeline edge "
    "advances for this long mid-run (armed after the first observed "
    "frag; the runners stop the sentinel at quiescence, so drain-and-"
    "halt never counts). A chaos credit_starve window trips exactly "
    "this SLO (scripts/slo_smoke.py pins the asymmetry).",
)
_register(
    "FD_SLO_HB_MS", int, 1500,
    "tile_heartbeat liveness SLO: alert when a RUNning tile's cnc "
    "heartbeat stops advancing for this long (the wedge signature the "
    "supervisor kills on — this SLO makes it visible in UNsupervised "
    "runs too). A chaos hb_stall window trips exactly this SLO.",
)
_register(
    "FD_SLO_BURN", float, 2.0,
    "Burn-rate multiple that alerts: a latency SLO alerts when "
    "(observed bad fraction / error budget) >= this in BOTH the fast "
    "and the slow window (multi-window multi-burn-rate detection; 2.0 "
    "= consuming error budget at twice the sustainable rate).",
)
_register(
    "FD_SLO_FAST_S", float, 1.0,
    "Fast burn-rate window, seconds. The fast window makes detection "
    "prompt; the slow window keeps a transient spike from alerting.",
)
_register(
    "FD_SLO_SLOW_S", float, 4.0,
    "Slow burn-rate window, seconds. A window is only evaluated once "
    "the sentinel's history actually spans it, so runs shorter than "
    "this cannot latency-alert (liveness SLOs are unaffected).",
)
_register(
    "FD_SLO_QUIC_INGEST_MS", int, 500,
    "p99 budget for the QUIC front-door admission span (stream "
    "completion at the quic tile -> frag publish into the feed, the "
    "'quic_ingest' edge), ms. This is the queue the admission/shedding "
    "defenses exist to keep shallow: a breach means completed "
    "transactions are stalling INSIDE the front door instead of being "
    "admitted or shed.",
)
_register(
    "FD_SLO_SHARD_BALANCE_PCT", int, 150,
    "fd_pod shard-occupancy balance budget, percent: on a mesh run "
    "the busiest shard lane's dispatched-lane count may exceed the "
    "laziest's by at most this ratio x100 (150 = within 1.5x) once "
    "every shard has seen real volume. A breach means shard placement "
    "is starving a device — aggregate throughput degrades to the "
    "slowest shard's. Evaluated over the per-shard flight rows "
    "(verify.shardN), so it works cross-process like every other SLO.",
)
_register(
    "FD_SLO_DRAIN_EFF_PCT", int, 10,
    "fd_drain filter-effectiveness budget, percent: with the drain "
    "stage armed and real volume through it, at least this fraction "
    "of published clean txns must carry a definitely-novel claim "
    "(drain_novel / (drain_novel + drain_maybe) x100). A breach means "
    "the filter is paying its dispatch cost without skipping probes — "
    "banks too small for the tag rate, or rotation starved.",
)
_register(
    "FD_SLO_HEAP_SLOPE_KB", int, 512,
    "fd_soak heap-growth tripwire budget, KiB per minute: the slope-"
    "kind heap_slope SLO alerts when the least-squares fit over the "
    "soak probe's tracemalloc samples grows faster than this. Only a "
    "soak run registers a slope source (sentinel.set_slope_source), so "
    "ordinary pipeline runs never arm it.",
)
_register(
    "FD_SLO_POOL_SLOPE_MILLI", int, 250,
    "fd_soak slot-pool occupancy tripwire budget, milli-slots per "
    "minute: the pool_occupancy_slope SLO alerts when the fitted "
    "trend of outstanding fd_feed slots (FREE excluded) grows faster "
    "than this — the leaked-slot / stuck-inflight signature that only "
    "shows over hours. 250 = a quarter slot per minute.",
)
_register(
    "FD_SLO_COMPILE_SLOPE", int, 6,
    "fd_soak compile-cache tripwire budget, new engine-cache entries "
    "per hour: the compile_cache_slope SLO alerts when EngineRegistry "
    "entries + recorded compiles keep accreting past the prewarmed "
    "ladder — the unbounded-recompile signature (a shape leak or a "
    "reconfig that never retires old engines).",
)
_register(
    "FD_SLO_TENANT_SHED_PCT", int, 1,
    "fd_fabric tenant-fairness budget, percent: once real multi-tenant "
    "volume has offered, an HONEST tenant (one offering within its "
    "FD_TENANT_RATE bucket) may have at most this fraction of its "
    "offered transactions shed. A breach means admission is starving a "
    "within-rate tenant — the starved_tenant siege profile exists to "
    "prove an over-offering attacker is shed WITHOUT tripping this.",
)
# --------------------------------------------------------------------------
# fd_xray — tail-sampled exemplar traces, per-edge queue attribution,
# and automated postmortems (disco/xray.py). All read per run; tail
# thresholds resolve from the FD_SLO_* budgets above (docs/SLO.md is
# the single source of truth).
# --------------------------------------------------------------------------

_register(
    "FD_XRAY", bool, True,
    "fd_xray exemplar traces + per-edge queue/backpressure telemetry + "
    "autopsy bundles. '0' is the overhead-bisection hatch: span "
    "sampling, dwell/stall/depth observes, and autopsy writes all off "
    "(pipeline output is bit-identical either way — xray only "
    "observes). Rides on FD_FLIGHT: with flight off there are no trace "
    "spans to sample from.",
)
_register(
    "FD_XRAY_SAMPLE", int, 64,
    "Head-sampling rate for exemplar traces: 1 in N transactions, "
    "keyed DETERMINISTICALLY off the trace id (the tsorig stamp) with "
    "one shared multiplicative hash, so every tile — across threads "
    "and worker processes, zero coordination — samples the SAME txns "
    "and the sink correlates full span chains by id. 0 disables head "
    "sampling (tail/quarantine/breaker/CTL_ERR triggers stay armed).",
)
_register(
    "FD_XRAY_RING", int, 512,
    "Exemplar spans kept per xray ring (one single-writer ring per "
    "publish edge plus per-tile trigger rings — the flight-recorder "
    "pattern). Memory is O(cap) tuples per ring.",
)
_register(
    "FD_XRAY_QUEUE_SAMPLE", int, 16,
    "Per-edge queue-dwell sampling stride: every Nth drained frag "
    "observes (producer tspub -> consumer drain) into the edge's "
    "xray.queue dwell histogram — the queue-wait half of the "
    "fd_report --waterfall decomposition. Values < 1 clamp to 1 "
    "(every frag); disable queue telemetry with FD_XRAY=0, not here.",
)
_register(
    "FD_XRAY_DIR", str, None,
    "Directory for xray_autopsy_*.json postmortem bundles. When set, "
    "an autopsy is written on every sentinel alert (via the xray "
    "flusher thread), tile crash, and pipeline HALT (see "
    "docs/RUNBOOK.md 'reading an xray autopsy'). Unset (the default) "
    "writes nothing — sampling still runs, so the HALT flight dump "
    "carries the exemplar rings regardless.",
)

_register(
    "FD_REPORT_REGRESS_PCT", float, 10.0,
    "scripts/fd_report.py regression threshold: a device measurement "
    "more than this far below its series' rolling best-of baseline "
    "(same metric x mode x batch) is flagged as a regression.",
)

# --------------------------------------------------------------------------
# fd_soak — the long-horizon soak harness (disco/soak.py) and the
# zero-downtime live-reconfig control channel it exercises. All read
# per run; the slope-kind SLO budgets live in the FD_SLO_* section.
# --------------------------------------------------------------------------

_register(
    "FD_RECONFIG", str, None,
    "Path to the live-reconfig request file (JSON: ladder / flag "
    "flips / drain mode). When set, the soak's reconfig controller "
    "installs a SIGHUP handler and also polls the file's mtime: on "
    "either signal it prewarms the requested rung ladder off-thread, "
    "then swaps it into the running VerifyTile at the next inflight-"
    "window barrier — zero dropped txns, digest-exact continuity. "
    "Unset (the default) installs nothing.",
)
_register(
    "FD_SOAK_SEED", int, 606,
    "fd_soak master seed: the phase schedule, per-phase corpus/tenant "
    "mix, offered-load drift, and chaos schedules all derive from it, "
    "so a soak (and its failure) replays bit-identically.",
)
_register(
    "FD_SOAK_PHASES", int, 6,
    "Number of soak phases. Each phase rotates to the next siege-"
    "derived workload profile, re-draws the corpus mix, and shifts "
    "offered load on the deterministic schedule.",
)
_register(
    "FD_SOAK_PHASE_S", float, 600.0,
    "Wall-clock seconds per soak phase. The scripted N-hour soak is "
    "FD_SOAK_PHASES x this; scripts/soak_smoke.py compresses it to "
    "a ~60 s CI lane without changing the judgment layer.",
)
_register(
    "FD_SOAK_PROBE_MS", int, 500,
    "fd_soak resource-probe sampling interval: each tick samples "
    "tracemalloc heap, slot-pool occupancy, engine-cache entries, and "
    "flight/xray ring high-water marks for the slope fits feeding the "
    "slope-kind sentinel SLOs.",
)
_register(
    "FD_SOAK_RESPAWN_BUDGET", int, 30,
    "fd_soak respawn-rate budget, restarts per hour (stager restarts "
    "+ supervised tile respawns combined): a soak phase that exceeds "
    "the pro-rated budget fails its verdict — sustained crash-respawn "
    "storms are a failure even when every restart individually "
    "succeeds.",
)

# --------------------------------------------------------------------------
# bench.py ladder knobs (orchestrator + workers).
# --------------------------------------------------------------------------

_register(
    "FD_BENCH_VERIFY", str, "direct",
    "Verify mode for a bench worker / the rlc smoke lane: rlc | direct. "
    "In the orchestrator, setting it forces a single-mode ladder.",
    choices=("rlc", "direct"),
)
_register(
    "FD_BENCH_RLC", str, "1",
    "'0' re-parks the rlc rung from the bench ladder (escape hatch; "
    "direct remains measured).",
)
_register(
    "FD_BENCH_BATCH", int, 8192,
    "Device bench batch (lanes per timed verify call).",
)
_register("FD_BENCH_REPS", int, 10, "Timed repetitions on device.")
_register(
    "FD_BENCH_MSG_LEN", int, 192,
    "Signed-message bytes per lane (~typical Solana txn payload).",
)
_register(
    "FD_BENCH_MODE", str, None,
    "'replay' runs the 100k replay gate instead of the verify ladder "
    "(equivalent to --replay).",
    choices=("replay",),
)
_register(
    "FD_BENCH_REPLAY_N", int, 100000,
    "Replay-gate corpus size (txns).",
)
_register(
    "FD_BENCH_REPLAY_BATCH", int, 8192,
    "Verify-tile batch for the device replay gate.",
)
_register(
    "FD_BENCH_REPLAY_TIMEOUT", float, 900.0,
    "Per-run pipeline budget for the replay gates (the CPU gate's "
    "call site defaults to 1200).",
)
_register(
    "FD_BENCH_REPLAY_TOTAL_TIMEOUT", float, 3000.0,
    "Hard subprocess timeout for the whole replay-gate worker.",
)
_register("FD_BENCH_PACK_N", int, 65536, "Pack-gate block size (txns).")
_register(
    "FD_BENCH_PACK_ACCTS", int, 16384,
    "Distinct account keys in the pack-gate corpus.",
)
_register(
    "FD_BENCH_TPU_BUDGET", float, 740.0,
    "Total wall budget for the device rungs of the verify ladder.",
)
_register(
    "FD_BENCH_ATTEMPT_TIMEOUT", float, 420.0,
    "Hard timeout for one bench worker attempt.",
)
_register(
    "FD_BENCH_RLC_MIN_BUDGET", float, 240.0,
    "Leftover budget required before spending an A/B rung.",
)
_register(
    "FD_BENCH_DIRECT_MIN_BUDGET", float, 300.0,
    "Budget reserved for the direct rung before the rlc rung may spend "
    "(a numberless round is worse than a direct-only round).",
)
_register(
    "FD_BENCH_STAGE_ATTRIB", bool, True,
    "Record per-stage ms attribution (sha, decompress, sc, rlc_combine, "
    "msm, glue — scripts/profile_stages.stage_attribution) in every "
    "verify-ladder artifact. '0' skips the extra per-stage compiles "
    "when the rung budget is tight; the artifact then carries "
    "stage_ms: null.",
)
_register(
    "FD_BENCH_SWEEP_B", str, None,
    "Comma-separated batch sizes for the rlc fill-efficiency B-sweep "
    "rungs (e.g. '8192,16384,32768' — the BENCH_r06 shape pick). Each "
    "size is its own budgeted worker attempt; unset skips the measured "
    "sweep (the analytic msm_plan prediction is always recorded).",
)

# --------------------------------------------------------------------------
# Driver / test harness knobs. These are read OUTSIDE the package scan
# (tests/conftest.py, __graft_entry__.py, native getenv) but registered
# here so docs/FLAGS.md documents every FD_* name with one semantics.
# --------------------------------------------------------------------------

_register(
    "FD_DRYRUN_BATCH", int, 2048,
    "dryrun_multichip total lanes (read in __graft_entry__.py, which "
    "stays registry-free by design — see lint_baseline.json).",
)
_register(
    "FD_DRYRUN_SWEEP", bool, False,
    "'1' sweeps per-device batch in dryrun_multichip (each point is its "
    "own shard_map compile; opt-in). Read in __graft_entry__.py.",
)
_register(
    "FD_DRYRUN_CHILD", str, None,
    "Internal recursion guard for dryrun_multichip's clean-subprocess "
    "re-exec. Never set by hand. Read in __graft_entry__.py.",
)
_register(
    "FD_RUN_PALLAS_TESTS", bool, False,
    "'1' forces the pallas kernel test files to run even off-TPU "
    "(interpret mode; slow). Read in tests.",
)
_register(
    "FD_RUN_XSLOW", bool, False,
    "Enables the extra-slow test tier (e.g. full SHA-512 NIST vectors). "
    "Read in tests.",
)
_register(
    "FD_NO_AVX512", bool, False,
    "Pins the native ed25519 host verifier to the scalar path even "
    "when CPUID reports AVX-512 IFMA (read by native/ed25519_avx512.cc "
    "via getenv).",
)

# --------------------------------------------------------------------------
# Accessors.
# --------------------------------------------------------------------------


def _lookup(name: str) -> Flag:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unregistered FD_* flag {name!r} — add it to "
            "firedancer_tpu/flags.py (fdlint enforces this)"
        ) from None


def is_set(name: str) -> bool:
    """True when the flag is present AND non-empty in the environment."""
    _lookup(name)
    return bool(os.environ.get(name))


def get_raw(name: str) -> Optional[str]:
    """The raw environment string, or None when unset/empty.

    Truthiness-compatible with the `os.environ.get(name)` reads this
    registry replaced (`if flags.get_raw("FD_VERIFY_MODE"):`)."""
    _lookup(name)
    return os.environ.get(name) or None


def get_str(name: str, default: Any = _UNSET) -> Optional[str]:
    flag = _lookup(name)
    raw = os.environ.get(name)
    if not raw:
        return flag.default if default is _UNSET else default
    return raw


def get_int(name: str, default: Any = _UNSET) -> int:
    flag = _lookup(name)
    raw = os.environ.get(name)
    if not raw:
        return flag.default if default is _UNSET else default
    try:
        # Base 10, matching the int(os.environ.get(...)) call sites this
        # registry replaced (leading zeros stay decimal, no hex).
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer (see docs/FLAGS.md)"
        ) from None


def get_float(name: str, default: Any = _UNSET) -> float:
    flag = _lookup(name)
    raw = os.environ.get(name)
    if not raw:
        return flag.default if default is _UNSET else default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not a number (see docs/FLAGS.md)"
        ) from None


_TRUE = ("1", "true", "yes", "on")


def get_bool(name: str, default: Any = _UNSET) -> bool:
    flag = _lookup(name)
    raw = os.environ.get(name)
    if not raw:
        return flag.default if default is _UNSET else default
    return raw.lower() in _TRUE


def dump_markdown() -> str:
    """docs/FLAGS.md body — the registry is the only source of truth."""
    lines = [
        "# FD_* environment flags",
        "",
        "Generated from the typed registry (`firedancer_tpu/flags.py`) by",
        "`python scripts/fdlint.py --dump-flags > docs/FLAGS.md`.",
        "Do not edit by hand; edit the registry and regenerate.",
        "",
        "`trace-time` flags are captured while a jax/pallas computation",
        "traces: the value is baked into the compiled graph and NOT",
        "re-read per step — set them before the first compile. fdlint's",
        "trace-safety pass only permits registry reads of trace-time",
        "flags inside traced code.",
        "",
        "| Flag | Type | Default | Trace-time | Description |",
        "|---|---|---|---|---|",
    ]
    for name in sorted(REGISTRY):
        f = REGISTRY[name]
        default = "(unset)" if f.default is None else repr(f.default)
        doc = f.doc
        if f.choices:
            doc += " Choices: " + ", ".join(f"`{c}`" for c in f.choices) + "."
        doc = doc.replace("|", "\\|")
        lines.append(
            f"| `{name}` | {f.type.__name__} | `{default}` | "
            f"{'yes' if f.trace_time else 'no'} | {doc} |"
        )
    lines.append("")
    return "\n".join(lines)
