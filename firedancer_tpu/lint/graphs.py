"""fdlint pass 7 — graph-audit: prove structural contracts on the
traced jaxprs the engine registry actually ships.

Passes 1-6 prove source-level contracts (trace safety, flag registry,
boundary asserts, native atomics, limb-bound certificates, ownership).
This pass closes the remaining gap: the invariants the hot path DEPENDS
on — "the local fill half contains zero collectives", "the combine tail
does exactly one all_gather", "no f64 / host callback / pinned
device_put ever enters a hot graph", "the traced MSM executes the madd
count msm_plan predicts" — held only as runtime parity tests. Here they
are proved from the graph itself: `jax.make_jaxpr` traces every
registry graph abstractly on CPU (no device work, no execution), and a
primitive-transfer table walks the closed jaxpr against a declared
per-graph contract.

Contracts are declared as GRAPH_CONTRACTS literals next to the code
that builds each graph (disco/engine.py for the engine classes,
ops/verify_rlc.py for the RLC halves, ops/msm.py for the MSM stage) and
are read with ast.literal_eval — never imported, so a syntax error in a
hot module cannot take the auditor down with it.

Contract grammar (all keys optional except collectives/axes/dtypes):

    "graph_name": {
        "collectives": {"all_gather": 1},  # EXACT primitive -> count
        "axes": ["dp"],                    # allowed collective axes
        "dtypes": ["bool", "int32", ...],  # closed dtype lattice
        "madds": {"engine": "xla"|"kernel", "tolerance_pct": 2.0},
        "vmem_mb": 64.0,                   # pallas residency budget
        "derived_from": ["a", "b"],        # composition, not a trace
    }

Rules:
    graph-collective  collective inventory or axis set drifted
    graph-callback    pure_callback/io_callback/debug_callback or a
                      device-pinned device_put entered a hot graph
    graph-dtype       a dtype outside the declared lattice (f64 is
                      never declarable; value-range enveloping inside
                      int32 is fdcert's job — pass 6)
    graph-cost-drift  walked fill madds disagree with msm_plan's
                      analytic count beyond the declared tolerance, a
                      tolerance wider than TOLERANCE_CAP_PCT, or a
                      pallas residency estimate above vmem_mb
    graph-unmodeled   a primitive outside the transfer table, or a
                      broken composition witness (LOUD: the graph is
                      no longer modeled; bless the primitive here or
                      fix the wrapper — burn-down baseline only)

Two-layer proof: thin wrappers (the monolithic step, the shard_map
carriers) are not re-traced — an AST witness checks the wrapper calls
exactly the traced halves and introduces the declared collectives and
nothing else. The traced-half inventories then transfer. This keeps
the whole pass under the CI lane budget on one CPU core.

Module import is stdlib-only: fdlint's fast lanes (passes 1-6, the doc
dumps, --changed gating) import this module without paying for jax.
Everything that traces lives behind certify_all()/check_fixture().
"""

from __future__ import annotations

import ast
import collections
import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .common import Violation

RULE_COLLECTIVE = "graph-collective"
RULE_CALLBACK = "graph-callback"
RULE_DTYPE = "graph-dtype"
RULE_COST = "graph-cost-drift"
RULE_UNMODELED = "graph-unmodeled"

ALL_RULES = (RULE_COLLECTIVE, RULE_CALLBACK, RULE_DTYPE, RULE_COST,
             RULE_UNMODELED)

#: A madds tolerance wider than this is itself a graph-cost-drift
#: violation: drift gates must not be dodged by widening the gate.
TOLERANCE_CAP_PCT = 5.0

CERT_FILE = "lint_graph_cert.json"
CERT_VERSION = 1

#: Modules carrying GRAPH_CONTRACTS literals (repo-relative).
CONTRACT_MODULES = (
    "firedancer_tpu/disco/engine.py",
    "firedancer_tpu/ops/verify_rlc.py",
    "firedancer_tpu/ops/msm.py",
    "firedancer_tpu/ops/dedup_filter.py",
)

#: Import-closure seeds: a git-touched file reachable from these makes
#: `fdlint --check --changed` re-run the full graph audit.
GRAPH_MODULES = CONTRACT_MODULES + (
    "firedancer_tpu/ops/verify.py",
    "firedancer_tpu/ops/frontend_pallas.py",
    "firedancer_tpu/parallel/mesh.py",
    "firedancer_tpu/msm_plan.py",
    "firedancer_tpu/lint/graphs.py",
    "firedancer_tpu/disco/drain.py",
)

# ------------------------------------------------------------------ #
# Primitive transfer table                                           #
# ------------------------------------------------------------------ #

COLLECTIVE_PRIMS = frozenset({
    "all_gather", "psum", "ppermute", "all_to_all", "reduce_scatter",
    "psum_scatter", "pgather", "pmax", "pmin", "axis_index",
})

CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})

#: Structural primitives with sub-jaxprs the walker recurses into.
#: pallas_call is deliberately NOT here: kernels are leaves (their
#: internal discipline is proved at source level by fdcert/pass 6),
#: but their operands, residency and fill shape are inventoried.
CONTROL_PRIMS = frozenset({
    "scan", "while", "cond", "jit", "shard_map", "custom_jvp_call",
    "custom_vjp_call", "closed_call", "remat", "checkpoint",
})

#: Pure data/compute primitives observed across every registry graph.
#: Anything outside the union of these tables fails graph-unmodeled.
BLESSED_PRIMS = frozenset({
    "abs", "add", "and", "argmax", "argmin", "broadcast_in_dim",
    "clamp", "concatenate", "convert_element_type", "div",
    "dot_general", "dynamic_slice", "dynamic_update_slice", "eq",
    "gather", "ge", "gt", "iota", "le", "lt", "max", "min", "mul",
    "ne", "neg", "not", "or", "pad", "reduce_and", "reduce_max",
    "reduce_min", "reduce_or", "reduce_sum", "rem", "reshape", "rev",
    "scatter", "scatter-add", "select_n", "shift_left",
    "shift_right_arithmetic", "shift_right_logical", "sign", "slice",
    "sort", "squeeze", "stop_gradient", "sub", "transpose", "xor",
    # repo-defined comparison primitive (ops.sc25519 limb less-equal);
    # pure elementwise compare, no transfer semantics of its own
    "le_to",
})

#: Dtypes that may never appear in any hot graph, under any contract:
#: the x64 lattice (silent 2x memory + cost) and floats wider than f32.
FORBIDDEN_DTYPES = frozenset({
    "float64", "int64", "uint64", "complex64", "complex128",
})

# ------------------------------------------------------------------ #
# Graph schedule                                                     #
# ------------------------------------------------------------------ #

#: (graph, kind, schedule): kind 'trace' (make_jaxpr + walk) or
#: 'derive' (AST composition witness over traced halves); schedule
#: 'audit' = audit rung only (structure is rung-invariant: every loop
#: bound is a scan `length` parameter derived from B, which the
#: per-rung msm_stage traces pin at every ladder rung), 'all' = every
#: ladder rung.
GRAPH_PLAN = (
    ("direct", "trace", "audit"),
    ("frontend", "trace", "audit"),
    ("decompress", "trace", "audit"),
    ("rlc_local", "trace", "audit"),
    ("rlc_tail", "trace", "audit"),
    ("pod_tail", "trace", "audit"),
    ("kernel_tail", "trace", "audit"),
    # The kernel stage is the production (pallas) engine — its cost
    # model is reconciled at EVERY ladder rung; the xla fallback stage
    # is reconciled at the audit rung, where the stage-parity check
    # additionally pins it against the in-graph rlc_local fills.
    ("msm_stage_xla", "trace", "audit"),
    ("msm_stage_kernel", "trace", "all"),
    ("rlc_mono", "derive", "audit"),
    ("pod_local", "derive", "audit"),
    ("rlc_sharded", "derive", "audit"),
    ("direct_sharded", "derive", "audit"),
    # fd_drain: the dedup pre-filter round is traced standalone; the
    # fused verify+filter drain step is a witnessed derivation over the
    # traced `direct` verify graph and `drain_filter` (both
    # collective-free, so the fused step is provably so too).
    ("drain_filter", "trace", "audit"),
    ("drain_pair", "derive", "audit"),
)

#: Composition witnesses for the derived graphs: the wrapper function
#: must call every `must_call` name, and the collective-constructor
#: names appearing in its body must be exactly `wrapper_collectives`.
DERIVED_WITNESS = {
    "rlc_mono": {
        "from": ("rlc_local", "rlc_tail"),
        "wrapper": ("firedancer_tpu/ops/verify_rlc.py",
                    "verify_batch_rlc"),
        "must_call": ("verify_rlc_local", "verify_rlc_combine"),
        "wrapper_collectives": {},
    },
    "pod_local": {
        "from": ("rlc_local",),
        # _rlc_split_jits is the shared split-pair builder since
        # fd_fabric: verify_rlc_split_sharded (pod) and
        # verify_rlc_split_global (fabric) are both thin wrappers over
        # it, so the composition witness lives on the builder.
        "wrapper": ("firedancer_tpu/parallel/mesh.py",
                    "_rlc_split_jits"),
        "must_call": ("verify_rlc_local", "verify_rlc_combine"),
        "wrapper_collectives": {},
    },
    "rlc_sharded": {
        "from": ("rlc_local", "pod_tail"),
        "wrapper": ("firedancer_tpu/parallel/mesh.py",
                    "verify_rlc_step_sharded"),
        "must_call": ("verify_batch_rlc",),
        "wrapper_collectives": {},
    },
    "direct_sharded": {
        "from": ("direct",),
        "wrapper": ("firedancer_tpu/parallel/mesh.py",
                    "verify_step_sharded"),
        "must_call": ("verify_batch",),
        "wrapper_collectives": {"psum": 3},
    },
    "drain_pair": {
        "from": ("direct", "drain_filter"),
        "wrapper": ("firedancer_tpu/disco/drain.py", "drain_pair"),
        "must_call": ("verify_batch", "dedup_filter"),
        "wrapper_collectives": {},
    },
}


# ------------------------------------------------------------------ #
# Contract IO (stdlib)                                               #
# ------------------------------------------------------------------ #

def read_contracts(root: str) -> Dict[str, dict]:
    """All GRAPH_CONTRACTS entries across CONTRACT_MODULES, via
    ast.literal_eval (never imported). Returns name -> {"contract",
    "module", "line"}. Raises ValueError on duplicates or non-literal
    declarations — a malformed contract must fail the pass, not skip
    the graph."""
    out: Dict[str, dict] = {}
    for rel in CONTRACT_MODULES:
        path = os.path.join(root, rel)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=rel)
        for node in tree.body:
            if not isinstance(node, ast.Assign):
                continue
            names = [t.id for t in node.targets
                     if isinstance(t, ast.Name)]
            if "GRAPH_CONTRACTS" not in names:
                continue
            table = ast.literal_eval(node.value)
            if not isinstance(table, dict):
                raise ValueError(f"{rel}: GRAPH_CONTRACTS is not a dict")
            for name, contract in table.items():
                if name in out:
                    raise ValueError(
                        f"{rel}: duplicate graph contract {name!r} "
                        f"(first in {out[name]['module']})")
                out[name] = {"contract": contract, "module": rel,
                             "line": node.lineno}
    return out


# ------------------------------------------------------------------ #
# Jaxpr walker                                                       #
# ------------------------------------------------------------------ #

class Inventory:
    """What one walked graph actually contains."""

    def __init__(self) -> None:
        self.collectives: collections.Counter = collections.Counter()
        self.axes: Set[str] = set()
        self.callbacks: collections.Counter = collections.Counter()
        self.device_put_pinned = 0
        self.dtypes: Set[str] = set()
        self.fills: List[Tuple[int, int, int]] = []   # (rounds, lanes, mult)
        self.pallas: List[dict] = []
        self.unknown: collections.Counter = collections.Counter()
        self.eqns = 0

    @property
    def fill_madds(self) -> int:
        return sum(r * l * m for r, l, m in self.fills)

    def as_dict(self) -> dict:
        return {
            "collectives": dict(sorted(self.collectives.items())),
            "axes": sorted(self.axes),
            "callbacks": int(sum(self.callbacks.values())),
            "device_put_pinned": self.device_put_pinned,
            "dtypes": sorted(self.dtypes),
            "fills": sorted([r, l * m] for r, l, m in self.fills),
            "fill_madds": self.fill_madds,
            "pallas_calls": len(self.pallas),
            "vmem_mb": round(max(
                [p["vmem_bytes"] for p in self.pallas] or [0])
                / (1024.0 * 1024.0), 3),
            "eqns": self.eqns,
        }


def _aval_dtypes(vars_) -> Set[str]:
    out = set()
    for v in vars_:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            out.add(str(aval.dtype))
    return out


def _closed(j):
    """Normalize open Jaxpr params (shard_map) to something walkable."""
    return j if hasattr(j, "jaxpr") else _ClosedShim(j)


class _ClosedShim:
    def __init__(self, jaxpr):
        self.jaxpr = jaxpr
        self.consts = ()


def _axis_names(params: dict) -> List[str]:
    raw = params.get("axis_name", params.get("axes", ()))
    if isinstance(raw, str):
        return [raw]
    return [a for a in (raw or ()) if isinstance(a, str)]


def _block_dims(bm, aval_shape) -> Optional[Tuple[int, ...]]:
    shape = getattr(bm, "block_shape", None)
    if shape is None:
        return None
    dims = []
    for i, d in enumerate(shape):
        if d is None:
            dims.append(aval_shape[i] if i < len(aval_shape) else 1)
        else:
            # Blocked(block_size=n); a squeezed (mapped) dimension
            # carries no block_size: one slice per grid step.
            dims.append(int(getattr(d, "block_size", None) or 1))
    return tuple(dims)


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _pallas_record(eqn, mult: int) -> dict:
    """Leaf inventory of one pallas_call: name, residency estimate
    (block shapes when the grid mapping exposes them, full operands
    otherwise), and fill identification — a kernel streaming an
    (R, 96, L) int16 round buffer (msm._stage_niels: yp | ym | t2d
    blocks) or >=3 identical (R, 32, L) int16 round buffers is a staged
    bucket fill executing R*L madds (msm._STAGE_DTYPE is the only int16
    in the repo's graphs, so the signature is unambiguous)."""
    avals_in = [v.aval for v in eqn.invars]
    avals_out = [v.aval for v in eqn.outvars]
    gm = eqn.params.get("grid_mapping")
    blocks = list(getattr(gm, "block_mappings", ()) or ())
    vmem = 0
    for i, a in enumerate(avals_in + avals_out):
        dims = _block_dims(blocks[i], a.shape) if i < len(blocks) else None
        if dims is None or len(dims) != len(a.shape):
            dims = a.shape
        vmem += _prod(dims) * a.dtype.itemsize
    name = str(eqn.params.get("name_and_src_info", "")).split(" ")[0]
    staged = [a for a in avals_in
              if len(a.shape) == 3 and a.shape[1] in (32, 96)
              and str(a.dtype) == "int16"]
    fill = None
    if staged and len({a.shape for a in staged}) == 1 and (
            staged[0].shape[1] == 96 or len(staged) >= 3):
        r, _, lanes = staged[0].shape
        fill = (int(r), int(lanes), mult)
    return {"name": name, "vmem_bytes": int(vmem), "fill": fill,
            "in": [list(a.shape) for a in avals_in],
            "out": [list(a.shape) for a in avals_out]}


def walk_jaxpr(closed, inv: Inventory, mult: int = 1) -> None:
    """Recursive primitive-transfer walk of a ClosedJaxpr. `mult` is
    the product of enclosing scan lengths, so collective and fill
    counts are EXECUTED counts, not lexical ones."""
    inv.dtypes |= _aval_dtypes(closed.jaxpr.constvars)
    inv.dtypes |= _aval_dtypes(closed.jaxpr.invars)
    for eqn in closed.jaxpr.eqns:
        name = eqn.primitive.name
        inv.eqns += 1
        inv.dtypes |= _aval_dtypes(eqn.invars)
        inv.dtypes |= _aval_dtypes(eqn.outvars)
        if name in COLLECTIVE_PRIMS:
            inv.collectives[name] += mult
            inv.axes |= set(_axis_names(eqn.params))
        elif name in CALLBACK_PRIMS:
            inv.callbacks[name] += mult
        elif name == "device_put":
            devices = list(eqn.params.get("devices", ()) or ())
            srcs = list(eqn.params.get("srcs", ()) or ())
            if any(d is not None for d in devices + srcs):
                inv.device_put_pinned += mult
        elif name == "pallas_call":
            rec = _pallas_record(eqn, mult)
            inv.pallas.append(rec)
            if rec["fill"] is not None:
                inv.fills.append(rec["fill"])
        elif name == "scan":
            length = int(eqn.params["length"])
            nc = int(eqn.params["num_consts"])
            ncar = int(eqn.params["num_carry"])
            body = eqn.params["jaxpr"]
            n_xs = len(eqn.invars) - nc - ncar
            carry = [v.aval for v in body.jaxpr.invars[nc:nc + ncar]]
            pts = collections.Counter(
                a.shape[1] for a in carry
                if getattr(a, "shape", None) is not None
                and len(a.shape) == 2 and a.shape[0] == 32
                and str(a.dtype) == "int32")
            if n_xs == 0 and pts and max(pts.values()) >= 4:
                # XLA bucket fill: a lengthless-xs fori scan carrying a
                # >=4-plane (32, L) int32 point accumulator. One
                # unified madd per lane per round.
                lanes = max((v, k) for k, v in pts.items())[1]
                inv.fills.append((length, int(lanes), mult))
            walk_jaxpr(body, inv, mult * length)
        elif name == "cond":
            # Branch-max merge: collectives/fills take the heaviest
            # branch, dtypes union — exact for the clamp-style conds
            # these graphs contain.
            subs = []
            for br in eqn.params["branches"]:
                sub = Inventory()
                walk_jaxpr(br, sub, mult)
                subs.append(sub)
            heaviest = max(
                subs, key=lambda s: (sum(s.collectives.values()),
                                     s.fill_madds, s.eqns))
            inv.collectives += heaviest.collectives
            inv.callbacks += heaviest.callbacks
            inv.device_put_pinned += heaviest.device_put_pinned
            inv.fills += heaviest.fills
            inv.pallas += heaviest.pallas
            for sub in subs:
                inv.axes |= sub.axes
                inv.dtypes |= sub.dtypes
                inv.unknown += sub.unknown
                inv.eqns += sub.eqns
        elif name == "while":
            # Trip count is dynamic: walk both sub-jaxprs at mult so
            # anything forbidden inside is still seen at least once; a
            # fill inside a while can never reconcile and is reported
            # as unmodeled.
            for k in ("cond_jaxpr", "body_jaxpr"):
                walk_jaxpr(_closed(eqn.params[k]), inv, mult)
        elif name in CONTROL_PRIMS:
            for k in ("jaxpr", "call_jaxpr"):
                if k in eqn.params:
                    walk_jaxpr(_closed(eqn.params[k]), inv, mult)
        elif name in BLESSED_PRIMS:
            pass
        else:
            inv.unknown[name] += 1


# ------------------------------------------------------------------ #
# Analytic expectations (msm_plan is the single cost source)         #
# ------------------------------------------------------------------ #

def expected_fills(batch: int, engine: str,
                   torsion_k: int = 64) -> List[Tuple[int, int]]:
    """The (rounds, lanes) grid triple msm_plan models for one RLC MSM
    stage at `batch`: the z-MSM, the 253-bit MSM, and the torsion
    certification. The kernel engine (and the lazy XLA plan) runs the
    torsion fill at the 5-bit masked grid; the legacy XLA baseline
    keeps its historical full 7-bit grid."""
    from firedancer_tpu import msm_plan as mp

    z = (mp.default_rounds(batch), mp.WINDOWS_Z * mp.N_BUCKETS)
    m = (mp.default_rounds(batch + 1), mp.WINDOWS_253 * mp.N_BUCKETS)
    if engine == "xla":
        t = (mp.default_rounds(2 * batch), torsion_k * mp.N_BUCKETS)
    else:
        tb = 1 << mp.TORSION_BUCKET_BITS
        t = (mp.default_rounds(2 * batch, tb), torsion_k * tb)
    return [z, m, t]


def expected_madds(batch: int, engine: str, torsion_k: int = 64) -> int:
    return sum(r * l for r, l in expected_fills(batch, engine, torsion_k))


# ------------------------------------------------------------------ #
# Tracing (jax only from here down)                                  #
# ------------------------------------------------------------------ #

def _jax_cpu(shards: int):
    """CPU-only jax with `shards` virtual host devices — the same
    set-up tests/conftest.py does (the config update pins the CPU even
    when jax was imported before the env var was set)."""
    from firedancer_tpu.parallel import multihost

    multihost.patch_host_device_count(shards)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def trace_inventory(fn, args, x64: bool = False) -> Inventory:
    """make_jaxpr + walk. `x64` traces under the x64 lattice — used by
    fixtures to prove the f64 rule can fire at all (with x64 disabled,
    jax silently coerces float64 to float32 and the plant would be
    invisible)."""
    import jax

    inv = Inventory()
    if x64:
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(fn)(*args)
    else:
        closed = jax.make_jaxpr(fn)(*args)
    walk_jaxpr(closed, inv)
    return inv


def _builders(jax, rung: int, shards: int, plan):
    """(fn, args) builders for every traced graph at one rung. Shapes
    mirror disco/engine._warm_locked (max_msg_len=1232, torsion K=64)."""
    import functools

    import jax.numpy as jnp

    from firedancer_tpu.ops import msm as msm_mod
    from firedancer_tpu.ops import verify as verify_mod
    from firedancer_tpu.ops import verify_rlc as vr
    from firedancer_tpu.ops.frontend_pallas import (
        frontend_decompress_auto, frontend_rlc_auto)
    from firedancer_tpu.parallel import mesh as mesh_mod

    sds = jax.ShapeDtypeStruct
    msg_len = 1232
    torsion_k = 64
    direct_args = (
        sds((rung, msg_len), jnp.uint8), sds((rung,), jnp.int32),
        sds((rung, 64), jnp.uint8), sds((rung, 32), jnp.uint8),
    )
    rlc_args = direct_args + (
        sds((rung, 32), jnp.uint8),
        sds((torsion_k, 2 * rung), jnp.int32),
    )
    pts = tuple(sds((32, rung), jnp.int32) for _ in range(4))
    pts2 = tuple(sds((32, 2 * rung), jnp.int32) for _ in range(4))

    # No jax.jit wrappers anywhere below: make_jaxpr over the bare
    # function yields the identical jaxpr that sits inside the
    # registry's jit graphs, without paying the jit layer per trace.
    def local_fn(engine):
        return functools.partial(
            vr.verify_rlc_local, plan=plan, engine=engine)

    def tail_fn(engine):
        return functools.partial(
            vr.verify_rlc_combine, plan=plan, engine=engine)

    _parts_cache: dict = {}

    def parts_shapes(engine):
        # Parts avals for the combine-tail traces. verify_rlc_local
        # returns the three partials verbatim ({w_r, ok_r, w_m, ok_m,
        # sub, sub_ok}), so eval_shape over the cheap stage function
        # reproduces the pytree at a fraction of the full-local
        # eval_shape cost; a drift in the assembly shows up as a shape
        # error inside the tail trace, never silently.
        if engine not in _parts_cache:
            stage = xla_stage if engine == "xla" else kernel_stage
            (w_r, ok_r), (w_m, ok_m), (sub, sub_ok) = jax.eval_shape(
                stage, *stage_args)
            _parts_cache[engine] = {
                "w_r": w_r, "ok_r": ok_r, "w_m": w_m, "ok_m": ok_m,
                "sub": sub, "sub_ok": sub_ok,
            }
        return _parts_cache[engine]

    def xla_stage(z, pts_r, m_all, pts_m, both, u):
        return (msm_mod.msm_partial(z, pts_r, msm_mod.WINDOWS_Z,
                                    plan=plan),
                msm_mod.msm_partial(m_all, pts_m, msm_mod.WINDOWS_253,
                                    plan=plan),
                msm_mod.subgroup_partial(both, u))

    def kernel_stage(z, pts_r, m_all, pts_m, both, u):
        return (msm_mod.msm_fast_partial(z, pts_r, msm_mod.WINDOWS_Z,
                                         interpret=True, plan=plan),
                msm_mod.msm_fast_partial(m_all, pts_m,
                                         msm_mod.WINDOWS_253,
                                         interpret=True, plan=plan),
                msm_mod.subgroup_fast_partial(both, u, interpret=True))

    stage_args = (
        sds((rung, 32), jnp.uint8), pts,
        sds((rung + 1, 32), jnp.uint8),
        tuple(sds((32, rung + 1), jnp.int32) for _ in range(4)),
        pts2, sds((torsion_k, 2 * rung), jnp.int32),
    )

    def pod_tail():
        mesh = mesh_mod.make_mesh(shards)
        _local8, combine8 = mesh_mod.verify_rlc_split_sharded(mesh, plan)
        shapes = jax.tree_util.tree_map(
            lambda a: sds((shards,) + a.shape, a.dtype),
            parts_shapes("xla"))
        return combine8, (shapes,)

    def drain_filter():
        # The fd_drain dedup pre-filter round at its default window
        # size: the batch dimension rides the rung ladder (it is the
        # feed batch), the bank width is FD_DRAIN_FILTER_BITS-static.
        from firedancer_tpu.ops import dedup_filter as df
        w = df.filter_words(df.DEFAULT_FILTER_BITS)
        return df.dedup_filter, (
            sds((rung,), jnp.uint32), sds((rung,), jnp.uint32),
            sds((rung,), jnp.bool_),
            sds((w,), jnp.uint32), sds((w,), jnp.uint32))

    return {
        "direct": lambda: (verify_mod.verify_batch, direct_args),
        "frontend": lambda: (
            frontend_rlc_auto,
            (sds((rung, 64 + msg_len), jnp.uint8),
             sds((rung,), jnp.int32), sds((rung, 32), jnp.uint8),
             sds((rung, 32), jnp.uint8))),
        "decompress": lambda: (frontend_decompress_auto,
                               (sds((2 * rung, 32), jnp.uint8),)),
        "rlc_local": lambda: (local_fn("xla"), rlc_args),
        "rlc_tail": lambda: (tail_fn("xla"), (parts_shapes("xla"),)),
        "pod_tail": pod_tail,
        "kernel_tail": lambda: (tail_fn("interpret"),
                                (parts_shapes("interpret"),)),
        "msm_stage_xla": lambda: (xla_stage, stage_args),
        "msm_stage_kernel": lambda: (kernel_stage, stage_args),
        "drain_filter": drain_filter,
    }


# ------------------------------------------------------------------ #
# Witnesses (stdlib AST)                                             #
# ------------------------------------------------------------------ #

_COLLECTIVE_CALL_NAMES = frozenset(
    COLLECTIVE_PRIMS | {"all_gather", "psum", "ppermute"})


def _wrapper_witness(root: str, module: str, func: str,
                     must_call: Sequence[str]) -> Tuple[Optional[str],
                                                        Dict[str, int]]:
    """(error, collective_calls) for one wrapper function: error is a
    message when the function is missing or no longer calls every
    traced half; collective_calls counts lexical collective
    constructor calls inside the wrapper body."""
    path = os.path.join(root, module)
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=module)
    fn = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == func:
            fn = node
            break
    if fn is None:
        return f"{module}::{func} not found", {}
    called: Set[str] = set()
    coll: collections.Counter = collections.Counter()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            leaf = None
            if isinstance(node.func, ast.Name):
                leaf = node.func.id
            elif isinstance(node.func, ast.Attribute):
                leaf = node.func.attr
            if leaf:
                called.add(leaf)
                if leaf in _COLLECTIVE_CALL_NAMES:
                    coll[leaf] += 1
    missing = [c for c in must_call if c not in called]
    if missing:
        return (f"{module}::{func} no longer calls "
                f"{', '.join(missing)} — composition witness broken",
                dict(coll))
    return None, dict(coll)


# ------------------------------------------------------------------ #
# Contract checking                                                  #
# ------------------------------------------------------------------ #

def _check_inventory(name: str, rung: int, contract: dict,
                     inv_d: dict, where: Tuple[str, int],
                     engine_for_madds: Optional[str],
                     violations: List[Violation]) -> bool:
    """Inventory dict vs contract; appends violations, returns ok."""
    module, line = where
    ok = True

    def flag(rule: str, check: str, msg: str) -> None:
        nonlocal ok
        ok = False
        violations.append(Violation(
            rule=rule, path=module, line=line,
            key=f"{name}@{rung}:{check}", message=f"{name}@{rung}: {msg}"))

    want_coll = {k: int(v) for k, v in
                 (contract.get("collectives") or {}).items()}
    got_coll = inv_d["collectives"]
    if got_coll != want_coll:
        flag(RULE_COLLECTIVE, "collectives",
             f"collective inventory {got_coll} != declared {want_coll}")
    want_axes = sorted(contract.get("axes") or [])
    if inv_d["axes"] != want_axes:
        flag(RULE_COLLECTIVE, "axes",
             f"collective axes {inv_d['axes']} != declared {want_axes}")
    if inv_d["callbacks"]:
        flag(RULE_CALLBACK, "callbacks",
             f"{inv_d['callbacks']} host callback(s) in a hot graph")
    if inv_d["device_put_pinned"]:
        flag(RULE_CALLBACK, "device_put",
             f"{inv_d['device_put_pinned']} device-pinned device_put(s)"
             " in a hot graph")
    allowed = set(contract.get("dtypes") or [])
    bad = sorted((set(inv_d["dtypes"]) - allowed)
                 | (set(inv_d["dtypes"]) & FORBIDDEN_DTYPES))
    if bad:
        flag(RULE_DTYPE, "dtypes",
             f"dtypes {bad} outside the declared lattice "
             f"{sorted(allowed)}")
    for prim, count in sorted(inv_d.get("unknown", {}).items()):
        flag(RULE_UNMODELED, f"prim:{prim}",
             f"unmodeled primitive {prim!r} (x{count}) — bless it in "
             "lint/graphs.py or remove it from the graph")
    madds = contract.get("madds")
    if madds:
        tol = float(madds.get("tolerance_pct", 0.0))
        if tol > TOLERANCE_CAP_PCT:
            flag(RULE_COST, "tolerance",
                 f"madds tolerance {tol}% exceeds the "
                 f"{TOLERANCE_CAP_PCT}% cap — drift gates must not be "
                 "widened away")
        engine = engine_for_madds or madds.get("engine", "xla")
        exp = expected_madds(rung, engine)
        got = inv_d["fill_madds"]
        if got == exp:
            drift = 0.0
        else:
            drift = abs(got - exp) * 100.0 / exp if exp else 100.0
        inv_d["expected_madds"] = exp
        inv_d["drift_pct"] = round(drift, 4)
        if drift > tol:
            flag(RULE_COST, "madds",
                 f"walked fill madds {got} vs msm_plan {exp} "
                 f"({drift:.3f}% > {tol}% tolerance)")
    budget = contract.get("vmem_mb")
    if budget is not None and inv_d["vmem_mb"] > float(budget):
        flag(RULE_COST, "vmem",
             f"pallas residency estimate {inv_d['vmem_mb']} MB exceeds "
             f"the declared {budget} MB budget")
    return ok


# ------------------------------------------------------------------ #
# The audit                                                          #
# ------------------------------------------------------------------ #

def _audit_rungs(root: str) -> Tuple[List[int], int]:
    from firedancer_tpu import flags

    raw = flags.get_str("FD_GRAPH_RUNGS")
    if raw:
        rungs = sorted(int(tok) for tok in raw.split(",") if tok)
    else:
        from firedancer_tpu.disco.engine import rung_ladder
        rungs = sorted(rung_ladder())
    return rungs, rungs[0]


def certify_all(root: str, rungs: Optional[Sequence[int]] = None,
                shards: Optional[int] = None) -> Tuple[List[Violation],
                                                       dict]:
    """Trace + walk + check every scheduled graph. Returns
    (violations, certificate). The certificate is deterministic
    (sorted keys, rounded floats) so CI can regenerate-and-diff it."""
    from firedancer_tpu import flags
    from firedancer_tpu import msm_plan as mp

    violations: List[Violation] = []
    try:
        contracts = read_contracts(root)
    except (OSError, ValueError, SyntaxError) as e:
        return [Violation(RULE_UNMODELED, CONTRACT_MODULES[0], 1,
                          "contracts:parse", str(e))], {}

    if shards is None:
        shards = flags.get_int("FD_GRAPH_SHARDS")
    if rungs is None:
        rungs, audit_rung = _audit_rungs(root)
    else:
        rungs = sorted(rungs)
        audit_rung = rungs[0]
    jax = _jax_cpu(shards)
    plan = mp.BASELINE_PLAN

    cert_graphs: Dict[str, dict] = {}
    prims_seen: Set[str] = set()
    planned: List[Tuple[str, str, int]] = []
    for name, kind, sched in GRAPH_PLAN:
        for rung in (rungs if sched == "all" else [audit_rung]):
            planned.append((name, kind, rung))

    import gc
    import sys
    import time as _time

    builders_by_rung: Dict[int, dict] = {}

    def get_builders(rung: int) -> dict:
        if rung not in builders_by_rung:
            builders_by_rung[rung] = _builders(jax, rung, shards, plan)
        return builders_by_rung[rung]

    # Tracing churns through millions of short-lived tracer objects;
    # with the cyclic GC enabled, later traces in the same process run
    # ~2x slower than fresh ones (full collections scale with the live
    # heap). Nothing in a trace creates uncollectable cycles we care
    # about mid-audit, so switch GC off for the loop and collect once
    # at the end — this is what keeps the CI lane inside its budget on
    # a single core.
    gc_was_enabled = gc.isenabled()
    gc.disable()
    for name, kind, rung in planned:
        entry_key = f"{name}@{rung}"
        t0 = _time.monotonic()
        info = contracts.get(name)
        if info is None:
            violations.append(Violation(
                RULE_UNMODELED, CONTRACT_MODULES[0], 1,
                f"{name}@{rung}:contract",
                f"graph {name!r} has no GRAPH_CONTRACTS entry"))
            continue
        contract, where = info["contract"], (info["module"], info["line"])
        if kind == "trace":
            fn, args = get_builders(rung)[name]()
            inv = Inventory()
            closed = jax.make_jaxpr(fn)(*args)
            walk_jaxpr(closed, inv)
            inv_d = inv.as_dict()
            inv_d["unknown"] = dict(sorted(inv.unknown.items()))
            engine = ("kernel" if "kernel" in name else
                      "xla" if contract.get("madds") else None)
            ok = _check_inventory(name, rung, contract, inv_d, where,
                                  engine, violations)
            inv_d.pop("unknown")
            prims_seen |= {e for e in _prims_of(closed)}
            cert_graphs[entry_key] = {
                "contract": contract, "traced": inv_d,
                "derived": False, "ok": ok,
            }
        else:
            w = DERIVED_WITNESS[name]
            err, wrapper_coll = _wrapper_witness(
                root, w["wrapper"][0], w["wrapper"][1], w["must_call"])
            ok = True
            if err is not None:
                ok = False
                violations.append(Violation(
                    RULE_UNMODELED, where[0], where[1],
                    f"{name}@{rung}:witness", f"{name}@{rung}: {err}"))
            if wrapper_coll != dict(w["wrapper_collectives"]):
                ok = False
                violations.append(Violation(
                    RULE_COLLECTIVE, where[0], where[1],
                    f"{name}@{rung}:wrapper-collectives",
                    f"{name}@{rung}: wrapper {w['wrapper'][1]} contains "
                    f"collective calls {wrapper_coll}, declared "
                    f"{w['wrapper_collectives']}"))
            # The derived contract must equal the merge of its parts
            # plus whatever the wrapper itself introduces.
            merged: collections.Counter = collections.Counter(
                w["wrapper_collectives"])
            merged_axes: Set[str] = set()
            for part in w["from"]:
                pc = contracts.get(part, {}).get("contract", {})
                merged += collections.Counter(pc.get("collectives") or {})
                merged_axes |= set(pc.get("axes") or [])
            if w["wrapper_collectives"]:
                merged_axes |= set(contract.get("axes") or [])
            want = {k: int(v) for k, v in
                    (contract.get("collectives") or {}).items()}
            if dict(merged) != want:
                ok = False
                violations.append(Violation(
                    RULE_COLLECTIVE, where[0], where[1],
                    f"{name}@{rung}:collectives",
                    f"{name}@{rung}: declared collectives {want} != "
                    f"composition {dict(merged)} of {list(w['from'])}"))
            cert_graphs[entry_key] = {
                "contract": contract, "derived": True,
                "from": [f"{p}@{audit_rung}" for p in w["from"]],
                "witness": f"{w['wrapper'][0]}::{w['wrapper'][1]}",
                "ok": ok,
            }
        if flags.get_bool("FD_GRAPH_TIMING"):
            print(f"[fdgraph] {entry_key} ({kind}): "
                  f"{_time.monotonic() - t0:.1f}s", file=sys.stderr)
    if gc_was_enabled:
        gc.enable()
        gc.collect()

    # Cross-check: the in-graph rlc_local fills must equal the
    # standalone msm_stage_xla fills at the audit rung (one MSM stage,
    # two routes into the trace — they can never disagree).
    local_e = cert_graphs.get(f"rlc_local@{audit_rung}")
    stage_e = cert_graphs.get(f"msm_stage_xla@{audit_rung}")
    if local_e and stage_e and not local_e["derived"] \
            and local_e["traced"]["fills"] != stage_e["traced"]["fills"]:
        info = contracts["rlc_local"]
        violations.append(Violation(
            RULE_COST, info["module"], info["line"],
            f"rlc_local@{audit_rung}:stage-parity",
            f"rlc_local fills {local_e['traced']['fills']} != standalone "
            f"msm stage fills {stage_e['traced']['fills']}"))
        local_e["ok"] = False

    cert = {
        "version": CERT_VERSION,
        "audit_rung": audit_rung,
        "rungs": list(rungs),
        "shards": shards,
        "plan": mp.plan_token(plan),
        "tolerance_cap_pct": TOLERANCE_CAP_PCT,
        "rules": list(ALL_RULES),
        "graphs": {k: cert_graphs[k] for k in sorted(cert_graphs)},
        "primitives": sorted(prims_seen),
    }
    return violations, cert


def _prims_of(closed) -> Set[str]:
    out: Set[str] = set()

    def rec(c):
        for eqn in c.jaxpr.eqns:
            out.add(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for k in ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr"):
                if k in eqn.params:
                    rec(_closed(eqn.params[k]))
            for br in eqn.params.get("branches", ()):
                rec(br)
    rec(closed)
    return out


def check_repo(root: str) -> List[Violation]:
    """Pass-7 entry point for fdlint: violations only."""
    return certify_all(root)[0]


def dump_certificate(root: str) -> str:
    """The graph certificate as canonical JSON. Refuses (SystemExit)
    while violations are open: a certificate must never be regenerated
    to paper over a failing contract."""
    violations, cert = certify_all(root)
    if violations:
        lines = "\n".join(f"  {v.format()}" for v in violations)
        raise SystemExit(
            f"refusing to dump graph certificate with "
            f"{len(violations)} open violation(s):\n{lines}")
    return json.dumps(cert, indent=1, sort_keys=True) + "\n"


def cert_sha256(root: str) -> Optional[str]:
    """sha256 of the committed certificate, or None when absent —
    bench.py stamps this into artifacts so a bench line is always
    attributable to the graph contract set it ran under."""
    path = os.path.join(root, CERT_FILE)
    try:
        with open(path, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None


# ------------------------------------------------------------------ #
# Fixtures                                                           #
# ------------------------------------------------------------------ #

def check_fixture(path: str) -> List[Violation]:
    """Trace-and-check the graphs a fixture module declares: the
    module defines GRAPH_CONTRACTS plus FIXTURE_GRAPHS = {name:
    {"build": builder_name, "x64": bool}}; each builder returns (fn,
    args). Used by the mutation tests — fixture files live under
    tests/fixtures/lint/, outside every scan root."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_fdgraph_fixture_" + os.path.basename(path).replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rel = os.path.basename(path)
    violations: List[Violation] = []
    for name, meta in sorted(mod.FIXTURE_GRAPHS.items()):
        fn, args = getattr(mod, meta["build"])()
        inv = trace_inventory(fn, args, x64=bool(meta.get("x64")))
        inv_d = inv.as_dict()
        inv_d["unknown"] = dict(sorted(inv.unknown.items()))
        contract = mod.GRAPH_CONTRACTS[name]
        _check_inventory(name, int(meta.get("rung", 0)), contract,
                         inv_d, (rel, 1), meta.get("engine"),
                         violations)
    return violations


# ------------------------------------------------------------------ #
# --changed gating + docs rendering (stdlib)                         #
# ------------------------------------------------------------------ #

def _module_to_path(root: str, dotted_mod: str) -> Optional[str]:
    rel = dotted_mod.replace(".", "/")
    for cand in (rel + ".py", rel + "/__init__.py"):
        if os.path.isfile(os.path.join(root, cand)):
            return cand
    return None


def import_closure(root: str) -> Set[str]:
    """Repo-relative paths statically reachable from GRAPH_MODULES via
    firedancer_tpu-internal imports (ast-walk BFS; stdlib and external
    imports are ignored). The committed certificate itself is in the
    closure: hand-edits must re-run the audit."""
    seen: Set[str] = set()
    queue = [m for m in GRAPH_MODULES
             if os.path.isfile(os.path.join(root, m))]
    while queue:
        rel = queue.pop()
        if rel in seen:
            continue
        seen.add(rel)
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=rel)
        except (OSError, SyntaxError):
            continue
        pkg_parts = rel.split("/")[:-1]
        for node in ast.walk(tree):
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg_parts[:len(pkg_parts) - node.level + 1]
                    stem = ".".join(base + ([node.module]
                                            if node.module else []))
                else:
                    stem = node.module or ""
                if stem:
                    mods.append(stem)
                    mods += [f"{stem}.{a.name}" for a in node.names]
            for m in mods:
                if not m.startswith("firedancer_tpu"):
                    continue
                p = _module_to_path(root, m)
                if p and p not in seen:
                    queue.append(p)
    seen.add(CERT_FILE)
    return seen


def touches_graphs(root: str, changed: Sequence[str]) -> bool:
    closure = import_closure(root)
    return any(c in closure for c in changed)


def render_contracts_markdown(root: str) -> str:
    """docs/GRAPHS.md: the contract catalog, rendered from the same
    GRAPH_CONTRACTS literals the audit proves — no tracing, so the doc
    pin test stays cheap. Regenerate with
    `python scripts/fdlint.py --dump-graph-contracts`."""
    contracts = read_contracts(root)
    by_name = {name: (kind, sched)
               for name, kind, sched in GRAPH_PLAN}
    lines = [
        "# Engine graph contracts (fdlint pass 7)",
        "",
        "**AUTOGENERATED — do not edit.** Rendered from the",
        "`GRAPH_CONTRACTS` literals by",
        "`python scripts/fdlint.py --dump-graph-contracts`; a test pins",
        "this file against the declarations and `lint_graph_cert.json`",
        "carries the proved inventories (see docs/LINT.md, pass 7).",
        "",
        "| graph | proof | schedule | collectives | axes | dtypes |"
        " madds model | vmem budget | declared in |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for name in sorted(contracts):
        c = contracts[name]["contract"]
        kind, sched = by_name.get(name, ("?", "?"))
        proof = "traced" if kind == "trace" else "derived (AST witness)"
        sched_s = ("every ladder rung" if sched == "all"
                   else "audit rung")
        coll = json.dumps(c.get("collectives") or {}, sort_keys=True)
        axes = ", ".join(c.get("axes") or []) or "—"
        dts = ", ".join(c.get("dtypes") or []) or "—"
        madds = c.get("madds")
        madds_s = (f"{madds['engine']} ± {madds['tolerance_pct']}%"
                   if madds else "—")
        vmem = c.get("vmem_mb")
        vmem_s = f"{vmem} MB" if vmem is not None else "—"
        lines.append(
            f"| `{name}` | {proof} | {sched_s} | `{coll}` | {axes} | "
            f"{dts} | {madds_s} | {vmem_s} | "
            f"`{contracts[name]['module']}` |")
    lines += [
        "",
        "## Rules",
        "",
        "- `graph-collective` — collective inventory or axis set "
        "drifted from the declaration.",
        "- `graph-callback` — a host callback or device-pinned "
        "`device_put` entered a hot graph.",
        "- `graph-dtype` — a dtype outside the declared lattice "
        "(f64/i64 are never declarable).",
        "- `graph-cost-drift` — walked fill madds vs `msm_plan` beyond "
        "tolerance, a tolerance above the "
        f"{TOLERANCE_CAP_PCT}% cap, or a pallas residency estimate "
        "above `vmem_mb`.",
        "- `graph-unmodeled` — a primitive outside the transfer table "
        "or a broken composition witness (burn-down baseline only).",
        "",
        "Derived graphs transfer the inventories of their traced "
        "halves through an AST witness on the wrapper (see "
        "`firedancer_tpu/lint/graphs.py:DERIVED_WITNESS`).",
        "",
    ]
    return "\n".join(lines)
