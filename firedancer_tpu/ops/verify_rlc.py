"""Batch Ed25519 verification by random linear combination (RLC).

The TPU-first answer to the per-signature cost wall: a single-lane
verify pays ~256 doublings + 128 table adds in the double-scalar-mult
(ops/verify.py), and no amount of kernel tuning removes that work. RLC
batch verification (the standard ed25519 batch equation, e.g.
ed25519-dalek / RFC 8032 §8.2 discussion) replaces B per-lane
scalar-mults with ONE multi-scalar-multiplication whose doubling chain
is shared across the whole batch:

    T = (sum_i z_i s_i mod L) * B  +  sum_i z_i * (-R_i)  +  sum_i (z_i h_i mod L) * (-A_i)
    batch valid  <=>  T == identity  AND  all live R_i, A_i torsion-free

with z_i fresh random 126-bit scalars chosen AFTER the signatures are
known. The MSM is computed with Pippenger bucket accumulation
(ops/msm.py) — bucket fill cost amortizes the doublings over all lanes.

Soundness (why the torsion condition is load-bearing): the RLC equation
alone is only sound against defects in the PRIME-ORDER component. An
adversary can craft lanes whose per-lane defect D_i = s_i*B - h_i*A_i
- R_i lies entirely in the 8-torsion subgroup (e.g. R_i = r_i*B + T
with T the order-2 point): each such lane fails the per-lane
byte-compare, but the combined torsion defect sum z_i t_i mod 8 cancels
with probability up to 1/2 per batch — catastrophic for a consensus
path. The fix is a randomized subgroup certification
(msm.subgroup_check) over all live lanes' R_i and A_i: K independent
random aggregates, each multiplied by the group order and compared to
the identity. Torsion-free R and A (plus torsion-free B) make every
D_i torsion-free, restoring the RLC bound. Combined soundness per
batch accept: <= 2^-126 for prime-order defects + <= 2^-K for
torsion defects (K = FD_RLC_TORSION_K, default 64). Honest traffic
(real keys and nonces are prime-order) never trips the check; a
tripped check only routes the batch to the exact per-lane path.

Semantics parity with the reference's DEFAULT (2-point) verify
(fd_ed25519_user.c:346-433, FD_ED25519_VERIFY_USE_2POINT=1; round-5,
pinned by the 396 Zcash malleability vectors — see ops/verify.py):
- s range check (ERR_SIG) exactly as the per-lane path.
- A or R failing decompression is definite ERR_PUBKEY (the reference's
  frombytes_vartime_2 reports both with the shared code); small-order A
  is definite ERR_PUBKEY, small-order R definite ERR_SIG. These lanes
  are excluded from the combination (z_i = 0).
- The per-lane compare is on GROUP ELEMENTS (projective cross-multiply
  against the decoded R), so a non-canonical-but-decodable r encoding
  stays LIVE — the RLC equation on points is exactly the right test.

Failure handling is the caller's job (disco/tiles.py): if the batch
equation fails, at least one lane is bad — re-dispatch the batch on the
per-lane path. Worst case (adversary salts every batch) costs one extra
RLC pass (~0.4x a direct pass); the clean-traffic common case runs
~2-3x faster than per-lane verify.

Engine selection (round-6 un-park): RLC is the PRIMARY device verify
mode, and on TPU its MSM runs on the VMEM Pallas Pippenger kernels
(ops/msm_pallas.py) — bucket state resident in VMEM across the fill
rounds, the running-sum aggregation, and the cross-window Horner, so
the doubling chain is paid once per batch. The round-4 parking decision
was made on the XLA-graph MSM only (VERDICT.md r5 weak #4: "parked on
the wrong evidence"); the kernel engine had never run as the RLC
backend. FD_MSM_IMPL picks explicitly: 'pallas' | 'xla' |
'interpret' (the production kernels under the Pallas interpreter, so
CPU CI can parity-test the exact engine that ships); 'auto' resolves
to pallas on TPU platforms. docs/ROOFLINE.md carries the op-count
analysis that motivates the promotion.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu import flags
from firedancer_tpu.disco import flight
from firedancer_tpu.tango import tempo

from . import curve25519 as ge
from . import fe25519 as fe
from . import msm as msm_mod
from . import sc25519 as sc
# Top-level, not trace-time: frontend_pallas transitively materializes
# sha512/sign's module-scope jnp constants; importing inside the traced
# body would leak tracers into those globals on the first call.
from .frontend_pallas import frontend_decompress_auto, frontend_rlc_auto
from .verify import (
    FD_ED25519_ERR_PUBKEY,
    FD_ED25519_ERR_SIG,
    FD_ED25519_SUCCESS,
)

def msm_engine() -> str:
    """Trace-time MSM engine for the RLC pass: 'pallas' (VMEM Pippenger
    kernels — the production TPU engine), 'xla' (graph MSM, CPU hosts),
    or 'interpret' (the Pallas kernels under the interpreter: slow, but
    it exercises the production engine's exact staging/fill/aggregation
    code on CPU CI). FD_MSM_IMPL forces any of the three; 'auto' (the
    default) resolves to pallas exactly when the attached backend is a
    TPU family (ops.backend.use_pallas). An unrecognized value is an
    error — a typo'd force must never quietly test the wrong engine."""
    impl = flags.get_str("FD_MSM_IMPL")
    if impl == "interpret":
        return "interpret"
    if impl not in ("", "auto", "xla", "pallas"):
        raise ValueError(
            f"unknown FD_MSM_IMPL {impl!r} "
            "(want auto|xla|pallas|interpret)"
        )
    from .backend import use_pallas

    return "pallas" if use_pallas("FD_MSM_IMPL") else "xla"


def fresh_z(batch: int, rng: np.random.Generator | None = None) -> np.ndarray:
    """(B, 32) uint8: uniform random 126-bit scalars (top 16 bytes zero).

    Host-side entropy: z must be unpredictable to whoever crafted the
    signatures, so it is drawn per batch, never fixed in the graph.
    126 bits = 18 exact 7-bit MSM windows (msm.WINDOWS_Z), keeping every
    window's digit distribution uniform.

    z is FULLY uniform — no bit is forced. (An earlier revision forced
    z_i odd to avoid zero weights; all-odd z makes order-2 torsion
    defects cancel pairwise DETERMINISTICALLY, z_i + z_j always even —
    part of the torsion unsoundness fixed by msm.subgroup_check.) A
    zero z_i drops lane i's check with probability 2^-126 per lane,
    which is absorbed by the stated soundness bound.

    Default entropy is os.urandom (CSPRNG) — the soundness claim rests
    on z being unpredictable, which a statistical PRNG does not provide.
    The rng parameter exists for deterministic tests only.
    """
    import os

    z = np.zeros((batch, 32), np.uint8)
    if rng is None:
        z[:, :16] = np.frombuffer(
            os.urandom(batch * 16), np.uint8
        ).reshape(batch, 16)
    else:
        z[:, :16] = rng.integers(0, 256, (batch, 16), dtype=np.uint8)
    z[:, 15] &= 0x3F
    return z


def fresh_u(k: int, batch: int,
            rng: np.random.Generator | None = None) -> np.ndarray:
    """(K, batch) int32 digits uniform in [0, 128): trial weights for the
    torsion subgroup certification (msm.subgroup_check). 7-bit digits
    load the fill buckets exactly like one MSM window, so the overflow
    analysis (msm._default_rounds) carries over unchanged. Same
    entropy requirements as fresh_z: os.urandom in production, the rng
    parameter for deterministic tests only."""
    import os

    if rng is None:
        raw = np.frombuffer(os.urandom(k * batch), np.uint8)
    else:
        raw = rng.integers(0, 256, k * batch, dtype=np.uint8)
    return (raw.astype(np.int32) & 0x7F).reshape(k, batch)




def verify_batch_rlc(msgs, msg_lengths, sigs, pubkeys, z_bytes, u_digits,
                     axis_name: str | None = None, plan=None):
    """One RLC pass over a batch.

    Args are as ops.verify.verify_batch, plus z_bytes (B, 32) uint8
    126-bit random weights (from fresh_z) and u_digits (K, 2B) int32
    trial weights for the torsion certification (from fresh_u; columns
    0..B-1 weight the pubkey points, B..2B-1 the R points).

    axis_name shards the batch over a device mesh (round-10): called
    under shard_map with per-device lane slices, the per-lane stages
    run locally and the MSMs combine per-window PARTIALS across the
    mesh (ops/msm.py axis_name plumbing) before the doubling-chain
    tails — the u*B term folds per shard (sum_d u_d*B == (sum_d u_d)*B
    in the group, so no scalar collective is needed), and batch_ok is
    the replicated global verdict. parallel/mesh.verify_rlc_step_sharded
    is the tile-facing builder.

    Returns (status, definite, batch_ok):
      status:   (B,) int32 — correct for lanes where definite is True;
                provisionally SUCCESS elsewhere.
      definite: (B,) bool — lanes whose status is final regardless of
                the batch equation (s-range / pubkey / R-encoding fails).
      batch_ok: () bool — True iff the combined equation holds AND every
                live lane's A and R are certified torsion-free, i.e.
                every non-definite lane is genuinely SUCCESS. On False
                the caller re-runs the per-lane path.

    fd_pod split (round-18): the body is verify_rlc_local (per-lane
    stages + local bucket fills, no collectives) composed with
    verify_rlc_combine (the cross-mesh gathers + doubling-chain tails)
    — the exact op sequence the monolithic step always ran, so this
    single-graph path stays bit-exact while parallel/mesh.py can jit
    the two halves separately and double-buffer them.

    plan (None = msm.active_plan()): the fd_msm2 MSM schedule, threaded
    to both halves so a (local, combine) pair always agrees on window
    counts and Horner stride (disco/engine.py resolves the per-rung
    winner from the EngineRegistry).
    """
    if plan is None:
        plan = msm_mod.active_plan()
    status, definite, parts = verify_rlc_local(
        msgs, msg_lengths, sigs, pubkeys, z_bytes, u_digits, plan=plan)
    batch_ok = verify_rlc_combine(parts, axis_name=axis_name, plan=plan)
    return status, definite, batch_ok


def verify_rlc_local(msgs, msg_lengths, sigs, pubkeys, z_bytes, u_digits,
                     plan=None, engine=None):
    """The LOCAL half of one RLC pass: s-range, stacked decompression,
    the fused SHA/mod-L front half, the status ladder, and the three
    Pippenger bucket fills/aggregations over THIS shard's lanes — no
    collectives, no doubling-chain tails.

    Returns (status, definite, parts): status/definite as
    verify_batch_rlc; parts the pytree of per-shard partials
    verify_rlc_combine consumes —
      w_r / ok_r    window partials + fill verdict of the z*(-R) MSM
      w_m / ok_m    same for the [m*(-A), u*B] 253-bit MSM
      sub / sub_ok  per-trial torsion aggregates + fill verdict
    Every leaf is a small array ((32, nw)-limb coords, () bools), so
    shipping parts between two jitted graphs costs microseconds.

    plan: the fd_msm2 schedule for all three fills. A lazy plan routes
    the XLA torsion fill through the 5-bit masked-digit grid (the same
    soundness argument subgroup_check_fast has always shipped) — the
    baseline keeps the historical 7-bit unified-add fill bit-identical.

    engine (None = msm_engine(), i.e. the trace-time flag): explicit
    MSM engine override. fdlint pass 7 traces the kernel-schedule graph
    on CPU by passing 'interpret' here — same dispatch the flag drives,
    no environment mutation inside the auditor.
    """
    if plan is None:
        plan = msm_mod.active_plan()
    r_bytes = sigs[:, :32]
    s_bytes = sigs[:, 32:]

    s_ok = sc.sc_check_range(s_bytes)

    # One decompression pass over A and R stacked: same lane-work, half
    # the traced graph (the power chain appears once). The niels forms
    # for the MSM fills ride along from the kernel (free in-VMEM vs
    # multi-ms XLA chains).
    from .decompress_pallas import decompress_choice

    bsz = pubkeys.shape[0]
    if engine is None:
        engine = msm_engine()
    on_tpu = engine == "pallas"
    # niels outputs are only consumed by the kernel MSM path, so both
    # backends must be on (a split config would compute and drop them).
    want_niels = on_tpu and decompress_choice(2 * bsz) != "xla"
    # Engine dispatch lives with the rest of the front half
    # (frontend_pallas): the Montgomery-batched decompress on eligible
    # shapes, staged composition otherwise — bit-exact either way.
    dec = frontend_decompress_auto(
        jnp.concatenate([pubkeys, r_bytes], axis=0),
        want_niels=want_niels,
    )
    both, both_ok = dec[:2]
    both_niels = dec[2] if want_niels else None
    a_point = tuple(c[:, :bsz] for c in both)
    r_point = tuple(c[:, bsz:] for c in both)
    pub_ok = both_ok[:bsz]
    r_dec_ok = both_ok[bsz:]

    # 2-point semantics (round-5, pinned by the Zcash malleability
    # vectors — see ops/verify.py): the per-lane path compares group
    # ELEMENTS, so a non-canonical-but-decodable r encoding is LIVE
    # (the RLC equation on points is exactly the right test), an
    # undecodable r is ERR_PUBKEY (frombytes_vartime_2's shared code),
    # and small-order A (ERR_PUBKEY) / R (ERR_SIG) are definite fails.
    so_both = ge.small_order_mask(both)
    a_small = so_both[:bsz]
    r_small = so_both[bsz:]

    status = jnp.where(
        ~s_ok,
        FD_ED25519_ERR_SIG,
        jnp.where(
            ~pub_ok | ~r_dec_ok | a_small,
            FD_ED25519_ERR_PUBKEY,
            jnp.where(r_small, FD_ED25519_ERR_SIG, FD_ED25519_SUCCESS),
        ),
    ).astype(jnp.int32)
    definite = ~(s_ok & pub_ok & r_dec_ok & ~a_small & ~r_small)

    # Zero out excluded lanes' weights; z=0 contributes the identity.
    live = ~definite
    z_live = jnp.where(live[:, None], z_bytes, 0).astype(jnp.uint8)

    # h = SHA-512(r||pub||msg) mod L, m = z*h mod L, zs = z*s mod L —
    # the fused front-end (ops/frontend_pallas.py) runs all three as
    # one VMEM kernel chained onto the compression when active and the
    # shape is eligible; the staged fallback keeps the historical
    # per-stage dispatch (FD_SHA_IMPL / FD_SC_IMPL, registry reads at
    # trace time — fdlint pass 1 sanctions exactly that). The z-live
    # masking rides INTO the fused muls (dead lanes: z = 0 -> m = zs =
    # 0, bit-identical to the staged path). u = sum zs mod L.
    _h_bytes, m_bytes, zs = frontend_rlc_auto(
        jnp.concatenate([r_bytes, pubkeys, msgs], axis=1),
        msg_lengths.astype(jnp.int32) + 64,
        z_live, s_bytes,
    )
    u_bytes = sc.sc_sum(zs)

    neg_r = ge.point_neg(r_point)
    neg_a = ge.point_neg(a_point)

    # Fold the u*B term into the 253-bit MSM as one extra lane (point B,
    # scalar u) — one engine, no separate fixed-base path.
    from .sign import _b_point

    b_pt, _ = _b_point(1)
    m_all = jnp.concatenate([m_bytes, u_bytes], axis=0)
    pts_all = tuple(
        jnp.concatenate([c_a, c_b], axis=1)
        for c_a, c_b in zip(neg_a, b_pt)
    )
    # niels forms from the decompress kernel: the negated point's form
    # is the coordinate swap (ym, yp, t2dn); the single B lane's form
    # is three tiny XLA ops.
    # Separate dict literals: chained assignment would alias one object
    # and let a future in-place mutation leak across the three kwargs.
    kw_r = {}
    kw_m = {}
    kw_sub = {}
    if both_niels is not None and on_tpu:
        yp, ym, t2d, t2dn = both_niels
        kw_r = {"niels": (ym[:, bsz:], yp[:, bsz:], t2dn[:, bsz:])}
        b_niels = (fe.fe_add(b_pt[1], b_pt[0]),
                   fe.fe_sub(b_pt[1], b_pt[0]),
                   fe.fe_mul(b_pt[3], fe.FE_D2))
        kw_m = {"niels": tuple(
            jnp.concatenate([na, nb], axis=1)
            for na, nb in zip(
                (ym[:, :bsz], yp[:, :bsz], t2dn[:, :bsz]), b_niels
            )
        )}
        kw_sub = {"niels": (yp, ym, t2d)}
    # Decompressed points have Z == 1, so the niels fast path applies.
    # Torsion certification is over the live lanes' A and R (the
    # stacked decompression output `both` is already in that column
    # order); dead lanes get zero trial weights — unweighted, identity
    # contribution.
    live2 = jnp.concatenate([live, live], axis=0)
    u_live = jnp.where(live2[None, :], u_digits, 0)
    if engine == "xla":
        w_r, ok_r = msm_mod.msm_partial(
            z_live, neg_r, msm_mod.WINDOWS_Z, plan=plan)
        w_m, ok_m = msm_mod.msm_partial(
            m_all, pts_all, msm_mod.WINDOWS_253, plan=plan)
        if plan.lazy:
            # The lazy engine's torsion grid: 5-bit masked trial digits
            # (subgroup_check_fast's shipping soundness argument) over
            # the certified niels madd — the fill that dominates the
            # whole MSM stage's lane count at production batch sizes.
            from firedancer_tpu.msm_plan import TORSION_BUCKET_BITS

            sub_agg, sub_okf = msm_mod.subgroup_partial(
                both, u_live, bucket_bits=TORSION_BUCKET_BITS,
                lazy=True)
        else:
            sub_agg, sub_okf = msm_mod.subgroup_partial(both, u_live)
    else:
        interp = engine == "interpret"
        w_r, ok_r = msm_mod.msm_fast_partial(
            z_live, neg_r, msm_mod.WINDOWS_Z, interpret=interp,
            plan=plan, **kw_r)
        w_m, ok_m = msm_mod.msm_fast_partial(
            m_all, pts_all, msm_mod.WINDOWS_253, interpret=interp,
            plan=plan, **kw_m)
        sub_agg, sub_okf = msm_mod.subgroup_fast_partial(
            both, u_live, interpret=interp, **kw_sub)
    parts = {
        "w_r": w_r, "ok_r": ok_r,
        "w_m": w_m, "ok_m": ok_m,
        "sub": sub_agg, "sub_ok": sub_okf,
    }
    return status, definite, parts


def _gather_parts(parts, axis_name: str):
    """ONE all_gather for the whole combine tail (round-17).

    The per-leaf gather path (msm._gather_point_sum + _all_shards_ok,
    once per grid) issued 15 collectives per combine; every partial
    leaf is tiny ((32, nw) limb planes, () verdicts), so the tail was
    latency-bound on collective COUNT, not bytes. Ravel every leaf
    into one flat int32 vector (verdict bools widen to int32), gather
    the (n_shards, N) table once, then rebuild the per-leaf shard
    stacks and fold them through the SAME rules the per-leaf path
    always used — combine_stacked in mesh order for coordinate stacks,
    AND across shards for verdicts — so every folded value is
    bit-identical to the historical path and only the data movement is
    fused. Returns GLOBAL parts; the caller runs the per-grid combines
    with axis_name=None. fdlint pass 7 proves the 'exactly one
    all_gather in the combine tail' contract against this graph."""
    leaves, treedef = jax.tree_util.tree_flatten(parts)
    flat = jnp.concatenate(
        [jnp.ravel(leaf).astype(jnp.int32) for leaf in leaves])
    table = jax.lax.all_gather(flat, axis_name)          # (n_shards, N)
    stacks = []
    off = 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape, dtype=np.int64))
        stacks.append(
            table[:, off:off + n].reshape((-1,) + leaf.shape)
            .astype(leaf.dtype))
        off += n
    stacked = jax.tree_util.tree_unflatten(treedef, stacks)
    out = {k: msm_mod.combine_stacked(stacked[k])
           for k in ("w_r", "w_m", "sub")}
    out.update({k: jnp.all(stacked[k], axis=0)
                for k in ("ok_r", "ok_m", "sub_ok")})
    return out


def verify_rlc_combine(parts, axis_name: str | None = None, plan=None,
                       engine=None):
    """The TAIL half of one RLC pass: combine the per-shard partials
    across the mesh (ONE fused all_gather via _gather_parts when
    axis_name; identity when None), run the three doubling-chain tails
    (two window Horners + the [L] torsion ladder), and fold the global
    batch verdict.

    The engine is re-resolved from the same trace-time flag the local
    half read (or forced via the engine parameter, as verify_rlc_local),
    so a (local, combine) pair traced under one environment always
    agrees on partial shapes. The kernel-path torsion combine
    evaluates every Mosaic-padded trial lane — sound, because the pad
    lanes carry zero coordinates that trivially pass the identity test
    (msm.subgroup_fast_partial documents the argument)."""
    if plan is None:
        plan = msm_mod.active_plan()
    if engine is None:
        engine = msm_engine()
    if axis_name is not None:
        parts = _gather_parts(parts, axis_name)
        axis_name = None
    if engine == "xla":
        t1, ok1 = msm_mod.msm_combine(
            parts["w_r"], parts["ok_r"], msm_mod.WINDOWS_Z,
            axis_name=axis_name, plan=plan)
        t2, ok2 = msm_mod.msm_combine(
            parts["w_m"], parts["ok_m"], msm_mod.WINDOWS_253,
            axis_name=axis_name, plan=plan)
        sub_ok, sub_fill_ok = msm_mod.subgroup_combine(
            parts["sub"], parts["sub_ok"], axis_name=axis_name)
    else:
        interp = engine == "interpret"
        t1, ok1 = msm_mod.msm_fast_combine(
            parts["w_r"], parts["ok_r"], msm_mod.WINDOWS_Z,
            interpret=interp, axis_name=axis_name, plan=plan)
        t2, ok2 = msm_mod.msm_fast_combine(
            parts["w_m"], parts["ok_m"], msm_mod.WINDOWS_253,
            interpret=interp, axis_name=axis_name, plan=plan)
        sub_ok, sub_fill_ok = msm_mod.subgroup_fast_combine(
            parts["sub"], parts["sub_ok"], interpret=interp,
            axis_name=axis_name)
    # T = u*B + sum z(-R) + sum m(-A); identity <=> X == 0 and Y == Z.
    t = ge.point_add(t1, t2, need_t=False)
    batch_ok = (
        fe.fe_is_zero(t[0]) & fe.fe_eq(t[1], t[2]) & ok1 & ok2
        & sub_ok & sub_fill_ok
    )
    return batch_ok


class RlcAsyncResult:
    """Duck-types the slice of the jax.Array surface the verify tile's
    completion shim uses (`is_ready()`, `np.asarray`) over an RLC pass
    with lazy per-lane fallback.

    The RLC pass and the fallback both dispatch asynchronously; the
    fallback is only ever dispatched once the RLC verdict is known to be
    False, so clean batches cost one pass and dirty batches two — the
    shim's in-flight accounting and ordering are untouched.
    """

    def __init__(self, rlc_out, fallback_fn, args, span):
        self._status, self._definite, self._ok = rlc_out
        self._fallback_fn = fallback_fn
        self._args = args
        self._span = span       # the verifier's flight.span_maker()
        self._fb = None
        self._resolved = None
        self.used_fallback = False
        self.t_fallback = 0     # tickcount of the per-lane launch

    def _start_fallback(self):
        self.t_fallback = tempo.tickcount()
        with self._span("fd.rlc.fallback_launch"):
            self._fb = self._fallback_fn(*self._args)
        self._args = None
        self.used_fallback = True

    def is_ready(self) -> bool:
        if self._resolved is not None:
            return True
        if self._fb is not None:
            return self._fb.is_ready()
        if not self._ok.is_ready():
            return False
        if bool(self._ok):
            self._resolved = np.asarray(self._status)
            return True
        self._start_fallback()
        return self._fb.is_ready()

    def __array__(self, dtype=None, copy=None):
        if self._resolved is None:
            if self._fb is None:
                if bool(self._ok):          # blocks on the RLC pass
                    self._resolved = np.asarray(self._status)
                else:
                    self._start_fallback()
            if self._resolved is None:
                self._resolved = np.asarray(self._fb)  # blocks on fallback
        out = self._resolved
        return out.astype(dtype) if dtype is not None else out


def make_async_verifier(fallback_fn, rng: np.random.Generator | None = None,
                        rlc_fn=None, torsion_k: int | None = None,
                        weights_sharding=None, on_weights=None):
    """A drop-in for jit(verify_batch) with RLC fast-pass semantics.

    Returns fn(msgs, lens, sigs, pubs) -> RlcAsyncResult. fallback_fn is
    the compiled per-lane verifier used when the batch equation fails;
    rlc_fn overrides the jitted RLC pass (e.g. a shared compiled
    instance in tests). Fresh z and torsion-trial u weights are drawn
    per call (never baked into the graph), from os.urandom by default —
    the soundness contract (module docstring) requires CSPRNG entropy
    in production; pass rng only for deterministic tests. torsion_k is
    the subgroup-check trial count (default FD_RLC_TORSION_K or 64).

    weights_sharding (a mesh engine's (z_sharding, u_sharding)) places
    the weights straight from the host onto the chips that use them:
    z as (B, 32) by lane, u in the (K, 2, B) block layout
    parallel/mesh shards, each chip receiving its own lanes' columns.
    rlc_fn then takes u as (K, 2, B). Without it (one chip) both go to
    the default device as (B, 32) and (K, 2B). on_weights(ns), where
    given, is told the host time of each batch's draw and placement.
    """
    import jax

    rlc = rlc_fn if rlc_fn is not None else jax.jit(verify_batch_rlc)
    if torsion_k is None:
        torsion_k = flags.get_int("FD_RLC_TORSION_K")

    span = flight.span_maker()

    def fn(msgs, lens, sigs, pubs):
        bsz = msgs.shape[0]
        t0 = time.perf_counter_ns()
        with span("fd.rlc.weights"):
            if weights_sharding is None:
                z = jnp.asarray(fresh_z(bsz, rng))
                u = jnp.asarray(fresh_u(torsion_k, 2 * bsz, rng))
            else:
                z_sh, u_sh = weights_sharding
                z = jax.device_put(fresh_z(bsz, rng), z_sh)
                u = jax.device_put(
                    fresh_u(torsion_k, 2 * bsz, rng).reshape(
                        torsion_k, 2, bsz), u_sh)
        if on_weights is not None:
            on_weights(time.perf_counter_ns() - t0)
        with span("fd.rlc.launch"):
            out = rlc(msgs, lens, sigs, pubs, z, u)
        return RlcAsyncResult(out, fallback_fn, (msgs, lens, sigs, pubs),
                              span)

    return fn


# --------------------------------------------------------------------- #
# fdlint pass 7 (graph-audit) contracts — literals, read with
# ast.literal_eval by firedancer_tpu/lint/graphs.py, never imported.
# Grammar + rules: docs/GRAPHS.md.  `rlc_mono`/`pod_local`/`rlc_sharded`
# are derived graphs: thin wrappers proved by AST witness over the
# traced halves (lint/graphs.py:DERIVED_WITNESS), so their collective
# inventory is the composition of the halves' inventories.
# --------------------------------------------------------------------- #

GRAPH_CONTRACTS = {
    "rlc_local": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "madds": {"engine": "xla", "tolerance_pct": 2.0},
    },
    "rlc_tail": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
    },
    "pod_tail": {
        "collectives": {"all_gather": 1},
        "axes": ["dp"],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
    },
    "kernel_tail": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int16", "int32", "uint32", "uint8"],
        "vmem_mb": 64.0,
    },
    "rlc_mono": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "derived_from": ["rlc_local", "rlc_tail"],
    },
    "rlc_sharded": {
        "collectives": {"all_gather": 1},
        "axes": ["dp"],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "derived_from": ["rlc_local", "pod_tail"],
    },
    "pod_local": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "derived_from": ["rlc_local"],
    },
}
