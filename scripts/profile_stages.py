"""On-chip stage profile for the verify pipeline + VPU roofline probes.

Times each stage of verify_batch independently at the bench batch size so
optimization effort lands where the milliseconds are. Run on the real TPU:
    python scripts/profile_stages.py [batch]

`stage_attribution()` is the importable round-10 harness: it times the
verify pass's logical stages at an exact input shape with the SAME
flag-selected engines the production graph uses, and attributes the
leftover (total - sum of stages) to `glue` — the dsm_attrib.py-style
subtraction, generalized to the whole verify column. bench.py records
its dict (`stage_ms`) in every verify-ladder artifact, and the ROOFLINE
budget table is stated in its keys.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

import numpy as np

import jax
import jax.numpy as jnp


def bench_fn(fn, args, reps=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        out,
    )
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        out,
    )
    return (time.perf_counter() - t0) / reps


# Artifact schema: every key is always present (the fused_smoke lane
# pins this), `glue` is the subtraction residual and may be negative
# when stages overlap (that is signal — fusion working — not an error).
STAGE_KEYS = ("sha", "decompress", "sc", "rlc_combine", "msm", "glue")


def stage_attribution(msgs, lens, sigs, pubs, mode="rlc", reps=3,
                      warmup=1, total_ms=None, seed=7):
    """Per-stage ms attribution of the verify pass at this input shape.

    Times each logical stage as its own jitted launch with the engines
    the CURRENT flag environment selects (fused front-end, kernel vs
    XLA MSM, ...), then attributes `glue = total - sum(stages)` — the
    inter-stage cost (byte<->limb transposes, canonicalize chains,
    dispatch) that no per-stage timer can see, measured by subtraction
    exactly like scripts/dsm_attrib.py isolates the DSM's terms.

    Keys (STAGE_KEYS, all always present):
      sha         — SHA-512 over r||pub||msg. When the fused front-end
                    is active and the shape eligible this is the FUSED
                    kernel (compression + Barrett mod-L + the RLC
                    coefficient muls in one VMEM launch), and `sc` /
                    `rlc_combine` report 0.0 — their work is inside
                    this number (`fused: true` marks that).
      decompress  — the stacked (A, R) point decompression.
      sc          — sc_reduce64 of the digest (staged path only).
      rlc_combine — m = z*h, zs = z*s, u = sum zs (rlc mode; the
                    sc_sum stays outside the fused kernel and is
                    always charged here).
      msm         — rlc: the two Pippenger MSMs + torsion cert at the
                    flag-selected engine; direct: the double-scalarmult.
      glue        — total - sum(above); negative = overlap/fusion
                    across the stage boundaries the timers cut at.

    total_ms: the measured end-to-end ms/batch (bench.py passes its
    timed number so the residual is attributed against the production
    graph, not a re-measurement); None re-measures here.

    Returns {**{k: ms}, 'total': ms, 'fused': bool, 'engine': str,
    'mode': mode}. Works on any backend (CPU CI runs it at the smoke
    shape); on-chip it is the ROOFLINE per-stage table's source.
    """
    from firedancer_tpu.ops import curve25519 as ge
    from firedancer_tpu.ops import msm as msm_mod
    from firedancer_tpu.ops import sc25519 as sc
    from firedancer_tpu.ops.frontend_pallas import (
        frontend_choice,
        frontend_impl,
        frontend_rlc_auto,
        sha512_mod_l_auto,
        staged_coeff_muls,
    )
    from firedancer_tpu.ops.sha512 import sha512_batch_auto
    from firedancer_tpu.ops.verify import _dsm_auto, verify_batch
    from firedancer_tpu.ops.verify_rlc import (
        fresh_u, fresh_z, msm_engine, verify_batch_rlc,
    )

    msgs = jnp.asarray(msgs)
    lens = jnp.asarray(lens).astype(jnp.int32)
    sigs = jnp.asarray(sigs)
    pubs = jnp.asarray(pubs)
    bsz = msgs.shape[0]
    r_bytes, s_bytes = sigs[:, :32], sigs[:, 32:]
    hash_in = jnp.concatenate([r_bytes, pubs, msgs], axis=1)
    hlens = lens + 64
    from firedancer_tpu import flags

    rng = np.random.default_rng(seed)
    z = jnp.asarray(fresh_z(bsz, rng))
    u = jnp.asarray(fresh_u(flags.get_int("FD_RLC_TORSION_K"),
                            2 * bsz, rng))

    impl = frontend_impl()
    fused = frontend_choice(bsz) != "staged"
    engine = msm_engine() if mode == "rlc" else (
        "pallas" if impl == "pallas" else "xla")
    out = {k: 0.0 for k in STAGE_KEYS}

    def t(fn, args):
        return 1e3 * bench_fn(jax.jit(fn), args, reps=reps, warmup=warmup)

    # -- sha / sc / rlc_combine (the scalar front half) -----------------
    h_bytes = None
    if mode == "rlc":
        if fused:
            out["sha"] = t(
                lambda m, l, zz, ss: frontend_rlc_auto(m, l, zz, ss),
                (hash_in, hlens, z, s_bytes))
            _h, m_bytes, zs = frontend_rlc_auto(hash_in, hlens, z, s_bytes)
        else:
            h64 = sha512_batch_auto(hash_in, hlens)
            out["sha"] = t(sha512_batch_auto, (hash_in, hlens))
            out["sc"] = t(sc.sc_reduce64_auto, (h64,))
            h_bytes = sc.sc_reduce64_auto(h64)
            # The EXACT production dispatch (frontend_pallas.
            # staged_coeff_muls honors FD_SC_IMPL=pallas on TPU), so
            # the artifact times the engine the verify graph ran, not
            # a hardcoded XLA stand-in.
            out["rlc_combine"] = t(staged_coeff_muls,
                                   (z, h_bytes, s_bytes))
            m_bytes, zs = staged_coeff_muls(z, h_bytes, s_bytes)
        out["rlc_combine"] += t(sc.sc_sum, (zs,))
    else:
        if fused:
            out["sha"] = t(sha512_mod_l_auto, (hash_in, hlens))
        else:
            h64 = sha512_batch_auto(hash_in, hlens)
            out["sha"] = t(sha512_batch_auto, (hash_in, hlens))
            out["sc"] = t(sc.sc_reduce64_auto, (h64,))
        h_bytes = sha512_mod_l_auto(hash_in, hlens)

    # -- decompress (stacked A, R — both modes) --------------------------
    ar = jnp.concatenate([pubs, r_bytes], axis=0)
    out["decompress"] = t(lambda x: ge.decompress_auto(x), (ar,))
    both, _ = ge.decompress_auto(ar)[:2]
    a_point = tuple(c[:, :bsz] for c in both)
    r_point = tuple(c[:, bsz:] for c in both)

    # -- msm (rlc: 2 MSMs + torsion cert; direct: the DSM) ---------------
    if mode == "rlc":
        import functools

        plan = msm_mod.active_plan()
        if engine == "xla":
            msm_impl = functools.partial(msm_mod.msm, plan=plan)
            sub_impl = msm_mod.subgroup_check
        else:
            interp = engine == "interpret"
            msm_impl = functools.partial(msm_mod.msm_fast,
                                         interpret=interp, plan=plan)
            sub_impl = functools.partial(
                msm_mod.subgroup_check_fast, interpret=interp)
        neg_r = ge.point_neg(r_point)
        neg_a = ge.point_neg(a_point)
        out["msm"] = (
            t(lambda s_, p: msm_impl(s_, p, n_windows=msm_mod.WINDOWS_Z)[0],
              (z, neg_r))
            + t(lambda s_, p: msm_impl(
                s_, p, n_windows=msm_mod.WINDOWS_253)[0],
                (m_bytes, neg_a))
            + t(lambda p, u_: sub_impl(p, u_)[0], (both, u))
        )
    else:
        neg_a = ge.point_neg(a_point)
        out["msm"] = t(lambda h, a, s_: _dsm_auto()(h, a, s_),
                       (h_bytes, neg_a, s_bytes))

    # -- total + the subtraction residual --------------------------------
    if total_ms is None:
        if mode == "rlc":
            total_ms = 1e3 * bench_fn(
                jax.jit(verify_batch_rlc),
                (msgs, lens, sigs, pubs, z, u), reps=reps, warmup=warmup)
        else:
            total_ms = 1e3 * bench_fn(
                jax.jit(verify_batch), (msgs, lens, sigs, pubs),
                reps=reps, warmup=warmup)
    staged = sum(out[k] for k in STAGE_KEYS if k != "glue")
    out["glue"] = total_ms - staged
    out = {k: round(v, 3) for k, v in out.items()}
    out["total"] = round(total_ms, 3)
    out["fused"] = bool(fused)
    out["engine"] = engine
    out["mode"] = mode
    out.update(_decompress_attrib(2 * bsz))
    if mode == "rlc":
        out.update(_msm_attrib())
    return out


def _decompress_attrib(stacked_lanes):
    """PR-14 decompress attribution fields for the artifact: whether
    the Montgomery-batched engine served this shape, the ANALYTIC
    fe_invert-chain count (the 2B -> 2B/64 acceptance number — an
    exact function of FD_DECOMPRESS_BATCH, not a measurement), and
    the certified ladder schedule in effect. Validated by
    scripts/bench_log_check._validate_stage_ms."""
    from firedancer_tpu import flags
    from firedancer_tpu.ops import decompress_pallas as dp
    from firedancer_tpu.ops import fe25519 as fe_mod

    sched = flags.get_str("FD_DECOMPRESS_SQ_SCHED", "auto")
    if sched == "auto":
        for name, fn in fe_mod._SQ_SCHEDULES.items():
            if fn is fe_mod.fe_sq_sched():
                sched = name
                break
    return {
        "decompress_batched": bool(dp.batched_active(stacked_lanes)),
        "decompress_inversions": int(dp.inversion_count(stacked_lanes)),
        "decompress_sched": sched,
    }


def _msm_attrib(plan=None):
    """fd_msm2 MSM attribution fields for the artifact: the ACTIVE
    Pippenger schedule token (FD_MSM_PLAN / FD_MSM_WINDOW /
    FD_MSM_SIGNED resolution, or an explicit plan) and its signed-digit
    bit — so a stage_ms.msm number can never be read without knowing
    which schedule produced it. Validated by
    scripts/bench_log_check._validate_stage_ms."""
    from firedancer_tpu.msm_plan import plan_from_flags, plan_token

    if plan is None:
        plan = plan_from_flags()
    return {"msm_plan": plan_token(plan), "msm_signed": bool(plan.signed)}


def msm_stage_ms(batch, reps=1, warmup=1, seed=0, plan=None,
                 torsion_k=None):
    """Time JUST the MSM stage at the rlc verify shape — the two
    Pippenger MSMs (z*(-R) over WINDOWS_Z, h*(-A) over WINDOWS_253)
    plus the torsion certification, each as its own jitted launch under
    `plan` (None = the FD_MSM_* flags) — the cheap way to grade the
    fd_msm2 signed-digit cut at B=8192 on a CPU host, where a full
    stage_attribution re-times every other stage too. Engine dispatch
    follows FD_MSM_IMPL exactly like verify_rlc (xla graph off-TPU).
    RUNBOOK: 'Reading an msm-search rejection'.

    Uses _bench_util.bench (host-pull timing): the MSM tail is a
    doubling chain, timed until its result is on the host."""
    import functools

    from _bench_util import bench as _pull_bench
    from firedancer_tpu import flags
    from firedancer_tpu.msm_plan import TORSION_BUCKET_BITS
    from firedancer_tpu.ops import curve25519 as ge
    from firedancer_tpu.ops import msm as msm_mod
    from firedancer_tpu.ops.verify_rlc import fresh_u, fresh_z, msm_engine

    if plan is None:
        plan = msm_mod.active_plan()
    if torsion_k is None:
        torsion_k = flags.get_int("FD_RLC_TORSION_K")
    rng = np.random.RandomState(seed)
    host = np.random.default_rng(seed)
    z = jnp.asarray(fresh_z(batch, host))
    u = jnp.asarray(fresh_u(torsion_k, 2 * batch, host))
    scal253 = jnp.asarray(
        rng.randint(0, 128, (batch, 32), dtype=np.uint8))
    ybytes = jnp.asarray(
        rng.randint(0, 256, (batch, 32), dtype=np.uint8))
    pt, _ = jax.jit(ge.decompress)(ybytes)[:2]   # Z == 1 by construction
    both = tuple(jnp.concatenate([c, c], axis=1) for c in pt)

    engine = msm_engine()
    if engine == "xla":
        msm_impl = functools.partial(msm_mod.msm, plan=plan)
        if plan.lazy:
            sub_impl = functools.partial(
                msm_mod.subgroup_check,
                bucket_bits=TORSION_BUCKET_BITS, lazy=True)
        else:
            sub_impl = msm_mod.subgroup_check
    else:
        interp = engine == "interpret"
        msm_impl = functools.partial(msm_mod.msm_fast,
                                     interpret=interp, plan=plan)
        sub_impl = functools.partial(
            msm_mod.subgroup_check_fast, interpret=interp)

    def _t(fn, args):
        return 1e3 * _pull_bench(jax.jit(fn), args, reps=reps,
                                 warmup=warmup)

    ms = (
        _t(lambda s, p: msm_impl(s, p, n_windows=msm_mod.WINDOWS_Z)[0],
           (z, pt))
        + _t(lambda s, p: msm_impl(
            s, p, n_windows=msm_mod.WINDOWS_253)[0], (scal253, pt))
        + _t(lambda p, u_: sub_impl(p, u_)[0], (both, u))
    )
    rec = {"batch": batch, "torsion_k": int(torsion_k),
           "engine": engine, "msm_ms": round(ms, 3)}
    rec.update(_msm_attrib(plan))
    return rec


def decompress_stage_ms(batch, reps=3, warmup=1, seed=0):
    """Time JUST the decompress stage at the stacked (A, R) shape the
    verify pass presents (2*batch lanes through the flag-dispatched
    engine) — the cheap way to grade the PR-14 >= 2x cut at B=8192 on
    a CPU host, where a full stage_attribution would spend hours in
    the XLA-graph MSM. RUNBOOK: 'Re-measuring the decompress stage'."""
    from firedancer_tpu.ops import curve25519 as ge

    # _bench_util.bench, not the local bench_fn: this stage is a
    # ~252-square chain, timed until its result is on the host.
    from _bench_util import bench as _pull_bench

    rng = np.random.RandomState(seed)
    ar = jnp.asarray(
        rng.randint(0, 256, (2 * batch, 32), dtype=np.uint8))
    ms = 1e3 * _pull_bench(jax.jit(lambda x: ge.decompress_auto(x)),
                           (ar,), reps=reps, warmup=warmup)
    rec = {"batch": batch, "stacked_lanes": 2 * batch,
           "decompress_ms": round(ms, 3)}
    rec.update(_decompress_attrib(2 * batch))
    return rec


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    dev = jax.devices()[0]
    print(f"device={dev} batch={batch}")

    from firedancer_tpu.ops import curve25519 as ge
    from firedancer_tpu.ops import sc25519 as sc
    from firedancer_tpu.ops.sha512 import sha512_batch

    rng = np.random.RandomState(0)
    msgs = jnp.asarray(rng.randint(0, 256, (batch, 256), dtype=np.uint8))
    lens = jnp.full((batch,), 256, jnp.int32)
    ybytes = jnp.asarray(rng.randint(0, 256, (batch, 32), dtype=np.uint8))
    sbytes = jnp.asarray(rng.randint(0, 128, (batch, 32), dtype=np.uint8))
    limbs = jnp.asarray(rng.randint(0, 256, (32, batch), dtype=np.int32))

    # --- roofline probes -------------------------------------------------
    n_ops = 64
    def imul_chain(x):
        acc = x
        for _ in range(n_ops):
            acc = acc * x + x
        return acc

    def fmul_chain(x):
        acc = x
        for _ in range(n_ops):
            acc = acc * x + x
        return acc

    xi = jnp.asarray(rng.randint(0, 1 << 10, (32, batch), dtype=np.int32))
    xf = xi.astype(jnp.float32)
    t = bench_fn(jax.jit(imul_chain), (xi,))
    rate = n_ops * 32 * batch / t / 1e12
    print(f"int32 mul+add chain: {t*1e3:8.3f} ms  {rate:.3f} Tmac/s")
    t = bench_fn(jax.jit(fmul_chain), (xf,))
    rate = n_ops * 32 * batch / t / 1e12
    print(f"f32   mul+add chain: {t*1e3:8.3f} ms  {rate:.3f} Tmac/s")

    # --- field op costs --------------------------------------------------
    from firedancer_tpu.ops import fe25519 as fe

    def mulchain(a, b):
        for _ in range(8):
            a = fe.fe_mul(a, b)
        return a

    t = bench_fn(jax.jit(mulchain), (limbs, limbs))
    print(f"fe_mul (XLA) x8:     {t*1e3:8.3f} ms  ({t/8*1e6:.1f} us/mul)")

    # --- stages ----------------------------------------------------------
    t = bench_fn(jax.jit(sha512_batch), (msgs, lens))
    print(f"sha512 (256B):       {t*1e3:8.3f} ms")

    t = bench_fn(jax.jit(lambda y: ge.decompress(y)), (ybytes,))
    print(f"decompress:          {t*1e3:8.3f} ms")

    pt, _ = jax.jit(ge.decompress)(ybytes)
    pt = tuple(jnp.asarray(c) for c in pt)

    from firedancer_tpu.ops.dsm_pallas import double_scalarmult_pallas

    t = bench_fn(
        jax.jit(double_scalarmult_pallas), (sbytes, pt, sbytes)
    )
    print(f"dsm (pallas):        {t*1e3:8.3f} ms")

    t = bench_fn(jax.jit(ge.compress), (pt,))
    print(f"compress:            {t*1e3:8.3f} ms")

    t = bench_fn(jax.jit(sc.sc_reduce64),
                 (jnp.concatenate([sbytes, sbytes], axis=1),))
    print(f"sc_reduce64:         {t*1e3:8.3f} ms")

    # --- RLC-mode stages (round-3: where the >=500k/s budget goes) ------
    from firedancer_tpu.ops import msm as msm_mod
    from firedancer_tpu.ops.verify_rlc import fresh_u, fresh_z

    host_rng = np.random.default_rng(7)
    z = jnp.asarray(fresh_z(batch, host_rng))
    u = jnp.asarray(fresh_u(64, 2 * batch, host_rng))
    both = tuple(jnp.concatenate([c, c], axis=1) for c in pt)  # 2B points

    t = bench_fn(
        jax.jit(lambda s, p: msm_mod.msm(
            s, p, n_windows=msm_mod.WINDOWS_Z)[0]),
        (z, pt),
    )
    print(f"msm z*(-R) [18w]:    {t*1e3:8.3f} ms")

    scal253 = jnp.asarray(
        np.concatenate([np.asarray(sbytes), np.zeros((batch, 0), np.uint8)],
                       axis=1))
    t = bench_fn(
        jax.jit(lambda s, p: msm_mod.msm(
            s, p, n_windows=msm_mod.WINDOWS_253)[0]),
        (scal253, pt),
    )
    print(f"msm h*(-A) [37w]:    {t*1e3:8.3f} ms")

    t = bench_fn(jax.jit(msm_mod.subgroup_check), (both, u))
    print(f"torsion cert (K=64): {t*1e3:8.3f} ms")

    # --- round-3 kernel suite -------------------------------------------
    from firedancer_tpu.ops.curve_pallas import (
        compress_pallas,
        decompress_pallas,
    )
    from firedancer_tpu.ops.sc_pallas import sc_mul_pallas, sc_reduce64_pallas
    from firedancer_tpu.ops.sha512_pallas import sha512_batch_pallas

    t = bench_fn(jax.jit(sha512_batch_pallas), (msgs, lens))
    print(f"sha512 kernel:       {t*1e3:8.3f} ms")
    t = bench_fn(jax.jit(sc_reduce64_pallas),
                 (jnp.concatenate([sbytes, sbytes], axis=1),))
    print(f"sc_reduce kernel:    {t*1e3:8.3f} ms")
    t = bench_fn(jax.jit(sc_mul_pallas), (sbytes, sbytes))
    print(f"sc_mul kernel:       {t*1e3:8.3f} ms")
    t = bench_fn(jax.jit(decompress_pallas), (ybytes,))
    print(f"decompress kernel:   {t*1e3:8.3f} ms")
    t = bench_fn(jax.jit(compress_pallas), (pt,))
    print(f"compress kernel:     {t*1e3:8.3f} ms")
    t = bench_fn(
        jax.jit(lambda p, u_: msm_mod.subgroup_check_fast(p, u_)), (both, u)
    )
    print(f"torsion cert kernel: {t*1e3:8.3f} ms")
    t = bench_fn(
        jax.jit(lambda s, p: msm_mod.msm_fast(
            s, p, n_windows=msm_mod.WINDOWS_253)[0]),
        (scal253, pt),
    )
    print(f"msm_fast [37w]:      {t*1e3:8.3f} ms")
    # staging alone (sort + slot table): how much of msm_fast is XLA.
    t = bench_fn(
        jax.jit(lambda s: msm_mod._staging_indices(
            s, msm_mod.WINDOWS_253, batch, 140)[0]),
        (scal253,),
    )
    print(f"msm staging (sort):  {t*1e3:8.3f} ms")

    # --- round-10 fused front-end ---------------------------------------
    from firedancer_tpu.ops.frontend_pallas import (
        frontend_eligible,
        frontend_rlc_pallas,
        sha512_mod_l_pallas,
    )

    hash_in = jnp.concatenate([sbytes, ybytes, msgs], axis=1)
    hlens = lens + 64
    if frontend_eligible(batch):
        t = bench_fn(
            jax.jit(sha512_mod_l_pallas), (hash_in, hlens))
        print(f"fused sha+mod-L:     {t*1e3:8.3f} ms")
        t = bench_fn(
            jax.jit(frontend_rlc_pallas), (hash_in, hlens, z, sbytes))
        print(f"fused rlc frontend:  {t*1e3:8.3f} ms")
    else:
        print(f"fused frontend:      ineligible at B={batch}")


def attrib_main():
    """JSON per-stage attribution at the bench shape (both modes):
    python scripts/profile_stages.py --attrib [batch [msg_len]]."""
    import json

    argv = [a for a in sys.argv[1:] if not a.startswith("-")]
    batch = int(argv[0]) if argv else 8192
    msg_len = int(argv[1]) if len(argv) > 1 else 192
    rng = np.random.RandomState(0)
    msgs = rng.randint(0, 256, (batch, msg_len), dtype=np.uint8)
    lens = np.full((batch,), msg_len, np.int32)
    sigs = rng.randint(0, 256, (batch, 64), dtype=np.uint8)
    sigs[:, 63] &= 0x0F                    # keep s in range
    pubs = rng.randint(0, 256, (batch, 32), dtype=np.uint8)
    for mode in ("rlc", "direct"):
        rec = stage_attribution(msgs, lens, sigs, pubs, mode=mode)
        rec["batch"], rec["msg_len"] = batch, msg_len
        print(json.dumps(rec))


def decompress_main():
    """JSON decompress-stage-only timing:
    python scripts/profile_stages.py --decompress [batch]."""
    import json

    argv = [a for a in sys.argv[1:] if not a.startswith("-")]
    batch = int(argv[0]) if argv else 8192
    print(json.dumps(decompress_stage_ms(batch)))


def msm_main():
    """JSON MSM-stage-only timing under the active (or given) plan:
    python scripts/profile_stages.py --msm [batch [plan_token]]."""
    import json

    argv = [a for a in sys.argv[1:] if not a.startswith("-")]
    batch = int(argv[0]) if argv else 8192
    plan = None
    if len(argv) > 1:
        from firedancer_tpu.msm_plan import parse_plan

        plan = parse_plan(argv[1])
    print(json.dumps(msm_stage_ms(batch, plan=plan)))


if __name__ == "__main__":
    if "--attrib" in sys.argv:
        attrib_main()
    elif "--decompress" in sys.argv:
        decompress_main()
    elif "--msm" in sys.argv:
        msm_main()
    else:
        main()
