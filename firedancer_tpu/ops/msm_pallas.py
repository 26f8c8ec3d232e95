"""Pallas TPU kernels for the Pippenger MSM (ops/msm.py fast path).

Two kernels replace the HBM-streamed XLA inner loops:

1. **Bucket fill** (`fill_buckets_pallas`): the (windows x buckets) lane
   grid lives in VMEM scratch across a sequential grid; every grid step
   streams one round's gathered points from HBM and performs ONE unified
   mixed point-add across all lanes. Points arrive in precomputed niels
   form (y+x, y-x, 2d*t, Z==1), cutting the add to 7 field muls — the
   same precomputation the reference bakes into its constant base tables
   (ref/fd_ed25519_ge.c precomp), applied here to runtime points.
   Invalid slots are staged as the niels identity (1, 1, 0), which the
   unified formulas absorb exactly — no masks in the hot loop.

2. **Bucket aggregation** (`aggregate_buckets_pallas`): sum_b b * S_b
   per window via the classic two-running-sums walk (b = 255 .. 1),
   sequential over the bucket axis but vectorized across windows on the
   lane axis — 510 point-adds on (32, nw)-lane tiles, microseconds in
   VMEM versus milliseconds if XLA streamed each through HBM.

The surrounding sort/gather staging and the final cross-window Horner
stay in XLA (gathers and fused elementwise chains are what XLA is good
at). See ops/msm.py for the algorithm-level description.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fe25519 as fe

NLIMBS = fe.NLIMBS


def _madd_niels(p, q_niels):
    """Unified mixed add: p extended (x, y, z, t) + q in niels form
    (yp = y+x, ym = y-x, t2d = 2d*t), q.Z == 1. 7 field muls."""
    x1, y1, z1, t1 = p
    yp2, ym2, t2d2 = q_niels
    a = fe.fe_mul_kernel(fe.fe_sub(y1, x1), ym2)
    b = fe.fe_mul_kernel(fe.fe_add(y1, x1), yp2)
    c = fe.fe_mul_kernel(t1, t2d2)
    d = fe.fe_add(z1, z1)
    e = fe.fe_sub(b, a)
    f = fe.fe_sub(d, c)
    g = fe.fe_add(d, c)
    h = fe.fe_add(b, a)
    return (fe.fe_mul_kernel(e, f), fe.fe_mul_kernel(g, h),
            fe.fe_mul_kernel(f, g), fe.fe_mul_kernel(e, h))


def _point_add_ext(p, q, d2):
    """Unified extended add (9 muls); d2 = limbs of 2d, (NLIMBS, 1)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe.fe_mul_kernel(fe.fe_sub(y1, x1), fe.fe_sub(y2, x2))
    b = fe.fe_mul_kernel(fe.fe_add(y1, x1), fe.fe_add(y2, x2))
    c = fe.fe_mul_kernel(fe.fe_mul_kernel(t1, t2), d2)
    zz = fe.fe_mul_kernel(z1, z2)
    d = fe.fe_add(zz, zz)
    e = fe.fe_sub(b, a)
    f = fe.fe_sub(d, c)
    g = fe.fe_add(d, c)
    h = fe.fe_add(b, a)
    return (fe.fe_mul_kernel(e, f), fe.fe_mul_kernel(g, h),
            fe.fe_mul_kernel(f, g), fe.fe_mul_kernel(e, h))


def _identity4(lanes):
    one = (jax.lax.broadcasted_iota(jnp.int32, (NLIMBS, lanes), 0) == 0)
    one = one.astype(jnp.int32)
    zero = jnp.zeros((NLIMBS, lanes), jnp.int32)
    return (zero, one, one, zero)


def fill_buckets_pallas(rounds, lane_tile: int = 2048,
                        interpret: bool = False):
    """Accumulate staged niels rounds into bucket points.

    rounds: (R, 96, L) int16 — round r's point for every (window,
    bucket) lane as three 32-limb blocks yp | ym | t2d, identity-staged
    where the slot is empty (ops/msm._stage_niels). The three blocks are
    read through their own BlockSpec index maps, so the kernel sees the
    same three (1, 32, lane_tile) tiles a round it always did.
    Returns extended bucket points (x, y, z, t), each (32, L).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rounds, _, n_lanes = rounds.shape
    if n_lanes % lane_tile:
        # fd_msm2 plan grids (windows x buckets, buckets not a power of
        # two for signed-magnitude plans) are staged to a multiple of
        # 256 lanes but rarely divide 2048: pick the largest divisor of
        # n_lanes that is a multiple of 128 and <= the requested tile,
        # falling back to the whole array as one tile (interpret/CPU).
        lane_tile = max(
            (t for t in range(128, min(lane_tile, n_lanes) + 1, 128)
             if n_lanes % t == 0),
            default=n_lanes,
        )
    n_tiles = n_lanes // lane_tile

    def kern(ypr, ymr, t2dr, ox, oy, oz, ot, xs, ys, zs, ts):
        ri = pl.program_id(1)

        @pl.when(ri == 0)
        def _init():
            x0, y0, z0, t0 = _identity4(lane_tile)
            xs[...] = x0
            ys[...] = y0
            zs[...] = z0
            ts[...] = t0

        p = (xs[...], ys[...], zs[...], ts[...])
        q = (ypr[0].astype(jnp.int32), ymr[0].astype(jnp.int32),
             t2dr[0].astype(jnp.int32))   # staged rounds ride HBM as int16
        x, y, z, t = _madd_niels(p, q)
        xs[...] = x
        ys[...] = y
        zs[...] = z
        ts[...] = t

        @pl.when(ri == n_rounds - 1)
        def _emit():
            ox[...] = x
            oy[...] = y
            oz[...] = z
            ot[...] = t

    def spec_in(k):
        return pl.BlockSpec((1, NLIMBS, lane_tile),
                            lambda i, r: (r, k, i))

    spec_out = pl.BlockSpec((NLIMBS, lane_tile), lambda i, r: (0, i))
    out_shape = jax.ShapeDtypeStruct((NLIMBS, n_lanes), jnp.int32)
    return pl.pallas_call(
        kern,
        grid=(n_tiles, n_rounds),
        in_specs=[spec_in(k) for k in range(3)],
        out_specs=[spec_out] * 4,
        out_shape=[out_shape] * 4,
        scratch_shapes=[
            pltpu.VMEM((NLIMBS, lane_tile), jnp.int32) for _ in range(4)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(rounds, rounds, rounds)


def mul_by_group_order_pallas(pt, d2_col, bits_col, interpret: bool = False):
    """[L]P over a (32, K)-lane point batch, fully in VMEM.

    The XLA version (msm._mul_by_group_order) is a 252-step lax.scan
    whose per-step while-loop overhead dwarfs its (32, K) arithmetic;
    here the double/conditional-add ladder runs inside one kernel with
    the point state resident in VMEM. L is public (vartime is fine) but
    the ladder is still branch-free: the conditional add is an
    arithmetic select so every lane runs the identical op stream.

    pt: (X, Y, Z, T) of (32, K) limbs. d2_col: (32, 1) limbs of 2d.
    bits_col: (256, 1) int32 — bits of L, MSB-first starting at index 0
    (bits_col[0] is the leading 1 bit), zero-padded after index
    n_bits-1 (the padding is never read; the loop bound is static).
    Returns (X, Y, Z, T) of (32, K) limbs of [L]P.
    """
    from jax.experimental import pallas as pl
    from firedancer_tpu.ops import sc25519 as sc

    n_bits = sc.L.bit_length()
    k = pt[0].shape[1]
    kpad = (-k) % 128
    if kpad:
        pt = tuple(jnp.pad(c, ((0, 0), (0, kpad))) for c in pt)
    lanes = k + kpad

    def kern(px, py, pz, pt_, d2r, bits, ox, oy, oz, ot):
        d2 = d2r[...]
        base = (px[...], py[...], pz[...], pt_[...])

        def body(i, r):
            r = _point_double_ext(r)
            added = _point_add_ext(r, base, d2)
            # Scalar select from SMEM: a vector (1, 1) bit would need a
            # sublane+lane broadcast, which Mosaic rejects; a scalar
            # broadcasts freely into the arithmetic select.
            sel = bits[i]
            return tuple(sel * a + (1 - sel) * c
                         for a, c in zip(added, r))

        # bits[0] is the leading 1: init = P, then n_bits-1 = 252
        # double/(conditional-)add steps.
        r = jax.lax.fori_loop(1, n_bits, body, base)
        ox[...] = r[0]
        oy[...] = r[1]
        oz[...] = r[2]
        ot[...] = r[3]

    from jax.experimental.pallas import tpu as pltpu

    spec_fe = pl.BlockSpec((NLIMBS, lanes), lambda: (0, 0))
    spec_d2 = pl.BlockSpec((NLIMBS, 1), lambda: (0, 0))
    spec_bits = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = jax.ShapeDtypeStruct((NLIMBS, lanes), jnp.int32)
    x, y, z, t = pl.pallas_call(
        kern,
        in_specs=[spec_fe] * 4 + [spec_d2, spec_bits],
        out_specs=[spec_fe] * 4,
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(*pt, d2_col, bits_col.reshape(-1))
    if kpad:
        x, y, z, t = (c[:, :k] for c in (x, y, z, t))
    return (x, y, z, t)


def _point_double_ext(p):
    """dbl-2008-hwcd a=-1 with fe_mul_unrolled (kernel-safe)."""
    from .pow_pallas import _sq

    x1, y1, z1, _ = p
    a = _sq(x1)
    b = _sq(y1)
    zz = _sq(z1)
    c = fe.fe_add(zz, zz)
    d_ = fe.fe_neg(a)
    e = fe.fe_sub(fe.fe_sub(_sq(fe.fe_add(x1, y1)), a), b)
    g = fe.fe_add(d_, b)
    f = fe.fe_sub(g, c)
    h = fe.fe_sub(d_, b)
    return (fe.fe_mul_kernel(e, f), fe.fe_mul_kernel(g, h),
            fe.fe_mul_kernel(f, g), fe.fe_mul_kernel(e, h))


def window_horner_pallas(w_res, d2_col, n_windows: int,
                         interpret: bool = False, w_bits: int = 7):
    """Cross-window Horner combine, fully in VMEM: the 2^(7t)-weighted
    sum of the per-window points, MSB-first (msm._window_horner is the
    XLA reference — an (n_windows-1)-step lax.scan whose per-step
    overhead on TPU dwarfs its (32, 1)-lane arithmetic).

    w_res: (X, Y, Z, T) of (32, nw) limbs, window t in column t.
    Returns (32, 1)-column points. Window columns are pre-broadcast in
    XLA to (nw*32, 128) row blocks so the in-kernel loop reads window t
    with one dynamic sublane-block slice (the dsm window-read pattern;
    dynamic LANE slicing is what Mosaic cannot do).
    """
    from jax.experimental import pallas as pl

    nw = n_windows

    def prep(c):
        # (32, nw) -> (nw*32, 128): window-major rows, lane-broadcast.
        return jnp.broadcast_to(
            jnp.transpose(c[:, :nw], (1, 0)).reshape(nw * NLIMBS, 1),
            (nw * NLIMBS, 128),
        )

    def kern(wx, wy, wz, wt, d2r, ox, oy, oz, ot):
        d2 = d2r[...]

        def col(j):
            return tuple(
                w[pl.ds(j * NLIMBS, NLIMBS), :] for w in (wx, wy, wz, wt)
            )

        def body(i, r):
            for _ in range(w_bits):
                r = _point_double_ext(r)
            return _point_add_ext(r, col(nw - 2 - i), d2)

        r = jax.lax.fori_loop(0, nw - 1, body, col(nw - 1))
        ox[...] = r[0]
        oy[...] = r[1]
        oz[...] = r[2]
        ot[...] = r[3]

    spec_w = pl.BlockSpec((nw * NLIMBS, 128), lambda: (0, 0))
    spec_d2 = pl.BlockSpec((NLIMBS, 1), lambda: (0, 0))
    spec_out = pl.BlockSpec((NLIMBS, 128), lambda: (0, 0))
    out_shape = jax.ShapeDtypeStruct((NLIMBS, 128), jnp.int32)
    x, y, z, t = pl.pallas_call(
        kern,
        in_specs=[spec_w] * 4 + [spec_d2],
        out_specs=[spec_out] * 4,
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(*(prep(c) for c in w_res), d2_col)
    return (x[:, :1], y[:, :1], z[:, :1], t[:, :1])


def aggregate_buckets_pallas(buckets, d2_col, interpret: bool = False):
    """sum_b b * S_b per window, running-sums walk (b = 255 .. 1).

    buckets: (x, y, z, t) each (n_buckets, 32, nw_pad) — bucket-major;
    the grid walks buckets top-down, streaming one (32, nw_pad) slice
    per step (auto double-buffered), with the two running sums (S =
    suffix bucket sum, T = the weighted answer) in VMEM scratch. Bucket
    0 is never visited (digit 0 contributes identity by construction).
    d2_col: (32, 1) int32 limbs of 2d (kernels can't capture constants).
    Returns (x, y, z, t) each (32, nw_pad).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_buckets, _, nw = buckets[0].shape
    n_steps = n_buckets - 1                    # buckets top .. 1

    def kern(bx, by, bz, bt, d2r, ox, oy, oz, ot, *scr):
        g = pl.program_id(0)
        d2 = d2r[...]
        q = (bx[0], by[0], bz[0], bt[0])
        sx, sy, sz, st_, tx, ty, tz, tt = scr

        @pl.when(g == 0)
        def _init():
            for r, v in zip(scr, q + q):
                r[...] = v

        @pl.when(g > 0)
        def _step():
            s = _point_add_ext((sx[...], sy[...], sz[...], st_[...]), q, d2)
            t_ = _point_add_ext((tx[...], ty[...], tz[...], tt[...]), s, d2)
            for r, v in zip(scr, s + t_):
                r[...] = v

        @pl.when(g == n_steps - 1)
        def _emit():
            ox[...] = tx[...]
            oy[...] = ty[...]
            oz[...] = tz[...]
            ot[...] = tt[...]

    spec_b = pl.BlockSpec(
        (1, NLIMBS, nw), lambda g: (n_buckets - 1 - g, 0, 0)
    )
    spec_d2 = pl.BlockSpec((NLIMBS, 1), lambda g: (0, 0))
    spec_out = pl.BlockSpec((NLIMBS, nw), lambda g: (0, 0))
    out_shape = jax.ShapeDtypeStruct((NLIMBS, nw), jnp.int32)
    return pl.pallas_call(
        kern,
        grid=(n_steps,),
        in_specs=[spec_b] * 4 + [spec_d2],
        out_specs=[spec_out] * 4,
        out_shape=[out_shape] * 4,
        scratch_shapes=[
            pltpu.VMEM((NLIMBS, nw), jnp.int32) for _ in range(8)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*buckets, d2_col)
