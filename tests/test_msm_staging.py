"""Bucket-slot tables of the MSM fill: ops/msm._staging_from_digits.

The table is built with a two-operand sort, a compare-reduce for the
bucket starts and a row gather of the sorted lane order. The oracle
below is the argsort / take_along_axis / searchsorted construction it
replaced, kept verbatim: both must return identical (idx, ok) for every
digit array, including overflowing buckets and lane counts that are not
a multiple of the row width.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firedancer_tpu.ops import msm


def _oracle(d, bsz, max_rounds, n_buckets):
    nw = d.shape[0]
    order = jnp.argsort(d, axis=1, stable=True)
    sorted_d = jnp.take_along_axis(d, order, axis=1)
    buckets = jnp.arange(n_buckets, dtype=jnp.int32)
    starts = jax.vmap(
        lambda row: jnp.searchsorted(row, buckets, side="left")
    )(sorted_d)
    ends = jnp.concatenate(
        [starts[:, 1:], jnp.full((nw, 1), bsz, starts.dtype)], axis=1
    )
    counts = ends - starts
    ok = jnp.max(jnp.where(buckets[None, :] > 0, counts, 0)) <= max_rounds
    r_iota = jnp.arange(max_rounds, dtype=jnp.int32)
    pos = starts[:, :, None] + r_iota[None, None, :]
    valid = (r_iota[None, None, :] < counts[:, :, None]) & (
        buckets[None, :, None] > 0
    )
    pos_flat = jnp.clip(pos.reshape(nw, -1), 0, bsz - 1)
    idx = jnp.take_along_axis(order, pos_flat, axis=1).reshape(
        nw, n_buckets, max_rounds
    )
    idx = jnp.where(valid, idx, -1)
    return idx, ok


def _uniform(rng, nw, bsz, nb):
    return rng.integers(0, nb, size=(nw, bsz), dtype=np.int32)


def _overflow(rng, nw, bsz, nb):
    # One live bucket of one row gets max_rounds + 1 points.
    d = _uniform(rng, nw, bsz, nb)
    rounds = msm._default_rounds(bsz, nb)
    d[nw // 2, :rounds + 1] = nb - 1
    d[nw // 2, rounds + 1:] = 0
    return d


# (nw, B, n_buckets, max_rounds or None for the engine's default, digits)
CASES = {
    # The three grids of the rlc program, at reduced B.
    "z_grid": (msm.WINDOWS_Z, 512, 128, None, _uniform),
    "w253_grid_odd_lanes": (msm.WINDOWS_253, 513, 128, None, _uniform),
    "torsion_grid": (16, 2 * 512, 32, None, _uniform),
    "all_zero": (msm.WINDOWS_Z, 256, 128, None,
                 lambda rng, nw, b, nb: np.zeros((nw, b), np.int32)),
    "all_one_bucket": (4, 96, 32, None,
                       lambda rng, nw, b, nb: np.full((nw, b), 7, np.int32)),
    "bucket_past_max_rounds": (8, 512, 32, None, _overflow),
    "lanes_not_row_multiple": (4, 100, 32, None, _uniform),
    "lanes_below_one_row": (3, 17, 32, None, _uniform),
    "max_rounds_equals_lanes": (5, 64, 32, 64, _uniform),
    # Signed plans stage |d| on an odd magnitude grid of 2^(w-1) + 1.
    "signed_magnitude_grid": (10, 300, 65, None, _uniform),
    # No caller passes such digits; the table still matches bit for bit.
    "digits_out_of_range": (6, 160, 32, None,
                            lambda rng, nw, b, nb: rng.integers(
                                -3, nb + 3, size=(nw, b), dtype=np.int32)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seed", [0, 2147483901])
def test_staging_matches_sort_gather_oracle(case, seed):
    nw, bsz, nb, rounds, make = CASES[case]
    if rounds is None:
        rounds = msm._default_rounds(bsz, nb)
    d = jnp.asarray(make(np.random.default_rng(seed), nw, bsz, nb))
    idx, ok = jax.jit(msm._staging_from_digits, static_argnums=(1, 2, 3))(
        d, bsz, rounds, nb)
    want_idx, want_ok = jax.jit(_oracle, static_argnums=(1, 2, 3))(
        d, bsz, rounds, nb)
    assert idx.dtype == want_idx.dtype == jnp.int32
    assert idx.shape == want_idx.shape == (nw, nb, rounds)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    assert bool(ok) == bool(want_ok)
    if case == "bucket_past_max_rounds":
        assert not bool(ok)
    if case in ("z_grid", "all_zero", "max_rounds_equals_lanes"):
        assert bool(ok)


def test_staging_table_routes_every_live_lane_once():
    # Independent of the oracle: each lane with a nonzero digit sits in
    # exactly one slot of its own bucket, in lane order within it.
    nw, bsz, nb = 6, 200, 32
    rng = np.random.default_rng(7)
    d = _uniform(rng, nw, bsz, nb)
    rounds = msm._default_rounds(bsz, nb)
    idx, ok = msm._staging_from_digits(jnp.asarray(d), bsz, rounds, nb)
    idx = np.asarray(idx)
    assert bool(ok)
    for t in range(nw):
        for b in range(nb):
            lanes = idx[t, b][idx[t, b] >= 0]
            want = np.flatnonzero(d[t] == b) if b else np.array([], int)
            np.testing.assert_array_equal(lanes, want)


# --------------------------------------------------------------------- #
# Niels round staging: ops/msm._stage_niels.
#
# The staging gathers one int16 row per slot from a table of the points'
# yp | ym | t2d limbs (negated forms and identities folded into the row
# index). The oracle below is the column-gather formula it replaced:
# three gathers of int32 columns, selects for empty slots and signs, a
# convert to int16 and identity pad lanes. Both must stage the same
# limbs bit for bit.
# --------------------------------------------------------------------- #

from firedancer_tpu.ops import fe25519 as fe  # noqa: E402


def _stage_oracle(niels, idx, max_rounds, lanes, bsz, neg, lane_pad):
    yp, ym, t2d = niels
    sel = jnp.transpose(idx, (2, 0, 1)).reshape(max_rounds * lanes)
    m = (sel >= 0)[None, :]
    safe = jnp.clip(sel, 0, bsz - 1)
    one0 = (jnp.arange(fe.NLIMBS, dtype=jnp.int32) == 0)[:, None]
    gyp = jnp.where(m, yp[:, safe], one0.astype(jnp.int32))
    gym = jnp.where(m, ym[:, safe], one0.astype(jnp.int32))
    gtd = jnp.where(m, t2d[:, safe], 0)
    if neg is not None:
        ng = jnp.transpose(neg, (2, 0, 1)).reshape(
            max_rounds * lanes)[None, :]
        gyp, gym = jnp.where(ng, gym, gyp), jnp.where(ng, gyp, gym)
        gtd = jnp.where(ng, -gtd, gtd)

    def stage(g, ident_one):
        g = jnp.transpose(
            g.reshape(fe.NLIMBS, max_rounds, lanes), (1, 0, 2)
        ).astype(jnp.int16)
        if lane_pad:
            g = jnp.pad(g, ((0, 0), (0, 0), (0, lane_pad)))
            if ident_one:
                g = g.at[:, 0, lanes:].set(1)
        return g

    return stage(gyp, True), stage(gym, True), stage(gtd, False)


def _niels_limbs(rng, bsz):
    # Staged limbs obey |limb| <= 1024 (the lazy-carry invariant).
    return tuple(jnp.asarray(rng.integers(-1024, 1025, size=(32, bsz),
                                          dtype=np.int32))
                 for _ in range(3))


def _affine_limbs(rng, bsz):
    # (X, Y, Z = 1, T) with reduced 8-bit limbs; only X, Y, T are read.
    x, y, t = (rng.integers(0, 256, size=(32, bsz), dtype=np.int32)
               for _ in range(3))
    one = np.zeros((32, bsz), np.int32)
    one[0] = 1
    return tuple(jnp.asarray(c) for c in (x, y, one, t))


# (nw, B, n_buckets, signed, lane_pad, all_empty, niels given)
STAGE_CASES = {
    "z_grid_unsigned": (msm.WINDOWS_Z, 256, 128, False, 0, False, True),
    "torsion_grid_5bit": (16, 512, 32, False, 0, False, True),
    "signed_grid_neg_and_pad": (10, 300, 65, True, (-650) % 256, False,
                                True),
    "all_slots_empty": (4, 128, 32, True, 128, True, True),
    "niels_from_points": (6, 200, 32, False, 0, False, False),
}


@pytest.mark.parametrize("case", sorted(STAGE_CASES))
def test_stage_niels_matches_column_gather_oracle(case):
    nw, bsz, nb, signed, lane_pad, empty, given = STAGE_CASES[case]
    rng = np.random.default_rng(2147483926)
    rounds = msm._default_rounds(bsz, nb, signed=signed)
    lanes = nw * nb
    s = rng.integers(-(nb - 1) if signed else 0, nb, size=(nw, bsz),
                     dtype=np.int32)
    idx, _ = msm._staging_from_digits(jnp.asarray(np.abs(s)), bsz, rounds,
                                      nb)
    if empty:
        idx = jnp.full_like(idx, -1)
    neg = (msm._neg_table(jnp.asarray(s < 0), idx, bsz) if signed
           else None)
    if given:
        niels, points = _niels_limbs(rng, bsz), None
    else:
        points, niels = _affine_limbs(rng, bsz), None
        x, y, _, t = points
        want_niels = (fe.fe_add(y, x), fe.fe_sub(y, x),
                      fe.fe_mul(t, fe.FE_D2))
    got = jax.jit(msm._stage_niels, static_argnums=(2, 3, 4, 7))(
        points, idx, rounds, lanes, bsz, niels, neg, lane_pad)
    want = _stage_oracle(niels if given else want_niels, idx, rounds,
                         lanes, bsz, neg, lane_pad)
    assert got.dtype == jnp.int16
    assert got.shape == (rounds, 3 * fe.NLIMBS, lanes + lane_pad)
    for k, w in enumerate(want):
        np.testing.assert_array_equal(
            np.asarray(got[:, k * fe.NLIMBS:(k + 1) * fe.NLIMBS]),
            np.asarray(w))
    if signed and not empty:
        assert bool(jnp.any(neg))


def _affine_lanes(pt, n):
    """(X, Y, Z, ...) limb columns -> the first n lanes as affine ints."""
    x, y, z = (fe.limbs_to_int(np.asarray(c))[:n] for c in pt[:3])
    return [(int(a) * pow(int(c), fe.P - 2, fe.P) % fe.P,
             int(b) * pow(int(c), fe.P - 2, fe.P) % fe.P)
            for a, b, c in zip(x, y, z)]


@pytest.mark.parametrize("which", ["msm", "subgroup"])
def test_fast_partials_match_xla_partials(which):
    """The staged rounds feed the fill kernel (interpret mode): the
    kernel-path partials equal the XLA-path partials as group elements,
    per window (msm) and per trial (subgroup)."""
    from firedancer_tpu.ballet import ed25519 as oracle
    from firedancer_tpu.msm_plan import BASELINE_PLAN
    from firedancer_tpu.ops.verify_rlc import fresh_u

    rng = np.random.default_rng(2147483927)
    bsz = 6
    pts = [oracle.scalarmult(int(k), oracle.B)
           for k in rng.integers(1, 2**40, size=bsz)]
    coords = np.zeros((4, 32, bsz), np.int32)
    for i, (px, py) in enumerate(pts):
        for j, v in enumerate((px, py, 1, px * py % fe.P)):
            coords[j, :, i] = [(v >> (8 * k)) & 0xFF for k in range(32)]
    points = tuple(jnp.asarray(c) for c in coords)
    if which == "msm":
        scal = np.zeros((bsz, 32), np.uint8)
        scal[:, :2] = rng.integers(0, 256, size=(bsz, 2))
        scal[:, 1] &= 0x3F                  # two exact 7-bit windows
        got, ok = msm.msm_fast_partial(jnp.asarray(scal), points, 2,
                                       interpret=True, plan=BASELINE_PLAN)
        want, want_ok = msm.msm_partial(jnp.asarray(scal), points, 2,
                                        plan=BASELINE_PLAN)
        n = 2
    else:
        u = jnp.asarray(fresh_u(8, bsz, rng))
        got, ok = msm.subgroup_fast_partial(points, u, interpret=True)
        want, want_ok = msm.subgroup_partial(points, u, bucket_bits=5)
        n = 8
    assert bool(ok) and bool(want_ok)
    assert _affine_lanes(got, n) == _affine_lanes(want, n)
