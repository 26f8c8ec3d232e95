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
