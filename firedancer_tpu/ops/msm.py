"""Batched multi-scalar multiplication (Pippenger) on TPU.

Computes sum_i c_i * P_i for a batch of per-lane scalars and points with
ONE shared doubling chain — the structural cost that per-lane
double-scalar-mult (ops/curve25519.double_scalarmult) cannot amortize.
This is the engine behind RLC batch verification (ops/verify_rlc.py).

Shape of the computation (w = 8-bit windows, byte-aligned so digit
extraction is free):

1. **Bucket fill.** For every window t, lane digits d_i route point P_i
   into bucket (t, d_i). The fill is batch-uniform: a static number of
   ROUNDS, each adding one gathered point per (window, bucket) lane —
   lanes are (n_windows x 256) wide, so every round is one unified
   point_add across all windows at once. Slot indices come from one
   stable two-operand sort per window (digits, lane iota), bucket
   starts by a fused compare-reduce, and rank-within-bucket arithmetic
   read through a row gather of the sorted lane order — no scatters,
   and no element-wise gathers, which TPU XLA runs several times
   slower per element than row gathers.
2. **Bucket aggregation.** sum_b b * S_b via bit decomposition:
   sum_k 2^k (sum over buckets with bit k set), each inner sum a
   pairwise tree-reduce over the bucket axis — log-depth, batch-uniform.
3. **Cross-window Horner.** S = 2^8 * S + W_t, MSB-first; (32, 1)-lane
   elementwise chains that XLA fuses.

Data-dependence escape hatch: the fill uses a STATIC round count
(max_rounds). If any bucket receives more points (Poisson tail, or
adversarially-biased digits of h), the fill would be incomplete — the
function detects this and reports ok=False so the caller falls back to
the exact per-lane path. Never a wrong result, only a slow path.

Reference basis: Pippenger's algorithm (public-domain technique; cf.
the batched bucket MSMs in GPU ZK provers), re-shaped for TPU: no
atomics, no scatters, unified complete adds, one-hot-free gathers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import curve25519 as ge
from . import fe25519 as fe
from .msm_recode import madd_niels_lazy, recode_signed
from firedancer_tpu.msm_plan import (
    BASELINE_PLAN, MsmPlan, PLAN_WIDTHS, parse_plan, plan_buckets,
    plan_windows,
)

W_BITS = 7
N_BUCKETS = 1 << W_BITS
# Window counts are chosen so EVERY window of the scalar distribution is
# either uniform or almost-always-zero — a top window whose digits
# concentrate on a few NONZERO values overloads those buckets and forces
# the static-round fill into its fallback (zero digits are free: bucket
# 0 is never accumulated). 252 = 36*7, so scalars mod L (< 2^252 + eps)
# are uniform in windows 0..35 and ~always 0 in window 36; RLC z weights
# are drawn < 2^126 = 2^(18*7) so all 18 windows are uniform.
WINDOWS_128 = 19   # any 128-bit scalar (window 18 in {0..3})
WINDOWS_Z = 18     # RLC z weights: uniform < 2^126
WINDOWS_253 = 37   # scalars mod L

# Scalar bit-widths keyed by the BASELINE (w=7) window count callers
# pass — the public n_windows argument stays the u7 vocabulary
# (WINDOWS_Z / WINDOWS_128 / WINDOWS_253) and a non-default MsmPlan
# re-derives its own window count from the underlying scalar width via
# msm_plan.plan_windows. Unknown counts fall back to 7 * n_windows.
SCALAR_BITS = {WINDOWS_Z: 126, WINDOWS_128: 128, WINDOWS_253: 253}


def active_plan() -> MsmPlan:
    """The MsmPlan selected by the FD_MSM_* flags — the device-ops
    alias for msm_plan.plan_from_flags (one resolution rule; the
    jax-free engine registry calls the msm_plan spelling)."""
    from firedancer_tpu.msm_plan import plan_from_flags

    return plan_from_flags()


def _digits(scalars_bytes: jnp.ndarray, n_windows: int,
            w_bits: int = W_BITS) -> jnp.ndarray:
    """(B, 32) uint8 -> (n_windows, B) int32 w_bits-wide windows, LSB
    first. Any w_bits <= 8 works: a window spans at most two bytes
    (sh + w_bits <= 15), so the two-byte splice below covers it."""
    b = jnp.moveaxis(scalars_bytes.astype(jnp.int32), -1, 0)  # (32, B)
    zero = jnp.zeros_like(b[0])
    outs = []
    for w in range(n_windows):
        bit = w_bits * w
        i, sh = bit >> 3, bit & 7
        lo = b[i] if i < 32 else zero
        hi = b[i + 1] if i + 1 < 32 else zero
        outs.append(((lo + (hi << 8)) >> sh) & ((1 << w_bits) - 1))
    return jnp.stack(outs)


def _reduce_pairs(pt, n):
    """Tree-reduce a (..., n) bucket axis by pairwise point_add. Odd
    widths (signed-magnitude grids are 2^(w-1)+1 wide) split off their
    leading element into a carry folded back at the end — for powers of
    two the op sequence is exactly the historical halving tree."""
    carry = None
    while n > 1:
        if n % 2:
            head = tuple(c[..., :1] for c in pt)
            carry = head if carry is None else ge.point_add(carry, head)
            pt = tuple(c[..., 1:] for c in pt)
            n -= 1
        a = tuple(c[..., 0::2] for c in pt)
        b = tuple(c[..., 1::2] for c in pt)
        pt = ge.point_add(a, b)
        n //= 2
    return pt if carry is None else ge.point_add(pt, carry)


def _default_rounds(bsz: int, n_buckets: int = N_BUCKETS,
                    signed: bool = False) -> int:
    # Poisson tail bound: with uniform digits each nonzero bucket holds
    # ~lam = B/(n_buckets-1) points; lam + 7*sqrt(lam) + 8 puts the
    # per-batch overflow probability below ~1e-7 even across thousands
    # of buckets. Adversarially-biased digits only cost the fallback.
    # Signed callers pass the LIVE magnitude count 2^(w-1) (bucket 0 is
    # dead; each live bucket catches digit rate 2/2^w). The formula
    # lives in firedancer_tpu/msm_plan.py (stdlib-only) so the bench
    # orchestrator's fill-efficiency predictions can never drift from
    # the engine's actual round count.
    from firedancer_tpu.msm_plan import default_rounds

    return default_rounds(bsz, n_buckets, signed=signed)


def _plan_dims(n_windows: int, bsz: int, plan: MsmPlan,
               _force_windows: int | None = None):
    """(nw, n_buckets, default max_rounds) for a non-baseline plan,
    re-derived from the scalar width behind the caller's baseline
    window count. _force_windows is the search harness's parity-control
    knob ONLY (a signed plan at the unsigned count drops the carry
    window — the negative control the gate must catch)."""
    scalar_bits = SCALAR_BITS.get(n_windows, W_BITS * n_windows)
    nw = plan_windows(scalar_bits, plan.w, plan.signed)
    if _force_windows is not None:
        nw = _force_windows
    nb = plan_buckets(plan)
    live = (1 << (plan.w - 1)) if plan.signed else nb
    return nw, nb, _default_rounds(bsz, live, signed=plan.signed)


def _neg_table(neg_flags: jnp.ndarray, idx: jnp.ndarray,
               bsz: int) -> jnp.ndarray:
    """Gather per-lane sign flags through the slot table: neg[t, b, r]
    is True iff slot (t, b, r) holds a lane whose signed digit was
    negative (empty slots are False — identity has no sign)."""
    nw, nb, rounds = idx.shape
    safe = jnp.clip(idx.reshape(nw, -1), 0, bsz - 1)
    neg = jnp.take_along_axis(neg_flags, safe, axis=1).reshape(
        nw, nb, rounds
    )
    return neg & (idx >= 0)


def _top_tree_planes(n_windows: int, nw: int, plan: MsmPlan) -> int:
    """Bit planes for the plan's TOP window when it must bypass the
    bucket grid, else 0. The static-round Poisson bound prices UNIFORM
    w-bit digits; a top window covering r < w significant scalar bits
    concentrates its mass on 2^r values (signed recode is the worst
    case: the final borrow lands ~B/2 lanes on magnitude 1), so at
    production B that one window deterministically overflows a round
    count the other windows never approach. Such windows are instead
    summed directly (_top_window_sum) — digits there are in [0, 2^r]
    (signed; the borrow can add 1) or [0, 2^r) (unsigned), so r+1 / r
    bit planes suffice. r >= w means the top window is a full uniform
    digit and the grid handles it (the baseline geometry); r < 0 only
    under the search harness's _force_windows truncation control,
    which must keep the plain (wrong-by-construction) grid path."""
    scalar_bits = SCALAR_BITS.get(n_windows, W_BITS * n_windows)
    r = scalar_bits - plan.w * (nw - 1)
    if r < 0 or r >= plan.w:
        return 0
    return r + 1 if plan.signed else r


def _top_window_sum(top_digits, points, planes: int):
    """W_top = sum_i top_i * P_i by MSB-first bit-plane masked tree
    reduction over the LANE axis: planes x (select + pairwise point_add
    tree) + one tiny doubling ladder — exact for any digit values, no
    round bound to overflow. O(planes * B) add-lanes in O(planes *
    log B) sequential depth; at the shapes that need it (planes <= 7)
    this is ~1% of the bucket fill's lane count."""
    bsz = points[0].shape[1]
    ident_b = ge.identity((bsz,))
    acc = ge.identity((1,))
    for k in range(planes - 1, -1, -1):
        m = ((top_digits >> k) & 1) == 1
        masked = ge.point_select(m, points, ident_b)
        t_k = _reduce_pairs(masked, bsz)
        acc = ge.point_add(ge.point_double(acc), t_k)
    return acc                                             # (32, 1)


def _plan_staging(scalars_bytes, bsz: int, max_rounds: int, nw: int,
                  n_buckets: int, plan: MsmPlan, tree_planes: int = 0):
    """Digit extraction + (for signed plans) balanced recode + magnitude
    bucketing: returns (idx, neg, ok, top) with neg None on unsigned
    plans. Signed digits route |d| into bucket |d| (dead bucket 0, live
    magnitudes 1..2^(w-1)) and fold the sign into the gather — the
    certified recode (ops/msm_recode.py) guarantees |d| <= 2^(w-1), so
    the magnitude grid is exactly plan_buckets wide. When tree_planes >
    0 the top window's digit row is split off for _top_window_sum (top)
    and the grid stages only the nw-1 uniform windows."""
    d = _digits(scalars_bytes, nw, plan.w)
    s = recode_signed(d, plan.w) if plan.signed else d
    top = None
    if tree_planes:
        top, s = s[nw - 1], s[:nw - 1]
    if not plan.signed:
        idx, ok = _staging_from_digits(s, bsz, max_rounds, n_buckets)
        return idx, None, ok, top
    idx, ok = _staging_from_digits(jnp.abs(s), bsz, max_rounds, n_buckets)
    return idx, _neg_table(s < 0, idx, bsz), ok, top


def combine_stacked(pt):
    """Fold a leading-axis stack of point partials ((N, ...) limb
    arrays per coordinate) into their group sum with unified adds, in
    stack order — the one folding rule every cross-shard combine path
    (monolithic all_gather and the fd_pod split tail alike) goes
    through, so the two compositions can never drift bit-wise."""
    n = pt[0].shape[0]
    acc = tuple(c[0] for c in pt)
    for d in range(1, n):
        acc = ge.point_add(acc, tuple(c[d] for c in pt))
    return acc


def _gather_point_sum(pt, axis_name: str):
    """Combine per-device point partials into the global sum, on every
    device: all_gather the (X, Y, Z, T) limb arrays over the mesh axis
    and point_add the device slices. Point addition is the GROUP
    operation, so a raw psum cannot combine partials — but the partials
    are tiny ((32, nw) limbs per coordinate), so gather + a handful of
    unified adds costs microseconds against the milliseconds of bucket
    work they summarize. This is the only cross-device traffic in the
    sharded MSM."""
    g = tuple(jax.lax.all_gather(c, axis_name) for c in pt)  # (N, ...)
    return combine_stacked(g)


def _all_shards_ok(ok, axis_name: str):
    """Global AND of a per-shard () bool (fill-overflow flags: ONE
    overflowing shard invalidates the whole batch result)."""
    return jnp.all(jax.lax.all_gather(ok, axis_name))


def _staging_indices(scalars_bytes, n_windows: int, bsz: int,
                     max_rounds: int):
    """Slot table for the bucket fill: (idx, ok) where idx[t, b, r] is
    the lane of the r-th point in bucket (t, b) or -1, and ok is False
    iff some bucket overflowed max_rounds."""
    d = _digits(scalars_bytes, n_windows)                 # (nw, B)
    return _staging_from_digits(d, bsz, max_rounds)


# Lanes per row when the slot table reads the sorted lane order (a power
# of two): each bucket gathers the rows that hold its slots.
ORDER_ROW = 32


def _staging_from_digits(d: jnp.ndarray, bsz: int, max_rounds: int,
                         n_buckets: int = N_BUCKETS):
    """As _staging_indices, but from an explicit (nw, B) int32 digit
    array in [0, n_buckets) — each row an independent weighting of the
    same points (used by the torsion subgroup check, where rows are
    independent random trials rather than positional windows).

    Built with no element-wise gather. On a TPU v5e, XLA's gather of
    single int32 elements costs about 10 ns an element and a gather of
    32-lane rows under 2 ns a row, and at B=8192 the three slot tables
    of an rlc pass hold 2.3M slots. So the stable sort carries the lane
    iota as a second operand (the permutation, with no gather of the
    sorted keys), a bucket's start is one fused compare-reduce (the
    count of digits below it: what searchsorted returns on the sorted
    row), and a bucket's slots, consecutive in the permutation, come
    from one row gather of the ORDER_ROW-lane rows that hold them,
    shifted into place by selects."""
    nw = d.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    _, order = jax.lax.sort((d, lane), dimension=1, num_keys=1,
                            is_stable=True)               # (nw, B)

    # starts[t, b] = first sorted position of digit b in window t.
    buckets = jnp.arange(n_buckets, dtype=jnp.int32)
    starts = jnp.sum(d[:, :, None] < buckets[None, None, :], axis=1,
                     dtype=jnp.int32)                     # (nw, n_buckets)
    ends = jnp.concatenate(
        [starts[:, 1:], jnp.full((nw, 1), bsz, starts.dtype)], axis=1
    )
    counts = ends - starts                                # (nw, n_buckets)
    ok = jnp.max(jnp.where(buckets[None, :] > 0, counts, 0)) <= max_rounds

    # Slot table: idx[t, b, r] = lane index of the r-th point in bucket
    # (t, b), or -1. Bucket 0 contributes nothing (digit 0 == identity).
    r_iota = jnp.arange(max_rounds, dtype=jnp.int32)
    valid = (r_iota[None, None, :] < counts[:, :, None]) & (
        buckets[None, :, None] > 0
    )
    # A live slot r of bucket (t, b) is order[t, starts + r]: it lies in
    # the k_rows rows from starts // ORDER_ROW on, at starts % ORDER_ROW
    # + r of that block. Rows past a window's lanes feed dead slots only.
    row_len = -(-bsz // ORDER_ROW)            # pad lanes are never read
    k_rows = (max_rounds + ORDER_ROW - 2) // ORDER_ROW + 1
    rows = jnp.pad(order, ((0, 0), (0, row_len * ORDER_ROW - bsz)))
    rows = rows.reshape(nw * row_len, ORDER_ROW)
    # starts >= 0, so truncating div/rem are floor div/mod.
    first = (jnp.arange(nw, dtype=jnp.int32)[:, None] * row_len
             + jax.lax.div(starts, ORDER_ROW))            # (nw, nb)
    row = jnp.minimum(
        first[:, :, None] + jnp.arange(k_rows, dtype=jnp.int32),
        nw * row_len - 1)
    block = rows[row].reshape(nw, n_buckets, k_rows * ORDER_ROW)
    # Shift each block left by starts % ORDER_ROW, one bit at a time.
    shift = jax.lax.rem(starts, ORDER_ROW)[:, :, None]
    step = 1
    while step < ORDER_ROW:
        block = jnp.where((shift & step) != 0,
                          jnp.roll(block, -step, axis=2), block)
        step *= 2
    idx = jnp.where(valid, block[:, :, :max_rounds], -1)  # (nw, nb, R)
    return idx, ok


def msm(scalars_bytes: jnp.ndarray, points, n_windows: int,
        max_rounds: int | None = None, axis_name: str | None = None,
        plan: MsmPlan | None = None):
    """sum_i scalars_i * P_i (XLA reference path).

    scalars_bytes: (B, 32) uint8 little-endian (windows beyond
      n_windows must be zero). points: (X, Y, Z, T) of (32, B) limbs.
    axis_name (under shard_map): B is the LOCAL lane count; the
      per-window bucket sums are combined across the mesh before the
      Horner tail, so the returned point is the global MSM over all
      shards' lanes (replicated), and ok is the global fill verdict.
    plan (None = active_plan()): the fd_msm2 schedule. BASELINE_PLAN
      runs the historical u7 path bit-identically; lazy plans require
      points with Z == 1 (decompress output / affine constants — the
      niels fill's mixed add assumes it, exactly like msm_fast).
    Returns (point, ok): point is (X, Y, Z, T) of (32, 1) limbs; ok is a
      () bool — False iff a bucket overflowed max_rounds (result then
      invalid; caller must use the exact path).
    """
    if plan is None:
        plan = active_plan()
    w_res, ok = msm_partial(scalars_bytes, points, n_windows,
                            max_rounds=max_rounds, plan=plan)
    return msm_combine(w_res, ok, n_windows, axis_name=axis_name,
                       plan=plan)


def msm_partial(scalars_bytes: jnp.ndarray, points, n_windows: int,
                max_rounds: int | None = None,
                plan: MsmPlan | None = None,
                _force_windows: int | None = None):
    """The LOCAL half of msm(): digit staging + bucket fill + per-window
    bucket aggregation over this shard's lanes only — no collectives, no
    doubling-chain tails. Returns (w_res, ok): w_res a (32, nw)-limb
    point per window (W_t = sum over local lanes; nw is the PLAN's
    window count — n_windows for the baseline), ok the local fill
    verdict. msm_combine finishes the job; fd_pod's split-step
    dispatcher jits the two halves separately so batch k's combine can
    execute while batch k+1's fill is already dispatched."""
    if plan is None:
        plan = active_plan()
    bsz = points[0].shape[1]
    if plan == BASELINE_PLAN and _force_windows is None:
        if max_rounds is None:
            max_rounds = _default_rounds(bsz)
        idx, ok = _staging_indices(scalars_bytes, n_windows, bsz,
                                   max_rounds)
        return _fill_and_aggregate(idx, points, max_rounds,
                                   n_windows), ok
    nw, nb, rounds = _plan_dims(n_windows, bsz, plan, _force_windows)
    if max_rounds is None:
        max_rounds = rounds
    planes = _top_tree_planes(n_windows, nw, plan)
    idx, neg, ok, top = _plan_staging(scalars_bytes, bsz, max_rounds, nw,
                                      nb, plan, tree_planes=planes)
    nw_grid = nw - 1 if planes else nw
    if plan.lazy:
        w_res = _fill_and_aggregate_lazy(idx, neg, points, max_rounds,
                                         nw_grid, nb, plan.w)
    else:
        w_res = _fill_and_aggregate(idx, points, max_rounds, nw_grid,
                                    n_buckets=nb, w_bits=plan.w)
    if planes:
        w_top = _top_window_sum(top, points, planes)
        w_res = tuple(jnp.concatenate([c, ct], axis=1)
                      for c, ct in zip(w_res, w_top))
    return w_res, ok


def msm_combine(w_res, ok, n_windows: int, axis_name: str | None = None,
                plan: MsmPlan | None = None):
    """The TAIL half of msm(): combine per-shard window partials across
    the mesh (axis_name; identity when None) and run the cross-window
    Horner doubling chain (plan.w doublings per window — the window
    count itself is read off w_res, so both halves agree by shape).
    msm() == msm_combine(*msm_partial(...)) by construction — the
    composition is the exact op sequence the monolithic path always
    ran, so the split is bit-exact."""
    if plan is None:
        plan = active_plan()
    if axis_name is not None:
        w_res = _gather_point_sum(w_res, axis_name)
        ok = _all_shards_ok(ok, axis_name)
    return _window_horner(w_res, w_res[0].shape[1], w_bits=plan.w), ok


def _aggregate_windows(acc, nw: int, n_buckets: int, w_bits: int):
    """Per-window bucket aggregation over a filled (32, nw*nb) lane
    accumulator: W_t = sum_b b * S_{t,b} = sum_k 2^k * (sum_{b: bit k
    set} S_b). A lax.scan over the bit masks keeps the traced graph
    ~w_bits x smaller than unrolling (this path must stay compilable on
    CPU test hosts). Works for any bucket-index range < 2^w_bits —
    signed-magnitude grids (max index 2^(w-1)) included."""
    s_buckets = tuple(
        c.reshape(fe.NLIMBS, nw, n_buckets) for c in acc
    )
    buckets = jnp.arange(n_buckets, dtype=jnp.int32)
    ident_nb = ge.identity((nw, n_buckets))
    bit_masks = jnp.stack([
        jnp.broadcast_to((((buckets >> k) & 1) == 1)[None, :],
                         (nw, n_buckets))
        for k in range(w_bits - 1, -1, -1)
    ])                                                     # (w_bits, nw, nb)

    def agg_step(carry, bit):
        masked = ge.point_select(bit, s_buckets, ident_nb)
        t_k = _reduce_pairs(masked, n_buckets)             # (32, nw, 1)
        t_k = tuple(c[..., 0] for c in t_k)                # (32, nw)
        out = ge.point_add(ge.point_double(carry), t_k)
        return out, None

    w_res, _ = jax.lax.scan(agg_step, ge.identity((nw,)), bit_masks)
    return w_res


def _fill_and_aggregate(idx, points, max_rounds: int, nw: int,
                        n_buckets: int = N_BUCKETS,
                        w_bits: int = W_BITS):
    """Bucket fill + per-window bucket aggregation (XLA path): returns
    w_res, a (32, nw)-limb point per window, W_t = sum_b b * S_{t,b}.
    Defaults are the historical u7 grid — bit-identical graph."""
    bsz = points[0].shape[1]
    lanes = nw * n_buckets
    ident = ge.identity((lanes,))

    def fill_round(r, acc):
        sel = jax.lax.dynamic_index_in_dim(
            idx, r, axis=2, keepdims=False
        ).reshape(lanes)                                   # (L,)
        m = sel >= 0
        safe = jnp.clip(sel, 0, bsz - 1)
        q = tuple(c[:, safe] for c in points)
        q = ge.point_select(m, q, ident)
        # Adding the identity is exact under the unified formulas, so a
        # plain add-then-keep is fine; select keeps masked lanes stable.
        return ge.point_select(m, ge.point_add(acc, q), acc)

    acc = jax.lax.fori_loop(0, max_rounds, fill_round, ident)
    return _aggregate_windows(acc, nw, n_buckets, w_bits)


def _fill_and_aggregate_lazy(idx, neg, points, max_rounds: int, nw: int,
                             n_buckets: int, w_bits: int):
    """The fd_msm2 lazy niels fill (XLA path): 7-mul mixed adds through
    the certified madd_niels_lazy (ops/msm_recode.py) instead of the
    9-mul unified extended add, with the sign of a signed digit folded
    into the gather (yp <-> ym swap + t2d negation — one elementwise
    select, no extra field ops). Empty slots gather the identity niels
    (1, 1, 0), which scales the accumulator's representation
    projectively (same group element) — NO per-round point_select, so
    the whole round is madd-only. REQUIRES points with Z == 1 (the
    mixed add assumes it). neg: (nw, nb, R) bool from _neg_table, or
    None for unsigned plans."""
    bsz = points[0].shape[1]
    lanes = nw * n_buckets
    x, y, z, t = points
    yp = fe.fe_add(y, x)
    ym = fe.fe_sub(y, x)
    t2d = fe.fe_mul(t, fe.FE_D2)
    one0 = (jnp.arange(fe.NLIMBS, dtype=jnp.int32) == 0)[:, None]
    one0 = one0.astype(jnp.int32)

    idx_r = jnp.transpose(idx, (2, 0, 1)).reshape(max_rounds, lanes)
    neg_r = (jnp.transpose(neg, (2, 0, 1)).reshape(max_rounds, lanes)
             if neg is not None else None)

    def fill_round(r, acc):
        sel = jax.lax.dynamic_index_in_dim(idx_r, r, axis=0,
                                           keepdims=False)
        m = (sel >= 0)[None, :]
        safe = jnp.clip(sel, 0, bsz - 1)
        gyp = jnp.where(m, yp[:, safe], one0)
        gym = jnp.where(m, ym[:, safe], one0)
        gtd = jnp.where(m, t2d[:, safe], 0)
        if neg_r is not None:
            ng = jax.lax.dynamic_index_in_dim(
                neg_r, r, axis=0, keepdims=False
            )[None, :]
            gyp, gym = (jnp.where(ng, gym, gyp),
                        jnp.where(ng, gyp, gym))
            gtd = jnp.where(ng, -gtd, gtd)
        return madd_niels_lazy(*acc, gyp, gym, gtd)

    acc = jax.lax.fori_loop(0, max_rounds, fill_round,
                            ge.identity((lanes,)))
    return _aggregate_windows(acc, nw, n_buckets, w_bits)


def _window_horner(w_res, nw: int, w_bits: int = W_BITS):
    """Combine per-window sums: sum_t 2^(w t) W_t, MSB-first Horner as a
    lax.scan over windows (graph stays small; lanes are (32, 1))."""
    res = tuple(c[:, nw - 1:nw] for c in w_res)            # (32, 1)
    if nw == 1:
        return res
    stacked = tuple(
        jnp.moveaxis(c[:, :nw - 1], 1, 0)[::-1][:, :, None]  # (nw-1, 32, 1)
        for c in w_res
    )

    def horner_step(carry, wt):
        for _ in range(w_bits):
            carry = ge.point_double(carry)
        return ge.point_add(carry, wt), None

    res, _ = jax.lax.scan(horner_step, res, stacked)
    return res


def _mul_by_group_order(pt):
    """[L]P over a (32, K)-lane point batch, L the prime group order
    (sc25519.L). L is a fixed PUBLIC scalar, so this is a lax.scan over
    its bit pattern — double always, add where the bit is set; batch-
    uniform, no per-lane tables, one traced body."""
    from . import sc25519 as sc

    bits = [int(b) for b in bin(sc.L)[2:]]
    k = pt[0].shape[-1]
    bits_arr = jnp.asarray(bits[1:], dtype=jnp.bool_)

    def step(carry, bit):
        carry = ge.point_double(carry)
        added = ge.point_add(carry, pt)
        return ge.point_select(jnp.broadcast_to(bit, (k,)), added, carry), None

    out, _ = jax.lax.scan(step, pt, bits_arr)              # init = leading 1
    return out


def subgroup_check(points, u_digits: jnp.ndarray,
                   max_rounds: int | None = None,
                   axis_name: str | None = None,
                   bucket_bits: int = W_BITS, lazy: bool = False):
    """Randomized prime-subgroup (torsion-freeness) certification.

    points: (X, Y, Z, T) of (32, B) limbs. u_digits: (K, B) int32 in
    [0, N_BUCKETS) — K independent uniform random weightings, drawn
    AFTER the points are known (verify_rlc.fresh_u). Trial j computes
    Agg_j = sum_i u_{j,i} P_i through the shared bucket machinery (rows
    act as windows, so all K trials fill in one pass), then checks
    [L]Agg_j == identity. Points weighted zero in a trial are unchecked
    by that trial.

    Why this certifies: P_i = P0_i + T_i with P0_i in the prime subgroup
    and T_i in the 8-torsion (cyclic, order 8). [L]Agg_j kills every
    prime component, leaving [L * sum_i u_{j,i} t_i mod 8] * T8 with L
    odd — identity iff sum u_ji t_i = 0 mod 8. If any T_i != 0 that
    survives one trial with probability <= 1/2 (= order-2 defects; 1/4
    order-4, 1/8 order-8), so K trials miss with probability <= 2^-K.
    Honest (torsion-free) points always pass.

    axis_name (under shard_map): the K trial rows weight the GLOBAL
    point set; each shard fills its local lanes' contributions and the
    per-trial aggregates combine across the mesh before the [L] ladder
    (Agg_j = sum over all shards' lanes), so the certification is over
    every live point, not per-shard.

    Returns (ok_subgroup, ok_fill): ok_subgroup () bool — every trial
    aggregated to the identity; ok_fill () bool — False iff a bucket
    overflowed max_rounds (trials then unusable; the caller must treat
    the set as uncertified and take its exact path).
    """
    agg, ok_fill = subgroup_partial(points, u_digits,
                                    max_rounds=max_rounds,
                                    bucket_bits=bucket_bits, lazy=lazy)
    return subgroup_combine(agg, ok_fill, axis_name=axis_name)


def subgroup_partial(points, u_digits: jnp.ndarray,
                     max_rounds: int | None = None,
                     bucket_bits: int = W_BITS, lazy: bool = False):
    """Local half of subgroup_check: the K per-trial aggregates over
    THIS shard's lanes only ((32, K)-limb coords) + the local fill
    verdict — no collectives, no [L] ladder.

    bucket_bits < W_BITS masks the trial digits (soundness preserved —
    subgroup_check_fast's 5-bit argument: the catch probability is
    governed by the digit distribution mod 8) and shrinks the lane
    grid; lazy routes the fill through the certified 7-mul niels madd
    (REQUIRES Z == 1 points, like msm_fast). Defaults are the
    historical 7-bit unified-add path, bit-identical."""
    bsz = points[0].shape[1]
    n_buckets = 1 << bucket_bits
    if max_rounds is None:
        max_rounds = _default_rounds(bsz, n_buckets)
    k = u_digits.shape[0]
    d = u_digits.astype(jnp.int32)
    if bucket_bits != W_BITS:
        d = d & (n_buckets - 1)
    idx, ok_fill = _staging_from_digits(d, bsz, max_rounds, n_buckets)
    if lazy:
        agg = _fill_and_aggregate_lazy(idx, None, points, max_rounds, k,
                                       n_buckets, bucket_bits)
    else:
        agg = _fill_and_aggregate(idx, points, max_rounds, k,
                                  n_buckets=n_buckets,
                                  w_bits=bucket_bits)
    return agg, ok_fill                                    # (32, K) coords


def subgroup_combine(agg, ok_fill, axis_name: str | None = None):
    """Tail half of subgroup_check: cross-mesh per-trial combine (when
    axis_name), the [L] doubling ladder, and the identity test.
    subgroup_check == subgroup_combine(*subgroup_partial(...)) — same
    op sequence, so the split is bit-exact."""
    if axis_name is not None:
        agg = _gather_point_sum(agg, axis_name)
        ok_fill = _all_shards_ok(ok_fill, axis_name)
    la = _mul_by_group_order(agg)
    ok = fe.fe_is_zero(la[0]) & fe.fe_eq(la[1], la[2])     # (K,) identity
    return jnp.all(ok), ok_fill


# Staged niels rounds are cast to int16 for the HBM round buffers: every
# staged limb obeys the |limb| <= 1024 lazy-carry invariant (fe_add /
# fe_sub / fe_mul outputs), far inside int16 range, and the fill kernel
# widens back to int32 on load — halving the fill's HBM traffic, which
# is the dominant byte stream of the whole MSM.
_STAGE_DTYPE = jnp.int16


def _stage_niels(points, idx, max_rounds: int, lanes: int, bsz: int,
                 niels=None, neg=None, lane_pad: int = 0):
    """Gather per-round niels operands: (R, 96, L + lane_pad) int16,
    each lane's column the three 32-limb blocks yp | ym | t2d, the
    niels identity (1, 1, 0) where a slot is empty.
    points must have Z == 1 (decompress output / affine constants).
    niels, if given, is the precomputed (yp, ym, t2d) from the
    decompress kernel — skips three XLA field ops over the whole point
    set. neg ((nw, nb, R) bool, signed plans) selects each negative
    digit's point negation, -P in niels form being (ym, yp, -t2d).
    lane_pad appends identity columns so non-power-of-two signed grids
    meet the kernel's lane alignment.

    One row gather does all of it. The row table holds every point's
    96 limbs, already int16, then the identity; signed plans stack the
    negated points and a second identity below. Empty slots, signs and
    pad lanes are folded into the row index, so the gathered rows need
    no select and no convert — only one transpose into round layout."""
    if niels is not None:
        yp, ym, t2d = niels
    else:
        x, y, z, t = points
        yp = fe.fe_add(y, x)
        ym = fe.fe_sub(y, x)
        t2d = fe.fe_mul(t, fe.FE_D2)

    limb = jnp.arange(3 * fe.NLIMBS, dtype=jnp.int32)
    ident = ((limb % fe.NLIMBS == 0) & (limb < 2 * fe.NLIMBS))[None, :]
    ident = ident.astype(_STAGE_DTYPE)                     # (1, 96)

    def rows(*blocks):
        return jnp.concatenate(blocks).T.astype(_STAGE_DTYPE)  # (B, 96)

    table = [rows(yp, ym, t2d), ident]
    sel = jnp.transpose(idx, (2, 0, 1)).reshape(max_rounds, lanes)
    sel = jnp.where(sel >= 0, sel, bsz)         # row bsz: the identity
    if neg is not None:
        table += [rows(ym, yp, -t2d), ident]
        ng = jnp.transpose(neg, (2, 0, 1)).reshape(max_rounds, lanes)
        sel = sel + (bsz + 1) * ng.astype(jnp.int32)
    if lane_pad:
        sel = jnp.pad(sel, ((0, 0), (0, lane_pad)), constant_values=bsz)
    staged = jnp.concatenate(table)[sel]                   # (R, L, 96)
    return jnp.transpose(staged, (0, 2, 1))                # (R, 96, L)


def msm_fast(scalars_bytes: jnp.ndarray, points, n_windows: int,
             max_rounds: int | None = None, interpret: bool = False,
             niels=None, axis_name: str | None = None,
             plan: MsmPlan | None = None):
    """Kernel-backed msm (same contract as msm(), including axis_name's
    cross-mesh window-partial combine before the Horner tail and the
    plan argument's schedule selection).

    REQUIRES points with Z == 1 (decompress output / affine constants) —
    the bucket fill uses precomputed niels form (y+x, y-x, 2d*t) with
    mixed adds, 7 muls instead of 9. Bucket accumulators and the
    aggregation running sums live in VMEM (ops/msm_pallas.py); the
    sort/gather staging and final Horner remain XLA.
    """
    if plan is None:
        plan = active_plan()
    w_res, ok = msm_fast_partial(scalars_bytes, points, n_windows,
                                 max_rounds=max_rounds,
                                 interpret=interpret, niels=niels,
                                 plan=plan)
    return msm_fast_combine(w_res, ok, n_windows, interpret=interpret,
                            axis_name=axis_name, plan=plan)


def msm_fast_partial(scalars_bytes: jnp.ndarray, points, n_windows: int,
                     max_rounds: int | None = None,
                     interpret: bool = False, niels=None,
                     plan: MsmPlan | None = None,
                     _force_windows: int | None = None):
    """Local half of msm_fast: niels staging + VMEM bucket fill +
    running-sum aggregation over this shard's lanes — no collectives,
    no Horner. Returns (w_res, ok) exactly like msm_partial (the kernel
    aggregation's nw padding is trimmed here, so the partial's shape is
    engine-independent and the fd_pod split tail can gather it). A
    signed plan folds digit signs into the niels staging (yp <-> ym
    swap + t2d negation), so the kernels themselves are untouched —
    magnitude grids just change the lane count, padded to the kernel's
    lane alignment with identity slots."""
    from . import msm_pallas as mp

    if plan is None:
        plan = active_plan()
    bsz = points[0].shape[1]
    if plan == BASELINE_PLAN and _force_windows is None:
        if max_rounds is None:
            max_rounds = _default_rounds(bsz)
        nw, nb = n_windows, N_BUCKETS
        idx, ok = _staging_indices(scalars_bytes, nw, bsz, max_rounds)
        neg, top, planes = None, None, 0
    else:
        nw, nb, rounds = _plan_dims(n_windows, bsz, plan, _force_windows)
        if max_rounds is None:
            max_rounds = rounds
        planes = _top_tree_planes(n_windows, nw, plan)
        idx, neg, ok, top = _plan_staging(scalars_bytes, bsz, max_rounds,
                                          nw, nb, plan,
                                          tree_planes=planes)
    nw_grid = nw - 1 if planes else nw

    lanes = nw_grid * nb
    lane_pad = (-lanes) % 256 if nb != N_BUCKETS else 0
    staged = _stage_niels(points, idx, max_rounds, lanes, bsz,
                          niels=niels, neg=neg, lane_pad=lane_pad)

    bx, by, bz, bt = mp.fill_buckets_pallas(staged, interpret=interpret)
    if lane_pad:
        bx, by, bz, bt = (c[:, :lanes] for c in (bx, by, bz, bt))

    # (32, L) -> bucket-major (nb, 32, nw_pad) for the aggregation walk.
    nw_pad = max(128, nw_grid)
    def to_bucket_major(c):
        c = jnp.transpose(
            c.reshape(fe.NLIMBS, nw_grid, nb), (2, 0, 1)
        )
        if nw_pad != nw_grid:
            c = jnp.pad(c, ((0, 0), (0, 0), (0, nw_pad - nw_grid)))
        return c

    w_res = mp.aggregate_buckets_pallas(
        tuple(to_bucket_major(c) for c in (bx, by, bz, bt)),
        fe.FE_D2.astype(jnp.int32),
        interpret=interpret,
    )
    w_res = tuple(c[:, :nw_grid] for c in w_res)
    if planes:
        # The tree-summed top window is XLA-side on both engines — it is
        # ~1% of the fill's lane count and keeps the kernels untouched.
        w_top = _top_window_sum(top, points, planes)
        w_res = tuple(jnp.concatenate([c, ct], axis=1)
                      for c, ct in zip(w_res, w_top))
    return w_res, ok


def msm_fast_combine(w_res, ok, n_windows: int, interpret: bool = False,
                     axis_name: str | None = None,
                     plan: MsmPlan | None = None):
    """Tail half of msm_fast: cross-mesh window-partial combine + the
    VMEM Horner doubling chain (plan.w doublings per window; the window
    count is read off w_res so both halves agree by shape). msm_fast ==
    the composition, bit-exact (same op order the monolithic path
    always ran)."""
    from . import msm_pallas as mp

    if plan is None:
        plan = active_plan()
    if axis_name is not None:
        w_res = _gather_point_sum(w_res, axis_name)
        ok = _all_shards_ok(ok, axis_name)
    res = mp.window_horner_pallas(
        w_res, fe.FE_D2.astype(jnp.int32), w_res[0].shape[1],
        interpret=interpret, w_bits=plan.w,
    )
    return res, ok


def _l_bits_col() -> jnp.ndarray:
    """(256, 1) int32: bits of the group order L, MSB-first from row 0,
    zero-padded (kernel input for mul_by_group_order_pallas)."""
    from . import sc25519 as sc

    bits = [int(b) for b in bin(sc.L)[2:]]
    out = np.zeros((256, 1), np.int32)
    out[: len(bits), 0] = bits
    return jnp.asarray(out)


def subgroup_check_fast(points, u_digits: jnp.ndarray,
                        bucket_bits: int = 5,
                        max_rounds: int | None = None,
                        interpret: bool = False,
                        niels=None, axis_name: str | None = None):
    """Kernel-backed subgroup_check (same contract and soundness,
    including axis_name's cross-mesh per-trial aggregate combine).

    REQUIRES points with Z == 1 (decompress output), like msm_fast.

    Two changes versus the XLA path, neither affecting soundness:
    - Trial digits are masked to `bucket_bits` (< 7) bits. Uniform
      digits stay uniform under the mask, and the per-trial catch
      probability is governed by the digit distribution mod 8, which
      5-bit digits preserve — but the bucket grid shrinks from
      (K, 128) to (K, 32), cutting the staged round buffers' HBM
      footprint ~4x (the fill is HBM-bound; tail efficiency
      lam/(lam + 7*sqrt(lam)) improves with larger lam per bucket).
    - The fill, aggregation, and the [L]-ladder all run in VMEM Pallas
      kernels (the XLA ladder alone cost more than the entire direct
      verify at production batch sizes).
    """
    agg, ok_fill = subgroup_fast_partial(
        points, u_digits, bucket_bits=bucket_bits, max_rounds=max_rounds,
        interpret=interpret, niels=niels,
    )
    return subgroup_fast_combine(agg, ok_fill, k=u_digits.shape[0],
                                 interpret=interpret, axis_name=axis_name)


def subgroup_fast_partial(points, u_digits: jnp.ndarray,
                          bucket_bits: int = 5,
                          max_rounds: int | None = None,
                          interpret: bool = False, niels=None):
    """Local half of subgroup_check_fast: masked-digit staging + VMEM
    fill + per-trial aggregation over this shard's lanes. Returns
    (agg, ok_fill) with agg at the kernel's Mosaic-padded trial width
    (k_pad = k rounded up to 128); the pad lanes are ZERO coordinate
    limbs, which every downstream group op maps to zero and the final
    identity test trivially passes — so a combine that does not know k
    can evaluate all k_pad lanes and reach the same verdict."""
    from . import msm_pallas as mp

    bsz = points[0].shape[1]
    n_buckets = 1 << bucket_bits
    if max_rounds is None:
        max_rounds = _default_rounds(bsz, n_buckets)
    d = u_digits.astype(jnp.int32) & (n_buckets - 1)
    k = d.shape[0]
    idx, ok_fill = _staging_from_digits(d, bsz, max_rounds, n_buckets)

    lanes = k * n_buckets
    staged = _stage_niels(points, idx, max_rounds, lanes, bsz,
                          niels=niels)
    bx, by, bz, bt = mp.fill_buckets_pallas(staged, interpret=interpret)

    k_pad = k + (-k) % 128                 # Mosaic lane-width alignment

    def to_bucket_major(c):
        c = jnp.transpose(c.reshape(fe.NLIMBS, k, n_buckets), (2, 0, 1))
        if k_pad != k:
            c = jnp.pad(c, ((0, 0), (0, 0), (0, k_pad - k)))
        return c

    agg = mp.aggregate_buckets_pallas(
        tuple(to_bucket_major(c) for c in (bx, by, bz, bt)),
        fe.FE_D2.astype(jnp.int32),
        interpret=interpret,
    )
    return agg, ok_fill


def subgroup_fast_combine(agg, ok_fill, k: int | None = None,
                          interpret: bool = False,
                          axis_name: str | None = None):
    """Tail half of subgroup_check_fast: cross-mesh per-trial combine,
    the VMEM [L] ladder, and the identity test over the first k trial
    lanes (k=None evaluates every padded lane — sound, see
    subgroup_fast_partial's zero-pad note)."""
    from . import msm_pallas as mp

    if axis_name is not None:
        agg = _gather_point_sum(agg, axis_name)
        ok_fill = _all_shards_ok(ok_fill, axis_name)
    la = mp.mul_by_group_order_pallas(
        agg, fe.FE_D2.astype(jnp.int32), _l_bits_col(), interpret=interpret
    )
    if k is not None:
        la = tuple(c[:, :k] for c in la)
    ok = fe.fe_is_zero(la[0]) & fe.fe_eq(la[1], la[2])     # (K,) identity
    return jnp.all(ok), ok_fill


# --------------------------------------------------------------------- #
# fdlint pass 7 (graph-audit) contracts — literals, read with
# ast.literal_eval by firedancer_tpu/lint/graphs.py, never imported.
# The msm_stage graphs are the three fill partials of one RLC verify
# (z-MSM, 253-bit MSM, torsion certification) traced standalone at
# EVERY ladder rung; their walked fill madds must reconcile with
# msm_plan's analytic executed-madd count within the tolerance.
# --------------------------------------------------------------------- #

GRAPH_CONTRACTS = {
    "msm_stage_xla": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int32", "uint32", "uint8"],
        "madds": {"engine": "xla", "tolerance_pct": 2.0},
    },
    "msm_stage_kernel": {
        "collectives": {},
        "axes": [],
        "dtypes": ["bool", "int16", "int32", "uint32", "uint8"],
        "madds": {"engine": "kernel", "tolerance_pct": 2.0},
        "vmem_mb": 64.0,
    },
}
