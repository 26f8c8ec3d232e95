"""fd_msm2 certified core: signed-window recoding + the lazy niels madd.

Two pieces of the signed-digit Pippenger engine live here, OUTSIDE the
(uncertifiable) sort/gather staging of ops/msm.py, precisely so the
fdcert abstract interpreter can prove them (lint/bounds.py pass 5 —
this module is a CERT_MODULE):

- ``recode_signed`` — the borrow-propagating balanced recode. Unsigned
  w-bit window digits d_t (LSB-first along axis 0) become signed
  digits in [-(2^(w-1)-1), 2^(w-1)] with sum(digit_t * 2^(w*t)) equal
  to the original scalar, provided the window count follows
  msm_plan.plan_windows (an extra all-carry window when w divides the
  scalar width; otherwise the top partial window absorbs the borrow).
  The per-step wrap routes through ``_recode_step``, which the
  certifier replaces by name with a precise hull transfer
  (lint/bounds.py ``_transfer_recode_step``): the plain interval
  product would book digits in [-2^w, 2^w] and fail the contract,
  while the branch hull proves the tight [-(2^(w-1)-1), 2^(w-1)]
  bound the magnitude-bucket staging indexes with. The carry chain
  itself is a Python loop over a static window count, so every
  iterate's interval is checked int32-wrap-free.

- ``madd_niels_lazy`` — the 7-mul extended+niels point add with
  lazy-reduction depth 3: the six cross sums (y1-x1, y1+x1, e, f, g,
  h) stay UNCARRIED limb sums feeding fe_mul's generic |limb| <= 1024
  contract; only d = z1+z1 takes fe_add's carry pass (without it,
  f = d - c reaches 1536 and the product conv row escapes int32 —
  exactly the bound this module's cert entry pins). All four outputs
  are fe_mul results, so the accumulator contract |limb| <= 512 is
  closed under iteration: the whole static-round fill is proven by
  proving one round.

Adding the identity niels (yp, ym, t2d) = (1, 1, 0) scales the
accumulator's representation projectively ((X:Y:Z:T) -> (2XZ : 2YZ :
2Z^2 : 2XY), the same group element), which is why the lazy fill needs
NO output point_select for empty slots — ops/msm_pallas.py's kernel
fill rides the identical argument.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import fe25519 as fe

# fdcert entry contracts (fdlint pass 5 — grammar in lint/bounds.py).
# The recode entries prove the carry chain at every shippable width
# (msm_plan.PLAN_WIDTHS); the madd entry proves one fill round at the
# accumulator/niels bounds the staging feeds it, closed under
# iteration because every output coordinate is an fe_mul result.
FDCERT_CONTRACTS = {
    "_recode_step": {
        "inputs": ["blocks:1:256", "int:8"], "out_abs": 128,
        "doc": "one borrow step: v in [0, 2^w] -> digit in "
               "[-(2^(w-1)-1), 2^(w-1)] (precise hull transfer)"},
    "recode_signed_w6": {
        "inputs": ["bytes2:43:8"], "out_abs": 32,
        "doc": "43-window (253-bit) balanced recode at w=6"},
    "recode_signed_w7": {
        "inputs": ["bytes2:37:8"], "out_abs": 64,
        "doc": "37-window (253-bit) balanced recode at w=7"},
    "recode_signed_w8": {
        "inputs": ["bytes2:32:8"], "out_abs": 128,
        "doc": "32-window (253-bit) balanced recode at w=8"},
    "madd_niels_lazy": {
        "inputs": ["limbs:32:512:2", "limbs:32:512:2", "limbs:32:512:2",
                   "limbs:32:512:2", "limbs:32:1024:2", "limbs:32:1024:2",
                   "limbs:32:512:2"],
        "out_abs": 512,
        "doc": "7-mul extended+niels add, lazy depth 3; accumulator "
               "contract closed under iteration"},
}


def _recode_step(v, w_bits):
    """One borrow-propagating step: v = d_t + c_in in [0, 2^w] maps to
    (digit, c_out) with digit = v - c_out * 2^w and c_out = (v > 2^(w-1)).
    The certifier swaps this for its precise hull transfer by name."""
    half = 1 << (w_bits - 1)
    borrow = (v > half).astype(jnp.int32)
    return v - (borrow << w_bits), borrow


def recode_signed(d, w_bits):
    """Balanced signed-window recode of unsigned w_bits-wide digits.

    d: (n_windows, ...) int-like, LSB-first windows, each in
    [0, 2^w - 1] (masked on entry so the proof covers byte inputs).
    Returns int32 signed digits of the same shape, each in
    [-(2^(w-1)-1), 2^(w-1)]. The final borrow is 0 whenever the window
    count follows msm_plan.plan_windows for the scalar width — the top
    window's raw digit never exceeds 2^(w-1) - 1, so it absorbs the
    incoming carry without wrapping."""
    d = jnp.asarray(d).astype(jnp.int32) & ((1 << w_bits) - 1)
    c = jnp.zeros(d.shape[1:], jnp.int32)
    outs = []
    for t in range(d.shape[0]):
        digit, c = _recode_step(d[t] + c, w_bits)
        outs.append(digit)
    return jnp.stack(outs, axis=0)


def recode_signed_w6(d):
    return recode_signed(d, 6)


def recode_signed_w7(d):
    return recode_signed(d, 7)


def recode_signed_w8(d):
    return recode_signed(d, 8)


def madd_niels_lazy(x1, y1, z1, t1, yp2, ym2, t2d2):
    """Extended (x1, y1, z1, t1) + niels (yp2, ym2, t2d2) -> extended,
    7 field muls, lazy-reduction depth 3 (see module docstring for the
    bound closure). With the identity niels (1, 1, 0) the result is
    the same group element, representation scaled — the fill's
    select-free empty-slot trick."""
    a = fe.fe_mul(y1 - x1, ym2)
    b = fe.fe_mul(y1 + x1, yp2)
    c = fe.fe_mul(t1, t2d2)
    d = fe.fe_add(z1, z1)
    e = b - a
    f = d - c
    g = d + c
    h = b + a
    return (fe.fe_mul(e, f), fe.fe_mul(g, h),
            fe.fe_mul(f, g), fe.fe_mul(e, h))
