"""The controls: the reference, put in the engine's place, with one of
the configuration's guarantees broken. Each must make `correct` false.

  no_equation      the batch equation is skipped: a lane that passes
                   the point checks (s < L, A and R decode, neither of
                   small order) is taken as valid. It is the shortcut an
                   engine whose batches often fall back would tempt:
                   trust the batch without its equation.
  no_point_checks  the point checks are skipped: s < L and the equation
                   (OpenSSL) alone decide. Undecodable and small-order
                   keys then read ERR_MSG, not ERR_PUBKEY.

CONTROLS names the controls each configuration is held against.
`hook(name)` returns an engine hook for the runners: it swaps the
registry entry's `fn` and returns the function that puts it back.
"""

from __future__ import annotations

import numpy as np
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from benchmark.ref import ed25519 as ref


class Ready:
    """The surface a dispatcher reads of an engine's result: is_ready,
    __array__, used_fallback."""

    used_fallback = False

    def __init__(self, st):
        self._st = st

    def is_ready(self) -> bool:
        return True

    def __array__(self, dtype=None, copy=None):
        return self._st if dtype is None else self._st.astype(dtype)


def _no_equation(msg, sig, pub) -> int:
    if int.from_bytes(sig[32:], "little") >= ref.L:
        return ref.ERR_SIG
    a, r = ref.decode_point(pub), ref.decode_point(sig[:32])
    if a is None or r is None or ref.is_small_order(a):
        return ref.ERR_PUBKEY
    if ref.is_small_order(r):
        return ref.ERR_SIG
    return ref.SUCCESS


def _no_point_checks(msg, sig, pub) -> int:
    if int.from_bytes(sig[32:], "little") >= ref.L:
        return ref.ERR_SIG
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return ref.ERR_MSG
    return ref.SUCCESS


RULES = {"no_equation": _no_equation, "no_point_checks": _no_point_checks}
CONTROLS = {"leader_ingest": ("no_equation",),
            "sigverify_offload": ("no_point_checks", "no_equation")}


def engine_fn(rule):
    def fn(msgs, lens, sigs, pubs):
        msgs, lens, sigs, pubs = (np.asarray(a) for a in (msgs, lens, sigs,
                                                          pubs))
        st = np.zeros(len(lens), np.int32)
        for i in np.flatnonzero(lens > 0).tolist():
            st[i] = rule(msgs[i, :lens[i]].tobytes(), sigs[i].tobytes(),
                         pubs[i].tobytes())
        return Ready(st)

    return fn


def hook(name: str):
    rule = RULES[name]

    def install(entry):
        saved = entry.fn
        entry.fn = engine_fn(rule)

        def restore():
            entry.fn = saved

        return restore

    return install
