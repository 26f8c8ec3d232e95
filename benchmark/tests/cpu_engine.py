"""A host stand-in for the device verify engine, and faults to plant in it.

`installed(batch, max_msg_len, fault)` registers, under the spec the
runners acquire, an entry whose `fn` verifies with the program's native
CPU verifier and hands back an object with the surface the dispatchers
read (is_ready, __array__, used_fallback). A fault breaks the answer the
way a broken device path would:

  unchanged   the output buffer is returned as it was (all zeros)
  half        the second half of the batch's filled lanes is never
              verified (zeros)
  altered     one lane's status is changed where it is produced
  no_equation the batch equation is skipped: a lane that fails only it
              (a bad signature over keys and R that decode) reads valid

`fallback_always` answers right but reports every batch as fallen back
to the per-lane pass, as an engine whose batch equation never holds
does; a cell whose batches must pass the equation refuses it. And
`compiling` (not a wrong answer) makes every call trace and compile a
new program, which a run must refuse inside its window.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark import control
from benchmark.control import Ready

FAULTS = ("unchanged", "half", "altered", "no_equation")


def verifier(fault=None):
    from firedancer_tpu.ballet.ed25519 import native

    def fn(msgs, lens, sigs, pubs):
        msgs, lens, sigs, pubs = (np.asarray(a) for a in (msgs, lens, sigs,
                                                          pubs))
        n = len(lens)
        if fault == "compiling":
            import jax
            import jax.numpy as jnp

            jax.jit(lambda x: x + 1)(jnp.zeros(1)).block_until_ready()
        if fault == "unchanged":
            return Ready(np.zeros(n, np.int32))
        st = np.asarray(native.verify_arrays(
            np.ascontiguousarray(msgs), lens.astype(np.uint32),
            np.ascontiguousarray(sigs), np.ascontiguousarray(pubs), n),
            np.int32).copy()
        if fault == "half":
            live = int((lens > 0).sum())
            st[live // 2:live] = 0
        elif fault == "altered":
            i = int(np.flatnonzero(lens > 0)[0]) if (lens > 0).any() else 0
            st[i] = -1 if st[i] == 0 else 0
        elif fault == "no_equation":
            for i in np.flatnonzero((st != 0) & (lens > 0)).tolist():
                st[i] = control.RULES["no_equation"](
                    msgs[i, :lens[i]].tobytes(), sigs[i].tobytes(),
                    pubs[i].tobytes())
        out = Ready(st)
        if fault == "fallback_always":
            out.used_fallback = True
        return out

    return fn


@contextlib.contextmanager
def installed(batch: int, max_msg_len: int, fault=None, mode: str = "rlc"):
    from firedancer_tpu.disco import engine as fd_engine

    reg = fd_engine.registry()
    spec = fd_engine.EngineSpec.for_tile("tpu", mode, batch, 0)
    e = reg.entry(spec)
    saved = (e.fn, e.state, set(e._warmed))
    e.fn = verifier(fault)
    # The native library loads (and may build) on first use: do it now,
    # as the real engine's warm would, not inside a window.
    np.asarray(e.fn(np.zeros((1, max_msg_len), np.uint8),
                    np.zeros(1, np.int32), np.zeros((1, 64), np.uint8),
                    np.zeros((1, 32), np.uint8)))
    e.state = fd_engine.ENGINE_WARM
    e._warmed.add((batch, max_msg_len))
    try:
        yield e
    finally:
        e.fn, e.state, e._warmed = saved[0], saved[1], saved[2]
        reg.retire([spec])
