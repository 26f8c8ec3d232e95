"""Fixed-shape signature batches for the offload cells.

A batch is four arrays, the layout the verify engine takes:
msgs (B, max_msg_len) uint8 zero-padded, lens (B,) int32,
sigs (B, 64) uint8, pubs (B, 32) uint8.

Canary lanes: a clean batch keeps a few lanes whose answer is a
failure that the engine settles before its batch equation (s >= L, an
undecodable or small-order key, a small-order R), so the batch equation
still holds and no fallback runs, while a verifier that returns its
output buffer unchanged, skips part of the batch, or accepts everything
reads them wrong. They sit one per equal stretch of the batch, so every
part of it holds some.

Equation lanes: the check batch (`equation_batch`) is a cycled batch
with a few more lanes whose message has one byte flipped. Such a lane
passes every point check and fails only the batch equation, so only an
engine whose equation really runs (and then settles the batch lane by
lane) reads it as a bad signature.
"""

from __future__ import annotations

import numpy as np

L_BYTES = (2**252 + 27742317777372353535851937790883648493).to_bytes(
    32, "little")
IDENTITY = (1).to_bytes(32, "little")        # order 1
ORDER2 = (2**255 - 20).to_bytes(32, "little")  # y = p - 1, order 2
UNDECODABLE = (2).to_bytes(32, "little")     # y = 2: no x on the curve

CANARY_KINDS = ("s_ge_l", "undecodable_a", "small_order_a", "small_order_r")


def assemble(items: list, max_msg_len: int):
    """(msgs, lens, sigs, pubs) from (msg, sig, pub) lanes."""
    b = len(items)
    msgs = np.zeros((b, max_msg_len), np.uint8)
    lens = np.zeros(b, np.int32)
    sigs = np.zeros((b, 64), np.uint8)
    pubs = np.zeros((b, 32), np.uint8)
    for i, (m, s, p) in enumerate(items):
        if len(m) > max_msg_len:
            raise ValueError(f"message of {len(m)} B over {max_msg_len}")
        msgs[i, :len(m)] = np.frombuffer(m, np.uint8)
        lens[i] = len(m)
        sigs[i] = np.frombuffer(s, np.uint8)
        pubs[i] = np.frombuffer(p, np.uint8)
    return msgs, lens, sigs, pubs


def plant_canaries(arrays, per_kind: int, rng) -> list:
    """Turn per_kind lanes of each canary kind into known failures, in
    place; returns [(lane, kind)]."""
    msgs, lens, sigs, pubs = arrays
    b = len(lens)
    n = per_kind * len(CANARY_KINDS)
    if n == 0:
        return []
    edges = np.linspace(0, b, n + 1).astype(np.int64)
    lanes = [int(rng.integers(edges[i], edges[i + 1])) for i in range(n)]
    kinds = [CANARY_KINDS[i % len(CANARY_KINDS)]
             for i in rng.permutation(n)]
    for lane, kind in zip(lanes, kinds):
        if kind == "s_ge_l":
            sigs[lane, 32:] = np.frombuffer(L_BYTES, np.uint8)
        elif kind == "undecodable_a":
            pubs[lane] = np.frombuffer(UNDECODABLE, np.uint8)
        elif kind == "small_order_a":
            pubs[lane] = np.frombuffer(IDENTITY, np.uint8)
        else:
            sigs[lane, :32] = np.frombuffer(ORDER2, np.uint8)
    return list(zip(lanes, kinds))


def offload_batches(items: list, n_batches: int, batch: int,
                    max_msg_len: int, canaries_per_kind: int, seed: int):
    """n_batches distinct batches of `batch` lanes each, from items in
    order, with canaries planted."""
    rng = np.random.default_rng([seed, 4])
    out = []
    for k in range(n_batches):
        arrays = assemble(items[k * batch:(k + 1) * batch], max_msg_len)
        plant_canaries(arrays, canaries_per_kind, rng)
        out.append(arrays)
    return out


def equation_batch(arrays, want, n_lanes: int, seed: int):
    """(copy of arrays, lanes): n_lanes lanes that the reference reads as
    valid (want == 0) get one message byte flipped, at places drawn from
    the seed."""
    rng = np.random.default_rng([seed, 5])
    msgs, lens, sigs, pubs = (a.copy() for a in arrays)
    ok = np.flatnonzero((np.asarray(want) == 0) & (lens > 0))
    lanes = sorted(int(i) for i in rng.choice(ok, n_lanes, replace=False))
    for lane in lanes:
        msgs[lane, int(rng.integers(0, lens[lane]))] ^= 0x01
    return (msgs, lens, sigs, pubs), lanes
