"""The mainnet-shaped transaction stream, made from a seed.

Shape (a traffic file's "mix" section names each share):

  * signer counts 1-4 in fixed shares (most transactions have one
    signer; multisig is a tail),
  * legacy and v0 (address lookup table) messages,
  * a share carrying ComputeBudget instructions (unit limit and price,
    what a leader's pack orders by),
  * instruction data of a fixed range of sizes, so messages spread up
    to the MTU,
  * exact duplicates of a recent transaction (the dedup stage's work),
  * transactions with one corrupted signature byte (the verify stage's
    work: the batch equation fails, so each such batch falls back),
  * truncated copies of a recent transaction (the parse stage's work).

Every seed gives the same multiset of sizes and kinds, in another order,
so two seeds ask for the same work. Signing runs in worker processes in
fixed chunks, so the stream does not depend on the number of workers.
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np

from benchmark.gen import txn

OK, DUP, BAD_SIG, GARBAGE = 0, 1, 2, 3
CHUNK = 2048  # transactions per signing task


def _exact(n: int, shares: dict, rng) -> np.ndarray:
    """n labels with exactly round(share * n) of each (the remainder
    goes to the largest share), shuffled."""
    keys = list(shares)
    counts = [int(round(shares[k] * n)) for k in keys]
    counts[int(np.argmax([shares[k] for k in keys]))] += n - sum(counts)
    out = np.concatenate([np.full(c, int(k), np.int64)
                          for k, c in zip(keys, counts)])
    return rng.permutation(out)


def _flags(n: int, share: float, rng) -> np.ndarray:
    k = int(round(share * n))
    return rng.permutation(np.arange(n) < k)


def plan(mix: dict, n_signed: int, seed: int) -> dict:
    """Per-transaction shape of n_signed signed transactions: signer
    counts, data sizes, v0 and budget flags — the same multiset for
    every seed."""
    rng = np.random.default_rng([seed, 1])
    lo, hi = mix["data_bytes"]
    sizes = lo + (np.arange(n_signed) * (hi - lo)) // max(1, n_signed)
    return {
        "signers": _exact(n_signed, mix["signers"], rng),
        "data_sz": rng.permutation(sizes),
        "v0": _flags(n_signed, mix["v0_share"], rng),
        "budget": _flags(n_signed, mix["budget_share"], rng),
    }


def signer_seed(seed: int, i: int, j: int) -> bytes:
    return hashlib.blake2b(struct.pack("<QQQB", seed & (2**64 - 1),
                                       seed >> 64, i, j),
                           digest_size=32).digest()


def sign_chunk(args) -> list:
    """One signing task: [(payload, [(msg, sig, pub)...])...] for the
    transactions start .. start+len(signers)-1. Runs in a worker."""
    seed, start, signers, data_sz, v0, budget = args
    rng = np.random.default_rng([seed, 2, start])
    out = []
    for k in range(len(signers)):
        i = start + k
        n_sign = int(signers[k])
        keys, pubs = zip(*(txn.keypair(signer_seed(seed, i, j))
                           for j in range(n_sign)))
        extra = [txn.COMPUTE_BUDGET_PROGRAM, rng.bytes(32), rng.bytes(32)]
        instrs = []
        if budget[k]:
            instrs.append((n_sign, [], b"\x02" + struct.pack(
                "<I", int(rng.integers(50_000, 1_400_000)))))
            instrs.append((n_sign, [], b"\x03" + struct.pack(
                "<Q", int(rng.integers(0, 3_000_000)))))
        data = rng.bytes(int(data_sz[k]))
        instrs.append((n_sign + 1, [0], data))
        luts = ([(rng.bytes(32), [int(rng.integers(0, 64))],
                  [int(rng.integers(0, 64))])] if v0[k] else None)
        blockhash = rng.bytes(32)
        msg = txn.message(list(pubs), extra, len(extra), blockhash, instrs,
                          luts)
        over = len(msg) + 1 + 64 * n_sign - txn.MTU
        if over > 0:
            # A fat multisig draw can pass the MTU: trim its data to fit,
            # as a client would have to.
            instrs[-1] = (n_sign + 1, [0], data[:len(data) - over])
            msg = txn.message(list(pubs), extra, len(extra), blockhash,
                              instrs, luts)
        payload = txn.transaction(list(keys), msg)
        sigs = [payload[1 + 64 * j: 65 + 64 * j] for j in range(n_sign)]
        out.append((payload, [(msg, s, p) for s, p in zip(sigs, pubs)]))
    return out


def sign_tasks(seed: int, shape: dict) -> list:
    n = len(shape["signers"])
    return [(seed, s, shape["signers"][s:s + CHUNK],
             shape["data_sz"][s:s + CHUNK], shape["v0"][s:s + CHUNK],
             shape["budget"][s:s + CHUNK]) for s in range(0, n, CHUNK)]


def stream(mix: dict, n_total: int, seed: int, pool) -> tuple[list, np.ndarray]:
    """(payloads, kinds) of the n_total-long stream, in publish order."""
    rng = np.random.default_rng([seed, 3])
    kinds = _exact(n_total, {str(OK): mix["ok_share"],
                             str(DUP): mix["dup_share"],
                             str(BAD_SIG): mix["bad_sig_share"],
                             str(GARBAGE): mix["garbage_share"]}, rng)
    # The first transaction has nothing before it to copy.
    first = int(np.argmax(kinds == OK))
    kinds[[0, first]] = kinds[[first, 0]]
    n_signed = int(((kinds == OK) | (kinds == BAD_SIG)).sum())
    shape = plan(mix, n_signed, seed)
    signed = [p for chunk in pool.map(sign_chunk, sign_tasks(seed, shape))
              for p, _ in chunk]
    window = int(mix["copy_window"])
    payloads, recent_ok = [], []
    nxt = 0
    for kind in kinds.tolist():
        if kind in (OK, BAD_SIG):
            p = signed[nxt]
            nxt += 1
            if kind == BAD_SIG:
                b = bytearray(p)
                b[1 + int(rng.integers(0, 64))] ^= 1 + int(rng.integers(0, 255))
                p = bytes(b)
            else:
                recent_ok.append(len(payloads))
                if len(recent_ok) > window:
                    recent_ok.pop(0)
        else:
            src = payloads[recent_ok[int(rng.integers(0, len(recent_ok)))]]
            if kind == DUP:
                p = src
            else:
                p = src[:int(rng.integers(1, len(src)))]
        payloads.append(p)
    return payloads, kinds


def lanes(mix: dict, n_lanes: int, seed: int, pool) -> list:
    """n_lanes (msg, sig, pub) signature requests: every signer of
    consecutive mainnet-shaped transactions, so message lengths follow
    the stream's."""
    mean_signers = sum(int(k) * v for k, v in mix["signers"].items())
    shape = plan(mix, int(n_lanes / mean_signers) + CHUNK, seed)
    out = []
    tasks = sign_tasks(seed, shape)
    for chunk in pool.imap(sign_chunk, tasks):
        for _, items in chunk:
            out.extend(items)
        if len(out) >= n_lanes:
            break
    return out[:n_lanes]
