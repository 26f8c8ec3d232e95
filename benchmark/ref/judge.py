"""What the reference says a run must produce, worked out in workers.

leader_ingest: the multiset of SHA-256 digests the sink must receive
for the payloads the source published — each payload that parses and
whose every signature verifies, once, however often it was sent.

sigverify_offload: every lane's status.
"""

from __future__ import annotations

import hashlib
from collections import Counter

import numpy as np

from benchmark.ref import ed25519, txn

TASK = 4096  # payloads or lanes per worker task


def _valid_digests(payloads: list) -> list:
    out = []
    for p in payloads:
        try:
            sigs, pubs, msg = txn.parse(p)
        except txn.ParseError:
            continue
        if all(ed25519.status(msg, s, k) == ed25519.SUCCESS
               for s, k in zip(sigs, pubs)):
            out.append(hashlib.sha256(p).digest())
    return out


def sink_expected(payloads: list, pool) -> Counter:
    tasks = [payloads[i:i + TASK] for i in range(0, len(payloads), TASK)]
    return Counter(set(d for part in pool.map(_valid_digests, tasks)
                       for d in part))


def _statuses(args) -> np.ndarray:
    msgs, lens, sigs, pubs = args
    return np.array([ed25519.status(msgs[i, :lens[i]].tobytes(),
                                    sigs[i].tobytes(), pubs[i].tobytes())
                     for i in range(len(lens))], np.int32)


def lane_statuses(arrays, pool) -> np.ndarray:
    msgs, lens, sigs, pubs = arrays
    tasks = [(msgs[i:i + TASK], lens[i:i + TASK], sigs[i:i + TASK],
              pubs[i:i + TASK]) for i in range(0, len(lens), TASK)]
    return np.concatenate(pool.map(_statuses, tasks))


def delta(want: Counter, got: Counter) -> tuple[int, int]:
    """(missing, unexpected): what the sink lacks, and what it holds
    that it must not (an invalid payload, or a second copy)."""
    return sum((want - got).values()), sum((got - want).values())
