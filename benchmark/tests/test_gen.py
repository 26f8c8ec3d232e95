"""The generators: the same seed gives the same traffic, every seed the
same work, and the reference reads what they make as intended."""

import numpy as np
import pytest

from benchmark import harness
from benchmark.gen import batches, corpus
from benchmark.ref import ed25519, judge, txn

MIX = harness.load_json(harness.ROOT + "/benchmark/traffic/mainnet_paced.json")["mix"]
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def pool():
    p = harness.worker_pool(2)
    yield p
    p.terminate()
    p.join()


def test_stream_same_seed_same_bytes_and_same_work_across_seeds(pool):
    a, ka = corpus.stream(MIX, 3000, SEED, pool)
    b, kb = corpus.stream(MIX, 3000, SEED, pool)
    c, kc = corpus.stream(MIX, 3000, SEED + 1, pool)
    assert a == b and (ka == kb).all()
    assert a != c
    assert np.bincount(ka).tolist() == np.bincount(kc).tolist()
    assert np.bincount(ka).tolist() == [2730, 150, 90, 30]
    assert all(len(p) <= txn.MTU for p in a)
    shape_a = corpus.plan(MIX, 1000, SEED)
    shape_c = corpus.plan(MIX, 1000, SEED + 1)
    for k in shape_a:
        assert sorted(shape_a[k].tolist()) == sorted(shape_c[k].tolist())


def test_stream_kinds_read_as_intended(pool):
    payloads, kinds = corpus.stream(MIX, 600, SEED, pool)
    for p, k in zip(payloads, kinds.tolist()):
        if k == corpus.GARBAGE:
            with pytest.raises(txn.ParseError):
                txn.parse(p)
            continue
        sigs, pubs, msg = txn.parse(p)
        st = [ed25519.status(msg, s, q) for s, q in zip(sigs, pubs)]
        if k == corpus.BAD_SIG:
            assert st[0] != ed25519.SUCCESS
        else:
            assert st == [ed25519.SUCCESS] * len(sigs)
    want = judge.sink_expected(payloads, pool)
    assert sum(want.values()) == int((kinds == corpus.OK).sum())


def test_offload_batches_canaries(pool):
    items = corpus.lanes(MIX, 2 * 64, SEED, pool)
    got = batches.offload_batches(items, 2, 64, 1232, 2, SEED)
    assert len(got) == 2
    for arrays in got:
        st = judge.lane_statuses(arrays, pool)
        assert (st == ed25519.ERR_SIG).sum() == 4
        assert (st == ed25519.ERR_PUBKEY).sum() == 4
        assert (st == ed25519.SUCCESS).sum() == 56
        # Canaries in both halves: a verifier that skips half the batch
        # misreads some.
        assert (st[:32] != 0).any() and (st[32:] != 0).any()


def test_equation_batch_fails_only_the_equation(pool):
    from firedancer_tpu.ballet.ed25519 import native

    items = corpus.lanes(MIX, 64, SEED, pool)
    arrays = batches.offload_batches(items, 1, 64, 1232, 2, SEED)[0]
    want = judge.lane_statuses(arrays, pool)
    eq, lanes = batches.equation_batch(arrays, want, 2, SEED)
    assert len(lanes) == 2 and (want[lanes] == ed25519.SUCCESS).all()
    st = judge.lane_statuses(eq, pool)
    assert (st[lanes] == ed25519.ERR_MSG).all()
    rest = np.setdiff1d(np.arange(64), lanes)
    assert (st[rest] == want[rest]).all()
    # A second witness: the program's native CPU verifier reads the same.
    msgs, lens, sigs, pubs = eq
    got = np.asarray(native.verify_arrays(msgs, lens.astype(np.uint32), sigs,
                                          pubs, 64), np.int32)
    assert (got == st).all()
    assert batches.equation_batch(arrays, want, 2, SEED)[1] == lanes


def test_reference_rfc8032_vector():
    # RFC 8032 section 7.1, TEST 1 (empty message).
    pub = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a"
                        "0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex("e5564300c360ac729086e2cc806e828a"
                        "84877f1eb8e5d974d873e06522490155"
                        "5fb8821590a33bacc61e39701cf9b46b"
                        "d25bf5f0595bbe24655141438e7a100b")
    assert ed25519.status(b"", sig, pub) == ed25519.SUCCESS
    bad = bytearray(sig)
    bad[40] ^= 1  # s stays below L; the equation fails
    assert ed25519.status(b"", bytes(bad), pub) == ed25519.ERR_MSG
    assert ed25519.status(b"x", sig, pub) == ed25519.ERR_MSG
    assert ed25519.status(b"", sig[:32] + batches.L_BYTES, pub) == \
        ed25519.ERR_SIG
    assert ed25519.status(b"", sig, batches.IDENTITY) == ed25519.ERR_PUBKEY
    assert ed25519.status(b"", sig, batches.UNDECODABLE) == ed25519.ERR_PUBKEY
    assert ed25519.status(b"", batches.ORDER2 + sig[32:], pub) == \
        ed25519.ERR_SIG


def test_judge_catches_a_dropped_and_an_extra_digest(pool):
    import hashlib
    from collections import Counter

    payloads, kinds = corpus.stream(MIX, 400, SEED, pool)
    want = judge.sink_expected(payloads, pool)
    ok = [hashlib.sha256(p).digest() for p, k in zip(payloads, kinds)
          if k == corpus.OK]
    assert judge.delta(want, Counter(ok)) == (0, 0)
    assert judge.delta(want, Counter(ok[1:])) == (1, 0)
    bad = next(p for p, k in zip(payloads, kinds) if k == corpus.BAD_SIG)
    assert judge.delta(want, Counter(ok + [hashlib.sha256(bad).digest()])) \
        == (0, 1)
    assert judge.delta(want, Counter(ok + ok[:1])) == (0, 1)
