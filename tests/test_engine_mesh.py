"""The sharded rlc engine through the registry's normal path.

EngineRegistry.acquire / EngineEntry.put / EngineEntry.fn at shards=4
on the virtual CPU mesh (conftest forces 8 devices), at a global batch
of 16 lanes (4 a shard): the split pair (local fill per shard, one
combine across the mesh) and the sharded per-lane fallback, against
the CPU oracle. The engine is built and warmed once for the module.
"""

import numpy as np
import pytest

from firedancer_tpu.ballet import ed25519 as oracle
from firedancer_tpu.disco.engine import EngineRegistry, EngineSpec

SHARDS = 4
B = 16
PER = B // SHARDS
MAX_LEN = 64
K = 8
L_BYTES = oracle.L.to_bytes(32, "little")
IDENTITY = (1).to_bytes(32, "little")        # small order: ERR_PUBKEY
UNDECODABLE = (2).to_bytes(32, "little")     # y = 2: ERR_PUBKEY


def _batch():
    """B signed lanes, with a canary the point checks settle on every
    shard (so the batch equation holds and no lane needs the fallback)."""
    rng = np.random.RandomState(25)
    msgs = np.zeros((B, MAX_LEN), np.uint8)
    lens = np.zeros(B, np.int32)
    sigs = np.zeros((B, 64), np.uint8)
    pubs = np.zeros((B, 32), np.uint8)
    for i in range(B):
        seed = bytes([i + 1, 25]) + bytes(30)
        _, _, pub = oracle.keypair_from_seed(seed)
        m = rng.randint(0, 256, rng.randint(1, MAX_LEN), dtype=np.uint8)
        msgs[i, :len(m)] = m
        lens[i] = len(m)
        sigs[i] = np.frombuffer(oracle.sign(m.tobytes(), seed), np.uint8)
        pubs[i] = np.frombuffer(pub, np.uint8)
    sigs[1, 32:] = np.frombuffer(L_BYTES, np.uint8)         # shard 0
    pubs[6] = np.frombuffer(UNDECODABLE, np.uint8)           # shard 1
    pubs[9] = np.frombuffer(IDENTITY, np.uint8)              # shard 2
    sigs[14, :32] = np.frombuffer(IDENTITY, np.uint8)        # shard 3
    return msgs, lens, sigs, pubs


def _oracle(msgs, lens, sigs, pubs, lanes=range(B)):
    return np.array([oracle.verify(msgs[i, :lens[i]].tobytes(),
                                   sigs[i].tobytes(), pubs[i].tobytes())
                     for i in lanes], np.int32)


@pytest.fixture(scope="module")
def clean():
    """The batch and its oracle statuses, made once (the Python oracle
    signs and verifies slowly)."""
    arrays = _batch()
    return arrays, _oracle(*arrays)


@pytest.fixture(scope="module")
def engine():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FD_RLC_TORSION_K", str(K))
        mp.setenv("FD_DRAIN", "off")
        spec = EngineSpec.for_tile("tpu", "rlc", B, SHARDS)
        entry, _ = EngineRegistry().acquire(spec, warm=True,
                                            max_msg_len=MAX_LEN)
        yield entry


def _run(entry, arrays):
    out = entry.fn(*[entry.put(a) for a in arrays])
    return np.asarray(out), out.used_fallback


def test_clean_batch_matches_oracle_without_fallback(engine, clean):
    arrays, want = clean
    assert sorted(set(want.tolist())) == [-2, -1, 0]
    got, fell_back = _run(engine, arrays)
    assert not fell_back
    assert (got == want).all()
    assert engine.fn_local is not None and engine.fn_tail is not None


@pytest.mark.parametrize("shard", range(SHARDS))
def test_equation_lane_on_each_shard_fails_the_global_batch(engine, clean,
                                                           shard):
    """A lane that fails only the batch equation, on shard `shard`:
    the one global verdict is false, and the per-lane pass reads every
    lane as the oracle does."""
    (msgs, lens, sigs, pubs), want = clean
    msgs = msgs.copy()
    lane = shard * PER                       # no canary sits there
    msgs[lane, 0] ^= 0x01
    want = want.copy()
    want[lane] = _oracle(msgs, lens, sigs, pubs, lanes=[lane])[0]
    assert want[lane] == oracle.FD_ED25519_ERR_MSG
    got, fell_back = _run(engine, (msgs, lens, sigs, pubs))
    assert fell_back
    assert (got == want).all()


def test_weights_arrive_sharded_by_lane(monkeypatch, clean):
    """z and u go from the host to the chips that use them: each device
    holds only its own lanes' rows of z and its own lanes' columns of
    both halves of u, in the (K, 2, B) layout. put() books each shard's
    lanes with a message, and the verifier books its weight work."""
    import jax.numpy as jnp

    from firedancer_tpu.ops import verify_rlc
    from firedancer_tpu.parallel import mesh as mesh_mod

    drawn = np.arange(K * 2 * B, dtype=np.int32).reshape(K, 2 * B) % 128
    monkeypatch.setattr(verify_rlc, "fresh_u", lambda k, n, rng=None:
                        drawn[:k, :n].copy())
    seen = {}

    def split(mesh, plan=None):
        def local(msgs, lens, sigs, pubs, z, u):
            seen.update(z=z, u=u)
            zero = jnp.zeros(B, jnp.int32)
            return zero, jnp.zeros(B, bool), None

        return local, lambda parts: jnp.asarray(True)

    monkeypatch.setattr(mesh_mod, "verify_rlc_split_sharded", split)
    monkeypatch.setenv("FD_RLC_TORSION_K", str(K))
    monkeypatch.setenv("FD_DRAIN", "off")
    entry, _ = EngineRegistry().acquire(
        EngineSpec.for_tile("tpu", "rlc", B, SHARDS), warm=False)
    (msgs, lens, sigs, pubs), _ = clean
    lens = lens.copy()
    lens[PER:PER + 3] = 0                    # shard 1 carries one lane
    np.asarray(entry.fn(*[entry.put(a) for a in (msgs, lens, sigs, pubs)]))

    z, u = seen["z"], seen["u"]
    assert z.shape == (B, 32) and u.shape == (K, 2, B)
    devices = list(entry.in_sharding.mesh.devices.flat)
    assert len(z.addressable_shards) == len(u.addressable_shards) == SHARDS
    for sh in z.addressable_shards:
        i = devices.index(sh.device)
        assert sh.index[0] == slice(i * PER, (i + 1) * PER)
        assert sh.data.shape == (PER, 32)
    for sh in u.addressable_shards:
        i = devices.index(sh.device)
        assert sh.data.shape == (K, 2, PER)
        own = slice(i * PER, (i + 1) * PER)
        np.testing.assert_array_equal(np.asarray(sh.data[:, 0]),
                                      drawn[:, own])
        np.testing.assert_array_equal(
            np.asarray(sh.data[:, 1]),
            drawn[:, B + i * PER:B + (i + 1) * PER])
    assert entry.shard_lanes == [PER, 1, PER, PER]
    assert entry.rlc_weights_calls == 1 and entry.rlc_weights_ns > 0
