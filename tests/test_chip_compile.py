"""Main-path kernels compiled for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached: what Mosaic refuses here (VMEM scope, unaligned
slices, tiling) it would refuse on the chip. Interpret-mode parity tests
cannot see those failures. Each test compiles one kernel at the served
batch (B=8192) and asserts the Pallas kernel is in the program
(tpu_custom_call), so no dispatcher quietly routed it to XLA.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and xdist workers all import this file. The
1232-byte front-end shape (about half a minute) belongs to the hand
rehearsal, not this file.
"""

import functools
import os

import pytest

B = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    import jax

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Described-chip compiles are written to the persistent cache but
    can never be read back without a chip: keep them out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text()


def _point(sds, lanes):
    import jax.numpy as jnp

    return tuple(sds((32, lanes), jnp.int32) for _ in range(4))


def test_fused_frontend_compiles(sds):
    import jax.numpy as jnp

    from firedancer_tpu.ops.frontend_pallas import frontend_rlc_pallas

    hlo = _compile(frontend_rlc_pallas, sds((B, 192), jnp.uint8),
                   sds((B,), jnp.int32), sds((B, 32), jnp.uint8),
                   sds((B, 32), jnp.uint8))
    assert "tpu_custom_call" in hlo


def test_batched_decompress_compiles(sds):
    import jax.numpy as jnp

    from firedancer_tpu.ops.curve_pallas import decompress_pallas

    # The verify paths decompress A and R stacked: 2B lanes.
    hlo = _compile(functools.partial(decompress_pallas, want_niels=True),
                   sds((2 * B, 32), jnp.uint8))
    assert "tpu_custom_call" in hlo


def test_msm_fill_and_aggregate_compile(sds):
    import jax.numpy as jnp

    from firedancer_tpu.ops import msm

    # The 253-bit MSM of the rlc pass: B lanes plus the folded u*B lane.
    fn = functools.partial(msm.msm_fast_partial,
                           n_windows=msm.WINDOWS_253)
    hlo = _compile(fn, sds((B + 1, 32), jnp.uint8), _point(sds, B + 1))
    assert hlo.count("tpu_custom_call") >= 2


def test_dsm_compiles(sds):
    import jax.numpy as jnp

    from firedancer_tpu.ops.dsm_pallas import double_scalarmult_pallas

    hlo = _compile(double_scalarmult_pallas, sds((B, 32), jnp.uint8),
                   _point(sds, B), sds((B, 32), jnp.uint8))
    assert "tpu_custom_call" in hlo


def test_served_shapes_take_the_kernels(monkeypatch):
    """The engine's kernel record at the served batch names the Pallas
    kernels for every stage once the platform resolves to the chip —
    the front end is fused at 8192 whatever max_msg_len is staged."""
    from firedancer_tpu.disco.engine import engine_kernels

    for var in ("FD_FRONTEND_IMPL", "FD_DECOMPRESS_IMPL", "FD_MSM_IMPL",
                "FD_DSM_IMPL"):
        monkeypatch.setenv(var, "pallas")
    assert engine_kernels("rlc", B) == {
        "frontend": "fused", "decompress": "pallas", "msm": "pallas",
        "dsm": "pallas"}
    assert engine_kernels("rlc", B, shards=4)["frontend"] == "fused"
    assert engine_kernels("direct", B)["frontend"] == "fused"


def test_torsion_niels_staging_is_one_row_gather(sds):
    """The torsion grid's niels staging at the served shape (2B points,
    K = 64 trials, 5-bit buckets) compiles to one gather, and to under
    half the scratch of the three column gathers it replaced
    (1,281,222,656 B)."""
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import msm

    bsz, k, nb = 2 * B, 64, 32
    rounds = msm._default_rounds(bsz, nb)

    def stage(idx, niels):
        return msm._stage_niels(None, idx, rounds, k * nb, bsz,
                                niels=niels)

    compiled = jax.jit(stage).lower(
        sds((k, nb, rounds), jnp.int32),
        tuple(sds((32, bsz), jnp.int32) for _ in range(3))).compile()
    assert compiled.as_text().count(" gather(") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1_281_222_656 // 2
