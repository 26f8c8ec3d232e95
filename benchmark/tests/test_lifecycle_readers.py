"""The verify-batch lifecycle readers: per-batch means of the verify
tile's phase counters over the window, and nothing to read where the
program keeps no such counters or no batch retired."""

import pytest

from benchmark import harness

MS = 1_000_000

# metric -> (numerator counter, denominator counter)
READERS = {
    "feed_ring_wait_ms": ("ring_wait_ns", "retired"),
    "feed_stage_ms": ("stage_ns", "retired"),
    "feed_ready_wait_ms": ("ready_wait_ns", "retired"),
    "verify_inflight_ms": ("inflight_ns", "retired"),
    "rlc_fallback_wait_ms": ("fallback_wait_ns", "rlc_fallback"),
    "verify_retire_ms": ("retire_ns", "retired"),
}


def _reader(name):
    return harness.load_module(f"{harness.BENCH_DIR}/metrics/{name}.py",
                               "m_" + name)


def _counters(retired, rlc_fallback, phase_ms):
    c = {"batches": retired + 1, "lanes": 520 * retired,
         "retired": retired, "rlc_fallback": rlc_fallback}
    for num, _ in READERS.values():
        c[num] = phase_ms * MS
    return c


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_takes_the_window_delta_per_batch(name):
    num, den = READERS[name]
    c0 = _counters(10, 9, 1_000)
    c1 = _counters(30, 25, 1_000)
    c1[num] = c0[num] + 40 * MS * (c1[den] - c0[den])
    assert _reader(name).read({"c0": c0, "c1": c1}) == pytest.approx(40.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_a_retired_batch(name):
    c0 = _counters(10, 9, 1_000)
    c1 = dict(c0, batches=12)            # dispatched, none retired yet
    assert _reader(name).read({"c0": c0, "c1": c1}) is None
    assert _reader(name).read({"c0": None, "c1": c1}) is None
    assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_from_a_program_without_the_counters(name):
    """The parent program's lane has no lifecycle counters: no value,
    no exception."""
    old0 = {"batches": 10, "lanes": 5_000, "rlc_fallback": 9}
    old1 = {"batches": 30, "lanes": 15_000, "rlc_fallback": 28}
    assert _reader(name).read({"c0": old0, "c1": old1}) is None


def test_fallback_wait_reads_nothing_without_a_fallback():
    c0 = _counters(10, 9, 1_000)
    c1 = _counters(30, 9, 1_000)
    assert _reader("rlc_fallback_wait_ms").read({"c0": c0, "c1": c1}) is None


def test_traced_leader_run_reports_the_lifecycle_split():
    """A small traced leader run on the CPU (the stand-in engine never
    falls back, so the fallback wait has nothing to read): the phase
    means come out beside the other per-layer metrics."""
    import json

    import cpu_engine
    from test_faults import DEVICE, SEED, small_cell

    cell = small_cell("leader_ingest.mainnet_paced")
    runner = harness.load_runner(cell)
    with cpu_engine.installed(64, cell["config"]["engine"]["max_msg_len"]):
        res = json.loads(runner.run(cell, seed=SEED, seconds=2.5, trace=True,
                                    device=DEVICE, t_proc0=harness.now_ns()))
    assert res["correct"] is True
    got = res["metrics"]
    for name in READERS:
        if name == "rlc_fallback_wait_ms":
            assert name not in got
            continue
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0, name
    assert got["verify_inflight_ms"]["value"] > 0
