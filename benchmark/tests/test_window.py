"""Window accounting from the sink's sampled count, the per-layer
readers over counter snapshots, and the wrap guard."""

import numpy as np
import pytest

from benchmark import harness
from benchmark.runners import leader_ingest as li

MS = 1_000_000


def test_count_at_reads_the_last_sample_at_or_before():
    t = np.array([10, 20, 30, 40], np.int64)
    c = np.array([0, 5, 9, 12], np.int64)
    assert li.count_at(t, c, 5) == 0
    assert li.count_at(t, c, 20) == 5
    assert li.count_at(t, c, 39) == 9
    assert li.count_at(t, c, 100) == 12
    # The rate over a window is the count's rise over the window.
    assert (li.count_at(t, c, 40) - li.count_at(t, c, 20)) == 7


def test_arrivals_bounded_by_the_first_sample_that_holds_them():
    # Three payloads due at 0, 10 and 20 ms; the sink's count reads 1 at
    # 15 ms, 3 at 40 ms. A window of [5, 30) ms holds the last two.
    due = np.array([0, 10, 20], np.int64) * MS
    first = {b"a": 0, b"b": 1, b"c": 2}
    sink_t = np.array([1, 15, 40], np.int64) * MS
    sink_c = np.array([0, 1, 3], np.int64)
    got = li.arrival_latencies([b"a", b"b", b"c"], first, due, sink_t,
                               sink_c, 5 * MS, 30 * MS)
    assert got == [(10 * MS, 30 * MS), (20 * MS, 20 * MS)]
    # A digest the source never published is not timed (the reference
    # counts it as unexpected).
    assert li.arrival_latencies([b"z"], first, due, sink_t, sink_c, 0,
                                30 * MS) == []
    with pytest.raises(harness.RunError):
        li.arrival_latencies([b"a", b"b", b"c", b"c"], first, due, sink_t,
                             sink_c, 0, 30 * MS)


def test_wrap_guard_fails_a_run_with_a_late_transaction():
    li.check_wrap([MS, 4_000 * MS])
    with pytest.raises(harness.RunError, match="wrap"):
        li.check_wrap([MS, (1 << 32)])


def _reader(name):
    return harness.load_module(
        f"{harness.BENCH_DIR}/metrics/{name}.py", "m_" + name.replace(".", "_"))


def test_counter_readers_take_window_deltas():
    c0 = {"batches": 10, "lanes": 1000, "rlc_fallback": 9}
    c1 = {"batches": 30, "lanes": 9192, "rlc_fallback": 29}
    r = {"batch": 8192, "c0": c0, "c1": c1}
    assert _reader("feed_fill_ratio").read(r) == pytest.approx(
        8192 / (20 * 8192))
    assert _reader("rlc_fallback_share").read(
        {"run_end": {"batches": 40, "rlc_fallback": 38}}) == 0.95
    assert _reader("rlc_fallback_share").read({"run_end": None}) is None
    # Nothing dispatched in the window: nothing to read.
    assert _reader("feed_fill_ratio").read(
        {"batch": 8192, "c0": c0, "c1": c0}) is None
    lags = list(range(1, 101))
    assert _reader("source_lag_p99_ms").read(
        {"source_lag_ns": [x * MS for x in lags]}) == 99.0
    assert _reader("verify_hop_p99_ms").read({"verify_hop_ns": []}) is None
    assert _reader("engine_warm_s").read({"engine_warm_s": 81.5}) == 81.5


def test_percentile_is_nearest_rank():
    assert harness.percentile(range(1, 101), 99) == 99
    assert harness.percentile([5], 99) == 5
    assert harness.percentile(range(1, 1001), 50) == 500
