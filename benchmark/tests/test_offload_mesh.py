"""The four-chip offload cell on the CPU: the per-chip reduction of a
trace and its readers on hand-made events, the check batches' lanes on
their chips, and whole runs at a small batch with a host stand-in for
the sharded engine (as cpu_engine.py stands in for the one-chip one)."""

import contextlib
import copy
import json
import os

import numpy as np
import pytest

import cpu_engine
from benchmark import chips, control, harness

CELL = "sigverify_offload_dp4.clean"
MS = 1_000_000
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_ms * MS), "dur_ns": int(dur_ms * MS)}


def four_chip_events(with_tail=True):
    """A 100 ms window over four chips: each runs jit_pod_local twice
    (20 ms, then 30 ms) and, where with_tail, jit_pod_tail once (4 ms);
    chip i idles 10 * i ms more than chip 0 (its ops end earlier)."""
    out = [ev("/host:CPU", "python", "bench.window", 0, 100),
           ev("/host:CPU", "python", "bench.put", 60, 5)]
    for i, plane in enumerate(PLANES):
        out += [ev(plane, "XLA Modules", "jit_pod_local(7)", 0, 20),
                ev(plane, "XLA Modules", "jit_pod_local(7)", 40, 30),
                ev(plane, "XLA Ops", "fusion.1", 0, 20),
                ev(plane, "XLA Ops", "fusion.2", 40, 30 - 10 * i)]
        if with_tail:
            out += [ev(plane, "XLA Modules", "jit_pod_tail(9)", 90, 4),
                    ev(plane, "XLA Ops", "all-gather", 90, 4)]
        # A program that started before the window is not counted.
        out.append(ev(plane, "XLA Modules", "jit_pod_local(7)", -30, 25))
    return out


def reading(events):
    return {"planes": chips.per_plane(events)}


def read(metric, rd):
    mod = harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "m_" + metric)
    return mod.read(rd)


def test_per_plane_reduction():
    planes = chips.per_plane(four_chip_events())
    assert sorted(planes) == PLANES
    # Chip i: 20 + (30 - 10 i) + 4 ms busy of 100.
    for i, plane in enumerate(PLANES):
        p = planes[plane]
        assert p["busy_share"] == pytest.approx((54 - 10 * i) / 100)
        assert p["modules"]["jit_pod_local"] == {
            "count": 2, "s": pytest.approx(0.05)}
        assert p["modules"]["jit_pod_tail"] == {
            "count": 1, "s": pytest.approx(0.004)}


def test_readers_on_four_planes():
    rd = reading(four_chip_events())
    assert read("device_idle_share.dp4_max", rd) == pytest.approx(76.0)
    assert read("shard_busy_spread", rd) == pytest.approx(30.0)
    assert read("kernel_ms.pod_local", rd) == pytest.approx(25.0)
    assert read("kernel_ms.pod_tail", rd) == pytest.approx(4.0)


def test_readers_read_nothing_where_their_names_are_absent():
    rd = reading(four_chip_events(with_tail=False))
    assert read("kernel_ms.pod_tail", rd) is None
    assert read("kernel_ms.pod_local", rd) == pytest.approx(25.0)
    # A one-chip program's trace holds neither program.
    one = [e for e in four_chip_events() if e["plane"] in (
        "/host:CPU", PLANES[0])]
    for e in one:
        e["name"] = e["name"].replace("pod_local", "verify_batch_rlc")
    rd = reading(one)
    assert read("kernel_ms.pod_local", rd) is None
    assert read("shard_busy_spread", rd) is None
    for metric in ("device_idle_share.dp4_max", "shard_busy_spread",
                   "kernel_ms.pod_local", "kernel_ms.pod_tail",
                   "rlc_weights_ms"):
        assert read(metric, {}) is None


def test_rlc_weights_reader():
    c0 = {"rlc_weights_ns": 1_000_000, "rlc_weights_calls": 10}
    c1 = {"rlc_weights_ns": 41_000_000, "rlc_weights_calls": 30}
    assert read("rlc_weights_ms", {"weights": (c0, c1)}) == pytest.approx(2.0)
    # No dispatch between the edges, or a program without the counters.
    assert read("rlc_weights_ms", {"weights": (c0, c0)}) is None
    none = {"rlc_weights_ns": None, "rlc_weights_calls": None}
    assert read("rlc_weights_ms", {"weights": (none, none)}) is None


def test_check_batch_lanes_sit_on_their_chip():
    runner = harness.load_runner(harness.load_cell(CELL))
    b, n = 64, 2
    arrays = (np.zeros((b, 8), np.uint8), np.full(b, 8, np.int32),
              np.zeros((b, 64), np.uint8), np.zeros((b, 32), np.uint8))
    want = np.zeros(b, np.int32)
    want[[0, 17, 33]] = -2                  # canaries are never drawn
    for chip in range(4):
        (msgs, *_), lanes = runner.chip_check_batch(
            arrays, want, n, 2**31 + 7, chip, 4)
        assert len(lanes) == n
        assert all(chip * 16 <= lane < (chip + 1) * 16 for lane in lanes)
        assert not set(lanes) & {0, 17, 33}
        flipped = np.flatnonzero((msgs != arrays[0]).any(axis=1))
        assert flipped.tolist() == lanes


# -------------------------------------------------- whole runs, CPU ----

BATCH = 64
SEED = 2**31 + 41
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 4}


@contextlib.contextmanager
def stand_in(fault=None):
    """cpu_engine.verifier under the sharded spec the runner acquires."""
    from firedancer_tpu.disco import engine as fd_engine

    reg = fd_engine.registry()
    spec = fd_engine.EngineSpec.for_tile("tpu", "rlc", BATCH, 4)
    e = reg.entry(spec)
    e.fn = cpu_engine.verifier(fault)
    np.asarray(e.fn(np.zeros((1, 8), np.uint8), np.zeros(1, np.int32),
                    np.zeros((1, 64), np.uint8), np.zeros((1, 32), np.uint8)))
    e.state = fd_engine.ENGINE_WARM
    e._warmed.add((BATCH, 1232))
    try:
        yield e
    finally:
        reg.retire([spec])


def run(fault=None, ctl=None, trace=False, seconds=1.5):
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config"]["engine"]["batch"] = BATCH
    cell["traffic"]["distinct_batches"] = 2
    runner = harness.load_runner(cell)
    details = {}
    with stand_in(fault):
        line = runner.run(cell, seed=SEED, seconds=seconds, trace=trace,
                          device=DEVICE, t_proc0=harness.now_ns(),
                          engine_hook=control.hook(ctl) if ctl else None,
                          details=details)
    return json.loads(line), details


def test_sound_run_is_correct_with_a_check_on_every_chip():
    res, details = run()
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"verify_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    for chip, (lanes, st) in enumerate(zip(
            details["equation_lanes"], details["equation_lane_statuses"])):
        assert all(chip * 16 <= lane < (chip + 1) * 16 for lane in lanes)
        assert st == [-3, -3]


def test_skipped_equation_is_wrong_on_every_chip():
    res, _ = run(fault="no_equation")
    assert res["correct"] is False
    assert res["compared"]["equation_batch_wrong"]["value"] == 2 * 4
    assert res["compared"]["wrong_statuses"]["value"] == 0


@pytest.mark.parametrize("ctl", ["no_equation", "no_point_checks"])
def test_control_is_not_correct(ctl):
    res, _ = run(ctl=ctl)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_traced_run_reports_engine_warm_and_no_chip_metric_on_the_cpu():
    res, _ = run(trace=True, seconds=2.5)
    assert res["correct"] is True
    # No device plane on the CPU: the per-chip readers read nothing;
    # the stand-in keeps no weight counters either.
    assert set(res["metrics"]) == {"engine_warm_s"}
