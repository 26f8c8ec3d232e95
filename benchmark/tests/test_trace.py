"""The trace reduction: on a hand-made event list, and on a small trace
recorded on a TPU v5e (tests/data) with the kernel readers over it."""

import os

import pytest

from benchmark import harness
from benchmark import trace as btrace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"
MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_ms * MS), "dur_ns": int(dur_ms * MS)}


def test_reduction_on_hand_made_events():
    events = [
        ev("/host:CPU", "python", "bench.window", 10, 100),
        ev("/host:CPU", "python", "bench.put", 40, 20),
        ev(DEV, "XLA Modules", "jit_verify_batch_rlc(1)", 5, 30),
        ev(DEV, "XLA Modules", "jit_verify_batch_rlc(1)", 70, 30),
        ev(DEV, "XLA Ops", "msm_fill", 5, 30),         # 10..35 in window
        ev(DEV, "XLA Ops", "frontend", 30, 10),        # overlaps: 30..40
        ev(DEV, "XLA Ops", "msm_fill", 70, 30),        # 70..100
        ev(DEV, "XLA Ops", "tail", 105, 20),           # 105..110
    ]
    red = btrace.reduce_events(events)
    assert red["window_s"] == pytest.approx(0.1)
    # Busy: 10..40 (30 ms) + 70..100 (30) + 105..110 (5) = 65 ms.
    assert red["busy_s"] == pytest.approx(0.065)
    assert red["idle_share"] == pytest.approx(0.35)
    assert red["ops_s"]["msm_fill"] == pytest.approx(0.055)
    assert red["modules"] == {"jit_verify_batch_rlc(1)": 1}
    gaps = dict((round(s, 6), name) for name, s in red["gaps"])
    assert gaps == {0.03: "bench.put", 0.005: "unattributed"}
    bd = btrace.breakdown(red)
    assert bd["device_ops"][0] == ["msm_fill", pytest.approx(0.055)]
    assert len(bd["idle_gaps"]) == 2
    assert btrace.op_time_s(red, ("msm_fill", "tail")) == pytest.approx(0.06)


def test_reduction_needs_the_window_span():
    with pytest.raises(ValueError):
        btrace.reduce_events([ev(DEV, "XLA Ops", "x", 0, 1)])


def test_reduction_on_a_recorded_chip_trace():
    # 0.35 s of sigverify_offload.clean on a TPU v5 lite (seed 201),
    # normalised events cut to a stretch of the recorded window.
    path = os.path.join(DATA, "offload_clean_v5e.events.json.gz")
    assert os.path.getsize(path) < 1 << 20
    red = btrace.reduce_events(btrace.load_events(path))
    assert red["device_planes"] == 1
    assert red["window_s"] == pytest.approx(0.35)
    assert 0.9 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert red["modules"] == {"jit_verify_batch_rlc(11979009812956978351)": 5}
    bd = btrace.breakdown(red)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    got = {}
    for name in ("kernel_ms.msm", "kernel_ms.frontend",
                 "device_idle_share.offload"):
        mod = harness.load_module(
            os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
            "t_" + name.replace(".", "_"))
        got[name] = mod.read({"trace": red})
    # Per rlc batch: the MSM kernels take some 17 ms and the front end
    # well under 1 ms of a program of about 85 ms.
    assert 10 < got["kernel_ms.msm"] < 30
    assert 0 < got["kernel_ms.frontend"] < 1
    assert 0 < got["device_idle_share.offload"] < 5


@pytest.mark.parametrize("change", ["renumbered", "added", "removed"])
def test_kernel_readers_go_silent_when_the_kernels_change(change):
    path = os.path.join(DATA, "offload_clean_v5e.events.json.gz")
    events = btrace.load_events(path)
    for e in events:
        if change == "renumbered" and e["name"].startswith(
                "verify_batch_rlc."):
            n = int(e["name"].rsplit(".", 1)[1])
            e["name"] = f"verify_batch_rlc.{n + 1}"
        elif change == "removed" and e["name"] == "verify_batch_rlc.11":
            e["name"] = "fusion.99999"
    if change == "added":
        one = next(e for e in events if e["name"] == "verify_batch_rlc.12")
        events.append(dict(one, name="verify_batch_rlc.22"))
    red = btrace.reduce_events(events)
    for name in ("kernel_ms.msm", "kernel_ms.frontend"):
        mod = harness.load_module(
            os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
            "t2_" + name.replace(".", "_"))
        assert mod.read({"trace": red}) is None
