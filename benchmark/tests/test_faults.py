"""Whole runs on the CPU at a small batch, skipping only the look for a
chip: sound runs come out correct; with the timed path broken
underneath (cpu_engine.FAULTS) or the control in the engine's place
(benchmark/control.py), `correct` comes out false."""

import copy
import json

import pytest

import cpu_engine
from benchmark import control, harness

BATCH = 64
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 29


def small_cell(name):
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config"]["engine"]["batch"] = BATCH
    tr = cell["traffic"]
    if "distinct_batches" in tr:
        tr["distinct_batches"] = 2
    else:
        tr["rate_txn_per_s"] = 250
        tr["ramp_s"] = 0.5
    return cell


def run(name, fault=None, ctl=None, seconds=1.5):
    cell = small_cell(name)
    hook = control.hook(ctl) if ctl else None
    runner = harness.load_runner(cell)
    with cpu_engine.installed(BATCH, cell["config"]["engine"]["max_msg_len"],
                              fault=fault):
        line = runner.run(cell, seed=SEED, seconds=seconds, trace=False,
                          device=DEVICE, t_proc0=harness.now_ns(),
                          engine_hook=hook)
    return json.loads(line)


CELLS = ["sigverify_offload.clean", "leader_ingest.mainnet_paced"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert all(v["value"] == 0 for v in res["compared"].values())
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", cpu_engine.FAULTS)
def test_broken_timed_path_is_not_correct(name, fault):
    res = run(name, fault=fault)
    assert res["correct"] is False and res["failed"] > 0


def test_equation_faults_fail_the_offload_cell():
    # An engine whose equation never holds answers right, lane by lane,
    # but every clean batch then falls back.
    res = run("sigverify_offload.clean", fault="fallback_always")
    assert res["correct"] is False
    assert res["compared"]["clean_batch_fallbacks"]["value"] > 0
    assert res["compared"]["wrong_statuses"]["value"] == 0
    # An engine whose equation always holds misreads only the check
    # batch's equation lanes: the cycled batches' canaries fail earlier.
    res = run("sigverify_offload.clean", fault="no_equation")
    assert res["compared"]["equation_batch_wrong"]["value"] == 2
    assert res["compared"]["wrong_statuses"]["value"] == 0


@pytest.mark.parametrize("name,ctl", [
    (name, ctl) for name in CELLS
    for ctl in control.CONTROLS[harness.load_cell(name)["config"]["name"]]])
def test_control_is_not_correct(name, ctl):
    res = run(name, ctl=ctl)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_a_compile_inside_the_window_fails_the_run():
    with pytest.raises(harness.RunError, match="compiles inside"):
        run("sigverify_offload.clean", fault="compiling")


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_ends_with_per_layer_metrics(name):
    # The profiler traces the window's last second and writes its trace
    # once the window has closed; on the CPU there is no TPU plane, so
    # only the host side of the reduction is exercised.
    cell = small_cell(name)
    runner = harness.load_runner(cell)
    with cpu_engine.installed(BATCH, cell["config"]["engine"]["max_msg_len"]):
        res = json.loads(runner.run(cell, seed=SEED, seconds=2.5, trace=True,
                                    device=DEVICE, t_proc0=harness.now_ns()))
    assert res["correct"] is True
    assert res["device"]["window_s"] == pytest.approx(1.0, abs=0.2)
    assert "breakdown" in res and list(res)[-1] == "compared"
    assert "engine_warm_s" in res["metrics"]
    assert not set(res["metrics"]) & {m["name"] for m in cell["end_to_end"]}
