"""A configuration, a traffic mix and a per-layer metric are found by
name: adding them takes new files and new entries, and no edit."""

import json
import os
import shutil

from conftest import ROOT

from benchmark import harness


def test_new_files_and_entries_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    conf = json.loads((root / "benchmark/configs/sigverify_offload.json")
                      .read_text())
    conf["name"] = "card_small"
    (root / "benchmark/configs/card_small.json").write_text(json.dumps(conf))
    mix = json.loads((root / "benchmark/traffic/clean.json").read_text())
    mix["name"] = "bursty"
    mix["distinct_batches"] = 3
    (root / "benchmark/traffic/bursty.json").write_text(json.dumps(mix))
    (root / "benchmark/metrics/batches_seen.py").write_text(
        "def read(reading):\n    return reading.get('batches')\n")
    bench["configs"].append({"name": "card_small", "source": "x",
                             "file": "benchmark/configs/card_small.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "card_small.bursty",
                               "config": "card_small", "traffic": "bursty",
                               "chips": 1, "why": "x"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["verify_per_s"]["workloads"].append("card_small.bursty")
    bench["per_layer"].append({"name": "batches_seen", "unit": "batches",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "verify_per_s",
                               "workloads": ["card_small.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("card_small.bursty", root=str(root))
    assert cell["config"]["name"] == "card_small"
    assert cell["traffic"]["distinct_batches"] == 3
    assert [m["name"] for m in cell["end_to_end"]] == ["verify_per_s",
                                                       "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["batches_seen"]
    assert harness.read_per_layer(cell, {"batches": 7}) == {
        "batches_seen": {"value": 7.0, "unit": "batches"}}
    # Nothing to read: the metric is left out of the line.
    assert harness.read_per_layer(cell, {}) == {}
    assert harness.load_runner(cell).run.__module__ == "bench_runner_offload"
    # The cells that were there see none of it.
    old = harness.load_cell("sigverify_offload.clean", root=str(root))
    assert "batches_seen" not in [m["name"] for m in old["per_layer"]]
    # No file that was there changed.
    for p, data in before.items():
        assert p.read_bytes() == data
