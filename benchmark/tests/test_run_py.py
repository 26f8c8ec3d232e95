"""run.py refuses to measure anywhere but on a TPU."""

import os
import subprocess
import sys

from conftest import ROOT


def test_run_py_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sigverify_offload.clean", "--seed", str(2**31 + 3), "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_py_names_an_unknown_workload():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no workload 'nope'" in p.stderr
    assert p.stdout.strip() == ""
