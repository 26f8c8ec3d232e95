"""What every runner shares: finding a cell's files by name, the device
check, the compile watch, the window clock, the per-layer readers and
the result line.

A cell (one entry of BENCHMARK.json's `workloads`) names a configuration
and a traffic mix. The configuration's file (its `file` in
BENCHMARK.json) names the runner, `runners/<runner>.py`; the traffic mix
is `traffic/<traffic>.json`; each per-layer metric is read by
`metrics/<metric>.py`, whose `read(reading)` returns a number or None
(nothing to read: the metric is left out of the line). Adding any of
these takes new files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class RunError(Exception):
    """The run cannot give a valid result (no chip, a compile inside the
    window, a tail past the stamp wrap): exit non-zero, print no result."""


def now_ns() -> int:
    return time.perf_counter_ns()


def sleep_until(t_ns: int) -> None:
    while True:
        left = t_ns - now_ns()
        if left <= 0:
            return
        time.sleep(min(left / 1e9, 0.05))


# --------------------------------------------------------------------------
# Cells, configurations, traffic and readers, found by name.
# --------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise RunError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything a run of cell `name` needs, read from BENCHMARK.json
    and the files it names under `root`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf_entry["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    reported = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    moved = {m["name"] for m in reported}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return {"name": name, "chips": cell["chips"], "cell": cell,
            "config": config, "traffic": traffic, "end_to_end": reported,
            "per_layer": per_layer, "root": root}


def load_runner(cell: dict):
    kind = cell["config"]["runner"]
    return load_module(os.path.join(cell["root"], "benchmark", "runners",
                                    kind + ".py"), f"bench_runner_{kind}")


def read_per_layer(cell: dict, reading: dict) -> dict:
    """{metric: {"value", "unit"}} of every per-layer metric of the cell
    whose reader finds something to read."""
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(os.path.join(cell["root"], "benchmark", "metrics",
                                       m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(reading)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# Device, compile cache, compile watch.
# --------------------------------------------------------------------------


def require_device(chips: int) -> dict:
    """The chips the cell asks for, or RunError: a measurement path that
    finds no TPU fails; it never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX finds {devs[0].platform if devs else 'no'}"
                       " devices")
    if len(devs) < chips:
        raise RunError(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the fixed path
    <checkout>/.jax_cache, handed to the program's own helper (which
    takes JAX_COMPILATION_CACHE_DIR where it is set)."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    # JAX read the variable when it was imported: where that came first,
    # the environment's directory is already in force, so set it here.
    jax.config.update("jax_compilation_cache_dir", path)
    from firedancer_tpu import compile_cache

    compile_cache.enable()
    return path


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileWatch:
    """Counts JAX traces, lowerings and backend compiles, and how many
    of them ended inside [t0, t1) of the perf_counter clock."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.stamps: list = []
        self._lock = threading.Lock()

    def install(self) -> "CompileWatch":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.stamps.append((now_ns(), event))

    def count(self, t0: int, t1: int) -> int:
        with self._lock:
            return sum(1 for t, _ in self.stamps if t0 <= t < t1)


# --------------------------------------------------------------------------
# Workers and statistics.
# --------------------------------------------------------------------------


def worker_pool(n: int | None = None):
    """Spawned workers (no fork of a process that holds JAX threads)."""
    import multiprocessing

    if n is None:
        n = max(1, min(8, (os.cpu_count() or 2) - 3))
    return multiprocessing.get_context("spawn").Pool(n)


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile (the smallest value with at least
    q % of the values at or below it)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return float(s[k])


# --------------------------------------------------------------------------
# The result line.
# --------------------------------------------------------------------------


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, compared: dict, breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared  # last: the numbers compared close the line
    return json.dumps(out)


def print_compared(compared: dict) -> None:
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)


def note(tag: str, **fields) -> None:
    """A line for the reader of the run's standard error."""
    print(f"{tag}: " + json.dumps(fields, default=str), file=sys.stderr,
          flush=True)
