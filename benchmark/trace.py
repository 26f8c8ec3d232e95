"""Profiler trace of the window, and its reduction to device numbers.

Capture: `Capture(dir)` starts JAX's profiler, and `window()` wraps the
traced stretch in a host span named "bench.window", the reduction's
clock edges. Only the process that holds the chip can trace it.

Reduction (`reduce_events`), over normalised events
{"plane", "line", "name", "start_ns", "dur_ns"}:

  window     the "bench.window" host span.
  busy       per device plane, the union of the op intervals on its
             "XLA Ops" line, clipped to the window; busy_s is the mean
             over the device planes, idle share is 1 - busy / window.
  ops        total op time by op name (clipped), device planes summed.
  modules    executions of each XLA module (program) that start inside
             the window, from the device "XLA Modules" line.
  gaps       idle stretches between busy intervals, each labelled with
             the "bench.*" host span that overlaps it most, else
             "unattributed".

`load_events(path)` reads an .xplane.pb with jax.profiler.ProfileData;
the test of the reduction runs on a small recorded chip trace kept as
normalised events (tests/data).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import time

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Longest traced stretch of a window. Longer traces perturb the host, and
# the leader path's device ops make stop_trace slow to write them.
TRACE_S = 1.0


class Capture:
    """start_trace on enter, stop_trace on exit; `path` is the written
    .xplane.pb afterwards."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.path = None

    def __enter__(self):
        import jax

        # No Python call tracing and no HLO protos: host spans and device
        # ops are all the reduction reads, and the trace stays small.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def window(self):
        import jax

        return jax.profiler.TraceAnnotation(WINDOW_SPAN)

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        for _ in range(50):  # the file can land a moment after stop
            found = sorted(glob.glob(os.path.join(
                self.log_dir, "**", "*.xplane.pb"), recursive=True))
            if found:
                self.path = found[-1]
                break
            time.sleep(0.1)
        return False


def short_name(name: str) -> str:
    """A device op's HLO instruction name: the trace names an op by its
    whole HLO text ("%fusion.34 = s32[...] fusion(...), ..."); the part
    before " = ", without the "%"."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> list:
    """Normalised events of an .xplane.pb (device planes' op and module
    lines, and the host's bench.* spans) — or of a .json.gz of them."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(HOST_SPAN_PREFIX):
                    continue
                name = short_name(e.name) if device else e.name
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": int(e.start_ns),
                            "dur_ns": int(e.duration_ns)})
    return out


def save_events(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_events(events: list) -> dict:
    wins = [e for e in events
            if e["name"] == WINDOW_SPAN and not e["plane"].startswith(
                DEVICE_PLANE_PREFIX)]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    w0 = wins[0]["start_ns"]
    w1 = w0 + wins[0]["dur_ns"]
    planes = sorted({e["plane"] for e in events
                     if e["plane"].startswith(DEVICE_PLANE_PREFIX)})
    ops: dict = {}
    modules: dict = {}
    busy_ns = []
    merged = []
    for plane in planes:
        iv = []
        for e in events:
            if e["plane"] != plane:
                continue
            s, t = e["start_ns"], e["start_ns"] + e["dur_ns"]
            if e["line"] == MODULES_LINE:
                if w0 <= s < w1:
                    modules[e["name"]] = modules.get(e["name"], 0) + 1
                continue
            s, t = max(s, w0), min(t, w1)
            if t <= s:
                continue
            iv.append((s, t))
            ops[e["name"]] = ops.get(e["name"], 0) + (t - s)
        u = _union(iv)
        busy_ns.append(sum(t - s for s, t in u))
        merged.extend(u)
    window_ns = w1 - w0
    busy = (sum(busy_ns) / len(busy_ns)) if busy_ns else 0.0
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
             for e in events
             if e["name"].startswith(HOST_SPAN_PREFIX)
             and e["name"] != WINDOW_SPAN
             and not e["plane"].startswith(DEVICE_PLANE_PREFIX)]
    gaps = []
    prev = w0
    for s, t in _union(merged) + [[w1, w1]]:
        if s > prev:
            best, label = 0, "unattributed"
            for hs, ht, name in spans:
                ov = min(ht, s) - max(hs, prev)
                if ov > best:
                    best, label = ov, name
            gaps.append((label, (s - prev) / 1e9))
        prev = max(prev, t)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": (1.0 - busy / window_ns) if window_ns > 0 else None,
        "ops_s": {k: v / 1e9 for k, v in ops.items()},
        "modules": modules,
        "gaps": gaps,
        "device_planes": len(planes),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def op_time_s(red: dict, names) -> float:
    """Total device time of the ops with these exact names."""
    return sum(red["ops_s"].get(n, 0.0) for n in names)


def numbered_ops(red: dict, stem: str) -> set:
    """Names of the ops in the window named <stem>.<n>: the custom-calls
    (Pallas kernels) of the program named stem."""
    return {k for k in red["ops_s"]
            if k.startswith(stem + ".") and k[len(stem) + 1:].isdigit()}


def module_count(red: dict, patterns) -> int:
    return sum(v for k, v in red["modules"].items()
               if any(p in k for p in patterns))
