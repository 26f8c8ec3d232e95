"""The traced window per chip, beside benchmark/trace.py's mean over the
device planes: a chip that lags the others is invisible in the mean.

`per_plane(events)` reduces normalised trace events (trace.load_events)
to, per device plane: its busy share of the window (the union of its
op intervals, clipped to the window), and per program (XLA module, by
name without its fingerprint) the executions that started inside the
window and their summed time. `module_ms` is one program's device time
per execution, the mean over the chips.
"""

from __future__ import annotations

from benchmark import trace as btrace


def per_plane(events: list) -> dict:
    """Each device plane's busy share of the traced window (the union
    of its op intervals, clipped to the window) and, per program, the
    executions that started inside the window and their summed time."""
    win = [e for e in events if e["name"] == btrace.WINDOW_SPAN
           and not e["plane"].startswith(btrace.DEVICE_PLANE_PREFIX)]
    if not win:
        raise ValueError("trace holds no bench.window span")
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    out = {}
    for e in events:
        if not e["plane"].startswith(btrace.DEVICE_PLANE_PREFIX):
            continue
        p = out.setdefault(e["plane"], {"iv": [], "modules": {}})
        s, t = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["line"] == btrace.MODULES_LINE:
            if w0 <= s < w1:
                name = e["name"].split("(", 1)[0]
                m = p["modules"].setdefault(name, {"count": 0, "s": 0.0})
                m["count"] += 1
                m["s"] += e["dur_ns"] / 1e9
        elif min(t, w1) > max(s, w0):
            p["iv"].append((max(s, w0), min(t, w1)))
    for p in out.values():
        busy = sum(t - s for s, t in btrace._union(p.pop("iv")))
        p["busy_share"] = busy / (w1 - w0)
    return out


def module_ms(reading, module: str):
    """Mean over the chips of `module`'s time per execution in the
    window, in ms; None where any chip's plane lacks the program."""
    planes = reading.get("planes")
    if not planes or any(module not in p["modules"]
                         for p in planes.values()):
        return None
    per_chip = [1e3 * p["modules"][module]["s"] / p["modules"][module]["count"]
                for p in planes.values()]
    return sum(per_chip) / len(per_chip)
