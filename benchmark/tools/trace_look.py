#!/usr/bin/env python3
"""Look at a profiler trace by hand: what planes and lines it holds, and
for each distinct device op its count, total time and the stats of its
first event.

    python3 benchmark/tools/trace_look.py <trace.xplane.pb> [out.json]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def look(path: str, max_ops: int = 400) -> dict:
    from jax.profiler import ProfileData

    from benchmark import trace as btrace

    out = {"planes": {}, "ops": {}}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(btrace.DEVICE_PLANE_PREFIX)
        out["planes"][plane.name] = {}
        for line in plane.lines:
            evs = list(line.events)
            out["planes"][plane.name][line.name] = len(evs)
            if not device:
                continue
            for e in evs:
                key = f"{line.name}|{btrace.short_name(e.name)}"
                rec = out["ops"].get(key)
                if rec is None:
                    if len(out["ops"]) >= max_ops:
                        continue
                    rec = out["ops"][key] = {
                        "n": 0, "total_s": 0.0, "name": e.name[:300],
                        "stats": {str(k): str(v)[:300] for k, v in e.stats}}
                rec["n"] += 1
                rec["total_s"] += e.duration_ns / 1e9
    return out


if __name__ == "__main__":
    res = look(sys.argv[1])
    text = json.dumps(res, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
