#!/usr/bin/env python3
"""Spreads of a cell's metrics over measure.py's records, for bounds.

    python3 benchmark/tools/spread.py DIR/<cell>.jsonl [...]

For each cell, set and metric: the runs' values, the median, and the
spread, (Q3 - Q1) / median with Python's statistics.quantiles(n=4); also
the spread with each set's run farthest from its median left out (how
the check judges tightness). The bound to take is about five times the
widest spread over the cells, never under 1 %.
"""

import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / abs(med) if med else None


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def main(paths) -> int:
    runs = defaultdict(lambda: defaultdict(list))
    for path in paths:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("trace") or "result" not in r:
                    continue
                for m, v in r["result"]["metrics"].items():
                    runs[(r["workload"], m)][r["set"]].append(v["value"])
    for (cell, metric), sets in sorted(runs.items()):
        row = {"cell": cell, "metric": metric}
        allv = []
        for s, vals in sorted(sets.items(), key=lambda kv: str(kv[0])):
            allv += vals
            row[f"set{s}"] = {"n": len(vals),
                              "median": statistics.median(vals),
                              "spread": spread(vals),
                              "spread_trim": spread(trimmed(vals))
                              if len(vals) > 2 else None,
                              "values": vals}
        row["spread_all"] = spread(allv)
        sp = [row[k]["spread"] for k in row if k.startswith("set")
              and row[k]["spread"] is not None]
        if sp:
            row["bound_5x"] = max(0.01, 5 * max(sp))
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
