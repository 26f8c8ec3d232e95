#!/usr/bin/env python3
"""Runs of one cell as the check makes them: each a process of its own.

    python3 benchmark/tools/measure.py --workload <cell> --seeds 1,2,3 \
        [--sets 2] [--seconds 30] [--trace-seeds 7,8,9] --out DIR

Runs `benchmark/run.py` for every seed of a set, `--sets` times over
(the same seeds in each set), then once with --trace 1 for each trace
seed. Appends one JSON record per run to DIR/<cell>.jsonl: seed, set,
exit code, wall seconds, the result line, and the end of standard
error. `python3 benchmark/tools/spread.py DIR/<cell>.jsonl` reads them.
This process never touches JAX, so each child has the chip to itself.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload, seed, seconds, trace, timeout):
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", workload, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(int(trace))],
            cwd=ROOT, capture_output=True, text=True, timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    rec = {"rc": rc, "wall_s": time.perf_counter() - t0,
           "stderr_tail": err[-3000:]}
    lines = [ln for ln in out.splitlines() if ln.strip()]
    if rc == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--timeout", type=float, default=1300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".jsonl")
    plan = [(s, k, False) for k in range(args.sets)
            for s in args.seeds.split(",")]
    plan += [(s, "trace", True) for s in args.trace_seeds.split(",") if s]
    bad = 0
    for seed, which, trace in plan:
        rec = one(args.workload, int(seed), seconds, trace, args.timeout)
        rec.update(workload=args.workload, seed=int(seed), set=which,
                   seconds=seconds, trace=trace)
        bad += rec["rc"] != 0 or not rec.get("result", {}).get("correct")
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        short = {k: v for k, v in rec.items() if k != "stderr_tail"}
        print(json.dumps(short)[:1500], flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
