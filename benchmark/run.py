#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

--trace 0 prints the cell's end-to-end metrics, --trace 1 its per-layer
metrics (with a profiler trace of the window). The run exits non-zero,
and prints no result, where JAX finds no TPU or fewer chips than the
cell asks for, where anything compiles inside the measured window, or
where the window cannot be measured validly.
"""

import time

T_PROC0 = time.perf_counter_ns()  # set-up is measured from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("run.py: --seed must be a whole number >= 0", file=sys.stderr)
        return 2

    from benchmark import harness

    try:
        cell = harness.load_cell(args.workload)
        harness.enable_compile_cache(ROOT)
        device = harness.require_device(cell["chips"])
        runner = harness.load_runner(cell)
        line = runner.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), device=device,
                          t_proc0=T_PROC0)
    except harness.RunError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
