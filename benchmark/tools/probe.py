#!/usr/bin/env python3
"""Many runs of one cell in ONE process: one engine warm for all of them.

    python3 benchmark/tools/probe.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--rates 1000,2000] [--control-seeds 4,5,6] \
        [--trace 1] [--keep DIR] [--out FILE]

For the knee sweep (--rates overrides the traffic file's rate), the
readings of a dozen seeds that a limit is set from, and the control's
readings (each run of --control-seeds is made once for each control
that benchmark/control.py names for the cell's configuration, that
control in the engine's place).
Each run prints one JSON line: the cell's result line and the window's
details. --keep DIR keeps each traced run's trace as normalised events
and a look at every device op (tools/trace_look.py). Not a benchmark
run: set-up is shared, so its setup_s means nothing.
"""

import time

T_PROC0 = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmark import control, harness
    from benchmark import trace as btrace

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache(ROOT)
    device = harness.require_device(cell["chips"])
    runner = harness.load_runner(cell)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    plan = [(int(s), None) for s in args.seeds.split(",") if s]
    plan += [(int(s), c) for s in args.control_seeds.split(",") if s
             for c in control.CONTROLS[cell["config"]["name"]]]
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    out = open(args.out, "a") if args.out else None
    bad = 0
    for rate in rates:
        for seed, ctl in plan:
            c = json.loads(json.dumps(cell))
            if rate is not None:
                c["traffic"]["rate_txn_per_s"] = rate
            details = {}
            keep = None
            if args.keep and args.trace:
                keep = os.path.join(args.keep, f"{args.workload}.{seed}."
                                    f"{int(rate or 0)}.xplane.pb")
            rec = {"workload": args.workload, "seed": seed, "rate": rate,
                   "control": ctl, "seconds": args.seconds}
            t0 = time.perf_counter()
            try:
                line = runner.run(c, seed=seed, seconds=args.seconds,
                                  trace=bool(args.trace), device=device,
                                  t_proc0=T_PROC0,
                                  engine_hook=(control.hook(ctl) if ctl
                                               else None),
                                  details=details, keep_trace=keep)
                rec["result"] = json.loads(line)
            except Exception as e:
                traceback.print_exc()
                rec["error"] = repr(e)[:500]
                bad += 1
            rec["details"] = details
            rec["wall_s"] = time.perf_counter() - t0
            if keep and os.path.exists(keep):
                from benchmark.tools import trace_look

                stem = keep[:-len(".xplane.pb")]
                btrace.save_events(btrace.load_events(keep),
                                   stem + ".events.json.gz")
                with open(stem + ".look.json", "w") as f:
                    json.dump(trace_look.look(keep), f, indent=1)
                os.remove(keep)
            s = json.dumps(rec, default=str)
            print("probe: " + s, flush=True)
            if out:
                out.write(s + "\n")
                out.flush()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
