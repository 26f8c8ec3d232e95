"""sigverify_offload_dp4: a four-chip host as one signature-verify card.

The offload run (runners/offload.py: its closed loop, by import, and
the same generator and reference) over the sharded registry engine:
the configuration's `shards` builds EngineSpec(..., shards), put()
sends each chip its own lanes of every global batch, and each batch is
one global batch equation over the chips (a local fill per chip, one
combine across them).

End-to-end readings, as runners/offload.py: verify_per_s, setup_s.

correct: as runners/offload.py, except that the equation check runs
once per chip. After the window, chip c's check batch is cycled batch
c with the traffic's `equation_lanes` lanes, all inside chip c's slice
of the batch, given one flipped message byte: each such batch must
fail its one global equation and read as the reference does lane by
lane. equation_batch_wrong sums the wrong statuses of the checks.

A traced run also reads the window per chip (`planes`,
benchmark/chips.py), and `weights` holds the engine's host counters at
the first and the last dispatch inside the window.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading

import numpy as np

from benchmark import chips as bchips
from benchmark import harness
from benchmark import trace as btrace
from benchmark.gen import batches, corpus
from benchmark.ref import judge

offload = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "offload.py"),
    "bench_runner_offload")

# The engine's host counters of the rlc weights' draw and placement.
WEIGHT_COUNTERS = ("rlc_weights_ns", "rlc_weights_calls")


class _Edges:
    """The entry as the closed loop drives it, recording the engine's
    weight counters (None where the program keeps none) at each
    dispatch."""

    def __init__(self, entry):
        self.entry = entry
        self.marks = []

    def put(self, a):
        return self.entry.put(a)

    def fn(self, *args):
        self.marks.append((harness.now_ns(), {
            k: getattr(self.entry, k, None) for k in WEIGHT_COUNTERS}))
        return self.entry.fn(*args)

    def window(self, t0: int, t1: int):
        """(counters at the window's first dispatch, at its last), or
        None where fewer than two dispatches fell inside."""
        inside = [c for t, c in self.marks if t0 <= t < t1]
        return (inside[0], inside[-1]) if len(inside) >= 2 else None


def chip_check_batch(arrays, want, n_lanes: int, seed: int, chip: int,
                     chips: int):
    """gen/batches.equation_batch with its lanes drawn from chip `chip`'s
    slice of the batch alone (the lanes shard_map gives that chip)."""
    per = len(want) // chips
    own = np.zeros(len(want), bool)
    own[chip * per:(chip + 1) * per] = True
    return batches.equation_batch(arrays, np.where(own, want, -1), n_lanes,
                                  seed * chips + chip)


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        t_proc0: int, engine_hook=None, details=None,
        keep_trace=None) -> str:
    """The cell's result line; engine_hook, details and keep_trace as
    in runners/leader_ingest.py."""
    from firedancer_tpu.disco import engine as fd_engine

    cfg, tr = cell["config"], cell["traffic"]
    eng = cfg["engine"]
    bsz, max_len, chips = eng["batch"], eng["max_msg_len"], eng["shards"]
    k_batches = tr["distinct_batches"]
    harness.enable_compile_cache(cell["root"])
    watch = harness.CompileWatch().install()
    pool = harness.worker_pool()
    run_dir = tempfile.mkdtemp(prefix="bench_offload_mesh_")
    try:
        made = {}

        def make():
            try:
                items = corpus.lanes(tr["mix"], k_batches * bsz, seed, pool)
                made["v"] = batches.offload_batches(
                    items, k_batches, bsz, max_len,
                    tr["canaries_per_kind"], seed)
            except BaseException as e:
                made["err"] = e

        gen = threading.Thread(target=make, name="bench_gen")
        gen.start()
        spec = fd_engine.EngineSpec.for_tile("tpu", eng["mode"], bsz, chips)
        t0 = harness.now_ns()
        entry, _ = fd_engine.registry().acquire(spec, warm=True,
                                                max_msg_len=max_len)
        engine_warm_s = (harness.now_ns() - t0) / 1e9
        restore = engine_hook(entry) if engine_hook else None
        gen.join()
        if "err" in made:
            raise made["err"]
        host = made["v"]
        edges = _Edges(entry)
        checks = []
        try:
            loop = offload._closed_loop(edges, host, eng["inflight"],
                                        float(tr["ramp_s"]), seconds,
                                        os.path.join(run_dir, "trace")
                                        if trace else None)
            mem_peak = harness.memory_peak_bytes(cell["chips"])
            want = [judge.lane_statuses(b, pool) for b in host]
            for c in range(chips):
                k = c % len(host)
                arrays, lanes = chip_check_batch(
                    host[k], want[k], tr["equation_lanes"], seed, c, chips)
                out = entry.fn(*[entry.put(a) for a in arrays])
                checks.append((k, arrays, lanes, np.asarray(out),
                               bool(getattr(out, "used_fallback", False))))
        finally:
            if restore:
                restore()
        t_start, t_end = loop["t_start"], loop["t_end"]
        compiles = watch.count(t_start, t_end)
        if compiles:
            raise harness.RunError(f"{compiles} JAX traces or compiles "
                                   "inside the measured window")
        wrong = sum(int((st != want[k]).sum()) for k, st in loop["done"])
        eq_wrong = 0
        for k, arrays, lanes, st, _ in checks:
            # Only the flipped lanes differ from cycled batch k.
            eq_want = want[k].copy()
            eq_want[lanes] = judge.lane_statuses(
                tuple(a[lanes] for a in arrays), pool)
            eq_wrong += int((st != eq_want).sum())
        checked = (len(loop["done"]) + chips) * bsz
        lanes_in = sum(bsz for t, _ in loop["t_done"] if t_start <= t < t_end)
        rate = lanes_in / seconds
        if keep_trace and loop["trace_path"]:
            shutil.copyfile(loop["trace_path"], keep_trace)
        details = details if details is not None else {}
        details.update(verify_per_s=rate, batches=len(loop["done"]),
                       fallbacks=loop["fallbacks"],
                       engine_warm_s=engine_warm_s,
                       shard_lanes=getattr(entry, "shard_lanes", None),
                       equation_lanes=[c[2] for c in checks],
                       equation_lane_statuses=[c[3][c[2]].tolist()
                                               for c in checks],
                       equation_batches_fell_back=[c[4] for c in checks])
        harness.note("window", **details)
        compared = {"wrong_statuses": {"value": wrong, "limit": 0},
                    "clean_batch_fallbacks": {"value": loop["fallbacks"],
                                              "limit": 0},
                    "equation_batch_wrong": {"value": eq_wrong, "limit": 0}}
        failed = wrong + loop["fallbacks"] + eq_wrong
        setup_s = (t_start - t_proc0) / 1e9
        device = dict(device, memory_peak_bytes=mem_peak)
        breakdown = None
        if not trace:
            metrics = {"verify_per_s": {"value": rate, "unit": "verifies/s"},
                       "setup_s": {"value": setup_s, "unit": "s"}}
            metrics = {m["name"]: metrics[m["name"]]
                       for m in cell["end_to_end"]}
        else:
            if loop["trace_path"] is None:
                raise harness.RunError("the profiler wrote no trace")
            events = btrace.load_events(loop["trace_path"])
            red = btrace.reduce_events(events)
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = btrace.breakdown(red)
            reading = {"batch": bsz, "trace": red,
                       "planes": bchips.per_plane(events),
                       "engine_warm_s": engine_warm_s,
                       "fallbacks": loop["fallbacks"],
                       "weights": edges.window(t_start, t_end)}
            metrics = harness.read_per_layer(cell, reading)
        harness.print_compared(compared)
        return harness.result_line(failed == 0, checked, failed, metrics,
                                   device, compared, breakdown)
    finally:
        pool.terminate()
        pool.join()
        shutil.rmtree(run_dir, ignore_errors=True)
