"""sigverify_offload: the chip as a signature-verify card.

Each request is one (pubkey, signature, message) lane. The window drives
the registry engine as the verify tile's dispatcher does: host arrays
placed through `EngineEntry.put`, verified by `EngineEntry.fn`, with a
fixed number of batches in flight, a closed loop that waits for each
batch's statuses before it sends the next. It cycles a few distinct
batches made from the seed; no stage of this path keeps results.

End-to-end readings:
  verify_per_s  lanes whose statuses came back inside the window, over
                the window.
  setup_s       process start to the window's start.

correct: every status of every batch completed in the run equals the
reference's status for that lane (benchmark/ref/ed25519.py); no cycled
batch fell back to the per-lane pass (each is valid but for lanes the
point checks settle, so its batch equation holds); and once the window
has closed, one more batch through the same entry, a cycled batch with
a few lanes that fail only the batch equation (gen/batches.py), reads
as the reference does.
"""

from __future__ import annotations

import collections
import os
import shutil
import tempfile
import threading

import numpy as np

from benchmark import harness
from benchmark import trace as btrace
from benchmark.gen import batches, corpus
from benchmark.ref import judge


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        t_proc0: int, engine_hook=None, details=None,
        keep_trace=None) -> str:
    """The cell's result line; engine_hook, details and keep_trace as
    in runners/leader_ingest.py."""
    from firedancer_tpu.disco import engine as fd_engine

    cfg, tr = cell["config"], cell["traffic"]
    eng = cfg["engine"]
    bsz, max_len = eng["batch"], eng["max_msg_len"]
    k_batches = tr["distinct_batches"]
    harness.enable_compile_cache(cell["root"])
    watch = harness.CompileWatch().install()
    pool = harness.worker_pool()
    run_dir = tempfile.mkdtemp(prefix="bench_offload_")
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
        spec = fd_engine.EngineSpec.for_tile("tpu", eng["mode"], bsz, 0)
        t0 = harness.now_ns()
        entry, _ = fd_engine.registry().acquire(spec, warm=True,
                                                max_msg_len=max_len)
        engine_warm_s = (harness.now_ns() - t0) / 1e9
        restore = engine_hook(entry) if engine_hook else None
        gen.join()
        if "err" in made:
            raise made["err"]
        host = made["v"]
        try:
            loop = _closed_loop(entry, host, eng["inflight"],
                                float(tr["ramp_s"]), seconds,
                                os.path.join(run_dir, "trace") if trace
                                else None)
            mem_peak = harness.memory_peak_bytes(cell["chips"])
            want = [judge.lane_statuses(b, pool) for b in host]
            eq_arrays, eq_lanes = batches.equation_batch(
                host[0], want[0], tr["equation_lanes"], seed)
            eq_out = entry.fn(*[entry.put(a) for a in eq_arrays])
            eq_st = np.asarray(eq_out)
            eq_fallback = bool(getattr(eq_out, "used_fallback", False))
        finally:
            if restore:
                restore()
        t_start, t_end = loop["t_start"], loop["t_end"]
        compiles = watch.count(t_start, t_end)
        if compiles:
            raise harness.RunError(f"{compiles} JAX traces or compiles "
                                   "inside the measured window")
        wrong = sum(int((st != want[k]).sum()) for k, st in loop["done"])
        eq_want = judge.lane_statuses(eq_arrays, pool)
        eq_wrong = int((eq_st != eq_want).sum())
        checked = (len(loop["done"]) + 1) * bsz
        lanes_in = sum(bsz for t, _ in loop["t_done"] if t_start <= t < t_end)
        rate = lanes_in / seconds
        if keep_trace and loop["trace_path"]:
            shutil.copyfile(loop["trace_path"], keep_trace)
        details = details if details is not None else {}
        details.update(verify_per_s=rate, batches=len(loop["done"]),
                       fallbacks=loop["fallbacks"],
                       engine_warm_s=engine_warm_s,
                       statuses={str(s): int((want[0] == s).sum())
                                 for s in np.unique(want[0])},
                       equation_lanes=eq_lanes,
                       equation_lane_statuses=eq_st[eq_lanes].tolist(),
                       equation_batch_fell_back=eq_fallback)
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
            red = btrace.reduce_events(btrace.load_events(loop["trace_path"]))
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = btrace.breakdown(red)
            reading = {"batch": bsz, "trace": red,
                       "engine_warm_s": engine_warm_s,
                       "fallbacks": loop["fallbacks"]}
            metrics = harness.read_per_layer(cell, reading)
        harness.print_compared(compared)
        return harness.result_line(failed == 0, checked, failed, metrics,
                                   device, compared, breakdown)
    finally:
        pool.terminate()
        pool.join()
        shutil.rmtree(run_dir, ignore_errors=True)


def _closed_loop(entry, host, inflight: int, ramp_s: float, seconds: float,
                 trace_dir):
    """Ramp, then the window; `inflight` batches always queued. Returns
    completion times, statuses per completed batch, and the window."""
    import jax

    ann = jax.profiler.TraceAnnotation
    q = collections.deque()
    done, t_done = [], []
    fallbacks = 0
    nxt = 0
    t_start = harness.now_ns() + int(ramp_s * 1e9)
    t_end = t_start + int(seconds * 1e9)
    # The window's last trace.TRACE_S seconds are traced: the profiler
    # runs only then, and writes its trace once the window has closed.
    t_trace = max(t_start, t_end - int(btrace.TRACE_S * 1e9))
    cap = win = None
    trace_path = None

    def dispatch():
        nonlocal nxt
        k = nxt % len(host)
        nxt += 1
        with ann("bench.put"):
            args = [entry.put(a) for a in host[k]]
        with ann("bench.dispatch"):
            q.append((entry.fn(*args), k))

    while True:
        now = harness.now_ns()
        if trace_dir and cap is None and now >= t_trace:
            cap = btrace.Capture(trace_dir).__enter__()
            win = cap.window()
            win.__enter__()
        if now >= t_end:
            break
        while len(q) < inflight:
            dispatch()
        out, k = q.popleft()
        with ann("bench.readback"):
            st = np.asarray(out)
        t_done.append((harness.now_ns(), k))
        done.append((k, st))
        fallbacks += bool(getattr(out, "used_fallback", False))
    if win is not None:
        win.__exit__(None, None, None)
    while q:
        out, k = q.popleft()
        done.append((k, np.asarray(out)))
        fallbacks += bool(getattr(out, "used_fallback", False))
    if cap is not None:
        cap.__exit__(None, None, None)
        trace_path = cap.path
    return {"done": done, "t_done": t_done, "fallbacks": fallbacks,
            "t_start": t_start, "t_end": t_end, "trace_path": trace_path}
