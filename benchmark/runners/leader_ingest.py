"""leader_ingest: a leader's TPU ingest through the program's served path.

The window drives `run_feed_pipeline` (disco/feed/runtime.py) as the
program lays it out on this host: the verify tile (fd_feed staging and
the device engine) in this process, dedup, pack and sink in the
program's worker process. The source is the benchmark's own process
(benchmark/source.py), publishing on replay_verify to a fixed-rate
schedule made from the seed.

End-to-end readings (each reported where the cell lists it):
  sink_txn_per_s  transactions the sink received inside the window, from
                  the sink's count sampled at the window's two edges.
  sink_p99_ms     99th percentile, over the transactions due inside the
                  window that reached the sink, of arrival - due time.
                  A transaction's due time is that of its first copy;
                  its arrival is bounded from above by the first sample
                  of the sink's count that includes it (digests arrive
                  in order).
  setup_s         process start to the window's start.

correct: the sink's digest multiset equals the reference's for exactly
the payloads the source published (parse, verify, dedup and delivery).
"""

from __future__ import annotations

import hashlib
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import types
from collections import Counter

import numpy as np

from benchmark import harness
from benchmark import trace as btrace
from benchmark.gen import corpus
from benchmark.ref import judge

WRAP_NS = 1 << 32   # the tiles' 32-bit ns stamps wrap at 4.29 s


class SourceTile:
    """Stands in the pipeline's source slot: its thread only waits for
    HALT; the publishing runs in benchmark/source.py."""

    name = "bench_source"

    def __init__(self, cnc, run_dir, topo, pod_path, payloads, rate, ramp_s,
                 seconds):
        self.cnc = cnc
        self.cpu_idx = None
        self.out_link = types.SimpleNamespace(lat_ns=[])
        self.dir = run_dir
        self.topo = topo
        self.pod_path = pod_path
        self.payloads = payloads
        self.rate, self.ramp_s, self.seconds = rate, ramp_s, seconds
        self.proc = None

    def run(self, max_ns: int) -> None:
        from firedancer_tpu.tango.rings import CNC_HALT

        t_end = harness.now_ns() + max_ns
        while self.cnc.signal_query() != CNC_HALT and harness.now_ns() < t_end:
            time.sleep(0.01)

    def done(self) -> bool:
        if self.proc is not None and self.proc.poll() not in (None, 0):
            raise harness.RunError(f"source exited rc={self.proc.returncode}")
        return os.path.exists(os.path.join(self.dir, "published"))

    def start(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.BENCH_DIR, "source.py"),
             "--wksp", self.topo.wksp_path, "--pod", self.pod_path,
             "--dir", self.dir, "--rate", repr(self.rate),
             "--ramp-s", repr(self.ramp_s), "--seconds", repr(self.seconds)],
            stdin=subprocess.PIPE, env=env)
        lens = np.fromiter((len(p) for p in self.payloads), np.int64,
                           len(self.payloads))
        offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        w = self.proc.stdin
        w.write(np.int64(len(self.payloads)).tobytes())
        w.write(offs.tobytes())
        w.write(b"".join(self.payloads))
        w.flush()
        return self.stop

    def stop(self) -> None:
        if self.proc is None:
            return
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


class Window:
    """In this process: snapshots of the verify tile's counters at the
    window's edges, its publish-latency samples inside the window, and
    the profiler trace of the window's last trace.TRACE_S seconds (the
    profiler runs only then, and writes its trace once the window has
    closed)."""

    def __init__(self, run_dir, trace_dir):
        self.dir = run_dir
        self.trace_dir = trace_dir
        self.verify = None
        self.edges = None
        self.c0 = self.c1 = None
        self.hop_ns = []
        self.trace_path = None
        self.err = None
        self.quit = threading.Event()
        self.thread = None

    def attach(self, verify) -> None:
        self.verify = verify
        self.thread = threading.Thread(target=self._guarded, daemon=True,
                                       name="bench_window")
        self.thread.start()

    def _guarded(self):
        try:
            self._run()
        except BaseException as e:  # surfaced by the runner after the run
            self.err = e

    def _run(self):
        path = os.path.join(self.dir, "start.json")
        while not os.path.exists(path):
            if self.quit.wait(0.005):
                return
        edges = harness.load_json(path)
        t0, t1 = edges["t_start_ns"], edges["t_end_ns"]
        harness.sleep_until(t0)
        v = self.verify
        self.c0 = v.fl.as_dict()
        v.out_link.lat_ns = []
        v.out_link._lat_seen = 0
        cap = None
        if self.trace_dir:
            harness.sleep_until(max(t0, t1 - int(btrace.TRACE_S * 1e9)))
            cap = btrace.Capture(self.trace_dir).__enter__()
            with cap.window():
                harness.sleep_until(t1)
        harness.sleep_until(t1)
        self.c1 = v.fl.as_dict()
        self.hop_ns = list(v.out_link.lat_ns)
        self.edges = edges
        if cap is not None:
            cap.__exit__(None, None, None)
            self.trace_path = cap.path


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        t_proc0: int, engine_hook=None, details=None,
        keep_trace=None) -> str:
    """The cell's result line. Tools may pass engine_hook (swaps the
    engine's fn; returns the function that restores it), details (a
    dict filled with the window's readings) and keep_trace (a path the
    trace file is copied to)."""
    from firedancer_tpu.disco import engine as fd_engine
    from firedancer_tpu.disco.feed.runtime import run_feed_pipeline
    from firedancer_tpu.disco.pipeline import build_topology
    from firedancer_tpu.tango.rings import Cnc

    cfg, tr = cell["config"], cell["traffic"]
    eng, rings, pack = cfg["engine"], cfg["rings"], cfg["pack"]
    harness.enable_compile_cache(cell["root"])
    watch = harness.CompileWatch().install()
    rate, ramp_s = float(tr["rate_txn_per_s"]), float(tr["ramp_s"])
    n_total = int(math.ceil(rate * (ramp_s + seconds))) + 1

    pool = harness.worker_pool()
    run_dir = tempfile.mkdtemp(prefix="bench_leader_")
    try:
        made = {}

        def make_stream():
            try:
                made["v"] = corpus.stream(tr["mix"], n_total, seed, pool)
            except BaseException as e:
                made["err"] = e

        gen = threading.Thread(target=make_stream, name="bench_gen")
        gen.start()
        spec = fd_engine.EngineSpec.for_tile("tpu", eng["mode"],
                                             eng["batch"], 0)
        t0 = harness.now_ns()
        entry, _ = fd_engine.registry().acquire(
            spec, warm=True, max_msg_len=eng["max_msg_len"])
        engine_warm_s = (harness.now_ns() - t0) / 1e9
        restore = engine_hook(entry) if engine_hook else None
        gen.join()
        if "err" in made:
            raise made["err"]
        payloads, _ = made["v"]

        topo = build_topology(os.path.join(run_dir, "wksp"),
                              depth=rings["depth"], wksp_sz=rings["wksp_bytes"])
        pod_path = os.path.join(run_dir, "topo.pod")
        with open(pod_path, "wb") as f:
            f.write(topo.pod.serialize())
        from firedancer_tpu.tango.rings import Workspace

        wksp = Workspace.join(topo.wksp_path)
        src = SourceTile(Cnc(wksp, topo.pod.query_cstr("firedancer.replay.cnc")),
                         run_dir, topo, pod_path, payloads, rate, ramp_s,
                         seconds)
        win = Window(run_dir, os.path.join(run_dir, "trace") if trace else None)
        try:
            res = run_feed_pipeline(
                topo, [], verify_backend="tpu", verify_batch=eng["batch"],
                verify_max_msg_len=eng["max_msg_len"],
                bank_cnt=pack["bank_cnt"], pack_scheduler=pack["scheduler"],
                tcache_depth=rings["tcache_depth"], record_digests=True,
                verify_opts={"inflight": cfg["layout"]["verify_inflight"]},
                timeout_s=ramp_s + seconds + 240.0,
                source_tile=src, source_done=src.done, pre_wait=src.start,
                tile_hook=win.attach)
        finally:
            win.quit.set()
            src.stop()
            if restore:
                restore()
        if win.thread is not None:
            win.thread.join(timeout=120)
        if win.err is not None:
            raise win.err
        if win.edges is None:
            raise harness.RunError("the window never opened")
        mem_peak = harness.memory_peak_bytes(cell["chips"])
        if keep_trace and win.trace_path:
            shutil.copyfile(win.trace_path, keep_trace)
        return _finish(cell, seconds, trace, device, t_proc0, watch, pool,
                       res, win, payloads, run_dir, engine_warm_s, mem_peak,
                       details if details is not None else {})
    finally:
        pool.terminate()
        pool.join()
        shutil.rmtree(run_dir, ignore_errors=True)


def count_at(sink_t, sink_c, t: int) -> int:
    """The sink's received count at time t: the last sample at or
    before t (samples are taken whenever the count moves)."""
    j = int(np.searchsorted(sink_t, t, side="right")) - 1
    return int(sink_c[j]) if j >= 0 else 0


def arrival_latencies(digests, first, due, sink_t, sink_c, t_start: int,
                      t_end: int) -> list:
    """[(due, arrival - due)] of the sink's arrivals due in [t_start,
    t_end). Arrival k (digests are in arrival order) is bounded from
    above by the first sample whose count exceeds k; its due time is
    that of the first published copy of its payload (first: digest ->
    stream index)."""
    out = []
    for k, d in enumerate(digests):
        i = first.get(d)
        if i is None or not t_start <= due[i] < t_end:
            continue
        j = int(np.searchsorted(sink_c, k + 1, side="left"))
        if j >= len(sink_t):
            raise harness.RunError("sink count samples end before the "
                                   "last arrival")
        out.append((int(due[i]), int(sink_t[j]) - int(due[i])))
    return out


def check_wrap(lat_ns) -> None:
    """A tail is only valid while every wait is under the 4.29 s wrap of
    the tiles' 32-bit stamps (the verify hop reads them)."""
    worst = max(lat_ns)
    if worst >= WRAP_NS:
        raise harness.RunError(
            f"a transaction waited {worst / 1e9:.3f} s, past the 4.29 s "
            "wrap of the tiles' 32-bit stamps: no valid tail")


def _finish(cell, seconds, trace, device, t_proc0, watch, pool, res, win,
            payloads, run_dir, engine_warm_s, mem_peak, details) -> str:
    cfg = cell["config"]
    vs = res.verify_stats[0]
    if not res.feed or vs["mode"] != cfg["engine"]["mode"]:
        raise harness.RunError(f"served path not as configured: feed="
                               f"{res.feed} mode={vs['mode']}")
    if vs["cpu_failover"]:
        raise harness.RunError(f"{vs['cpu_failover']} batches failed over "
                               "to the CPU: not a device measurement")
    t_start, t_end = win.edges["t_start_ns"], win.edges["t_end_ns"]
    compiles = watch.count(t_start, t_end)
    if compiles:
        raise harness.RunError(f"{compiles} JAX traces or compiles inside "
                               "the measured window")

    src = np.load(os.path.join(run_dir, "source.npz"))
    pub_ns = src["pub_ns"]
    n = len(pub_ns)
    due = int(src["t0"]) + (np.arange(n) * float(src["period"])).astype(np.int64)
    sink_t, sink_c = src["sink_t"], src["sink_c"]
    published = np.nonzero(pub_ns >= 0)[0]

    sink_rate = (count_at(sink_t, sink_c, t_end)
                 - count_at(sink_t, sink_c, t_start)) / seconds
    first = {}
    for i in published.tolist():
        first.setdefault(hashlib.sha256(payloads[i]).digest(), i)
    digests = res.sink_digests or []
    lat_due = arrival_latencies(digests, first, due, sink_t, sink_c,
                                t_start, t_end)
    lat_in = [lat for _, lat in lat_due]
    in_win = published[(due[published] >= t_start) & (due[published] < t_end)]
    due_in_win = int(((due >= t_start) & (due < t_end)).sum())
    lag_ns = (pub_ns[in_win] - due[in_win]).tolist()
    names = {m["name"] for m in cell["end_to_end"]}
    tail = {}
    if lat_in:
        tail = {"n": len(lat_in),
                "p50_ms": harness.percentile(lat_in, 50) / 1e6,
                "p99_ms": harness.percentile(lat_in, 99) / 1e6,
                "max_ms": max(lat_in) / 1e6}
    half = (t_start + t_end) // 2
    early = [lat for t, lat in lat_due if t < half]
    late = [lat for t, lat in lat_due if t >= half]
    details.update(
        sink_txn_per_s=sink_rate, offered_txn_per_s=len(in_win) / seconds,
        due=due_in_win, published=len(in_win),
        unoffered=due_in_win - len(in_win),
        source_lag_p99_ms=(harness.percentile(lag_ns, 99) / 1e6
                           if lag_ns else None),
        batches=win.c1["batches"] - win.c0["batches"],
        lanes=win.c1["lanes"] - win.c0["lanes"],
        rlc_fallback=win.c1["rlc_fallback"] - win.c0["rlc_fallback"],
        tail=tail,
        p50_first_half_ms=(harness.percentile(early, 50) / 1e6
                           if early else None),
        p50_second_half_ms=(harness.percentile(late, 50) / 1e6
                            if late else None),
        engine_warm_s=engine_warm_s)
    # Judged before the tail's guards, so that a run they refuse still
    # shows its comparison on standard error.
    want = judge.sink_expected([payloads[i] for i in published.tolist()],
                               pool)
    missing, unexpected = judge.delta(want, Counter(digests))
    compared = {"missing_txns": {"value": missing, "limit": 0},
                "unexpected_txns": {"value": unexpected, "limit": 0}}
    correct = missing == 0 and unexpected == 0
    details["compared"] = compared
    harness.note("window", **details)
    if "sink_p99_ms" in names:
        if not lat_in:
            raise harness.RunError("no transaction due in the window "
                                   "reached the sink")
        check_wrap(lat_in)
    setup_s = (t_start - t_proc0) / 1e9
    device = dict(device, memory_peak_bytes=mem_peak)
    breakdown = None
    if not trace:
        metrics = {"sink_txn_per_s": {"value": sink_rate, "unit": "txn/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        if "sink_p99_ms" in names:
            metrics["sink_p99_ms"] = {
                "value": harness.percentile(lat_in, 99) / 1e6, "unit": "ms"}
        metrics = {m["name"]: metrics[m["name"]] for m in cell["end_to_end"]}
    else:
        if win.trace_path is None:
            raise harness.RunError("the profiler wrote no trace")
        red = btrace.reduce_events(btrace.load_events(win.trace_path))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = btrace.breakdown(red)
        reading = {"batch": cfg["engine"]["batch"], "c0": win.c0,
                   "c1": win.c1, "run_end": vs, "source_lag_ns": lag_ns,
                   "verify_hop_ns": win.hop_ns, "trace": red,
                   "engine_warm_s": engine_warm_s}
        metrics = harness.read_per_layer(cell, reading)
    harness.print_compared(compared)
    return harness.result_line(correct, len(published), missing + unexpected,
                               metrics, device, compared, breakdown)
