"""The open-loop source keeps its schedule and stamps each frag's due time."""

import os
import subprocess
import sys
import time

import numpy as np

from benchmark import harness


def test_source_publishes_on_schedule_with_due_stamps(tmp_path):
    from firedancer_tpu.disco.pipeline import build_topology
    from firedancer_tpu.tango.rings import CNC_RUN, POLL_FRAG, Cnc, FSeq, \
        MCache, Workspace

    topo = build_topology(str(tmp_path / "wksp"), depth=256,
                          wksp_sz=1 << 25)
    pod_path = tmp_path / "pod"
    pod_path.write_bytes(topo.pod.serialize())
    wksp = Workspace.join(topo.wksp_path)

    def q(k):
        return topo.pod.query_cstr(f"firedancer.{k}")

    for t in ("verify", "dedup", "pack", "sink"):
        Cnc(wksp, q(f"{t}.cnc")).signal(CNC_RUN)
    rate, ramp, seconds = 400.0, 0.25, 0.75
    n = int(rate * (ramp + seconds)) + 1
    payloads = [bytes([1]) + i.to_bytes(8, "little") + bytes(100)
                for i in range(n)]
    offs = np.concatenate([[0], np.cumsum([len(p) for p in payloads])])
    proc = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH_DIR, "source.py"),
         "--wksp", topo.wksp_path, "--pod", str(pod_path), "--dir",
         str(tmp_path), "--rate", str(rate), "--ramp-s", str(ramp),
         "--seconds", str(seconds)],
        stdin=subprocess.PIPE, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    proc.stdin.write(np.int64(n).tobytes() + offs.astype(np.int64).tobytes()
                     + b"".join(payloads))
    proc.stdin.flush()
    # Play the verify tile: consume replay_verify, return credits, and
    # pretend the sink received what was consumed.
    mc = MCache(wksp, q("replay_verify.mcache"))
    fs = FSeq(wksp, q("replay_verify.fseq"))
    sink = FSeq(wksp, q("pack_sink.fseq"))
    got = []
    deadline = time.monotonic() + 60
    seq = 0
    while not (tmp_path / "published").exists():
        assert time.monotonic() < deadline
        r, f = mc.poll(seq)
        if r == POLL_FRAG:
            got.append((f.sig, f.tsorig, time.perf_counter_ns()))
            seq += 1
            fs.update(seq)
            sink.update(seq)
        else:
            time.sleep(50e-6)
    proc.stdin.close()
    assert proc.wait(timeout=30) == 0
    edges = harness.load_json(str(tmp_path / "start.json"))
    res = np.load(tmp_path / "source.npz")
    t0, period = int(res["t0"]), float(res["period"])
    pub = res["pub_ns"]
    due = t0 + (np.arange(n) * period).astype(np.int64)
    published = np.flatnonzero(pub >= 0)
    # Everything due before the window's end went out, in order, nothing
    # due after it, and no frag before it was due.
    assert published.tolist() == list(range(len(published)))
    assert (due[published] < edges["t_end_ns"]).all()
    assert len(published) >= int(rate * (ramp + seconds)) - 2
    assert (pub[published] >= due[published]).all()
    assert np.percentile(pub[published] - due[published], 50) < 5e6
    # Each frag carries its payload's sig and its due time as tsorig.
    for i, (sig, tsorig, _) in enumerate(got):
        assert sig == int.from_bytes(payloads[i][1:9], "little")
        assert tsorig == (int(due[i]) & 0xFFFFFFFF or 1)
    # The sink samples saw the count rise.
    assert res["sink_c"][-1] == len(got)
    wksp.leave()
