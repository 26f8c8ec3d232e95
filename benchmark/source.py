"""The open-loop source of the leader cells: a process of its own.

    python benchmark/source.py --wksp W --pod P --dir D --rate R
                               --ramp-s S --seconds S

Reads the stream from standard input (an int64 count n, n + 1 int64
offsets, then the payload bytes), waits until the verify tile and the
downstream tiles run, then publishes payload i on the replay_verify ring
when it falls due, at t0 + i / rate, stamping the frag's tsorig with the
due time (low 32 bits of the perf_counter clock, as the tiles' stamps),
so a stall counts against every later transaction. The measured window
is [t0 + ramp, t0 + ramp + seconds); what the ring had no credit for by
the window's end is never published, and so never offered.

Alongside, it samples the sink's received count (the pack_sink fseq,
which the sink bumps per frag) with the 64-bit clock, until standard
input closes. Files in D: start.json (t0, window edges) once publishing
begins, `published` once it ends, source.npz (publish times, sink
samples) at exit.
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

READY_TIMEOUT_S = 120.0
LEAD_NS = 50_000_000  # from "all tiles run" to the first due time


def read_stream(f):
    n = int(np.frombuffer(f.read(8), np.int64)[0])
    offs = np.frombuffer(f.read(8 * (n + 1)), np.int64)
    blob = f.read(int(offs[-1]))
    if len(blob) != offs[-1]:
        raise SystemExit("source: short stream on stdin")
    return offs, blob


class SinkSampler:
    def __init__(self, fseq, cap: int):
        self.fseq = fseq
        self.t = np.zeros(cap, np.int64)
        self.c = np.zeros(cap, np.int64)
        self.n = 0
        self.last = -1

    def sample(self, now: int) -> None:
        c = self.fseq.query()
        if c != self.last:
            if self.n == len(self.t):
                self.t = np.concatenate([self.t, np.zeros_like(self.t)])
                self.c = np.concatenate([self.c, np.zeros_like(self.c)])
            self.t[self.n] = now
            self.c[self.n] = c
            self.n += 1
            self.last = c


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--wksp", required=True)
    ap.add_argument("--pod", required=True)
    ap.add_argument("--dir", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--ramp-s", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from firedancer_tpu.disco.tiles import LinkNames, OutLink
    from firedancer_tpu.tango.rings import CNC_RUN, Cnc, FSeq, Workspace
    from firedancer_tpu.utils.pod import Pod

    stdin = sys.stdin.buffer
    offs, blob = read_stream(stdin)
    n = len(offs) - 1
    with open(args.pod, "rb") as f:
        pod = Pod.deserialize(f.read())
    wksp = Workspace.join(args.wksp)

    def q(key):
        return pod.query_cstr(f"firedancer.{key}")

    names = LinkNames(q("replay_verify.mcache"), q("replay_verify.dcache"),
                      q("replay_verify.fseq"))
    out = OutLink(wksp, names, mtu=pod.query_ulong("firedancer.mtu", 1232),
                  reliable_fseqs=[FSeq(wksp, q("replay_verify.fseq"))])
    sampler = SinkSampler(FSeq(wksp, q("pack_sink.fseq")), 1 << 16)
    stop = threading.Event()

    def wait_eof():
        stdin.read()
        stop.set()

    threading.Thread(target=wait_eof, daemon=True).start()

    cncs = [Cnc(wksp, q(f"{t}.cnc")) for t in ("verify", "dedup", "pack",
                                                  "sink")]
    deadline = time.perf_counter() + READY_TIMEOUT_S
    while not all(c.signal_query() == CNC_RUN for c in cncs):
        if time.perf_counter() > deadline or stop.is_set():
            print("source: tiles never reached RUN", file=sys.stderr)
            return 1
        time.sleep(0.005)

    period = 1e9 / args.rate
    t0 = time.perf_counter_ns() + LEAD_NS
    t_start = t0 + int(args.ramp_s * 1e9)
    t_end = t_start + int(args.seconds * 1e9)
    with open(os.path.join(args.dir, "start.json.tmp"), "w") as f:
        json.dump({"t0_ns": t0, "t_start_ns": t_start, "t_end_ns": t_end,
                   "rate": args.rate, "n": n}, f)
    os.replace(os.path.join(args.dir, "start.json.tmp"),
               os.path.join(args.dir, "start.json"))

    pub_ns = np.full(n, -1, np.int64)
    mv = memoryview(blob)
    i = 0
    clock = time.perf_counter_ns
    while i < n:
        now = clock()
        sampler.sample(now)
        if now >= t_end:
            break
        due = t0 + int(i * period)
        if now < due:
            if due - now > 300_000:
                time.sleep(100e-6)
            continue
        if not out.can_publish():
            time.sleep(20e-6)
            continue
        p = mv[offs[i]:offs[i + 1]].tobytes()
        sig = int.from_bytes(p[1:9], "little") if len(p) > 8 else 0
        out.publish(p, sig, tsorig=(due & 0xFFFFFFFF) or 1)
        pub_ns[i] = clock()
        i += 1
    open(os.path.join(args.dir, "published"), "w").close()

    while not stop.is_set():
        sampler.sample(clock())
        time.sleep(200e-6)
    sampler.sample(clock())
    np.savez(os.path.join(args.dir, "source.npz"), pub_ns=pub_ns,
             sink_t=sampler.t[:sampler.n], sink_c=sampler.c[:sampler.n],
             t0=t0, period=period)
    wksp.leave()
    return 0


if __name__ == "__main__":
    sys.exit(main())
