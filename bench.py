"""Benchmark: batched Ed25519 verify throughput on the attached device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Baseline: 1,000,000 verifies/s — one wiredancer FPGA card / 33 Skylake
cores (reference src/wiredancer/README.md:65-66; BASELINE.md).

Methodology mirrors the reference's test_ed25519 bench harness
(ballet/ed25519/test_ed25519.c:713-780): warmup, then timed repetitions of
the full verify (SHA-512 + decompress + double-scalar-mul + compare), with
correctness asserted on the results. Message size models a typical Solana
transaction payload (~192 bytes of signed message; MTU is 1232).

The default mode is an ORCHESTRATOR that never imports JAX: each
measurement runs in a worker subprocess with a hard timeout, so the worker
owns the chip alone and a failed rung cannot take the ladder down. A worker
that finds no TPU fails; there is no CPU stand-in. On total failure the
orchestrator emits a single JSON error line, never a raw traceback, and
exits non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from firedancer_tpu import flags


def _gen_inputs(batch: int, msg_len: int, cache_path: str):
    """Generate (or load cached) valid signature batches."""
    if cache_path and os.path.exists(cache_path):
        z = np.load(cache_path)
        if z["msgs"].shape == (batch, msg_len):
            return z["msgs"], z["lens"], z["sigs"], z["pubs"]
    from firedancer_tpu.ballet import ed25519 as oracle

    rng = np.random.RandomState(42)
    n_uniq = 64  # distinct signatures, tiled to the batch
    msgs = np.zeros((batch, msg_len), np.uint8)
    lens = np.full(batch, msg_len, np.int32)
    sigs = np.zeros((batch, 64), np.uint8)
    pubs = np.zeros((batch, 32), np.uint8)
    uniq = []
    for i in range(n_uniq):
        seed = rng.randint(0, 256, 32, dtype=np.uint8).tobytes()
        _, _, pub = oracle.keypair_from_seed(seed)
        m = rng.randint(0, 256, msg_len, dtype=np.uint8)
        uniq.append((m, oracle.sign(m.tobytes(), seed), pub))
    for b in range(batch):
        m, sig, pub = uniq[b % n_uniq]
        msgs[b] = m
        sigs[b] = np.frombuffer(sig, np.uint8)
        pubs[b] = np.frombuffer(pub, np.uint8)
    if cache_path:
        np.savez(cache_path, msgs=msgs, lens=lens, sigs=sigs, pubs=pubs)
    return msgs, lens, sigs, pubs


def _replay_lock():
    """Exclusive flock shared by EVERY replay-gate mode (--replay-cpu
    and the device --replay-worker). Two overlapping 100k replays on
    this 1-core host starve each other (the round-4 red artifact: a
    second run got 275 txns through its 3000s budget while contending
    with the first); the lock makes overlap impossible."""
    import fcntl

    f = open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".bench_replay.lock"), "w")
    fcntl.flock(f, fcntl.LOCK_EX)  # blocks until the other run finishes
    return f


def _cached_corpus(n: int, seed: int):
    """Load-or-generate the gate corpus, keyed by the generator/signer
    source (a stale corpus must never validate old payload semantics).
    Shared by both replay gates so their cache keys cannot diverge.
    Returns (corpus, gen_seconds)."""
    import hashlib
    import inspect
    import pickle

    import firedancer_tpu.ballet.txn as txn_mod
    import firedancer_tpu.disco.corpus as corpus_mod
    import firedancer_tpu.ops.sign as sign_mod

    code_tag = hashlib.sha256()
    for m in (corpus_mod, txn_mod, sign_mod):
        code_tag.update(inspect.getsource(m).encode())
    cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f".bench_corpus_{n}_{seed}_{code_tag.hexdigest()[:12]}.pkl",
    )
    from firedancer_tpu.disco.corpus import mainnet_corpus

    t0 = time.perf_counter()
    if os.path.exists(cache):
        with open(cache, "rb") as f:
            return pickle.load(f), 0.0
    corpus = mainnet_corpus(n, seed=seed)
    gen_s = time.perf_counter() - t0
    with open(cache, "wb") as f:
        pickle.dump(corpus, f)
    return corpus, gen_s


def _stage_latency_ms(res) -> dict:
    """PipelineResult.stage_latency (ns percentiles per stage) -> the
    artifact's ms schema (docs/LATENCY.md budget table)."""
    out = {}
    for stage, d in (getattr(res, "stage_latency", None) or {}).items():
        out[stage] = {
            "p50_ms": round(d.get("p50_ns", 0) / 1e6, 2),
            "p99_ms": round(d.get("p99_ns", 0) / 1e6, 2),
            "n": d.get("n", 0),
        }
    return out


def _rlc_fallbacks(res) -> int:
    """Total per-lane-fallback batches across verify lanes (the ROADMAP
    round-6 'record fallback counts in the artifact' gate)."""
    return sum(v.get("rlc_fallback", 0) or 0 for v in res.verify_stats)


def _rung_hist(res) -> "dict | None":
    """fd_engine per-rung dispatch histogram merged across verify lanes
    ({str(B): batches}; None when no lane ran the rung scheduler) — the
    artifact block that lets the sentinel's edge-histogram story be
    attributed to scheduling (scripts/bench_log_check.py pins the
    shape)."""
    merged: dict = {}
    for v in res.verify_stats:
        for b, n in (v.get("rung_hist") or {}).items():
            merged[b] = merged.get(b, 0) + n
    return merged or None


def _schema_version() -> int:
    from firedancer_tpu.disco.flight import ARTIFACT_SCHEMA_VERSION

    return ARTIFACT_SCHEMA_VERSION


def _xray_block(res) -> "dict | None":
    """The bounded fd_xray artifact block out of PipelineResult.xray
    (the full waterfall/suspects stay in dumps and autopsies — a
    BENCH_LOG line must stay one readable line)."""
    x = getattr(res, "xray", None)
    if not x:
        return None
    return {
        "sample_rate": x.get("sample_rate", 0),
        "exemplars": x.get("exemplars") or {},
        "traces": x.get("traces", 0),
        "top_slowest": (x.get("top_slowest") or [])[:3],
    }


def _replay_artifact(metric: str, corpus, res, run_s: float, gen_s: float,
                     timeout_s: float) -> tuple[dict, bool]:
    """The shared replay-gate artifact (round-11: ONE assembly for the
    CPU and device gates — the per-worker hand-built dicts drifted a
    field at a time before fd_flight centralized the view). Returns
    (record, ok)."""
    from firedancer_tpu.disco.corpus import sink_delta

    missing, unexpected = sink_delta(corpus, res.sink_digests)
    ok = missing == 0 and unexpected == 0
    # Classification: "mismatch" ONLY when received content was wrong
    # (unexpected > 0). A shortfall with clean content is a run cut
    # short — "timeout" at the budget boundary, else "incomplete"
    # (crash/kill) — never booked as corruption.
    if ok:
        status = "ok"
    elif unexpected > 0:
        status = "mismatch"
    elif run_s >= timeout_s - 1.0:
        status = "timeout"
    else:
        status = "incomplete"
    rec = {
        "metric": metric,
        "value": round(len(corpus.payloads) / run_s, 1),
        "unit": "txns/s",
        "vs_baseline": 1.0 if ok else 0.0,  # gate: content-exact
        "schema_version": _schema_version(),
        "status": status,
        "corpus": len(corpus.payloads),
        "unique_ok": corpus.n_unique_ok,
        "sink_recv": res.recv_cnt,
        "missing": missing,
        "unexpected": unexpected,
        "mismatches": missing + unexpected,
        "latency_p50_ms": round(res.latency_p50_ns / 1e6, 2),
        "latency_p99_ms": round(res.latency_p99_ns / 1e6, 2),
        "gen_s": round(gen_s, 1),
        "run_s": round(run_s, 1),
        # fd_feed/fd_chaos/fd_flight artifact fields: which runner
        # produced this, its feeder gauges + healing counters (views
        # over the flight registry), RLC fallback total, the sampled
        # per-stage latency table, and the always-on trace-span
        # histograms (docs/LATENCY.md states the p99 budget in these).
        "feed": bool(getattr(res, "feed", False)),
        "feed_fallback_reason": getattr(res, "feed_fallback_reason", None),
        "verify_stats": res.verify_stats,
        "rlc_fallbacks": _rlc_fallbacks(res),
        "rung_hist": _rung_hist(res),
        "stage_latency_ms": _stage_latency_ms(res),
        "stage_hist": getattr(res, "stage_hist", None),
        # fd_xray summary (behind the schema_version gate like every
        # round-11+ field; None with FD_XRAY=0): exemplar counts by
        # trigger class + the top-3 slowest exemplars with per-stage
        # breakdown — scripts/bench_log_check.py validates the shape.
        "xray": _xray_block(res),
    }
    return rec, ok


def replay_cpu_worker() -> int:
    """The host-side 100k correctness gate: the full tile pipeline
    (replay -> verify[cpu native] -> dedup -> pack -> sink) with the
    native C++ verifier. Same content-exact gate as the on-chip
    variant; reports timeouts as TIMEOUTS (missing vs unexpected split,
    see disco.corpus.sink_delta) instead of phantom mismatches."""
    import tempfile

    lock = _replay_lock()  # noqa: F841 - held for the process lifetime

    n = flags.get_int("FD_BENCH_REPLAY_N")
    corpus, gen_s = _cached_corpus(n, seed=1234)

    from firedancer_tpu.disco.pipeline import build_topology, run_pipeline

    # The CPU gate keeps its wider 1200s default (1-core host).
    timeout_s = flags.get_float("FD_BENCH_REPLAY_TIMEOUT", 1200.0)
    with tempfile.TemporaryDirectory() as d:
        topo = build_topology(
            os.path.join(d, "replay.wksp"), depth=4096, wksp_sz=1 << 27
        )
        t0 = time.perf_counter()
        res = run_pipeline(
            topo,
            corpus.payloads,
            verify_backend="cpu",
            timeout_s=timeout_s,
            tcache_depth=1 << 18,
            record_digests=True,
        )
        run_s = time.perf_counter() - t0
    rec, ok = _replay_artifact(
        "replay_pipeline_throughput_cpu", corpus, res, run_s, gen_s,
        timeout_s)
    print(json.dumps(rec))
    return 0 if ok else 1


def replay_worker() -> int:
    """The BASELINE correctness gate at scale: a mainnet-shaped corpus
    through the FULL tile pipeline (replay -> verify[device] -> dedup ->
    pack -> sink) on the attached device. Asserts the sink receives
    exactly the unique valid txns (0 mismatches vs the by-construction
    oracle statuses; see disco/corpus.py for the chain of trust) and
    reports throughput + end-to-end p50/p99 latency. Prints ONE JSON
    line like the main worker."""
    import tempfile

    import jax

    from firedancer_tpu import compile_cache

    _require_tpu(jax, "replay_pipeline_throughput", "txns/s")
    compile_cache.enable()

    lock = _replay_lock()  # noqa: F841 - held for the process lifetime

    n = flags.get_int("FD_BENCH_REPLAY_N")
    vbatch = flags.get_int("FD_BENCH_REPLAY_BATCH")
    corpus, gen_s = _cached_corpus(n, seed=1234)

    from firedancer_tpu.disco.pipeline import build_topology, run_pipeline

    timeout_s = flags.get_float("FD_BENCH_REPLAY_TIMEOUT")
    with tempfile.TemporaryDirectory() as d:
        topo = build_topology(
            os.path.join(d, "replay.wksp"), depth=4096, wksp_sz=1 << 27
        )
        t0 = time.perf_counter()
        res = run_pipeline(
            topo,
            corpus.payloads,
            verify_backend="tpu",
            verify_batch=vbatch,
            timeout_s=timeout_s,
            tcache_depth=1 << 18,  # dedup window must span the corpus
            record_digests=True,
        )
        run_s = time.perf_counter() - t0
    rec, ok = _replay_artifact(
        "replay_pipeline_throughput", corpus, res, run_s, gen_s,
        timeout_s)
    print(json.dumps(rec))
    return 0 if ok else 1


def pack_worker() -> int:
    """BASELINE stretch goal bench: account-conflict scheduling as XLA
    graph coloring on a 64k-txn block (fd_pack.c:446-461 semantics).
    Validates admissibility against the CPU oracle and compares first-wave
    rewards-per-CU against the CPU greedy scheduler. ONE JSON line."""
    import random

    import jax

    from firedancer_tpu import compile_cache

    _require_tpu(jax, "pack_gc_schedule", "txns/s")
    compile_cache.enable()

    from firedancer_tpu.ballet.pack import Pack, PackTxn, validate_schedule
    from firedancer_tpu.ops.pack_gc import schedule_block

    n = flags.get_int("FD_BENCH_PACK_N")
    n_accounts = flags.get_int("FD_BENCH_PACK_ACCTS")
    rng = random.Random(7)
    keys = [i.to_bytes(8, "little") + bytes(24) for i in range(n_accounts)]
    txns = []
    for i in range(n):
        w = frozenset(rng.sample(keys, rng.randint(1, 3)))
        r = frozenset(k for k in rng.sample(keys, rng.randint(0, 3))
                      if k not in w)
        txns.append(PackTxn(txn_id=i, rewards=rng.randint(1_000, 2_000_000),
                            est_cus=rng.randint(10_000, 1_400_000),
                            writable=w, readonly=r))

    t0 = time.perf_counter()
    waves, leftover = schedule_block(txns, n_colors=64, h_bits=8192)
    sched_s = time.perf_counter() - t0
    admissible = validate_schedule(waves)

    # CPU greedy wave 0 for the quality comparison.
    cpu = Pack(bank_cnt=1, depth=n + 1)
    for t in txns:
        cpu.insert(t)
    t0 = time.perf_counter()
    cpu_wave = []
    while True:
        t = cpu.schedule(0, scan_limit=256)
        if t is None:
            break
        cpu_wave.append(t)
    cpu_s = time.perf_counter() - t0

    def rpc(wave):
        return (sum(t.rewards for t in wave)
                / max(sum(t.est_cus for t in wave), 1))

    scheduled = sum(len(w) for w in waves)
    rec = {
        "metric": "pack_gc_schedule",
        "value": round(n / sched_s, 1),
        "unit": "txns/s",
        "vs_baseline": 1.0 if admissible else 0.0,  # gate: admissibility
        "schema_version": _schema_version(),
        "block": n,
        "scheduled": scheduled,
        "leftover": len(leftover),
        "waves": len(waves),
        "admissible": admissible,
        "wave0_rewards_per_cu": round(rpc(waves[0]), 4) if waves else 0,
        "cpu_greedy_rewards_per_cu": round(rpc(cpu_wave), 4),
        "schedule_s": round(sched_s, 2),
        "cpu_greedy_s": round(cpu_s, 2),
    }
    print(json.dumps(rec))
    return 0 if admissible else 1


def _require_tpu(jax, metric: str, unit: str) -> None:
    """Exit non-zero with an error line unless JAX's first device is a
    TPU: a bench number is a chip number or nothing."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"metric": metric, "value": 0, "unit": unit,
                          "vs_baseline": 0.0,
                          "error": f"no TPU (platform {platform!r})"}))
        sys.exit(1)


def worker() -> int:
    """Measure on the attached TPU; print the JSON line."""
    batch = flags.get_int("FD_BENCH_BATCH")
    reps = flags.get_int("FD_BENCH_REPS")
    msg_len = flags.get_int("FD_BENCH_MSG_LEN")

    import jax
    import jax.numpy as jnp

    from firedancer_tpu import compile_cache

    _require_tpu(jax, "ed25519_verify_throughput", "verifies/s")
    compile_cache.enable()

    mode = flags.get_str("FD_BENCH_VERIFY")
    if mode not in ("rlc", "direct"):
        print(json.dumps({"metric": "ed25519_verify_throughput", "value": 0,
                          "unit": "verifies/s", "vs_baseline": 0.0,
                          "error": f"unknown FD_BENCH_VERIFY mode {mode!r}"}))
        return 1
    dev = jax.devices()[0]
    print(f"bench worker: device={dev} batch={batch} reps={reps} mode={mode}",
          file=sys.stderr)
    cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), f".bench_cache_{batch}_{msg_len}.npz"
    )
    msgs, lens, sigs, pubs = _gen_inputs(batch, msg_len, cache)
    args = tuple(
        jax.device_put(jnp.asarray(a), dev) for a in (msgs, lens, sigs, pubs)
    )

    # fd_engine registry resolution (PR 13): the worker's verify graph
    # is a registry entry — the SAME build path VerifyTile's prewarm
    # uses (rlc = direct jit + make_async_verifier wrap, all inside
    # disco/engine.py) — built UNWARMED so the compile is paid (and
    # timed) on the real inputs below and every timed rep stays one
    # execution. B-sweep rungs each resolve through this lookup too, so
    # compile_cache_hit_est comes from flight's one heuristic instead
    # of a bench-local copy drifting against the tile prewarm's.
    from firedancer_tpu.disco import engine as fd_engine

    entry, _ = fd_engine.registry().acquire(
        fd_engine.EngineSpec(mode, batch, 0, fd_engine.current_frontend()),
        warm=False)
    fn = entry.fn
    fell_back = False

    t0 = time.perf_counter()
    out = fn(*args)
    res0 = np.asarray(out)
    compile_s = time.perf_counter() - t0
    entry.account_first_call(compile_s, msg_len=msg_len)
    if mode == "rlc":
        fell_back = bool(getattr(out, "used_fallback", False))
    if not bool((res0 == 0).all()) or fell_back:
        print(json.dumps({"metric": "ed25519_verify_throughput", "value": 0,
                          "unit": "verifies/s", "vs_baseline": 0.0,
                          "error": "correctness check failed"
                                   + (" (rlc fell back)" if fell_back else "")}))
        return 1

    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(reps)]
    finals = [np.asarray(o) for o in outs]
    dt = time.perf_counter() - t0
    bad = any(not bool((f == 0).all()) for f in finals)
    # COUNT fallbacks, don't just flag them: the artifact must record
    # how many timed reps took the per-lane path (ROADMAP round-6 gate
    # "record fallback counts in the bench artifact") — 0 on the clean
    # bench corpus, and any nonzero count also voids the rlc timing.
    fallback_cnt = sum(
        1 for o in outs if getattr(o, "used_fallback", False)
    ) if mode == "rlc" else 0
    fell_back = fallback_cnt > 0
    if bad or fell_back:
        # Not an assert: a fallback-tainted timing must never publish as
        # an "rlc" rate (and must fail over to the direct mode), even
        # under python -O.
        print(json.dumps({"metric": "ed25519_verify_throughput", "value": 0,
                          "unit": "verifies/s", "vs_baseline": 0.0,
                          "rlc_fallbacks": fallback_cnt,
                          "error": "timed reps failed correctness"
                                   + (" (rlc fell back)" if fell_back else "")}))
        return 1
    rate = batch * reps / dt

    rec = {
        "metric": "ed25519_verify_throughput",
        "value": round(rate, 1),
        "unit": "verifies/s",
        "vs_baseline": round(rate / 1_000_000, 4),
        "schema_version": _schema_version(),
        "batch": batch,
        "msg_len": msg_len,
        "reps": reps,
        "mode": mode,
        "device": str(dev),
        "compile_s": round(compile_s, 1),
        "engine_key": entry.key,
        "compile_cache_hit_est": entry.cache_hit_est,
        "ms_per_batch": round(1e3 * dt / reps, 2),
        "rlc_fallbacks": fallback_cnt,
    }
    try:
        from scripts.bench_log_check import graph_cert_stamp

        # fdgraph era (schema_version >= 3): the headline record names
        # the proved graph contract set it ran under.
        rec["graph_cert"] = graph_cert_stamp(
            os.path.dirname(os.path.abspath(__file__)))
    except ImportError:
        pass
    # Round-10 artifact fields. The analytic fill-efficiency of the
    # Pippenger bucket grids at this batch plus the predicted B-sweep
    # winner (firedancer_tpu/msm_plan.py — stdlib math, free; the
    # measured sweep is main()'s FD_BENCH_SWEEP_B rungs) go in BEFORE
    # the headline prints.
    rec["stage_ms"] = None
    if mode == "rlc":
        from firedancer_tpu import msm_plan

        torsion_k = flags.get_int("FD_RLC_TORSION_K")
        # The ledger's K-sweep prediction (ROOFLINE #3) matches on this
        # field: without it a K=32 rung is indistinguishable from K=64
        # in the log and the prediction can never auto-grade.
        rec["torsion_k"] = torsion_k
        eff = msm_plan.fill_efficiency(batch, torsion_k=torsion_k)
        rec["fill_efficiency"] = round(eff["total"], 4)
        rec["b_sweep_predicted"] = msm_plan.sweep_prediction(
            (8192, 16384, 32768), torsion_k=torsion_k)
    # Publish the headline NOW: stage attribution below jits fresh
    # per-stage graphs, and if the rung's external timeout kills this
    # worker mid-attribution the orchestrator salvages this line
    # (_run_worker's TimeoutExpired path) — the attribution must never
    # void the measurement it annotates. When attribution completes,
    # the enriched record prints after and last-JSON-line-wins.
    print(json.dumps(rec), flush=True)
    if flags.get_bool("FD_BENCH_STAGE_ATTRIB"):
        try:
            from scripts.profile_stages import stage_attribution

            rec["stage_ms"] = stage_attribution(
                msgs, lens, sigs, pubs, mode=mode,
                reps=3,
                total_ms=rec["ms_per_batch"],
            )
        except Exception as e:  # noqa: BLE001 - attribution must never
            # void the headline measurement it annotates.
            print(f"bench: stage attribution failed: {e!r}",
                  file=sys.stderr)
            rec["stage_ms_error"] = repr(e)
        print(json.dumps(rec))
    return 0


def _run_worker(timeout_s: float, mode: str | None = None,
                extra_env: dict | None = None) -> dict | None:
    """Spawn a worker subprocess; return its parsed JSON line or None."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    env = dict(os.environ)
    if mode is not None:
        env["FD_BENCH_VERIFY"] = mode
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )
    except subprocess.TimeoutExpired as e:
        # Salvage a headline the worker already published: the worker
        # prints its measurement record BEFORE the stage-attribution
        # compiles, so a timeout during attribution must not void the
        # number. Error records (value 0) are never salvaged.
        out = e.stdout or ""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        for line in reversed(out.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") and rec.get("value"):
                    print(f"bench: worker timed out after {timeout_s:.0f}s "
                          "AFTER publishing its headline — "
                          "salvaged (stage attribution lost)",
                          file=sys.stderr)
                    rec["timed_out_post_headline"] = True
                    return rec
                break
        print(f"bench: worker timed out after {timeout_s:.0f}s",
              file=sys.stderr)
        return None
    if proc.stderr:
        sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode != 0:
        # A failing worker (no TPU, correctness check failed) counts as a
        # failed attempt, never as a measurement.
        print(f"bench: worker rc={proc.returncode}", file=sys.stderr)
        return None
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    print(f"bench: worker rc={proc.returncode}, no JSON line", file=sys.stderr)
    return None


def replay_main() -> int:
    """Orchestrate the replay gate in a worker subprocess (same shape as
    main()): the worker owns the chip and gets a hard timeout, and
    failures land as a JSON error line, never a traceback."""
    timeout_s = flags.get_float("FD_BENCH_REPLAY_TOTAL_TIMEOUT")
    cmd = [sys.executable, os.path.abspath(__file__), "--replay-worker"]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        print(json.dumps({
            "metric": "replay_pipeline_throughput", "value": 0,
            "unit": "txns/s", "vs_baseline": 0.0,
            "error": f"replay worker timed out after {timeout_s:.0f}s",
        }))
        return 1
    if proc.stderr:
        sys.stderr.write(proc.stderr[-2000:])
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            print(line)
            return proc.returncode
    print(json.dumps({
        "metric": "replay_pipeline_throughput", "value": 0,
        "unit": "txns/s", "vs_baseline": 0.0,
        "error": f"replay worker rc={proc.returncode}, no JSON line",
    }))
    return 1


_BENCH_LOG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "BENCH_LOG.jsonl")


def _log_measurement(rec: dict) -> None:
    """Append a dated copy of every successful measurement to the repo's
    BENCH_LOG.jsonl.

    The entry is validated against the log's own schema gate
    (scripts/bench_log_check.py, the ci.sh hygiene lane) BEFORE the
    append: a line this writer produces that its own CI lane would
    reject is a bench bug, and refusing loudly here beats poisoning
    every future fd_report trend/ledger read."""
    entry = dict(rec)
    entry.setdefault("schema_version", _schema_version())
    entry["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    try:
        from scripts.bench_log_check import (graph_cert_stamp,
                                             validate_entry)
    except ImportError:
        validate_entry = None  # validator missing is a repo-layout bug,
        # but must not void a real measurement round.
        graph_cert_stamp = None
    if (graph_cert_stamp is not None
            and entry.get("metric") == "ed25519_verify_throughput"
            and entry.get("graph_cert") is None):
        # fdgraph era (schema_version >= 3): every verify number is
        # attributable to the proved graph contract set it ran under —
        # the sha of the committed certificate plus its per-rung MSM
        # cost-drift. No committed cert -> stamp stays absent and the
        # validator below refuses the append.
        entry["graph_cert"] = graph_cert_stamp(
            os.path.dirname(os.path.abspath(__file__)))
    if validate_entry is not None:
        errs = validate_entry(entry)
        if errs:
            raise ValueError(
                "bench: refusing to append a BENCH_LOG.jsonl line that "
                f"fails its own validator: {errs} (entry: {entry})"
            )
    try:
        with open(_BENCH_LOG, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass


def main() -> int:
    """Orchestrate the verify bench on the attached TPU within the
    driver's ~1200s patience.

    Ladder (each rung a subprocess with a hard timeout; round-6 flip —
    RLC batch verification over the VMEM Pallas MSM is the PRIMARY
    production mode, docs/ROOFLINE.md):
      1. rlc mode on device     — the primary rung. Its compile is the
         ladder's largest, so it is budgeted to always leave rung 2 a
         full attempt. FD_BENCH_RLC=0 skips it (park escape hatch).
      2. direct mode on device  — the measured fallback: it ALWAYS runs
         too, so every round records both modes and the artifact names
         which one produced the headline (headline_mode).
      3. direct A/B rungs (FD_MUL_IMPL et al.) — leftover budget only.
      4. direct compat (FD_SQ_IMPL=mul) — only if rung 2 failed.
    With no TPU every rung fails and so does the bench (error line,
    exit 1).
    Every successful worker measurement is appended to BENCH_LOG.jsonl;
    the headline is the best measured value across rungs, never a
    fallback-tainted rlc timing (the worker refuses those).
    """
    errors = []
    tpu_budget = flags.get_float("FD_BENCH_TPU_BUDGET")
    attempt_timeout = flags.get_float("FD_BENCH_ATTEMPT_TIMEOUT")
    rlc_min_s = flags.get_float("FD_BENCH_RLC_MIN_BUDGET")
    forced = flags.get_raw("FD_BENCH_VERIFY")
    if forced and forced not in ("rlc", "direct"):
        print(json.dumps({
            "metric": "ed25519_verify_throughput", "value": 0,
            "unit": "verifies/s", "vs_baseline": 0.0,
            "error": f"unknown FD_BENCH_VERIFY mode {forced!r}",
        }))
        return 1
    t_start = time.monotonic()

    def left() -> float:
        return tpu_budget - (time.monotonic() - t_start)

    best = None

    def attempt(mode: str, extra: dict | None, timeout_s: float):
        nonlocal best
        rec = _run_worker(timeout_s=timeout_s, mode=mode, extra_env=extra)
        if rec is None:
            errors.append(f"tpu {mode}" + (" compat" if extra else "")
                          + " failed/timed out")
            return None
        if extra:
            rec["compat_env"] = extra
        _log_measurement(rec)
        if best is None or rec.get("value", 0) > best.get("value", 0):
            best = rec
        return rec

    if forced:
        attempt(forced, None, min(attempt_timeout, max(left(), 60.0)))
    else:
        # PRIMARY rung: rlc (round-6 promotion). Budgeted so the direct
        # rung below keeps a full attempt even if the rlc compile eats
        # its whole timeout — a numberless round is worse than a
        # direct-only round.
        direct_min_s = flags.get_float("FD_BENCH_DIRECT_MIN_BUDGET")
        rlc_rec = None
        if flags.get_str("FD_BENCH_RLC") != "0":
            rlc_budget = min(attempt_timeout, left() - direct_min_s)
            if rlc_budget >= 120.0:
                rlc_rec = attempt("rlc", None, rlc_budget)
        # Measured fallback rung: direct always runs so the artifact
        # records both modes side by side.
        direct_rec = attempt("direct", None, min(attempt_timeout, left()))
        if direct_rec is not None and left() > rlc_min_s:
            # A/B the in-kernel multiply with leftover budget (best-of-
            # log still picks the headline). rolled first: the round-5
            # 7-rotation schedule — kernel_probe3 showed the unrolled
            # multiply is ~all sublane-rotation cost, not arithmetic.
            attempt("direct", {"FD_MUL_IMPL": "rolled"},
                    min(attempt_timeout, left() - 30.0))
        if direct_rec is not None and left() > rlc_min_s:
            # rolled squares (fe_mul_rolled(a,a)) vs specialized fe_sq:
            # the two measured within noise in the chain probe; the DSM
            # decides.
            attempt("direct", {"FD_MUL_IMPL": "rolled",
                               "FD_SQ_IMPL": "mul"},
                    min(attempt_timeout, left() - 30.0))
        if direct_rec is not None and left() > rlc_min_s:
            # f32 measured 112.9k vs schoolbook's 112.6k (2026-08-01):
            # kept as a rung only while it stays within budget.
            attempt("direct", {"FD_MUL_IMPL": "f32"},
                    min(attempt_timeout, left() - 30.0))
        if direct_rec is None and left() > 90.0:
            # Compat rung: roll back the round-4 KS canonicalize and
            # the specialized square together — the two constructions a
            # Mosaic update is most likely to reject (the KS form has
            # only interpret-mode coverage until first on-chip run).
            # Gated on the DIRECT rung failing, not on best being empty:
            # an rlc number in `best` must not suppress the round's only
            # chance at a direct measurement.
            attempt("direct", {"FD_SQ_IMPL": "mul",
                               "FD_CANON_IMPL": "seq"},
                    min(attempt_timeout, left()))
        # Round-10 fill-efficiency B-sweep (FD_BENCH_SWEEP_B, e.g.
        # "8192,16384,32768"): each size is its own budgeted rlc rung —
        # msm_plan predicts efficiency monotone in B, these rungs
        # measure the compile/VMEM/dispatch effects the model cannot
        # see. Stage attribution is skipped on sweep rungs (the default
        # shape's rung already carries it; sweep budget buys sizes, not
        # repeats). The winner becomes the headline via best-of-log.
        sweep_raw = flags.get_raw("FD_BENCH_SWEEP_B")
        if sweep_raw:
            b_results = {}
            for b_str in sweep_raw.split(","):
                try:
                    b = int(b_str)
                except ValueError:
                    errors.append(f"bad FD_BENCH_SWEEP_B entry {b_str!r}")
                    continue
                if b == flags.get_int("FD_BENCH_BATCH") and (
                        rlc_rec is not None):
                    # The primary rung measured this size — reuse its
                    # value so b_sweep_measured is complete (ROOFLINE
                    # prediction 9 reads the ordering from this one
                    # dict), but only skip the re-run when the primary
                    # actually SUCCEEDED; a parked/failed primary would
                    # otherwise leave the size silently unmeasured.
                    b_results[b] = rlc_rec.get("value", 0)
                    continue
                if left() <= rlc_min_s:
                    errors.append(f"B-sweep: no budget left for B={b}")
                    break
                rec = attempt("rlc", {"FD_BENCH_BATCH": str(b),
                                      "FD_BENCH_STAGE_ATTRIB": "0"},
                              min(attempt_timeout, left() - 30.0))
                if rec is not None:
                    b_results[b] = rec.get("value", 0)
            if b_results and best is not None:
                best = dict(best)
                best["b_sweep_measured"] = b_results
    if best is not None:
        out = dict(best)
        # Which mode produced the headline number (the artifact must
        # say, not leave it to whoever diffs BENCH_LOG later).
        out["headline_mode"] = out.get("mode")
        # Annotate the log with the headline SHAPE when a sweep ran or
        # a non-default batch won, so a BENCH_r06 diff can see which
        # sweep point produced the number without re-deriving it from
        # value ordering.
        if out.get("mode") == "rlc" and out.get("batch") and (
                out.get("b_sweep_measured")
                or out["batch"] != flags.get_int("FD_BENCH_BATCH")):
            _log_measurement({
                "metric": "note",
                "note": f"headline shape: mode={out['mode']} "
                        f"B={out['batch']} ({out.get('value', 0)} "
                        "verifies/s; round-10 fused front-end + "
                        "B-sweep pick)",
                "b_sweep_measured": out.get("b_sweep_measured"),
            })
        print(json.dumps(out))
        return 0
    print(json.dumps({
        "metric": "ed25519_verify_throughput",
        "value": 0,
        "unit": "verifies/s",
        "vs_baseline": 0.0,
        "schema_version": _schema_version(),
        "error": "; ".join(errors) or "no rung ran",
    }))
    return 1

if __name__ == "__main__":
    if "--pack" in sys.argv:
        sys.exit(pack_worker())
    if "--replay-cpu" in sys.argv:
        sys.exit(replay_cpu_worker())
    if "--replay-worker" in sys.argv:
        sys.exit(replay_worker())
    if "--replay" in sys.argv or flags.get_raw("FD_BENCH_MODE") == "replay":
        sys.exit(replay_main())
    if "--worker" in sys.argv:
        sys.exit(worker())
    sys.exit(main())
