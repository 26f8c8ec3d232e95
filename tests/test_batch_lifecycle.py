"""Verify-batch lifecycle: fd.* host spans on the profiler's clock and
the verify lane's per-batch phase counters (disco/flight.py span_maker,
disco/tiles.py book_retire, ops/verify_rlc.py RlcAsyncResult).

The counters split a retired fd_feed batch into ring wait, staging,
READY wait, in flight and retire, booked once per batch after its
publish; the spans name the host work of each phase in a jax.profiler
trace, beside the device ops.
"""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from firedancer_tpu.disco import flight

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ("stage_ns", "ready_wait_ns", "inflight_ns", "retire_ns")
LIFECYCLE = ("retired", "ring_wait_ns") + PHASES + ("fallback_wait_ns",)


def test_lifecycle_counters_are_specced():
    for name in LIFECYCLE:
        assert flight.TILE_IDX[name] >= 0
        assert flight.TILE_METRICS[flight.TILE_IDX[name]].kind == "counter"


def test_span_is_a_noop_without_jax():
    """A host-backend process (tiles + flight only) gets the shared
    no-op span and never loads JAX."""
    code = (
        "import sys\n"
        "import firedancer_tpu.disco.tiles\n"
        "from firedancer_tpu.disco import flight\n"
        "span = flight.span_maker()\n"
        "s = span('fd.verify.dispatch')\n"
        "assert s is flight._NO_SPAN, s\n"
        "with s:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"


def test_span_is_a_noop_with_flight_off(monkeypatch):
    import jax  # noqa: F401

    monkeypatch.setenv("FD_FLIGHT", "0")
    span = flight.span_maker()
    assert span("fd.verify.publish") is flight._NO_SPAN


def _host_events(path, name):
    from jax.profiler import ProfileData

    return [e for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events if e.name == name]


def test_span_lands_in_a_profiler_trace(tmp_path):
    """With JAX loaded each use of a site's maker is one host event in
    the trace (at the benchmark capture's host tracer level)."""
    import jax
    import jax.numpy as jnp

    span = flight.span_maker()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with span("fd.test.lifecycle"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    evs = _host_events(path, "fd.test.lifecycle")
    assert len(evs) == 3
    assert all(e.duration_ns > 0 for e in evs)


# ------------------------------------------------------------ booking ---


class _Verdict:
    """A ready device scalar standing in for the rlc batch verdict."""

    def __init__(self, ok):
        self.ok = ok

    def is_ready(self):
        return True

    def __bool__(self):
        return bool(self.ok)


class _Done:
    def __init__(self, statuses):
        self.statuses = np.asarray(statuses)

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        return self.statuses


def _rlc_result(ok):
    from firedancer_tpu.ops.verify_rlc import RlcAsyncResult

    status = np.zeros(4, np.int32)
    launched = []

    def fallback(*args):
        launched.append(args)
        return _Done([0, -1, 0, 0])

    return RlcAsyncResult((status, np.ones(4, bool), _Verdict(ok)),
                          fallback, ("m", "l", "s", "p"),
                          flight.span_maker()), launched


def _batch(out, t_first, t_commit, t_dispatch, tspub):
    from firedancer_tpu.disco.feed.slots import Slot
    from firedancer_tpu.disco.tiles import _InflightBatch

    slot = Slot(0, batch=16, max_msg_len=64)
    slot.n_txn = 1
    slot.t_first, slot.t_commit = t_first, t_commit
    slot.tspubs[0] = tspub & 0xFFFFFFFF
    return _InflightBatch(out=out, todo=[], oversize=[],
                          t_dispatch=t_dispatch, slot=slot)


def test_failed_verdict_stamps_and_books_the_fallback_wait():
    from firedancer_tpu.disco.tiles import book_retire
    from firedancer_tpu.tango import tempo

    out, launched = _rlc_result(ok=False)
    t0 = tempo.tickcount()
    ib = _batch(out, t0 - 5_000, t0 - 3_000, t0, t0 - 9_000)
    assert out.t_fallback == 0
    assert out.is_ready()              # the per-lane launch happens here
    assert out.used_fallback and len(launched) == 1
    assert out.t_fallback >= t0
    t_ready = tempo.tickcount()
    lane = flight.TileLane("verify")
    book_retire(lane, ib, t_ready, t_ready + 700)
    d = lane.as_dict()
    assert d["fallback_wait_ns"] == out.t_fallback - t0
    assert d["retired"] == 1
    assert d["ring_wait_ns"] == 4_000
    assert (d["stage_ns"], d["ready_wait_ns"]) == (2_000, 3_000)
    assert d["inflight_ns"] == t_ready - t0 and d["retire_ns"] == 700
    np.testing.assert_array_equal(np.asarray(out), [0, -1, 0, 0])


def test_clean_verdict_books_no_fallback_wait():
    from firedancer_tpu.disco.tiles import book_retire

    out, launched = _rlc_result(ok=True)
    ib = _batch(out, 1_000, 2_000, 3_000, 1_000)
    assert out.is_ready() and not out.used_fallback and not launched
    assert out.t_fallback == 0
    lane = flight.TileLane("verify")
    book_retire(lane, ib, 4_000, 4_500)
    d = lane.as_dict()
    assert d["fallback_wait_ns"] == 0 and d["retired"] == 1
    assert sum(d[k] for k in PHASES) == 4_500 - 1_000


@pytest.mark.parametrize("dwell_ns,booked", [
    (0, 0), (1_500_000, 1_500_000), ((1 << 32) - 10, 0)])
def test_ring_wait_follows_the_drain_dwell_rule(dwell_ns, booked):
    """The oldest frag's 32-bit publish stamp against t_first: a dwell
    the wrap-safe rule rejects (past ~4 s) books 0."""
    from firedancer_tpu.disco.tiles import book_retire

    t_first = (7 << 32) + 123_456
    ib = _batch(_Done([0]), t_first, t_first, t_first, t_first - dwell_ns)
    lane = flight.TileLane("verify")
    book_retire(lane, ib, t_first, t_first)
    assert lane.get("ring_wait_ns") == booked


def test_feed_pipeline_books_every_retired_batch(tmp_path, monkeypatch):
    """A feed run on the CPU backend: every dispatched batch retires
    once, and the four telescoping phases sum to first-staged ->
    retired exactly."""
    from firedancer_tpu.disco import tiles
    from firedancer_tpu.disco.corpus import mainnet_corpus
    from firedancer_tpu.disco.pipeline import build_topology, run_pipeline

    seen = {"n": 0, "span_ns": 0, "lanes": set()}
    real = tiles.book_retire

    def spy(fl, ib, t_ready, t_retired):
        seen["n"] += 1
        seen["span_ns"] += t_retired - ib.slot.t_first
        seen["lanes"].add(fl)
        assert ib.slot.t_first <= ib.slot.t_commit <= ib.t_dispatch
        assert ib.t_dispatch <= t_ready <= t_retired
        real(fl, ib, t_ready, t_retired)

    monkeypatch.setattr(tiles, "book_retire", spy)
    corpus = mainnet_corpus(n=120, seed=31, dup_rate=0.1, corrupt_rate=0.06,
                            parse_err_rate=0.04, sign_batch_size=128,
                            max_data_sz=140)
    topo = build_topology(str(tmp_path / "life.wksp"), depth=256)
    res = run_pipeline(topo, corpus.payloads, verify_backend="cpu",
                       timeout_s=240.0, feed=True, verify_batch=64)
    assert res.feed
    (fl,) = seen["lanes"]
    d = fl.as_dict()
    assert d["batches"] == res.verify_stats[0]["batches"] >= 2
    assert d["retired"] == d["batches"] == seen["n"]
    assert all(d[k] >= 0 for k in LIFECYCLE)
    assert d["fallback_wait_ns"] == 0      # no rlc pass on the CPU lane
    assert sum(d[k] for k in PHASES) == seen["span_ns"]


def test_prom_exports_the_lifecycle_counters(tmp_path):
    from firedancer_tpu.tango.rings import Workspace

    wksp = Workspace.create(str(tmp_path / "p.wksp"), 1 << 22)
    flight.create_regions(wksp, ["verify"], ["sink"])
    lane = flight.tile_lane(wksp, "verify")
    lane.inc("retired", 3)
    lane.inc("inflight_ns", 360_000_000)
    lane.publish()
    text = flight.render_prom(wksp)
    assert 'fd_flight_retired{tile="verify"} 3' in text
    assert 'fd_flight_inflight_ns{tile="verify"} 360000000' in text
    assert "# TYPE fd_flight_fallback_wait_ns counter" in text
    wksp.leave()
