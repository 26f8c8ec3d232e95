"""QuicTile lifecycle + fd_siege defense tests.

Covers the fd_siege satellite contract: step/done/on_halt under
connection churn, sink-content parity of a clean QUIC-ingested corpus
vs the direct replay path, the admission/shedding/quarantine defenses
with their accounting (admitted + shed == offered, shed ledger), and
the three quic chaos classes' tri-counter parity running against live
traffic.
"""

import hashlib
import os
import time
from collections import Counter

import pytest

from firedancer_tpu.disco.pipeline import (
    _make_source_out_link,
    build_topology,
    run_pipeline,
    run_quic_pipeline,
)
from firedancer_tpu.tango.quic.quic import Quic, QuicConfig
from firedancer_tpu.tango.rings import Workspace
from firedancer_tpu.tango.udpsock import UdpSock


def _corpus(n, seed=0, **kw):
    from firedancer_tpu.disco.corpus import mainnet_corpus

    kw.setdefault("dup_rate", 0.0)
    kw.setdefault("corrupt_rate", 0.0)
    kw.setdefault("parse_err_rate", 0.0)
    return mainnet_corpus(n=n, seed=seed, sign_batch_size=64,
                          max_data_sz=120, **kw)


def _client(listen_addr, txns, n_conns=1, junk_before=0, junk_seed=7):
    """Deliver txns over n_conns sequential QUIC connections (churn
    shape); optionally spray junk datagrams first from the same
    socket (abuse-attribution traffic)."""
    sock = UdpSock()
    tx_aio = sock.aio_tx()
    client = Quic(
        QuicConfig(is_server=False, identity_seed=os.urandom(32)),
        tx=lambda addr, d: tx_aio.send_one(addr, d),
    )
    if junk_before:
        import random

        rng = random.Random(junk_seed)
        for _ in range(junk_before):
            tx_aio.send_one(listen_addr, bytes(
                rng.randrange(256) for _ in range(48)))
    per = -(-len(txns) // n_conns) if txns else 1
    t0 = time.monotonic()
    for ci in range(n_conns):
        chunk = txns[ci * per:(ci + 1) * per]
        if not chunk and ci:
            break
        conn = client.connect(listen_addr, time.monotonic() - t0)
        sent = False
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            now = time.monotonic() - t0
            sock.service_rx(lambda a, d: client.rx(a, d, now))
            client.service(now)
            if conn.closed:
                break
            if conn.established and not sent:
                for t in chunk:
                    conn.send_stream(t)
                sent = True
            if (sent and not conn._send_queue
                    and not any(s.sent for s in conn.spaces)):
                conn.closed = True
                break
            time.sleep(0.001)
    sock.close()


# ------------------------------------------------------------ lifecycle ---

def test_quic_tile_step_done_halt_lifecycle(tmp_path):
    """Direct tile construction: done() semantics (streams seen +
    queues drained), on_halt socket teardown + halt-shed accounting."""
    from firedancer_tpu.disco.quic_tile import QuicTile, quic_tile_stats

    topo = build_topology(str(tmp_path / "lc.wksp"), depth=32)
    wksp = Workspace.join(topo.wksp_path)
    tile = QuicTile(
        wksp, "quic.cnc",
        out_link=_make_source_out_link(wksp, topo.pod),
        identity_seed=b"\x11" * 32, stop_after=2,
    )
    assert not tile.done()
    # Feed two completed streams through the admission path directly.
    class _FakeConn:
        peer_addr = ("t", 1)
    tile._on_stream(_FakeConn(), 2, b"\x01" + b"a" * 80)
    tile._on_stream(_FakeConn(), 6, b"\x01" + b"b" * 80)
    assert tile.streams_seen == 2 and not tile.done()  # queued, undrained
    tile.step()
    assert tile.pub_cnt == 2 and tile.done()
    st = quic_tile_stats(tile)
    assert st["admitted"] + st["shed_total"] == st["offered"] == 2
    # Queued-at-halt work books as shed (parity survives truncation).
    tile._on_stream(_FakeConn(), 10, b"\x01" + b"c" * 80)
    tile.on_halt()
    st = quic_tile_stats(tile)
    assert st["admitted"] + st["shed_total"] == st["offered"] == 3
    assert len(tile.shed_sha256) == 1
    assert tile.sock._sock.fileno() == -1  # socket closed
    wksp.leave()


def test_quic_tile_connection_churn(tmp_path):
    """Many short-lived connections deliver the corpus; every txn
    arrives exactly once and the endpoint books the churn."""
    corpus = _corpus(24, seed=5)
    topo = build_topology(str(tmp_path / "churn.wksp"), depth=64)
    res = run_quic_pipeline(
        topo, lambda addr: _client(addr, corpus.payloads, n_conns=6),
        n_txns=len(corpus.payloads), verify_backend="cpu",
        timeout_s=60.0, record_digests=True, quic_idle_timeout=2.0,
    )
    assert res.recv_cnt == len(corpus.payloads), res.diag
    assert res.quic is not None
    assert res.quic["quic_metrics"]["conns_created"] >= 6
    assert (res.quic["admitted"] + res.quic["shed_total"]
            == res.quic["offered"] == len(corpus.payloads))


def test_quic_feed_parity_vs_replay(tmp_path):
    """Sink-content parity: the same clean corpus through the QUIC
    front door (fd_feed topology) and through the direct replay path
    must produce identical sink digest multisets."""
    corpus = _corpus(32, seed=9)
    topo_r = build_topology(str(tmp_path / "rep.wksp"), depth=256)
    res_r = run_pipeline(topo_r, corpus.payloads, verify_backend="cpu",
                         timeout_s=60.0, record_digests=True)
    topo_q = build_topology(str(tmp_path / "qf.wksp"), depth=256)
    res_q = run_quic_pipeline(
        topo_q, lambda addr: _client(addr, corpus.payloads, n_conns=4),
        n_txns=len(corpus.payloads), verify_backend="cpu",
        timeout_s=60.0, record_digests=True, feed=True,
        quic_idle_timeout=2.0,
    )
    assert res_q.feed, res_q.feed_fallback_reason
    assert res_q.recv_cnt == res_r.recv_cnt == len(corpus.payloads)
    assert Counter(res_q.sink_digests) == Counter(res_r.sink_digests)


# ------------------------------------------------------------- defenses ---

def test_admission_bucket_sheds_and_ledgers(tmp_path, monkeypatch):
    """A connection bursting past its token bucket gets shed — with
    parity intact and every shed txn's sha256 in the ledger, so the
    sink holds exactly the admitted valid txns."""
    monkeypatch.setenv("FD_QUIC_ADMIT_RATE", "40")
    monkeypatch.setenv("FD_QUIC_ADMIT_BURST", "8")
    monkeypatch.setenv("FD_QUIC_ABUSE_THRESHOLD", "10000")  # isolate
    corpus = _corpus(36, seed=11)
    topo = build_topology(str(tmp_path / "adm.wksp"), depth=256)
    res = run_quic_pipeline(
        topo, lambda addr: _client(addr, corpus.payloads, n_conns=1),
        n_txns=len(corpus.payloads), verify_backend="cpu",
        timeout_s=60.0, record_digests=True, quic_idle_timeout=2.0,
    )
    q = res.quic
    assert q["admit_shed"] >= 1
    assert q["admitted"] + q["shed_total"] == q["offered"] \
        == len(corpus.payloads)
    assert len(q["shed_sha256"]) == q["shed_total"]
    ok = {hashlib.sha256(p).hexdigest() for p in corpus.payloads}
    admitted = set(q["admitted_sha256"])
    got = {(d.hex() if isinstance(d, bytes) else d)
           for d in res.sink_digests}
    assert got == (ok & admitted)


def test_abuse_breaker_quarantines_junk_peer(tmp_path, monkeypatch):
    """A peer spraying junk datagrams trips the connection-level
    breaker: its datagrams drop at the socket for the cooldown, while
    an honest peer's delivery is untouched."""
    monkeypatch.setenv("FD_QUIC_ABUSE_THRESHOLD", "8")
    monkeypatch.setenv("FD_QUIC_QUARANTINE_COOLDOWN_MS", "30000")
    corpus = _corpus(10, seed=13)

    def client_fn(addr):
        import threading

        atk = UdpSock()
        atk_tx = atk.aio_tx()
        # The honest peer starts only once the junk has passed the
        # threshold with room to spare: the tile reads its socket in
        # arrival order, so the trip and some drops come before the
        # honest txns, and a fast honest run cannot end the pipeline
        # before any quarantined datagram is dropped.
        tripped = threading.Event()

        def attack():
            import random

            rng = random.Random(3)
            for i in range(200):
                atk_tx.send_one(addr, bytes(
                    rng.randrange(256) for _ in range(40)))
                if i == 20:
                    tripped.set()
                time.sleep(0.001)
            atk.close()

        t = threading.Thread(target=attack, daemon=True)
        t.start()
        tripped.wait(timeout=10.0)
        _client(addr, corpus.payloads, n_conns=1)
        t.join(timeout=10.0)

    topo = build_topology(str(tmp_path / "quar.wksp"), depth=256)
    res = run_quic_pipeline(
        topo, client_fn, n_txns=len(corpus.payloads),
        verify_backend="cpu", timeout_s=60.0, record_digests=True,
        quic_idle_timeout=2.0,
    )
    q = res.quic
    assert q["conn_quarantine"] >= 1
    assert q["quarantine_drop"] >= 1
    assert res.recv_cnt == len(corpus.payloads)  # honest peer untouched


def test_slowloris_reassembly_budget_quarantines(tmp_path, monkeypatch):
    """A connection dribbling partial streams past the reassembly
    budget is quarantined; honest delivery completes."""
    monkeypatch.setenv("FD_QUIC_SLOW_MAX_BUF", "2048")
    monkeypatch.setenv("FD_QUIC_ABUSE_THRESHOLD", "8")
    corpus = _corpus(8, seed=17)

    def client_fn(addr):
        import threading

        def dribble():
            sock = UdpSock()
            tx_aio = sock.aio_tx()
            cl = Quic(QuicConfig(is_server=False,
                                 identity_seed=os.urandom(32)),
                      tx=lambda a, d: tx_aio.send_one(a, d))
            t0 = time.monotonic()
            conn = cl.connect(addr, 0.0)
            sent = False
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not conn.closed:
                now = time.monotonic() - t0
                sock.service_rx(lambda a, d: cl.rx(a, d, now))
                cl.service(now)
                if conn.established and not sent:
                    for _ in range(6):
                        conn.send_stream(b"\x55" * 900, fin=False)
                    sent = True
                time.sleep(0.002)
            sock.close()

        t = threading.Thread(target=dribble, daemon=True)
        t.start()
        _client(addr, corpus.payloads, n_conns=1)
        t.join(timeout=12.0)

    topo = build_topology(str(tmp_path / "slow.wksp"), depth=256)
    n = len(corpus.payloads)

    def stop_when(tile):
        # Quiesce only once the reassembly-budget scan has acted (the
        # housekeeping-rate scan races a fast honest delivery
        # otherwise); a broken defense times the run out instead.
        return (tile.streams_seen >= n and not tile._ready
                and not tile._deferred
                and tile.fl.get("conn_quarantine") >= 1)

    res = run_quic_pipeline(
        topo, client_fn, n_txns=n,
        verify_backend="cpu", timeout_s=40.0, record_digests=True,
        quic_idle_timeout=3.0, quic_stop_when=stop_when,
    )
    assert res.quic["conn_quarantine"] >= 1
    assert res.recv_cnt == len(corpus.payloads)


# ---------------------------------------------------------- chaos audit ---

def test_quic_chaos_classes_tri_counter_parity(tmp_path, monkeypatch):
    """quic_malformed / quic_conn_churn / quic_slowloris injected
    CONCURRENTLY with live client traffic: injected == detected ==
    healed per class, content delivered intact (slowloris defers, never
    loses), and the run quiesces only after every scheduled fault fired
    (chaos_quiet gating)."""
    monkeypatch.setenv("FD_CHAOS", "1")
    monkeypatch.setenv("FD_CHAOS_SEED", "3")
    monkeypatch.setenv(
        "FD_CHAOS_SCHEDULE",
        "quic_malformed@5,quic_malformed@40,quic_conn_churn@8,"
        "quic_slowloris@20:160")
    monkeypatch.setenv("FD_QUIC_HS_TIMEOUT_S", "0.5")
    corpus = _corpus(16, seed=21)
    topo = build_topology(str(tmp_path / "qchaos.wksp"), depth=256)
    res = run_quic_pipeline(
        topo, lambda addr: _client(addr, corpus.payloads, n_conns=2),
        n_txns=len(corpus.payloads), verify_backend="cpu",
        timeout_s=90.0, record_digests=True, quic_idle_timeout=2.0,
    )
    from firedancer_tpu.disco import chaos

    inj = chaos.active()
    assert inj is not None
    counters = inj.snapshot()["counters"]
    for cls, want in (("quic_malformed", 2), ("quic_conn_churn", 1),
                      ("quic_slowloris", 1)):
        c = counters[cls]
        assert c["injected"] == c["detected"] == c["healed"] == want, \
            (cls, c)
    # Deferral is delay, not loss: every valid txn still lands.
    assert res.recv_cnt == len(corpus.payloads)
    want = Counter(hashlib.sha256(p).digest() for p in corpus.payloads)
    got = Counter(d if isinstance(d, bytes) else bytes.fromhex(d)
                  for d in res.sink_digests)
    assert got == want


def test_defenses_off_hatch(tmp_path, monkeypatch):
    """FD_QUIC_DEFENSES=0: no admission, no shedding, no quarantine —
    the bisection hatch the siege overhead gate relies on."""
    monkeypatch.setenv("FD_QUIC_DEFENSES", "0")
    monkeypatch.setenv("FD_QUIC_ADMIT_RATE", "1")  # would shed if armed
    monkeypatch.setenv("FD_QUIC_ADMIT_BURST", "1")
    corpus = _corpus(12, seed=23)
    topo = build_topology(str(tmp_path / "off.wksp"), depth=256)
    res = run_quic_pipeline(
        topo, lambda addr: _client(addr, corpus.payloads, n_conns=1),
        n_txns=len(corpus.payloads), verify_backend="cpu",
        timeout_s=60.0, record_digests=True, quic_idle_timeout=2.0,
    )
    q = res.quic
    assert q["shed_total"] == 0 and q["conn_quarantine"] == 0
    assert res.recv_cnt == len(corpus.payloads)
