"""fd_fabric — multi-host, multi-tenant verify fabric (ROADMAP
direction 1, the pod taken across processes).

The reference scales past one host by running independent firedancer
instances; wiredancer scales past one FPGA by sharding the signature
stream over cards. The TPU-native composition: EVERY fabric process
runs its own complete ingest stack — tenant front door (token-bucket
admission, disco/feed/policy.TokenBucket), fd_feed SlotPool staging
lanes (one per local 'dp' device, disco/pod.ShardLane), its own flight
workspace — and all processes join ONE jax.distributed mesh of shape
(host, dp): 'host' is the DCN axis (one row per process), 'dp' the
on-host axis (ICI on real pods). The verify graphs are the PR-13 split
pair built over that mesh (parallel/mesh.verify_rlc_split_global):

    local_fill     per-shard SHA / decompress / status ladder /
                   Pippenger bucket fills — NO collectives, so a
                   host's batch bytes never leave the host
    combine_tail   ONE all_gather of the tiny per-shard window/trial
                   partials + doubling chains — the ONLY payload that
                   crosses DCN

matching tango's philosophy exactly: lossy broadcast stays host-local,
only partials cross the wire.

LOCKSTEP CONTRACT. shard_map collectives require every process to
dispatch the same graphs in the same order. Each fabric step all-
gathers a single "I still have work" int32 per host
(multihost_utils.process_allgather — the 4-byte control plane) and
every host dispatches one global batch per step, zero-padding when its
own lanes are empty; pad lanes resolve definite exactly like the feed
path's zeroed warm rows, so a short host never perturbs the verdict.

DETERMINISM / DIGEST PARITY. Tenant admission is a pure function of
the tenant's OWN virtual arrival clock (siege.TenantSpec.arrival_ns
drives the bucket, not wall time), and tenants are assigned WHOLE to
hosts by a deterministic greedy pack over simulated admitted counts
(assign_tenants). So the union of admitted transactions is identical
however many hosts the fabric runs — the merged verified-digest
multiset from an N-process run is bit-exact against the 1-process
control, the fabric smoke's headline gate.

JUDGMENT. Each process publishes its flight registry + tenant ledger
as a JSON dump; process 0 (or the parent runner) merges them with
flight.merge_snapshots and grades the merged edges/ledger with
sentinel.evaluate_edges_summary + evaluate_tenant_summary — one
cross-host judgment with exact counter/parity arithmetic
(admitted + shed == offered per tenant, always).

Host-side: numpy + flight/feed/pod helpers; jax is imported lazily in
FabricHost (the coordinator-side merge functions never touch jax).
"""

from __future__ import annotations

import json
import os
import time
from hashlib import sha256 as _sha256
from typing import Dict, List, Optional, Tuple

import numpy as np

from firedancer_tpu import flags
from firedancer_tpu.disco import flight
from firedancer_tpu.disco.feed.policy import TokenBucket
from firedancer_tpu.disco.pod import ShardLane

FABRIC_SCHEMA_VERSION = 2
DUMP_PREFIX = "fabric_proc"

# Virtual-clock scale: TenantSpec rates are txns/s, arrival clocks are
# ns — one bucket token per 1e9 virtual ns per rate_tps.
_NS_PER_S = 1e9


# --------------------------------------------------------------------------
# Tenant admission: per-tenant token buckets over virtual arrival time.
# --------------------------------------------------------------------------


class TenantAdmission:
    """The fabric front door for one host's OWNED tenants.

    One policy.TokenBucket per tenant, driven by the tenant's virtual
    arrival clock (TenantSpec.arrival_ns) with the rate pre-scaled to
    tokens/ns — admission is a pure function of the tenant's own
    stream, independent of host placement and wall time (the lockstep
    and digest-parity keystone). Shed work is ACCOUNTED, never silent:
    the ledger keeps admitted + shed == offered per tenant exactly
    (sentinel.evaluate_tenant_summary's parity gate), and shed payload
    digests land in shed_sha256, the same audit discipline as the QUIC
    front door's shed ledger."""

    def __init__(self, tenants, owned: Optional[List[str]] = None):
        specs = list(tenants)
        if owned is not None:
            keep = set(owned)
            specs = [t for t in specs if t.name in keep]
        self.specs = {t.name: t for t in specs}
        self.buckets: Dict[str, TokenBucket] = {
            t.name: TokenBucket(t.rate_tps / _NS_PER_S, t.burst)
            for t in specs
        }
        self.ledger: Dict[str, Dict[str, int]] = {
            t.name: {"offered": 0, "admitted": 0, "shed": 0}
            for t in specs
        }
        self.shed_sha256: List[bytes] = []

    def admit(self, tenant: str, arrival_ns: int,
              payload: Optional[bytes] = None) -> bool:
        """One offered txn at the tenant's virtual arrival instant."""
        led = self.ledger[tenant]
        led["offered"] += 1
        if self.buckets[tenant].admit(float(arrival_ns)):
            led["admitted"] += 1
            return True
        led["shed"] += 1
        if payload is not None:
            self.shed_sha256.append(_sha256(payload).digest())
        return False

    def fairness_view(self) -> Dict[str, dict]:
        """The sentinel tenant-source / artifact-ledger shape:
        {tenant: {offered, admitted, shed, honest}}."""
        return {
            name: dict(self.ledger[name],
                       honest=bool(self.specs[name].honest))
            for name in self.ledger
        }

    def parity_ok(self) -> bool:
        return all(v["admitted"] + v["shed"] == v["offered"]
                   for v in self.ledger.values())


def admitted_counts(plan) -> Dict[str, int]:
    """Simulated per-tenant admitted totals: every host runs this same
    pure bucket replay over every tenant's arrival clock, so the whole
    fabric agrees on placement weights without communication."""
    out: Dict[str, int] = {}
    for t in plan.tenants:
        b = TokenBucket(t.rate_tps / _NS_PER_S, t.burst)
        out[t.name] = sum(
            1 for ns in t.arrival_ns if b.admit(float(ns)))
    return out


def assign_tenants(plan, n_hosts: int) -> List[List[str]]:
    """Deterministic whole-tenant placement: largest simulated admitted
    load first onto the least-loaded host (ties break on host index,
    tenant order on (-load, name)). Whole tenants keep each bucket on
    exactly one host — the ledger needs no cross-host reconciliation
    and a tenant's admission decisions are single-writer by
    construction."""
    loads = admitted_counts(plan)
    order = sorted(loads, key=lambda n: (-loads[n], n))
    hosts: List[List[str]] = [[] for _ in range(n_hosts)]
    totals = [0] * n_hosts
    for name in order:
        h = min(range(n_hosts), key=lambda i: (totals[i], i))
        hosts[h].append(name)
        totals[h] += loads[name]
    return hosts


# --------------------------------------------------------------------------
# FabricHost: one process's ingest stack + its row of the global mesh.
# --------------------------------------------------------------------------


class _FabricInflight:
    __slots__ = ("status", "ok", "slots", "t_dispatch", "lanes")

    def __init__(self, status, ok, slots, t_dispatch: int, lanes: int):
        self.status = status
        self.ok = ok
        self.slots = slots
        self.t_dispatch = t_dispatch
        self.lanes = lanes


class FabricHost:
    """One fabric process: owned-tenant admission, per-dp ShardLane
    staging, one row of the (host, dp) mesh, a private flight
    workspace, and the lockstep dispatcher.

    Single-threaded by contract like PodVerifyService — one loop owns
    staging + dispatch; graph calls are async so FD_POD_INFLIGHT still
    sets the double-buffer depth. Works unchanged at n_hosts == 1 (the
    control run / graceful single-process fallback): the mesh is then
    (1, dp) and the control-plane all_gather short-circuits."""

    def __init__(self, plan, wksp_dir: str, per_shard: int,
                 max_msg_len: int = 256, label: str = "fabric.host",
                 torsion_k: Optional[int] = None,
                 inflight: Optional[int] = None, seed: int = 0):
        import jax

        from firedancer_tpu.disco import engine as fd_engine
        from firedancer_tpu.parallel import multihost
        from firedancer_tpu.tango.rings import Workspace

        self._jax = jax
        self.plan = plan
        self.proc_id = jax.process_index()
        self.n_hosts = jax.process_count()
        self.mesh = multihost.global_mesh()
        self._axes = tuple(self.mesh.axis_names)
        self.dp = int(self.mesh.devices.shape[1])
        self.per_shard = per_shard
        self.local_batch = self.dp * per_shard
        self.global_batch = self.n_hosts * self.local_batch
        self.max_msg_len = max_msg_len
        self.label = label
        self.seed = seed
        self._torsion_k = torsion_k or flags.get_int("FD_RLC_TORSION_K")
        self.inflight_max = max(1, inflight
                                or flags.get_int("FD_POD_INFLIGHT"))

        # Per-process flight workspace: fabric.py is the single writer
        # of every row here (fdlint ownership); other processes write
        # their OWN files, and the rows meet only in the coordinator's
        # merge_snapshots.
        from firedancer_tpu.disco.sentinel import SLO_NAMES

        os.makedirs(wksp_dir, exist_ok=True)
        self.wksp = Workspace.create(
            os.path.join(wksp_dir, f"fabric{self.proc_id}.wksp"),
            1 << 22)
        tile_labels = [label] + [f"{label}.shard{i}"
                                 for i in range(self.dp)]
        flight.create_regions(self.wksp, tile_labels, ["sink"],
                              slo_labels=SLO_NAMES)
        self.fl = flight.tile_lane(self.wksp, label)
        self.edge = flight.edge_hist(self.wksp, "sink")
        self.lanes = [
            ShardLane(i, per_shard, max_msg_len, wksp=self.wksp,
                      label=label, n_slots=self._slots_needed())
            for i in range(self.dp)
        ]
        self._rr = 0

        owned = assign_tenants(plan, self.n_hosts)[self.proc_id]
        self.admission = TenantAdmission(plan.tenants, owned=owned)

        # The split pair over the caller's (host, dp) mesh — registry
        # bypass, see engine.fabric_split_pair.
        self.fn_local, self.fn_tail, self.engine_key = \
            fd_engine.fabric_split_pair(self.mesh, self.global_batch)
        self.compile_s: Optional[float] = None
        self.cache_hit_est = False

        self._inflight: List[_FabricInflight] = []
        self._slot_payloads: Dict[Tuple[int, int], List[bytes]] = {}
        self._results: List[Tuple[int, bool]] = []
        self._digests: List[bytes] = []
        self._step = 0
        self.stat_lanes = 0
        self.stat_batches = 0
        self.stat_fallbacks = 0
        self.stat_parse_rejects = 0
        self.elapsed_s = 0.0

    def _slots_needed(self) -> int:
        """Enough FREE slots that a whole owned-tenant stream stages
        without blocking on retirement (the lockstep loop retires on
        its own cadence): every plan txn could land on one lane in the
        worst case, one lane per slot."""
        total = sum(len(t.txn_idx) for t in self.plan.tenants)
        return max(flags.get_int("FD_FEED_SLOTS"), total + 4)

    # -- global array plumbing -------------------------------------------

    def _global(self, local: np.ndarray):
        """This host's rows -> one global jax.Array sharded over
        (host, dp) on dim 0; batch bytes never cross DCN."""
        jax = self._jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        gshape = (local.shape[0] * self.n_hosts,) + local.shape[1:]
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P(self._axes)), local, gshape)

    def _global_u3(self, u3: np.ndarray):
        """(K, 2, B_local) trial weights -> global (K, 2, B) sharded on
        the LANE axis. Per-host entropy is sound: each lane's z/u
        weight is only ever read on the device owning that lane, so
        hosts drawing independent randomness still compute the exact
        RLC equation."""
        jax = self._jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        gshape = u3.shape[:2] + (u3.shape[2] * self.n_hosts,)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P(None, None, self._axes)),
            u3, gshape)

    def _local_rows(self, arr) -> np.ndarray:
        """This host's row block of a lane-sharded global output."""
        out = np.zeros((self.local_batch,) + arr.shape[1:], arr.dtype)
        base = self.proc_id * self.local_batch
        for sh in arr.addressable_shards:
            lo = (sh.index[0].start or 0) - base
            data = np.asarray(sh.data)
            out[lo:lo + data.shape[0]] = data
        return out

    def _any_host(self, flag: bool) -> bool:
        """The 4-byte lockstep control plane: OR of per-host work flags
        (process_allgather over DCN; short-circuits single-process)."""
        if self.n_hosts == 1:
            return flag
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(
            np.asarray([1 if flag else 0], np.int32))
        return bool(np.asarray(out).any())

    def _barrier(self, name: str) -> None:
        if self.n_hosts > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"fd_fabric:{name}")

    def _kv_barrier(self, name: str,
                    timeout_ms: int = 1_800_000) -> None:
        """Rendezvous through the distributed-runtime KV store — NO
        collectives, so it works before the gloo clique exists. The
        compile-skew killer: two processes timesharing one core can
        finish the big pod_local compile minutes apart, and gloo's
        context rendezvous inside the FIRST collective execution times
        out at 30 s — every process must compile first, meet here,
        and only then execute."""
        if self.n_hosts == 1:
            return
        from jax._src import distributed

        client = getattr(distributed.global_state, "client", None)
        if client is not None:
            client.wait_at_barrier(f"fd_fabric:{name}", timeout_ms)

    # -- warm -------------------------------------------------------------

    def warm(self) -> float:
        """AOT-compile both graphs, rendezvous, THEN warm on zero
        batches (the zero-lane batch resolves on the RLC pass alone,
        _warm_locked's trick); books the compile into the flight
        ledger under the fabric key.

        Compile and execute are deliberately split: compilation is
        process-local and its duration varies wildly across
        timeshared hosts, while the first EXECUTION initializes the
        gloo clique under a hard 30 s rendezvous — so every process
        compiles first, meets at the KV barrier, and only then
        executes. The replay loop keeps the AOT executables (same
        shapes/shardings every step), so no step ever recompiles."""
        jax = self._jax
        zeros = self._zero_batch()
        t0 = time.perf_counter()
        local_c = self.fn_local.lower(*zeros).compile()
        # the tail specializes on the parts output's exact shardings
        out_sds = jax.eval_shape(self.fn_local, *zeros)
        parts_sds = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            out_sds[2], local_c.output_shardings[2])
        tail_c = self.fn_tail.lower(parts_sds).compile()
        self.compile_s = time.perf_counter() - t0
        self.fn_local, self.fn_tail = local_c, tail_c
        self._kv_barrier("warm")
        out = self.fn_local(*zeros)
        ok = self.fn_tail(out[2])
        np.asarray(ok)
        rec = flight.record_compile(self.engine_key, self.compile_s)
        self.cache_hit_est = bool(rec["cache_hit_est"])
        return self.compile_s

    def _zero_batch(self):
        lb, mml = self.local_batch, self.max_msg_len
        rng = np.random.default_rng(0xFAB51C ^ self.seed)
        from firedancer_tpu.ops.verify_rlc import fresh_u, fresh_z

        u3 = fresh_u(self._torsion_k, 2 * lb, rng).reshape(
            self._torsion_k, 2, lb)
        return (
            self._global(np.zeros((lb, mml), np.uint8)),
            self._global(np.zeros(lb, np.int32)),
            self._global(np.zeros((lb, 64), np.uint8)),
            self._global(np.zeros((lb, 32), np.uint8)),
            self._global(fresh_z(lb, rng)),
            self._global_u3(u3),
        )

    # -- staging (pod placement over local dp lanes) ----------------------

    def _place(self, n_lanes: int) -> int:
        order = [(self._rr + i) % self.dp for i in range(self.dp)]
        fit = [i for i in order
               if self.lanes[i].room() >= n_lanes] or order
        best = min(fit, key=lambda i: self.lanes[i].backlog())
        self._rr = (best + 1) % self.dp
        return best

    def _stage_txn(self, payload: bytes) -> bool:
        from firedancer_tpu.ballet.txn import TxnParseError, parse_txn
        from firedancer_tpu.disco.tiles import meta_sig

        try:
            txn = parse_txn(payload)
            items = list(txn.verify_items(payload))
        except TxnParseError:
            return False
        if not items or any(len(m) > self.max_msg_len
                            for (_, _, m) in items):
            return False
        shard = self._place(len(items))
        lane = self.lanes[shard]
        lane.stage(items, meta_sig(payload),
                   digest=_sha256(payload).digest())
        # Payload kept per (lane, slot) for the per-txn CPU-oracle
        # fallback — the only correctness path when a salted batch
        # fails the global RLC equation.
        self._slot_payloads.setdefault(
            (shard, lane.cur.idx), []).append(payload)
        if lane.room() == 0:
            lane.commit("full")
        return True

    # -- lockstep dispatch ------------------------------------------------

    def _assemble_local(self):
        """One READY slot per local lane -> this host's row block
        (zero-pad missing lanes; all-None still produces the all-pad
        block a lockstep collective step requires)."""
        slots = [lane.pop_ready() for lane in self.lanes]
        per, mml = self.per_shard, self.max_msg_len
        msgs = np.zeros((self.local_batch, mml), np.uint8)
        lens = np.zeros(self.local_batch, np.int32)
        sigs = np.zeros((self.local_batch, 64), np.uint8)
        pubs = np.zeros((self.local_batch, 32), np.uint8)
        n_lanes = 0
        for i, s in enumerate(slots):
            if s is None:
                continue
            lo = i * per
            n = s.n_lane
            msgs[lo:lo + n] = s.msgs[:n]
            lens[lo:lo + n] = s.lens[:n]
            sigs[lo:lo + n] = s.sigs[:n]
            pubs[lo:lo + n] = s.pubs[:n]
            n_lanes += n
            self.lanes[i].fl.inc("batches")
            self.lanes[i].fl.inc("lanes", n)
        return slots, (msgs, lens, sigs, pubs), n_lanes

    def _dispatch_step(self) -> None:
        from firedancer_tpu.ops.verify_rlc import fresh_u, fresh_z

        slots, (msgs, lens, sigs, pubs), n_lanes = \
            self._assemble_local()
        while len(self._inflight) >= self.inflight_max:
            self._retire(self._inflight.pop(0))
        rng = np.random.default_rng(
            (0xFAB51C, self.seed, self.proc_id, self._step))
        lb = self.local_batch
        u3 = fresh_u(self._torsion_k, 2 * lb, rng).reshape(
            self._torsion_k, 2, lb)
        args = (self._global(msgs), self._global(lens),
                self._global(sigs), self._global(pubs),
                self._global(fresh_z(lb, rng)), self._global_u3(u3))
        t0 = time.monotonic_ns()
        status, definite, parts = self.fn_local(*args)
        ok = self.fn_tail(parts)
        self._inflight.append(
            _FabricInflight(status, ok, slots, t0, n_lanes))
        self._step += 1
        self.stat_batches += 1
        self.stat_lanes += n_lanes
        self.fl.inc("batches")
        if n_lanes:
            self.fl.inc("lanes", n_lanes)

    def _oracle_payload_ok(self, payload: bytes) -> bool:
        from firedancer_tpu.ballet.txn import TxnParseError, parse_txn

        try:
            items = list(parse_txn(payload).verify_items(payload))
        except TxnParseError:
            return False
        from firedancer_tpu.ballet.ed25519 import native as ed_native

        if ed_native.available():
            try:
                return all(st == 0
                           for st in ed_native.verify_items(items))
            except Exception:
                pass
        from firedancer_tpu.ballet.ed25519 import oracle as ed_oracle

        return all(ed_oracle.verify(msg, sig, pub) == 0
                   for (sig, pub, msg) in items)

    def _retire(self, ib: _FabricInflight) -> None:
        """Block on one batch's replicated verdict; fold per-txn
        results from this host's row block; per-txn CPU-oracle fallback
        when the global batch equation fails."""
        ok = bool(np.asarray(ib.ok))
        statuses = self._local_rows(ib.status) if ok else None
        if not ok:
            self.stat_fallbacks += 1
            self.fl.inc("rlc_fallback")
        now = time.monotonic_ns()
        per = self.per_shard
        for i, s in enumerate(ib.slots):
            if s is None:
                continue
            meta = self.lanes[i].pop_meta(s)
            payloads = self._slot_payloads.pop((i, s.idx), [])
            off = i * per
            for t in range(s.n_txn):
                cnt = int(s.tlanes[t])
                if ok:
                    lane_ok = cnt > 0 and bool(
                        (statuses[off:off + cnt] == 0).all())
                else:
                    lane_ok = (t < len(payloads)
                               and self._oracle_payload_ok(payloads[t]))
                psig, digest = (meta[t] if t < len(meta)
                                else (int(s.psigs[t]), None))
                self._results.append((psig, lane_ok))
                if lane_ok and digest is not None:
                    self._digests.append(digest)
                self.edge.observe(max(1, now - ib.t_dispatch))
                off += cnt
            self.lanes[i].release(s)

    # -- the replay driver -----------------------------------------------

    def replay(self, payloads: List[bytes]) -> dict:
        """Admit + stage this host's owned tenant streams, then run the
        lockstep step loop until EVERY host drains. Returns this
        host's result row (the per-process dump body)."""
        # Merged owned-tenant arrival order (virtual clock): realistic
        # interleave, still a pure function of the plan.
        events = sorted(
            (t.arrival_ns[j], t.name, t.txn_idx[j])
            for t in self.plan.tenants
            if t.name in self.admission.specs
            for j in range(len(t.txn_idx))
        )
        self._barrier("replay_start")
        t0 = time.perf_counter()
        for arrival_ns, tenant, idx in events:
            p = payloads[idx]
            if not self.admission.admit(tenant, arrival_ns, payload=p):
                self.fl.inc("admit_shed")
                continue
            if not self._stage_txn(p):
                self.stat_parse_rejects += 1
        for lane in self.lanes:
            if lane.cur is not None and lane.cur.n_txn:
                lane.commit("deadline")
        while True:
            my_more = any(lane.pool.ready_cnt() for lane in self.lanes)
            if not self._any_host(my_more):
                break
            self._dispatch_step()
        while self._inflight:
            self._retire(self._inflight.pop(0))
        self._barrier("replay_end")
        self.elapsed_s = time.perf_counter() - t0
        ok_cnt = sum(1 for _, okk in self._results if okk)
        return {
            "verified_ok": ok_cnt,
            "verified_fail": len(self._results) - ok_cnt,
            "parse_rejects": self.stat_parse_rejects,
            "steps": self._step,
            "lanes": self.stat_lanes,
            "batches": self.stat_batches,
            "rlc_fallbacks": self.stat_fallbacks,
            "elapsed_s": self.elapsed_s,
        }

    def shard_occupancy(self) -> List[int]:
        return [lane.fl.get("lanes") for lane in self.lanes]

    # -- the per-process dump --------------------------------------------

    def publish(self) -> None:
        self.fl.publish()
        for lane in self.lanes:
            lane.fl.publish()

    def write_dump(self, out_dir: str, result: dict) -> str:
        """Publish flight rows and write this process's judgment dump
        (atomic rename — the coordinator polls for completed files)."""
        from firedancer_tpu.parallel import multihost

        self.publish()
        snap = flight.snapshot_raw(self.wksp)
        active, reason = multihost.fabric_state()
        doc = {
            "schema_version": FABRIC_SCHEMA_VERSION,
            "proc_id": self.proc_id,
            "n_hosts": self.n_hosts,
            "dp": self.dp,
            "per_shard": self.per_shard,
            "global_batch": self.global_batch,
            "engine": self.engine_key,
            "compile_s": self.compile_s,
            "compile_cache_hit_est": self.cache_hit_est,
            "fabric_active": active,
            "fabric_fallback_reason": reason,
            "tenants": self.admission.fairness_view(),
            "digests": sorted(d.hex() for d in self._digests),
            "shed_sha256": sorted(
                d.hex() for d in self.admission.shed_sha256),
            "shard_lanes": [int(x) for x in self.shard_occupancy()],
            "snapshot": {
                "metrics": snap["metrics"],
                "edges": {k: np.asarray(v, np.uint64).tolist()
                          for k, v in snap["edges"].items()},
            },
            **result,
        }
        path = dump_path(out_dir, self.proc_id)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        return path


# --------------------------------------------------------------------------
# Coordinator: collect per-process dumps, merge, judge.
# --------------------------------------------------------------------------


def dump_path(out_dir: str, proc_id: int) -> str:
    return os.path.join(out_dir, f"{DUMP_PREFIX}{proc_id}.json")


def collect_dumps(out_dir: str, n_procs: int,
                  timeout_s: float = 600.0,
                  poll_s: float = 0.5) -> List[dict]:
    """Poll for all n_procs dumps (atomic-rename complete files);
    raises TimeoutError naming the missing processes."""
    deadline = time.monotonic() + timeout_s
    paths = [dump_path(out_dir, i) for i in range(n_procs)]
    while True:
        missing = [p for p in paths if not os.path.exists(p)]
        if not missing:
            break
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"fd_fabric coordinator: {len(missing)} process "
                f"dump(s) never arrived: {missing}")
        time.sleep(poll_s)
    out = []
    for p in paths:
        with open(p, encoding="utf-8") as f:
            out.append(json.load(f))
    return out


def merge_tenant_ledgers(dumps) -> Dict[str, dict]:
    """Union of per-host tenant ledgers (each tenant is owned by ONE
    host; summing is defensive, parity still must hold exactly)."""
    merged: Dict[str, dict] = {}
    for d in dumps:
        for name, row in (d.get("tenants") or {}).items():
            m = merged.setdefault(
                name, {"offered": 0, "admitted": 0, "shed": 0,
                       "honest": bool(row.get("honest", True))})
            for k in ("offered", "admitted", "shed"):
                m[k] += int(row.get(k, 0))
    return merged


def merge_and_judge(dumps: List[dict],
                    control: Optional[dict] = None,
                    budgets_ms: Optional[Dict[str, float]] = None
                    ) -> dict:
    """The cross-host judgment: flight.merge_snapshots over every
    process registry, sentinel grading over the MERGED edges + tenant
    ledger, per-host balance, digest multiset vs the single-process
    control. Returns the FABRIC_r* artifact core (the runner stamps
    ts/ok/gate_basis). jax-free — the parent runner judges without
    joining the mesh."""
    from firedancer_tpu.disco import sentinel

    dumps = sorted(dumps, key=lambda d: d.get("proc_id", 0))
    snaps = [
        {"metrics": d["snapshot"].get("metrics") or {},
         "edges": {k: np.asarray(v, np.uint64)
                   for k, v in (d["snapshot"].get("edges")
                                or {}).items()}}
        for d in dumps
    ]
    merged = flight.merge_snapshots(snaps)
    tenants = merge_tenant_ledgers(dumps)
    alerts = list(sentinel.evaluate_edges_summary(
        merged["edges"], budgets_ms=budgets_ms))
    alerts += sentinel.evaluate_tenant_summary(tenants)

    per_host = []
    for d in dumps:
        el = float(d.get("elapsed_s") or 0.0)
        ok_cnt = int(d.get("verified_ok") or 0)
        per_host.append({
            "proc_id": int(d.get("proc_id", 0)),
            "verified_ok": ok_cnt,
            "lanes": int(d.get("lanes") or 0),
            "steps": int(d.get("steps") or 0),
            "elapsed_s": round(el, 3),
            "throughput": round(ok_cnt / el, 3) if el else 0.0,
            "rlc_fallbacks": int(d.get("rlc_fallbacks") or 0),
            "shard_lanes": d.get("shard_lanes") or [],
            "fabric_fallback_reason": d.get("fabric_fallback_reason"),
        })
    total_ok = sum(h["verified_ok"] for h in per_host)
    wall = max((h["elapsed_s"] for h in per_host), default=0.0)
    host_lanes = [h["lanes"] for h in per_host]
    lo = min(host_lanes) if host_lanes else 0
    balance = (float(max(host_lanes)) / lo) if lo else float("inf")

    digests = sorted(x for d in dumps for x in (d.get("digests") or []))
    rec = {
        "metric": "fabric_aggregate_throughput",
        "schema_version": FABRIC_SCHEMA_VERSION,
        "unit": "verifies/s",
        "hosts": len(dumps),
        "devices": sum(int(d.get("dp") or 0) for d in dumps),
        "value": round(total_ok / wall, 3) if wall else 0.0,
        "wall_s": round(wall, 3),
        "verified_ok": total_ok,
        "per_host": per_host,
        "balance_ratio": (round(balance, 3)
                          if balance != float("inf") else None),
        "tenants": tenants,
        "tenant_parity": all(
            v["admitted"] + v["shed"] == v["offered"]
            for v in tenants.values()),
        "alert_cnt": len(alerts),
        "alerts": alerts,
        "digests": len(digests),
        "merged": {"metrics": merged["metrics"],
                   "edges": merged["edges"]},
    }
    if control is not None:
        c_digests = sorted(control.get("digests") or [])
        c_el = float(control.get("elapsed_s") or 0.0)
        c_ok = int(control.get("verified_ok") or 0)
        c_val = round(c_ok / c_el, 3) if c_el else 0.0
        rec["control"] = {
            "hosts": 1,
            "verified_ok": c_ok,
            "elapsed_s": round(c_el, 3),
            "value": c_val,
        }
        rec["digest_parity"] = bool(digests) and digests == c_digests
        rec["scaling_ratio"] = (round(rec["value"] / c_val, 3)
                                if c_val else 0.0)
    return rec
