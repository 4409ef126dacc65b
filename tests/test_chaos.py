"""Chaos harness: distributed join + KV RPC suites under seeded injected
faults (utils/faults.py). Every test asserts results equal the no-fault
oracle AND that no threads/sockets/flow-registry entries leak — the
leaktest.AfterTest + TestingKnobs discipline combined.

Fast seeds only: everything here is deterministic (one seeded RNG drives
all firing decisions) and finishes in seconds, so the suite runs inside
tier-1. Exclude with -m 'not chaos'."""

import threading
import time

import numpy as np
import pytest

from scripts.check_no_leaks import assert_no_leaks, snapshot

from cockroach_tpu.catalog import Catalog, Table
from cockroach_tpu.coldata.types import FLOAT64, INT64, Schema
from cockroach_tpu.flow.disthost import (
    HostFlowServer,
    cancel_flow,
    run_distributed_hosts,
    run_distributed_join,
    setup_flow,
)
from cockroach_tpu.kv import DB, Clock
from cockroach_tpu.kv.rpc import BatchClient, BatchServer
from cockroach_tpu.ops.aggregation import AggSpec
from cockroach_tpu.plan import builder as plan_builder
from cockroach_tpu.plan import spec as S
from cockroach_tpu.flow.runtime import run_operator
from cockroach_tpu.storage.lsm import Engine
from cockroach_tpu.utils import faults, locks, metric, racesan, settings
from cockroach_tpu.utils.faults import FaultSpec, InjectedFault

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


@pytest.fixture(autouse=True)
def _lock_order_detector():
    """Run every chaos scenario with the runtime deadlock detector armed:
    an inverted acquisition anywhere under fault injection raises
    LockOrderError instead of hanging the suite (the deadlock-build-tag
    discipline; see utils/locks.py)."""
    locks.reset()
    prev = settings.get("debug.lock_order.enabled")
    settings.set("debug.lock_order.enabled", True)
    yield
    settings.set("debug.lock_order.enabled", prev)
    locks.reset()


@pytest.fixture(autouse=True)
def _race_sanitizer():
    """...and with the runtime data-race sanitizer armed: every tracked
    control-plane field (utils/racesan.py note_read/note_write sites) runs
    the Eraser lockset algorithm while faults push threads down rarely
    taken paths — a lockset-disjoint access raises DataRaceError at the
    access instead of corrupting state (the make-testrace discipline)."""
    racesan.reset()
    prev = settings.get("debug.race_detector.enabled")
    settings.set("debug.race_detector.enabled", True)
    yield
    settings.set("debug.race_detector.enabled", prev)
    racesan.reset()


def _mini_catalog(n=600, c=16, seed=7) -> Catalog:
    """Small deterministic two-table catalog (fast chaos iterations; the
    tpch generator would dominate runtime)."""
    rng = np.random.default_rng(seed)
    cat = Catalog()
    cat.add(Table(
        name="orders",
        schema=Schema(("o_key", "o_cust", "o_val"),
                      (INT64, INT64, FLOAT64)),
        columns={
            "o_key": np.arange(n, dtype=np.int64),
            "o_cust": rng.integers(0, c, n, dtype=np.int64),
            "o_val": rng.uniform(1.0, 100.0, n),
        },
    ))
    cat.add(Table(
        name="cust",
        schema=Schema(("c_key", "c_grp"), (INT64, INT64)),
        columns={
            "c_key": np.arange(c, dtype=np.int64),
            "c_grp": np.arange(c, dtype=np.int64) % 4,
        },
    ))
    return cat


def _agg_plan(cat: Catalog) -> S.PlanNode:
    sch = cat.get("orders").schema
    return S.Aggregate(
        S.TableScan("orders"),
        group_cols=(sch.index("o_cust"),),
        aggs=(AggSpec("count_rows", None, "n"),
              AggSpec("sum", sch.index("o_val"), "total")),
        mode="complete",
    )


def _join_plan() -> S.HashJoin:
    return S.HashJoin(
        probe=S.TableScan("orders", ("o_key", "o_cust")),
        build=S.TableScan("cust", ("c_key", "c_grp")),
        probe_keys=(1,),
        build_keys=(0,),
    )


def _canon(res: dict) -> np.ndarray:
    rows = np.stack([np.asarray(res[k], dtype=np.float64)
                     for k in sorted(res.keys())], axis=1)
    return rows[np.lexsort(rows.T[::-1])]


def _assert_equal(got: dict, want: dict) -> None:
    assert sorted(got.keys()) == sorted(want.keys())
    np.testing.assert_allclose(_canon(got), _canon(want), rtol=1e-9)


# -- determinism ------------------------------------------------------------


def test_fault_registry_deterministic_replay():
    """Same seed, same specs => the exact same fault sequence (the whole
    point of seeding: a chaos failure replays)."""
    spec = {"site.a": FaultSpec(kind="error", p=0.5, max_fires=3),
            "site.b": FaultSpec(kind="delay", p=0.5, delay_s=0.0)}
    runs = []
    for _ in range(2):
        faults.arm(1234, {k: FaultSpec(**{
            "kind": v.kind, "p": v.p, "delay_s": v.delay_s,
            "max_fires": v.max_fires}) for k, v in spec.items()})
        for _ in range(30):
            for site in ("site.a", "site.b"):
                try:
                    faults.fire(site)
                except InjectedFault:
                    pass
        runs.append(faults.fired())
        faults.disarm()
    assert runs[0] == runs[1]
    assert any(s == "site.a" for s, _ in runs[0])  # it actually fired


def test_disarmed_sites_are_free():
    faults.disarm()
    faults.fire("kv.rpc.client.batch")  # no-op, no exception
    assert faults.partial_fraction("storage.wal.append") is None


# -- KV RPC under drops -----------------------------------------------------


def test_kv_rpc_drops_retry_to_oracle():
    """Client-wire drops AND server-eval drops: the retry layer re-dials
    and re-sends until the (max_fires-bounded) faults exhaust; every
    read then equals the no-fault oracle."""
    before = snapshot()
    db = DB(Engine(key_width=16, val_width=32, memtable_size=64), Clock())
    srv = BatchServer(db)
    client = BatchClient(srv.addr, deadline_s=2.0, max_retries=8)
    retries_before = metric.RPC_RETRIES.value
    faults.arm(11, {
        "kv.rpc.client.batch": FaultSpec(kind="drop", p=0.25, max_fires=4),
        "kv.rpc.server.eval": FaultSpec(kind="drop", p=0.25, max_fires=4),
    })
    try:
        oracle = {}
        for i in range(30):
            k = b"k%03d" % i
            v = b"v%03d" % (i * 7)
            client.put(k, v)
            oracle[k] = v
        for k, v in oracle.items():
            assert client.get(k) == v
        assert faults.fired(), "chaos run injected nothing"
        assert metric.RPC_RETRIES.value > retries_before
    finally:
        faults.disarm()
        client.close()
        srv.close()
    assert_no_leaks(before)


def test_batch_server_restart_same_port_and_idempotent_close():
    """Back-to-back start/stop on the SAME port never raises; close() is
    idempotent and leaves no thread or socket behind."""
    before = snapshot()
    db = DB(Engine(key_width=16, val_width=32, memtable_size=64), Clock())
    port = None
    for round_no in range(3):
        srv = BatchServer(db, port=port or 0)
        port = srv.addr[1]
        c = BatchClient(srv.addr)
        c.put(b"r%d" % round_no, b"x")
        c.close()
        srv.close()
        srv.close()  # idempotent
    assert_no_leaks(before)


def test_host_flow_server_restart_same_port_and_idempotent_close():
    before = snapshot()
    cat = _mini_catalog()
    port = None
    for _ in range(3):
        srv = HostFlowServer(cat, port=port or 0).serve_background()
        port = srv.addr[1]
        srv.close()
        srv.close()  # idempotent
    assert_no_leaks(before)


# -- distributed plane under chaos ------------------------------------------


def test_distributed_join_under_rpc_drops_equals_oracle():
    """Setup/stream RPC drops (bounded) against both hosts: retries — and,
    if they exhaust, degradation — still produce the oracle result, and
    no flow-registry entry outlives the query."""
    before = snapshot()
    cat = _mini_catalog()
    plan = _join_plan()
    want = run_operator(plan_builder.build(plan, cat))
    srvs = [HostFlowServer(cat).serve_background() for _ in range(2)]
    faults.arm(29, {
        "flow.host.setup": FaultSpec(kind="drop", p=0.4, max_fires=2),
        "flow.host.stream": FaultSpec(kind="error", p=0.4, max_fires=2),
    })
    try:
        got = run_distributed_join(plan, cat, [s.addr for s in srvs])
        _assert_equal(got, want)
        assert faults.fired(), "chaos run injected nothing"
        faults.disarm()
        for s in srvs:
            assert s.registry_size() == 0, "leaked flow-registry entries"
    finally:
        faults.disarm()
        for s in srvs:
            s.close()
    assert_no_leaks(before)


def test_distributed_agg_host_killed_mid_flow_degrades():
    """One host dies while its stream is still being established: the
    gateway cancels the flow everywhere, probes survivors, re-plans onto
    them, and still returns the oracle result (surfaced via the
    distsql_degraded_queries metric)."""
    before = snapshot()
    cat = _mini_catalog()
    plan = _agg_plan(cat)
    want = run_operator(plan_builder.build(plan, cat))
    srv_a = HostFlowServer(cat).serve_background()
    srv_b = HostFlowServer(cat).serve_background()
    degraded_before = metric.DIST_DEGRADED.value
    # every stream handshake stalls 0.4s; host B dies at 0.15s — so B is
    # guaranteed to go down after setup registered its fragment but
    # before its stream delivers (the "killed mid-flow" window)
    faults.arm(23, {
        "flow.host.stream": FaultSpec(kind="delay", p=1.0, delay_s=0.4),
    })
    killer = threading.Timer(0.15, srv_b.close)
    killer.start()
    try:
        got = run_distributed_hosts(plan, cat, [srv_a.addr, srv_b.addr])
        _assert_equal(got, want)
        assert metric.DIST_DEGRADED.value > degraded_before
        faults.disarm()
        assert srv_a.registry_size() == 0, "leaked flow-registry entries"
    finally:
        killer.cancel()
        faults.disarm()
        srv_a.close()
        srv_b.close()
    assert_no_leaks(before)


def test_distributed_agg_all_hosts_dead_falls_back_local():
    """No host reachable at all: the gateway degrades to single-host
    local execution rather than erroring."""
    cat = _mini_catalog()
    plan = _agg_plan(cat)
    want = run_operator(plan_builder.build(plan, cat))
    srv = HostFlowServer(cat).serve_background()
    dead_addr = srv.addr
    srv.close()  # nothing listens here anymore
    degraded_before = metric.DIST_DEGRADED.value
    got = run_distributed_hosts(plan, cat, [dead_addr])
    _assert_equal(got, want)
    assert metric.DIST_DEGRADED.value > degraded_before


def test_cancel_flow_purges_registry_and_poisons_late_arrivals():
    """cancel_flow removes every registered entry of the flow and fails
    late setups/stream-waits for it (no TTL-long lingering)."""
    cat = _mini_catalog()
    srv = HostFlowServer(cat, stream_wait_s=0.5).serve_background()
    try:
        frag = S.TableScan("orders")
        setup_flow(srv.addr, "doomed", {0: frag, 1: frag})
        assert srv.registry_size() == 2
        removed = cancel_flow(srv.addr, "doomed")
        assert removed == 2
        assert srv.registry_size() == 0
        # a late setup for the cancelled flow is rejected outright
        with pytest.raises(RuntimeError):
            setup_flow(srv.addr, "doomed", {2: frag})
        assert srv.registry_size() == 0
    finally:
        srv.close()


# -- WAL chaos --------------------------------------------------------------


def test_wal_torn_append_recovers_on_reopen(tmp_path):
    """A partial fault tears an append mid-record (the crash-mid-write
    shape): reopening truncates the torn tail and replays everything
    before it; the store keeps working."""
    wal = str(tmp_path / "w.wal")
    eng = Engine(key_width=16, val_width=8, wal_path=wal)
    eng.put(b"a", b"1", ts=3)
    faults.arm(31, {
        "storage.wal.append": FaultSpec(kind="partial", p=1.0, max_fires=1),
    })
    with pytest.raises(InjectedFault):
        eng.put(b"b", b"2", ts=4)
    faults.disarm()
    # crash: reopen from the WAL alone
    eng2 = Engine(key_width=16, val_width=8, wal_path=wal)
    assert eng2.get(b"a", ts=10) == b"1"
    assert eng2.get(b"b", ts=10) is None  # torn record truncated away
    eng2.put(b"c", b"3", ts=5)  # appending after truncation works
    assert eng2.get(b"c", ts=10) == b"3"


# -- seed matrix (tier-2) ----------------------------------------------------


@pytest.mark.slow
def test_chaos_matrix_sweeps_seed_offsets():
    """Tier-2: the whole fast chaos suite re-runs under shifted fault
    seeds (scripts/run_chaos_matrix.py) — different deterministic fault
    schedules, same convergence. Two offsets here keep it bounded; the
    CLI sweeps wider."""
    from scripts.run_chaos_matrix import run_matrix

    failed = run_matrix([0, 1], quiet=True)
    assert failed == [], f"chaos matrix failed at seed offsets {failed}"


# -- exactly-once KV writes -------------------------------------------------


def _put_req(k: bytes, v: bytes) -> dict:
    from cockroach_tpu.kv.rpc import _b64

    return {"op": "put", "key": _b64(k), "value": _b64(v)}


def _version_count(db, key: bytes) -> int:
    """Committed MVCC versions of `key` — the double-apply oracle: an
    exactly-once write leaves exactly one."""
    from cockroach_tpu.kv.changefeed import changes_between

    events, _ = changes_between(db, 0, db.clock.now())
    want = key.decode("utf-8", "replace")
    return sum(1 for e in events if e["key"] == want)


def test_exactly_once_response_dropped_retry_hits_replay_cache():
    """The server applies a mutation batch, then the response is dropped
    (the classic ambiguous window): the client's transport retry re-sends
    the SAME (cid, seq) stamp and the server answers from the replay
    cache — one version lands, never two."""
    before = snapshot()
    db = DB(Engine(key_width=16, val_width=32, memtable_size=64), Clock())
    srv = BatchServer(db)
    client = BatchClient(srv.addr, deadline_s=2.0, max_retries=4)
    hits_before = metric.REPLAY_CACHE_HITS.value
    faults.arm(43, {
        "kv.rpc.server.respond": FaultSpec(kind="drop", p=1.0, max_fires=1),
    })
    try:
        ts = client.put(b"eo-a", b"once")
        assert isinstance(ts, int)
        assert metric.REPLAY_CACHE_HITS.value > hits_before
        assert client.get(b"eo-a") == b"once"
        assert _version_count(db, b"eo-a") == 1, "double-applied!"
    finally:
        faults.disarm()
        client.close()
        srv.close()
    assert_no_leaks(before)


def test_exactly_once_across_server_crash_and_wal_restart(tmp_path):
    """Node killed mid-mutation-batch: the batch applies, the response is
    lost, the whole server AND engine go down. A fresh engine reopens
    from the WAL, a new server binds, and the client's retry (same
    stamp) dedups against the recovered replay cache — byte-exact
    convergence with zero double-applies."""
    import json as _json
    import socket as _socket

    from cockroach_tpu.flow.dcn import _recv_msg, _send_msg
    from cockroach_tpu.kv.rpc import AmbiguousResultError

    before = snapshot()
    wal = str(tmp_path / "eo.wal")
    eng = Engine(key_width=16, val_width=32, memtable_size=64, wal_path=wal)
    db = DB(eng, Clock())
    srv = BatchServer(db)
    # one attempt only: the dropped response surfaces as a typed
    # AmbiguousResultError carrying the stamp instead of a silent retry
    client = BatchClient(srv.addr, deadline_s=1.0, max_retries=1)
    ambiguous_before = metric.AMBIGUOUS_RESULTS.value
    hits_before = metric.REPLAY_CACHE_HITS.value
    faults.arm(53, {
        "kv.rpc.server.respond": FaultSpec(kind="drop", p=1.0, max_fires=1),
    })
    try:
        with pytest.raises(AmbiguousResultError) as ei:
            client.put(b"eo-b", b"exactly-once")
        faults.disarm()
        assert metric.AMBIGUOUS_RESULTS.value > ambiguous_before
        stamp = (ei.value.cid, ei.value.seq)
        assert stamp[0] == client.cid and stamp[1] is not None
        # crash: server down, engine down
        client.close()
        srv.close()
        eng.close()
        # restart: recover from the WAL alone; the applied batch AND its
        # dedup entry come back together (one atomic _REC_BATCH record)
        eng2 = Engine(key_width=16, val_width=32, memtable_size=64,
                      wal_path=wal)
        db2 = DB(eng2, Clock())
        srv2 = BatchServer(db2)
        try:
            # the application-level retry: re-send the SAME stamped
            # envelope (what BatchClient's transport retry does on the
            # wire) against the restarted server
            envelope = {"requests": [_put_req(b"eo-b", b"exactly-once")],
                        "cid": stamp[0], "seq": stamp[1]}
            s = _socket.create_connection(srv2.addr, timeout=5.0)
            try:
                _send_msg(s, _json.dumps(envelope).encode("utf-8"))
                resp = _json.loads(_recv_msg(s).decode("utf-8"))
            finally:
                s.close()
            assert "responses" in resp, resp
            assert metric.REPLAY_CACHE_HITS.value > hits_before
            assert db2.get(b"eo-b") == b"exactly-once"
            assert _version_count(db2, b"eo-b") == 1, "double-applied!"
        finally:
            srv2.close()
    finally:
        faults.disarm()
    assert_no_leaks(before)


def test_wal_torn_mid_batch_record_is_all_or_nothing(tmp_path):
    """A crash tears the WAL mid-_REC_BATCH: reopening recovers NEITHER
    the ops NOR the dedup entry (they live in one record), so the retry
    applies cleanly — exactly once, no half-applied batch."""
    wal = str(tmp_path / "torn.wal")
    eng = Engine(key_width=16, val_width=32, wal_path=wal)
    muts = [(b"tb-a", b"1", 5, 0, False), (b"tb-b", b"2", 6, 0, False)]
    resp = {"responses": [{"ts": 5}, {"ts": 6}]}
    faults.arm(59, {
        "storage.wal.append": FaultSpec(kind="partial", p=1.0, max_fires=1),
    })
    with pytest.raises(InjectedFault):
        eng.apply_rpc_batch("cl-torn", 1, muts, resp)
    faults.disarm()
    # crash + reopen: the torn batch record truncated away entirely
    eng2 = Engine(key_width=16, val_width=32, wal_path=wal)
    assert eng2.get(b"tb-a", ts=10) is None
    assert eng2.get(b"tb-b", ts=10) is None
    assert eng2.replay_cache_get("cl-torn", 1) is None
    # the retry (same stamp) applies exactly once
    eng2.apply_rpc_batch("cl-torn", 1, muts, resp)
    assert eng2.get(b"tb-a", ts=10) == b"1"
    assert eng2.get(b"tb-b", ts=10) == b"2"
    assert eng2.replay_cache_get("cl-torn", 1) == resp
    # and survives ANOTHER restart
    eng2.close()
    eng3 = Engine(key_width=16, val_width=32, wal_path=wal)
    assert eng3.replay_cache_get("cl-torn", 1) == resp
    assert eng3.get(b"tb-b", ts=10) == b"2"


# -- lease failover under heartbeat blackhole --------------------------------


def _wait_until(cond, timeout_s: float = 10.0, msg: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def test_heartbeat_blackhole_fences_node_and_reroutes_leases():
    """Node 1 holds range 1's epoch lease; its heartbeats get blackholed
    (scoped fault — peers keep renewing). Node 2 watches the record
    expire, bumps node 1's epoch (the fencing write), takes the lease,
    and gossip-advertises itself; the LeaseRouter reroutes writes. The
    dark node refuses range-addressed mutations with a typed error, and
    once the blackhole lifts its own heartbeat observes the fence and
    stops the whole node — resurrect-under-old-epoch is impossible."""
    from cockroach_tpu.kv.dist import LeaseRouter
    from cockroach_tpu.kv.liveness import (EpochFencedError,
                                           NotLeaseHolderError)
    from cockroach_tpu.server.node import Node

    before = snapshot()
    shared = DB(Engine(key_width=64, val_width=128), Clock())
    failovers_before = metric.LEASE_FAILOVERS.value
    # ttl >> heartbeat interval: a scheduler stall must not expire a
    # HEALTHY node's record mid-test (that would be a real — but
    # unscripted — failover and the assertions below would race it)
    n1 = Node(1, db=shared, heartbeat_interval_s=0.05, ttl_ms=1200,
              lease_ranges=[1]).start(gossip_port=0, kv_port=0)
    n2 = None
    try:
        _wait_until(
            lambda: str(n1.gossip.get_info("lease/1") or "").startswith("1:"),
            msg="n1 to acquire + advertise the lease")
        n2 = Node(2, db=shared, heartbeat_interval_s=0.05, ttl_ms=1200,
                  lease_ranges=[1], gossip_peers=[n1.gossip_addr()],
                  ).start(gossip_port=0, kv_port=0)
        router = LeaseRouter(n2.gossip, n2.dialer)
        _wait_until(
            lambda: str(n2.gossip.get_info("lease/1") or "").startswith("1:"),
            msg="n2 to learn the lease through gossip")
        router.batch(1, [_put_req(b"fo-a", b"from-n1")])
        # blackhole ONLY node 1's heartbeats (scoped site): its record
        # silently ages toward expiry while node 2 keeps renewing
        faults.arm(47, {
            "liveness.heartbeat.n1": FaultSpec(kind="error", p=1.0),
        })
        _wait_until(
            lambda: str(n2.gossip.get_info("lease/1") or "").startswith("2:"),
            msg="n2 to fence n1 and take the lease")
        assert metric.LEASE_FAILOVERS.value > failovers_before
        # the fenced holder cannot serve range-addressed mutations: its
        # lease guard answers a typed refusal, never a silent write
        stale = BatchClient(n1.kv_rpc.addr, deadline_s=2.0, max_retries=1)
        try:
            with pytest.raises((EpochFencedError, NotLeaseHolderError)):
                stale.batch([_put_req(b"fo-stale", b"zombie")], range_id=1)
        finally:
            stale.close()
        assert shared.get(b"fo-stale") is None, "fenced node served a write"
        # the router re-resolves to the new holder and the write lands
        router.batch(1, [_put_req(b"fo-b", b"from-n2")])
        faults.disarm()
        # blackhole lifts: n1's next heartbeat sees the bumped epoch and
        # stops the node — it never heartbeats the old epoch back to life
        _wait_until(lambda: n1._stop.is_set(),
                    msg="fenced n1 to stop itself")
        assert shared.get(b"fo-a") == b"from-n1"
        assert shared.get(b"fo-b") == b"from-n2"
    finally:
        faults.disarm()
        if n2 is not None:
            n2.stop()
        n1.stop()
    assert_no_leaks(before)


def test_wal_fsync_and_delay_faults(tmp_path):
    """fsync error-injection surfaces (WALFailover trigger shape); delay
    injection slows appends without corrupting them."""
    wal = str(tmp_path / "f.wal")
    eng = Engine(key_width=16, val_width=8, wal_path=wal, wal_fsync=True)
    faults.arm(37, {
        "storage.wal.fsync": FaultSpec(kind="error", p=1.0, max_fires=1),
    })
    with pytest.raises(InjectedFault):
        eng.put(b"x", b"1", ts=3)
    faults.disarm()
    faults.arm(41, {
        "storage.wal.append": FaultSpec(kind="delay", p=1.0,
                                        delay_s=0.01, max_fires=2),
    })
    t0 = time.monotonic()
    eng.put(b"y", b"2", ts=4)
    assert time.monotonic() - t0 >= 0.01
    faults.disarm()
    assert eng.get(b"y", ts=10) == b"2"


# -- range lifecycle chaos ----------------------------------------------------


def _ranger_cluster(load_seed=3):
    """2-store DistSender cluster with routing-path load stats installed
    (no background threads: chaos drives the queues synchronously)."""
    from cockroach_tpu.kv.dist import DistSender, Meta, Store
    from cockroach_tpu.kv.loadstats import RangeLoadStats

    meta = Meta(first_store=1)
    stores = [Store(i + 1, meta, key_width=16, val_width=16,
                    memtable_size=64) for i in range(2)]
    ds = DistSender(stores, meta)
    db = DB(ds, Clock())
    load = RangeLoadStats(half_life_s=5.0, sample_size=32, seed=load_seed)
    ds.load = load
    return meta, ds, db, load


def test_ranger_split_crash_between_meta_write_and_bookkeeping():
    """ranger.split.apply fires AFTER Meta.split_at but BEFORE the lease
    carry / cache repair / load handoff — the classic torn-split window.
    The item parks in purgatory; the retry finds the boundary already
    present, recovers both sides, and finishes the bookkeeping. Reads
    converge to the no-fault oracle with zero leaks."""
    from cockroach_tpu.kv.allocator import RangeLifecycle
    from cockroach_tpu.utils import settings

    before = snapshot()
    meta, ds, db, load = _ranger_cluster()
    life = RangeLifecycle(ds, load=load)
    settings.set("kv.range.split_qps_threshold", 5.0)
    try:
        model = {}
        for i in range(200):
            k, v = b"s%04d" % i, b"v%04d" % (i * 3)
            db.put(k, v)
            model[k] = v
        splits0 = metric.KV_RANGE_SPLITS.value
        faults.arm(61, {
            "ranger.split.apply": FaultSpec(kind="error", p=1.0,
                                            max_fires=1),
        })
        life.scan_once()
        life.split_queue.drain()
        assert faults.fired(), "chaos run injected nothing"
        # torn state: the meta write landed, the bookkeeping did not
        assert len(meta.snapshot()) == 2
        assert metric.KV_RANGE_SPLITS.value == splits0
        assert life.split_queue.purgatory_len() == 1
        faults.disarm()
        # retry from purgatory: idempotent recovery completes the split
        life.split_queue.drain(force_purgatory=True)
        assert life.split_queue.purgatory_len() == 0
        assert metric.KV_RANGE_SPLITS.value > splits0
        # both children carry load history (neither looks newborn-cold)
        assert all(load.qps(d.range_id) > 0 for d in meta.snapshot())
        for k, v in model.items():
            assert db.get(k) == v
        assert dict(db.scan(b"s", b"t")) == model
    finally:
        faults.disarm()
        settings.reset()
    assert_no_leaks(before)


def test_ranger_merge_crash_after_meta_write_converges():
    """ranger.merge.apply fires after Meta.merge_at removed the boundary
    but before the load fold / cache eviction. The retry sees the
    boundary gone, repairs the cache with the current owner, and
    converges — stale-descriptor routing self-heals, data intact."""
    from cockroach_tpu.kv.allocator import RangeLifecycle

    before = snapshot()
    meta, ds, db, load = _ranger_cluster()
    life = RangeLifecycle(ds, load=load)
    model = {}
    for i in range(120):
        k, v = b"c%04d" % i, b"w%04d" % i
        db.put(k, v)
        model[k] = v
    # admin-split a keyspace that is cold against the DEFAULT threshold,
    # and strand the right side on the other store (the merge must
    # colocate before it can fold the boundary)
    ds.split_at(b"c0060")
    right = meta.lookup(b"c0060")
    ds.move_range(right.range_id, to_store=2)
    merges0 = metric.KV_RANGE_MERGES.value
    faults.arm(67, {
        "ranger.merge.apply": FaultSpec(kind="error", p=1.0, max_fires=1),
    })
    try:
        life.scan_once()
        life.merge_queue.drain()
        assert faults.fired(), "chaos run injected nothing"
        # torn state: boundary gone from meta, bookkeeping lost
        assert len(meta.snapshot()) == 1
        assert metric.KV_RANGE_MERGES.value == merges0
        assert life.merge_queue.purgatory_len() == 1
        faults.disarm()
        life.merge_queue.drain(force_purgatory=True)
        assert life.merge_queue.purgatory_len() == 0
        # converged: one range, every key served, scans cross cleanly
        for k, v in model.items():
            assert db.get(k) == v
        assert dict(db.scan(b"c", b"d")) == model
        assert db.get(b"c0060") == model[b"c0060"]
    finally:
        faults.disarm()
    assert_no_leaks(before)


def test_ranger_lease_transfer_dropped_completes_on_retry():
    """ranger.lease.transfer fires after the data move but before the
    lease write lands (the dropped-transfer window): the range lives on
    the target store while the lease still names the old node. The
    purgatory retry detects the mismatch and completes the handoff —
    exactly once, converging holder == target node."""
    from cockroach_tpu.kv.allocator import RangeLifecycle
    from cockroach_tpu.kv.liveness import LeaseManager, NodeLiveness
    from cockroach_tpu.utils import settings

    before = snapshot()
    meta, ds, db, load = _ranger_cluster()
    nl1 = NodeLiveness(db, 1, ttl_ms=600_000)
    nl2 = NodeLiveness(db, 2, ttl_ms=600_000)
    nl1.heartbeat()
    nl2.heartbeat()
    lm = LeaseManager(nl1)
    lm.acquire(1)
    life = RangeLifecycle(ds, load=load, leases=lm, node_id=1,
                          store_nodes={1: 1, 2: 2})
    settings.set("kv.range.split_qps_threshold", 5.0)
    try:
        import random

        rng = random.Random(17)
        model = {}
        for _ in range(300):
            i = rng.randrange(40) if rng.random() < 0.8 \
                else 40 + rng.randrange(160)
            k, v = b"x%05d" % i, b"v%05d" % rng.randrange(10_000)
            db.put(k, v)
            model[k] = v
        # first: a clean load split so the rebalancer has something it
        # can move WITHOUT flipping the whole imbalance (a store's only
        # range never rebalances — the improvement guard)
        life.scan_once()
        life.split_queue.drain()
        assert len(meta.snapshot()) >= 2
        transfers0 = metric.KV_LEASE_TRANSFERS.value
        faults.arm(71, {
            "ranger.lease.transfer": FaultSpec(kind="error", p=1.0,
                                               max_fires=1),
        })
        life.scan_once()
        life.rebalance_queue.drain()
        assert faults.fired(), "chaos run injected nothing"
        # torn state: data moved, lease write lost
        moved = [d for d in meta.snapshot() if d.store_id == 2]
        assert moved, "rebalance never moved the hot range"
        assert all(lm.holder(d.range_id).node_id == 1 for d in moved)
        assert metric.KV_LEASE_TRANSFERS.value == transfers0
        assert life.rebalance_queue.purgatory_len() == 1
        faults.disarm()
        life.rebalance_queue.drain(force_purgatory=True)
        assert life.rebalance_queue.purgatory_len() == 0
        assert metric.KV_LEASE_TRANSFERS.value == transfers0 + 1
        # converged: the moved range's lease names the target's node
        moved = [d for d in meta.snapshot() if d.store_id == 2]
        assert any(lm.holder(d.range_id).node_id == 2 for d in moved)
        for k, v in model.items():
            assert db.get(k) == v
    finally:
        faults.disarm()
        settings.reset()
    assert_no_leaks(before)


# -- storage read/ingest plane ----------------------------------------------


def test_bulk_ingest_link_crash_atomic_abort_then_retry(tmp_path):
    """Crash in the AddSSTable link window (side file durable, WAL link
    record not yet written): the ingest aborts atomically — the run is
    invisible to the live engine AND to replay — and a retry lands it
    cleanly; exactly one copy of every row survives the crash cycle."""
    wal = str(tmp_path / "w.wal")
    eng = Engine(key_width=16, val_width=8, wal_path=wal)
    eng.put(b"keep", b"x", ts=1)
    keys = np.zeros((4, 16), np.uint8)
    for i in range(4):
        keys[i, :6] = np.frombuffer(b"ing%03d" % i, np.uint8)
    vals = np.full((4, 8), ord("v"), np.uint8)
    faults.arm(73, {
        "storage.ingest.link": FaultSpec(kind="error", p=1.0, max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            eng.ingest(keys, vals, ts=5)
        # atomic abort: nothing of the run is visible on the live engine
        assert eng.get(b"ing000", ts=10) is None
        assert len(eng.scan(None, None, ts=10)) == 1
        eng.ingest(keys, vals, ts=6)  # retry (fault budget exhausted)
    finally:
        faults.disarm()
    assert eng.get(b"ing002", ts=10) == b"v" * 8
    eng.close()
    # crash replay: the aborted attempt's orphan side file must not
    # resurrect — exactly one version of each row
    eng2 = Engine(key_width=16, val_width=8, wal_path=wal)
    assert eng2.get(b"keep", ts=10) == b"x"
    assert len(eng2.scan(None, None, ts=10)) == 5
    ckpt = str(tmp_path / "ckpt")
    eng2.checkpoint(ckpt)  # orphan cleanup path still works post-chaos
    import glob

    assert not glob.glob(wal + ".ingest*.npz")
    eng2.close()


def test_compaction_swap_crash_still_invalidates_cache():
    """Crash between a compaction's run-set swap and its bookkeeping: the
    replaced runs' block-cache windows MUST be invalidated anyway (the
    finally path) or reads could serve stale cached data for dead runs."""
    from cockroach_tpu.storage import blockcache

    eng = Engine(key_width=16, val_width=16, memtable_size=4,
                 l0_trigger=64)
    for i in range(48):
        eng.put(b"s%05d" % i, b"v%05d" % i, ts=i + 1)
    eng.flush()
    assert len(eng.runs) >= 2
    # warm the cache with seek windows from the soon-dead runs
    for i in (3, 17, 40):
        assert eng.get(b"s%05d" % i, ts=100) == b"v%05d" % i
        assert eng.get(b"s%05d" % i, ts=100) == b"v%05d" % i
    old_tokens = {eng._meta_for(r).token for r in eng.runs}
    faults.arm(79, {
        "storage.compaction.swap": FaultSpec(kind="error", p=1.0,
                                             max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            eng.compact(bottom=True)
    finally:
        faults.disarm()
    cache = blockcache.node_cache()
    assert not any(k[0] in old_tokens for k in cache._entries), \
        "dead runs' windows survived the crashed compaction"
    # the swap itself landed: reads stay correct and re-cacheable
    for i in (3, 17, 40, 47):
        assert eng.get(b"s%05d" % i, ts=100) == b"v%05d" % i


def test_bloom_corruption_detected_zero_false_negatives():
    """Silent bloom bit corruption after the build checksum: the lazy CRC
    verify on a first negative must detect it and disable the filter —
    reads stay correct (no row is ever lost to a corrupt filter), the
    corruption is counted, and absent keys still answer None."""
    faults.arm(83, {
        "storage.bloom.build": FaultSpec(kind="partial", p=1.0),
    })
    try:
        eng = Engine(key_width=16, val_width=16, memtable_size=4,
                     l0_trigger=64)
        for i in range(40):  # tiny memtable: several corrupt-filter runs
            eng.put(b"g%05d" % i, b"v%05d" % i, ts=i + 1)
        eng.flush()
        assert len(eng.runs) >= 4
    finally:
        faults.disarm()
    before = metric.BLOOM_CORRUPTIONS.value
    # zero false negatives: every present key is found despite corruption
    for i in range(40):
        assert eng.get(b"g%05d" % i, ts=100) == b"v%05d" % i
    # absent keys probe negatives -> corruption detected, answers correct
    for i in range(500, 540):
        assert eng.get(b"g%05d" % i, ts=100) is None
    assert metric.BLOOM_CORRUPTIONS.value > before
    # disabled filters keep serving (as "maybe") after detection
    assert eng.get(b"g%05d" % 7, ts=100) == b"v%05d" % 7


# -- control-plane fault sites (dialer / liveness / gossip / rangefeed) ------


def test_dialer_injected_connect_failure_then_retry_succeeds():
    """A transient connect failure at the nodedialer site: the dial raises
    through (an injected drop classifies exactly like a real one), the
    half-open probe slot is released, and the immediate retry lands a
    working connection — the breaker must NOT have tripped on a single
    unreported failure."""
    from cockroach_tpu.flow.gossip import Gossip
    from cockroach_tpu.kv.dialer import NodeDialer, advertise

    db = DB(Engine(key_width=16, val_width=32, memtable_size=64), Clock())
    srv = BatchServer(db)
    g = Gossip(99)
    advertise(g, 7, srv.addr)
    dialer = NodeDialer(g, trip_threshold=2, cooldown_s=0.4)
    faults.arm(61, {
        "kv.dialer.dial": FaultSpec(kind="error", p=1.0, max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            dialer.dial(7)
        c = dialer.dial(7)  # fault exhausted: the retry connects
        c.put(b"dk", b"dv")
        assert c.get(b"dk") == b"dv"
        dialer.report_ok(7)
    finally:
        faults.disarm()
        dialer.close()
        srv.close()


def test_epoch_bump_injected_cput_failure_then_retry_fences():
    """The fencer's IncrementEpoch write fails in flight (node-scoped to
    the node DOING the bump); the retry must complete the fence: the dead
    node's epoch bumps and its eventual heartbeat is fenced."""
    from cockroach_tpu.kv.hlc import ManualClock
    from cockroach_tpu.kv.liveness import EpochFencedError, NodeLiveness

    db = DB(Engine(key_width=16, val_width=32, memtable_size=64),
            ManualClock(start=1_000))
    n1 = NodeLiveness(db, 1, heartbeat_interval_ms=50, ttl_ms=100)
    n2 = NodeLiveness(db, 2, heartbeat_interval_ms=50, ttl_ms=100)
    n1.heartbeat()
    db.clock.advance(200)  # node 1's record expires
    faults.arm(67, {
        "liveness.epoch_bump.n2": FaultSpec(kind="error", p=1.0,
                                            max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            n2.increment_epoch(1)
        rec = n2.increment_epoch(1)  # retry lands the fencing write
        assert rec.epoch == 2
        assert rec.node_id == 1
        with pytest.raises(EpochFencedError):
            n1.heartbeat()  # the old epoch is dead for good
    finally:
        faults.disarm()


def test_gossip_injected_broadcast_failure_then_retry_converges():
    """A partitioned gossip link (node-scoped to the pushing node): the
    exchange raises, the next round retries and the peer's infos still
    propagate — run_background survives exactly this way."""
    from cockroach_tpu.flow.gossip import Gossip

    g2 = Gossip(node_id=2)
    g2.add_info("node:2:addr", "hostB:26257")
    addr = g2.serve()
    g1 = Gossip(node_id=1)
    g1.add_info("node:1:addr", "hostA:26257")
    faults.arm(71, {
        "gossip.broadcast.n1": FaultSpec(kind="error", p=1.0, max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            g1.exchange(addr)
        assert g1.get_info("node:2:addr") is None  # nothing leaked through
        learned = g1.exchange(addr)  # next round: the partition healed
        assert learned >= 1
        assert g1.get_info("node:2:addr") == "hostB:26257"
    finally:
        faults.disarm()
        g1.close()
        g2.close()


def test_rangefeed_injected_subscribe_failure_then_retry_streams():
    """A failed (re)subscription — the restart path every rangefeed
    consumer must retry through: the first subscribe raises before any
    socket exists, the retry connects and replays the catch-up scan."""
    from cockroach_tpu.kv.changefeed import (
        RangefeedServer, subscribe_rangefeed,
    )
    from cockroach_tpu.kv.hlc import ManualClock

    db = DB(Engine(key_width=16, val_width=64, memtable_size=64),
            ManualClock())
    db.txn(lambda t: t.put(b"rf1", b"before"))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    faults.arm(73, {
        "kv.rangefeed.subscribe": FaultSpec(kind="error", p=1.0,
                                            max_fires=1),
    })
    try:
        with pytest.raises(InjectedFault):
            subscribe_rangefeed(srv.addr, start=b"r", end=b"s")
        sock, frames = subscribe_rangefeed(srv.addr, start=b"r", end=b"s")
        sock.settimeout(15)
        got = None
        for f in frames:
            if "key" in f:
                got = f
                break
            if "resolved" in f and f["resolved"] > 0 and got is None:
                break  # checkpoint past the put without the event: fail
        assert got is not None and got["key"] == "rf1", \
            "catch-up scan lost the pre-subscribe write"
        sock.close()
    finally:
        faults.disarm()
        srv.close()


# -- runtime race sanitizer (utils/racesan.py) -------------------------------


class _SharedBox:
    """A stand-in control-plane object with one tracked field."""


def test_race_sanitizer_flags_lockset_disjoint_writes():
    """The seeded two-thread race: main writes under lock A, a second
    thread writes under lock B, main writes again under A — the candidate
    lockset refines to empty on a write/write and DataRaceError fires
    deterministically (no die-roll, no timing window)."""
    o = _SharedBox()
    la = locks.lock("chaos.race.a")
    lb = locks.lock("chaos.race.b")
    transfer_errs = []

    with la:
        racesan.note_write(o, "field")  # exclusive(main): quiet

    def writer_b():
        try:
            with lb:
                racesan.note_write(o, "field")
        except racesan.DataRaceError as e:  # pragma: no cover
            transfer_errs.append(e)

    t = threading.Thread(target=writer_b, name="chaos-writer-b")
    t.start()
    t.join(5)
    assert not t.is_alive()
    # the transfer access seeds C = {B}: not yet provably racy
    assert not transfer_errs
    # main's next write refines C to {B} ∩ {A} = ∅ — write/write with no
    # common lock, the sanitizer raises AT the access
    with pytest.raises(racesan.DataRaceError, match="field"):
        with la:
            racesan.note_write(o, "field")


def test_race_sanitizer_flags_unlocked_read_of_written_field():
    """write/read race: a second thread reads a written field holding no
    locks at all — the transfer seeds an empty candidate set on a
    write-involved field and raises immediately."""
    o = _SharedBox()
    lk = locks.lock("chaos.race.w")
    with lk:
        racesan.note_write(o, "field")
    errs = []

    def reader():
        try:
            racesan.note_read(o, "field")
        except racesan.DataRaceError as e:
            errs.append(e)

    t = threading.Thread(target=reader, name="chaos-reader")
    t.start()
    t.join(5)
    assert len(errs) == 1
    assert "no common lock" in str(errs[0])


def test_race_sanitizer_common_lock_stays_quiet():
    """The discipline the detector enforces, working: two threads
    hammering the same field under ONE shared lock never report."""
    o = _SharedBox()
    lk = locks.lock("chaos.race.common")
    errs = []

    def worker():
        try:
            for _ in range(50):
                with lk:
                    racesan.note_write(o, "field")
                    racesan.note_read(o, "field")
        except racesan.DataRaceError as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert not errs


def test_race_sanitizer_single_thread_unlocked_is_quiet():
    """Single-threaded init without locks is the NORMAL pattern
    (constructors fill fields before any thread exists) — the exclusive
    state never reports, whatever the lockset."""
    o = _SharedBox()
    for _ in range(5):
        racesan.note_write(o, "field")
        racesan.note_read(o, "field")


# -- spill-join fault sites -------------------------------------------------


def _spill_join_catalog(seed=23) -> Catalog:
    """Two tables big enough that the join Grace-partitions under a tiny
    workmem AND at least one partition's build side alone exceeds it
    (forcing the merge-probe run path)."""
    rng = np.random.default_rng(seed)
    cat = Catalog()
    cat.add(Table(
        name="probe",
        schema=Schema(("k", "w"), (INT64, INT64)),
        columns={"k": rng.integers(0, 1200, 4000, dtype=np.int64),
                 "w": rng.integers(0, 100, 4000, dtype=np.int64)},
    ))
    cat.add(Table(
        name="build",
        schema=Schema(("bk", "v"), (INT64, INT64)),
        columns={"bk": rng.integers(0, 1500, 36000, dtype=np.int64),
                 "v": rng.integers(0, 100, 36000, dtype=np.int64)},
    ))
    return cat


def _run_spill_join(cat: Catalog, workmem: int) -> dict:
    from cockroach_tpu.sql.rel import Rel

    prev = settings.get("sql.distsql.workmem_bytes")
    settings.set("sql.distsql.workmem_bytes", workmem)
    try:
        return (Rel.scan(cat, "probe")
                .join(Rel.scan(cat, "build"), on=[("k", "bk")],
                      how="inner", build_unique=False)
                .groupby(["k"], [("n", "count_rows", None),
                                 ("sv", "sum", "v")])
                .run())
    finally:
        settings.set("sql.distsql.workmem_bytes", prev)


def test_spill_partition_write_fault_surfaces_then_clean_rerun():
    """A host spill-partition write failure mid-staging surfaces as a
    typed QueryError carrying the injected fault (not silent row loss),
    every staging reservation drains (fire precedes the account), and a
    clean re-run equals the no-fault oracle."""
    from cockroach_tpu.utils.errors import QueryError

    cat = _spill_join_catalog()
    want = _run_spill_join(cat, workmem=2 << 30)
    faults.arm(31, {"flow.spill.partition_write":
                    FaultSpec(kind="error", p=1.0, max_fires=1)})
    try:
        with pytest.raises(QueryError) as ei:
            _run_spill_join(cat, workmem=1 << 16)
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert faults.fired(), "spill staging never hit the fault site"
    finally:
        faults.disarm()
    _assert_equal(_run_spill_join(cat, workmem=1 << 16), want)


def test_spill_merge_probe_fault_surfaces_then_clean_rerun():
    """An oversized-partition merge-probe run failure surfaces mid-query
    (as a typed QueryError) after partial output may already have
    streamed; monitors drain and a clean re-run is exact."""
    from cockroach_tpu.utils.errors import QueryError

    cat = _spill_join_catalog()
    want = _run_spill_join(cat, workmem=2 << 30)
    merge0 = metric.GRACE_JOIN_MERGE_PARTS.value
    faults.arm(37, {"flow.spill.merge_probe":
                    FaultSpec(kind="error", p=1.0, max_fires=1)})
    try:
        with pytest.raises(QueryError) as ei:
            _run_spill_join(cat, workmem=1 << 16)
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert faults.fired(), "join never reached the merge-probe path"
    finally:
        faults.disarm()
    got = _run_spill_join(cat, workmem=1 << 16)
    assert metric.GRACE_JOIN_MERGE_PARTS.value > merge0
    _assert_equal(got, want)


# -- admission chaos (admission.grant.stall / admission.bucket.refill) ------


def test_admission_grant_lost_withdraws_waiter_and_leaks_no_slot():
    """Error-kind admission.grant.stall: a queued waiter's grant is lost.
    The waiter must withdraw cleanly (typed busy, cause = the injected
    fault), lane depth returns to zero, no slot leaks, and a clean rerun
    admits — the grant/withdraw race discipline under injected failure."""
    from cockroach_tpu.utils import admission
    from cockroach_tpu.utils.errors import AdmissionRejectedError

    q = admission.WorkQueue(slots=1, max_queue_depth=8)
    assert q.admit(tenant_id=2)  # park the slot so the next admit queues
    faults.arm(79, {"admission.grant.stall":
                    FaultSpec(kind="error", p=1.0, max_fires=1)})
    try:
        with pytest.raises(AdmissionRejectedError) as ei:
            q.admit(tenant_id=3, timeout=5.0)
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert ei.value.retry_after_s > 0.0
        assert faults.fired(), "admit never reached the queued-grant path"
    finally:
        faults.disarm()
    assert q.queue_depth == 0
    assert q.lane_depths() == {admission.LANE_INTERACTIVE: 0,
                               admission.LANE_ANALYTICAL: 0}
    q.release()
    assert q.admit(tenant_id=3, timeout=5.0)  # clean rerun admits
    q.release()
    assert q.in_use == 0


def test_admission_grant_stall_delay_still_lands_grant():
    """Delay-kind admission.grant.stall only holds the stalled waiter's
    thread — the grant itself (decided under the queue lock by the
    releasing thread) still lands, and the slot accounting stays exact."""
    from cockroach_tpu.utils import admission

    q = admission.WorkQueue(slots=1)
    assert q.admit(tenant_id=2)
    faults.arm(83, {"admission.grant.stall":
                    FaultSpec(kind="delay", p=1.0, delay_s=0.2,
                              max_fires=1)})
    got = []

    def waiter():
        got.append(q.admit(tenant_id=3, timeout=10.0))
        q.release()

    t = threading.Thread(target=waiter, daemon=True)
    try:
        t.start()
        deadline = time.time() + 5.0
        while not faults.fired() and time.time() < deadline:
            time.sleep(0.005)
        assert faults.fired(), "waiter never queued into the stall site"
        q.release()  # grant races the stalled waiter: must land anyway
        t.join(timeout=10.0)
        assert got == [True]
    finally:
        faults.disarm()
    assert q.in_use == 0 and q.queue_depth == 0


def test_admission_bucket_refill_failure_is_typed_busy():
    """admission.bucket.refill error-kind: the tenant's token refill
    fails — the admit surfaces the typed 53300-shaped busy (cause = the
    injected fault, retry-after hint attached), the tenant's rejection
    counter moves, and the very next admit (fault spent) succeeds."""
    from cockroach_tpu.utils import admission
    from cockroach_tpu.utils.errors import AdmissionRejectedError

    q = admission.WorkQueue(slots=2)
    q.configure_tenant(5, rate=1000.0, burst=4)
    faults.arm(89, {"admission.bucket.refill":
                    FaultSpec(kind="error", p=1.0, max_fires=1)})
    try:
        with pytest.raises(AdmissionRejectedError) as ei:
            q.admit(tenant_id=5)
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert "refill" in str(ei.value)
        assert faults.fired()
    finally:
        faults.disarm()
    row = next(r for r in q.tenant_rows() if r["tenant_id"] == 5)
    assert row["rejected"] == 1
    assert q.admit(tenant_id=5)  # clean rerun admits
    q.release()
    assert q.in_use == 0


def test_admission_grant_stall_under_concurrent_load_converges():
    """Probabilistic stall/loss sweep under real contention: N threads ×
    M admits against 2 slots with admission.grant.stall armed at p=0.3.
    Every admit either holds-then-releases or surfaces the typed busy;
    afterwards zero slots are in use and the queue is empty (no grant is
    ever both counted and lost — the sanitizer-armed shared-state check
    rides the autouse fixtures)."""
    from cockroach_tpu.utils import admission
    from cockroach_tpu.utils.errors import AdmissionRejectedError

    q = admission.WorkQueue(slots=2, max_queue_depth=64)
    ok = []
    shed = []
    lock = threading.Lock()

    def worker(tid):
        for _ in range(12):
            try:
                if q.admit(tenant_id=tid, timeout=10.0):
                    time.sleep(0.001)
                    q.release()
                    with lock:
                        ok.append(tid)
            except AdmissionRejectedError:
                with lock:
                    shed.append(tid)

    faults.arm(97, {"admission.grant.stall":
                    FaultSpec(kind="error", p=0.3, max_fires=8)})
    try:
        threads = [threading.Thread(target=worker, args=(tid,),
                                    daemon=True) for tid in (2, 3, 4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
    finally:
        faults.disarm()
    assert len(ok) + len(shed) == 36
    assert q.in_use == 0 and q.queue_depth == 0
    assert q.lane_depths() == {admission.LANE_INTERACTIVE: 0,
                               admission.LANE_ANALYTICAL: 0}


# -- changefeed fan-out plane under injected faults --------------------------


def _feed_db():
    from cockroach_tpu.kv.hlc import ManualClock

    return DB(Engine(key_width=16, val_width=64, memtable_size=64),
              ManualClock())


def _feed_oracle(db):
    """(ts, key) -> value of the full committed history — the exactly-once
    reference every faulted stream must dedup to."""
    from cockroach_tpu.kv.changefeed import changes_between

    events, _resolved = changes_between(db, 0, db.clock.now())
    return {(e["ts"], e["key"]): e["value"] for e in events}


def _feed_drain(sock, frames, until_resolved, deadline_s=15):
    """Deduped event frames until the frontier reaches `until_resolved`,
    an error frame, or end-of-stream. Returns (events, resolved, err)."""
    sock.settimeout(deadline_s)
    events, resolved = {}, 0
    deadline = time.time() + deadline_s
    for f in frames:
        if "error" in f:
            return events, resolved, f
        if "resolved" in f:
            resolved = max(resolved, f["resolved"])
            if resolved >= until_resolved:
                break
        else:
            events[(f["ts"], f["key"])] = f["value"]
        if time.time() > deadline:
            break
    return events, resolved, None


def test_fanout_injected_send_fault_evicts_then_reconnect_exactly_once():
    """Site ``changefeed.subscriber.send``: the sender's first
    transmission dies mid-stream. The subscriber is evicted with a typed
    slow_consumer frame carrying its frontier, the emit loop survives,
    and a reconnect from that frontier replays the feed so the deduped
    union is bit-identical to the no-fault catch-up scan — exactly once
    per version."""
    from cockroach_tpu.kv.changefeed import (
        RangefeedServer, subscribe_rangefeed,
    )

    db = _feed_db()
    for i in range(6):
        db.txn(lambda t, i=i: t.put(b"sf%d" % i, b"v%d" % i))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    faults.arm(79, {
        "changefeed.subscriber.send": FaultSpec(kind="drop", p=1.0,
                                                max_fires=1),
    })
    try:
        sock, frames = subscribe_rangefeed(srv.addr)
        first, _ckpt, err = _feed_drain(sock, frames, db.clock.now())
        sock.close()
        assert err is not None and err["error"] == "slow_consumer", \
            "faulted send must evict with a typed goodbye"
        assert "frontier" in err
        assert metric.CHANGEFEED_EVICTIONS.value >= 1
        # the fault fired before any frame hit the wire: nothing was
        # checkpointed, so the carried frontier is the join point
        since = err["frontier"]
        assert since == 0
        sock2, frames2 = subscribe_rangefeed(srv.addr, since=since)
        hi = db.clock.now()
        second, ckpt2, err2 = _feed_drain(sock2, frames2, hi)
        sock2.close()
        assert err2 is None and ckpt2 >= hi, "emit loop wedged by fault"
        merged = dict(first)
        merged.update(second)
        assert merged == _feed_oracle(db), \
            "reconnect after injected send fault lost/duplicated a version"
    finally:
        faults.disarm()
        srv.close()


def test_fanout_injected_enqueue_fault_converges_without_buffer_leak():
    """Site ``changefeed.fanout.enqueue``: every other buffer append dies
    under a write stream. Each hit sheds the subscriber to catch-up (the
    engine re-feeds from the frontier, dedup by (ts, key)), so the stream
    still converges to the full history — and the changefeed staging
    account drains to zero after close: no leaked buffer bytes."""
    from cockroach_tpu.flow import memory as flowmem
    from cockroach_tpu.kv.changefeed import (
        RangefeedServer, subscribe_rangefeed,
    )

    db = _feed_db()
    # poll SLOWER than one cold overlay rebuild (~0.4s with dozens of
    # runs): each commit rewrites the run set, so a poller that fires
    # faster than it can rebuild serializes the writer to one txn per
    # rebuild under the store mutex and the test crawls
    srv = RangefeedServer(db, poll_interval_s=0.25)
    sheds0 = metric.CHANGEFEED_SHEDS.value
    # this test pins the SHED rung: transient fault (max_fires — the
    # retrying-caller knob) and a shed ceiling high enough that back-to-
    # back sheds during one slow rescan can't escalate to eviction (the
    # terminal rung has its own tests)
    prev_sheds = settings.get("changefeed.fanout.max_consecutive_sheds")
    settings.set("changefeed.fanout.max_consecutive_sheds", 100)
    faults.arm(83, {
        "changefeed.fanout.enqueue": FaultSpec(kind="error", p=0.5,
                                               max_fires=6),
    })
    try:
        sock, frames = subscribe_rangefeed(srv.addr)
        # spread the writes over a few poll intervals so enqueue runs
        # (and coin-flips) repeatedly while the consumer is live; after
        # each injected shed the sender rescans and returns LIVE, so the
        # next batch coin-flips again. A warm module can land a whole
        # batch inside one poll interval (one enqueue, ONE coin flip),
        # so keep writing rounds until the coin lands — each round is at
        # least one fresh flip, so 15 rounds at p=0.5 can't all miss
        i = 0
        for _round in range(15):
            for _ in range(12):
                db.txn(lambda t, i=i: t.put(b"eq%02d" % (i % 12),
                                            b"w%02d" % i))
                i += 1
                time.sleep(0.002)
            if metric.CHANGEFEED_SHEDS.value > sheds0:
                break
            time.sleep(0.3)  # let the poller batch + coin-flip this round
        hi = db.clock.now()
        events, resolved, err = _feed_drain(sock, frames, hi)
        sock.close()
        assert err is None, f"enqueue fault must shed, not evict: {err}"
        assert resolved >= hi, "frontier stalled under injected sheds"
        assert events == _feed_oracle(db), \
            "shed/rescan under enqueue faults lost or duplicated a version"
        assert metric.CHANGEFEED_SHEDS.value > sheds0, \
            "seed 83 at p=0.5 over ~dozens of enqueues must shed"
    finally:
        faults.disarm()
        srv.close()
        settings.set("changefeed.fanout.max_consecutive_sheds",
                     prev_sheds)
    assert flowmem.staging_monitor("changefeed").used == 0, \
        "fan-out buffer bytes leaked past hub close"


def test_fanout_injected_checkpoint_fault_resume_never_skips():
    """Site ``changefeed.frontier.checkpoint``: the first checkpoint
    write dies AFTER events reached the wire. The frontier must not
    advance past the failed checkpoint — the typed eviction carries the
    pre-fault frontier, and reconnecting from it re-delivers (dedup)
    rather than skips: resolved never runs ahead of delivery."""
    from cockroach_tpu.kv.changefeed import (
        RangefeedServer, subscribe_rangefeed,
    )

    db = _feed_db()
    for i in range(4):
        db.txn(lambda t, i=i: t.put(b"cp%d" % i, b"v%d" % i))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    faults.arm(89, {
        "changefeed.frontier.checkpoint": FaultSpec(kind="error", p=1.0,
                                                    max_fires=1),
    })
    try:
        sock, frames = subscribe_rangefeed(srv.addr)
        first, ckpt, err = _feed_drain(sock, frames, db.clock.now())
        sock.close()
        assert err is not None and err["error"] == "slow_consumer"
        assert ckpt == 0, "a checkpoint frame arrived despite the fault"
        assert err["frontier"] == 0, \
            "frontier advanced past a checkpoint that never hit the wire"
        sock2, frames2 = subscribe_rangefeed(srv.addr,
                                             since=err["frontier"])
        hi = db.clock.now()
        second, ckpt2, err2 = _feed_drain(sock2, frames2, hi)
        sock2.close()
        assert err2 is None and ckpt2 >= hi
        merged = dict(first)
        merged.update(second)
        assert merged == _feed_oracle(db), \
            "resume after failed checkpoint skipped a version"
    finally:
        faults.disarm()
        srv.close()


def test_race_sanitizer_guards_fanout_frontier():
    """The fan-out plane's shared state is racesan-tracked: a subscriber
    frontier write under some OTHER lock (not the hub's
    ``kv.fanout.state`` lock every product access holds) refines the
    candidate lockset to empty and raises deterministically — the seeded
    two-thread schedule for the new subscriber tree."""
    import socket as _socket

    from cockroach_tpu.kv import fanout

    db = _feed_db()
    hub = fanout.FanoutHub(db, poll_interval_s=3600)
    a, b = _socket.socketpair()
    try:
        sub = hub.add_subscriber(a, start_sender=False)
        with hub._mu:
            racesan.note_write(sub, "frontier")  # product-path lockset
        rogue = locks.lock("chaos.race.fanout")
        transfer_errs = []

        def writer_rogue():
            try:
                with rogue:
                    racesan.note_write(sub, "frontier")
            except racesan.DataRaceError as e:  # pragma: no cover
                transfer_errs.append(e)

        t = threading.Thread(target=writer_rogue,
                             name="chaos-fanout-rogue")
        t.start()
        t.join(5)
        assert not t.is_alive()
        assert not transfer_errs  # transfer access only seeds C = {rogue}
        # the next product-path write proves disjointness: {mu} ∩ {rogue}
        with pytest.raises(racesan.DataRaceError, match="frontier"):
            with hub._mu:
                racesan.note_write(sub, "frontier")
    finally:
        hub.close()
        a.close()
        b.close()
