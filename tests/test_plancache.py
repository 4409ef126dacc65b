"""Prepared-plan cache + canonical-shape tests (sql/plancache.py).

Covers the PR-6 acceptance sweep: shape bucketing must be bit-identical
to the unbucketed engine across the fusion matrix, the plan cache must
LRU-evict at its size cap, concurrent sessions must share one cache
safely, and DDL invalidation must never serve a stale plan (the
dropped-index case)."""

import threading

import numpy as np
import pytest

import cockroach_tpu.catalog as catalog_mod
from cockroach_tpu import coldata as cd
from cockroach_tpu.bench import queries as Q
from cockroach_tpu.bench import tpcds, tpch
from cockroach_tpu.flow import dispatch
from cockroach_tpu.flow import operators as ops
from cockroach_tpu.kv import DB, ManualClock
from cockroach_tpu.sql import Session, plancache
from cockroach_tpu.storage import rowcodec
from cockroach_tpu.storage.lsm import Engine
from cockroach_tpu.utils import metric, settings, tracing

_FAST_TPCH = {"q1", "q3", "q6", "q9", "q18"}
_FAST_TPCDS = {"q3", "q42"}


# --------------------------------------------------------------------------
# shape bucketing on/off bit-identity across the fusion matrix


@pytest.fixture(scope="module")
def hcat():
    return tpch.gen_tpch(sf=0.005, seed=7)


@pytest.fixture(scope="module")
def dcat():
    return tpcds.gen_tpcds(sf=0.01)


def _run_bucketed(cat, rel, buckets: bool):
    # the padded device image is pinned per table (__cap__), so a toggle
    # needs the device cache dropped to take effect
    for t in cat.tables.values():
        t._device = None
    settings.set("sql.distsql.fusion.enabled", True)
    settings.set("sql.distsql.shape_buckets.enabled", buckets)
    try:
        return rel.run()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
        settings.reset("sql.distsql.shape_buckets.enabled")
        for t in cat.tables.values():
            t._device = None


def _assert_identical(got, want):
    assert set(got) == set(want)
    for name in want:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape, name
        if g.dtype == object or w.dtype == object:
            assert list(g) == list(w), name
        else:
            # bit-identical, not allclose: padding must not leak into
            # results (masked rows only)
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize(
    "qname",
    [pytest.param(q, marks=() if q in _FAST_TPCH else (pytest.mark.slow,))
     for q in sorted(Q.QUERIES)],
)
def test_tpch_bucketing_equivalence(hcat, qname):
    rel = Q.QUERIES[qname](hcat)
    _assert_identical(_run_bucketed(hcat, rel, True),
                      _run_bucketed(hcat, rel, False))


@pytest.mark.parametrize(
    "qname",
    [pytest.param(q, marks=() if q in _FAST_TPCDS else (pytest.mark.slow,))
     for q in sorted(tpcds.QUERIES)],
)
def test_tpcds_bucketing_equivalence(dcat, qname):
    rel = tpcds.QUERIES[qname](dcat)
    _assert_identical(_run_bucketed(dcat, rel, True),
                      _run_bucketed(dcat, rel, False))


# --------------------------------------------------------------------------
# plan cache behavior through the Session


SCHEMA = cd.Schema.of(id=cd.INT64, qty=cd.INT64, grp=cd.INT64)


def _session(n=40):
    db = DB(
        Engine(key_width=24, val_width=rowcodec.value_width(SCHEMA) + 64,
               memtable_size=256),
        ManualClock(),
    )
    cat = catalog_mod.Catalog()
    s = Session(catalog=cat, db=db)
    s.execute("CREATE TABLE items (id INT PRIMARY KEY, qty INT, grp INT)")
    for i in range(n):
        s.execute(
            f"INSERT INTO items VALUES ({i}, {i % 7}, {i % 3})")
    return s


def _cache(sess):
    return plancache.cache_for(sess.catalog)


def test_plan_cache_hit_and_memo():
    s = _session()
    c = _cache(s)
    h0, m0 = c.hits, c.misses
    r1 = s.execute("SELECT qty FROM items WHERE id = 7")
    assert c.misses == m0 + 1
    # different literal, same fingerprint: plan-cache hit, rebind only
    r2 = s.execute("SELECT qty FROM items WHERE id = 8")
    assert c.hits == h0 + 1
    assert list(np.asarray(r1["qty"])) == [0]
    assert list(np.asarray(r2["qty"])) == [1]
    # verbatim repeat: the exact-text memo answers without a parse
    r3 = s.execute("SELECT qty FROM items WHERE id = 8")
    assert list(np.asarray(r3["qty"])) == list(np.asarray(r2["qty"]))


def test_plan_cache_lru_eviction():
    s = _session()
    c = _cache(s)
    c.clear()
    settings.set("sql.plan_cache.size", 2)
    try:
        s.execute("SELECT qty FROM items WHERE id = 1")
        s.execute("SELECT grp FROM items WHERE id = 1")
        assert len(c) == 2
        ev0 = c.evictions
        s.execute("SELECT qty, grp FROM items WHERE id = 1")
        assert len(c) == 2
        assert c.evictions == ev0 + 1
        # the first (least recently used) statement now misses again
        m0 = c.misses
        s.execute("SELECT qty FROM items WHERE id = 2")
        assert c.misses == m0 + 1
    finally:
        settings.reset("sql.plan_cache.size")


def test_plan_cache_concurrent_sessions():
    s1 = _session()
    s2 = Session(catalog=s1.catalog, db=s1.db, bootstrap=False)
    errs = []
    results = {}

    def work(name, sess):
        try:
            out = []
            for i in range(8):
                r = sess.execute(f"SELECT qty FROM items WHERE grp = {i % 3}")
                out.append(sorted(np.asarray(r["qty"]).tolist()))
            results[name] = out
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    ts = [threading.Thread(target=work, args=("a", s1)),
          threading.Thread(target=work, args=("b", s2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert results["a"] == results["b"]
    # both sessions share ONE cache on the catalog
    assert len(_cache(s1)) >= 1
    assert _cache(s1) is _cache(s2)


def test_plan_cache_sees_dml():
    """A cached plan must serve rows written AFTER it was cached (the
    operator tree re-snapshots the table on every run)."""
    s = _session(n=5)
    r1 = s.execute("SELECT qty FROM items WHERE grp = 0")
    n1 = len(np.asarray(r1["qty"]))
    s.execute("INSERT INTO items VALUES (100, 42, 0)")
    r2 = s.execute("SELECT qty FROM items WHERE grp = 0")
    got = sorted(np.asarray(r2["qty"]).tolist())
    assert len(got) == n1 + 1
    assert 42 in got


def test_plan_cache_invalidated_by_ddl_and_never_serves_dropped_index():
    s = _session()
    c = _cache(s)
    r_before = sorted(
        np.asarray(s.execute(
            "SELECT id FROM items WHERE qty = 3")["id"]).tolist())
    v0 = s.catalog.version
    s.execute("CREATE INDEX qty_idx ON items (qty)")
    assert s.catalog.version > v0
    assert len(c) == 0  # DDL evicts every cached plan
    # plan through the index, then drop it: the cached index-scan plan
    # must never serve again
    r_idx = sorted(
        np.asarray(s.execute(
            "SELECT id FROM items WHERE qty = 3")["id"]).tolist())
    assert r_idx == r_before
    s.execute("DROP INDEX qty_idx ON items")
    assert len(c) == 0
    # a row inserted after the drop is invisible to the dropped index's
    # frozen data — a stale plan would miss it
    s.execute("INSERT INTO items VALUES (200, 3, 1)")
    r_after = sorted(
        np.asarray(s.execute(
            "SELECT id FROM items WHERE qty = 3")["id"]).tolist())
    assert r_after == sorted(r_before + [200])


def test_plan_cache_disabled_setting():
    s = _session()
    c = _cache(s)
    c.clear()
    settings.set("sql.plan_cache.enabled", False)
    try:
        s.execute("SELECT qty FROM items WHERE id = 3")
        assert len(c) == 0
    finally:
        settings.reset("sql.plan_cache.enabled")


# --------------------------------------------------------------------------
# an entry's trees: a plan that keeps nothing between runs is built once a
# concurrent session, any other keeps one tree and its sessions queue

POINT = "SELECT qty FROM items WHERE id = {}"
GROUPED = "SELECT grp, sum(qty) AS s FROM items WHERE id < {} GROUP BY grp"


def _peer(sess):
    return Session(catalog=sess.catalog, db=sess.db, bootstrap=False)


def _waits() -> int:
    return tracing.totals().get("sql.plancache.entry_wait",
                                {"count": 0})["count"]


class _Held:
    """Patches ``cls.init`` so that its first caller stands still inside it
    (its tree is out with that session) until ``release``."""

    def __init__(self, monkeypatch, cls):
        self.entered, self.release = threading.Event(), threading.Event()
        first = threading.Lock()
        real = cls.init

        def init(op):
            if first.acquire(blocking=False):
                self.entered.set()
                assert self.release.wait(60)
            real(op)

        monkeypatch.setattr(cls, "init", init)

    def start(self, sess, text):
        """Send `text` on a thread that gets held; -> (thread, its box)."""
        box = {}
        t = _sender(sess, text, box)
        t.start()
        assert self.entered.wait(60)
        return t, box


def _sender(sess, text, box):
    def run():
        try:
            box["res"] = sess.execute(text)
        except Exception as e:  # noqa: BLE001 - asserted by the test
            box["err"] = e

    return threading.Thread(target=run)


def _only_entry(sess):
    (entry,) = _cache(sess)._entries.values()
    return entry


def test_concurrent_point_reads_each_get_a_tree_and_their_own_row(
        monkeypatch):
    s = _session()
    _cache(s).clear()
    assert list(s.execute(POINT.format(1))["qty"]) == [1]
    held = _Held(monkeypatch, ops.PointLookupOp)
    t0, box0 = held.start(s, POINT.format(9))
    builds, runs = (metric.PLAN_CACHE_POOL_BUILDS.value,
                    metric.PLAN_CACHE_POOL_RUNS.value)
    waits = _waits()
    peers = [(_peer(s), k, {}) for k in range(10, 18)]
    ts = [_sender(p, POINT.format(k), box) for p, k, box in peers]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    # all served while the first tree is still out: nobody stood in line
    assert not any(t.is_alive() for t in ts) and t0.is_alive()
    for _, k, box in peers:
        assert "err" not in box and list(box["res"]["qty"]) == [k % 7]
    assert metric.PLAN_CACHE_POOL_BUILDS.value > builds
    assert metric.PLAN_CACHE_POOL_RUNS.value == runs + len(peers)
    assert _waits() == waits
    held.release.set()
    t0.join(60)
    assert list(box0["res"]["qty"]) == [9 % 7]
    e = _only_entry(s)
    assert e.cap == settings.get("admission.sql.slots")
    assert 2 <= e._trees == len(e._free) <= 1 + len(peers)


def test_a_pooled_trees_first_run_compiles_nothing(monkeypatch):
    s = _session()
    _cache(s).clear()
    s.execute(POINT.format(1))
    held = _Held(monkeypatch, ops.PointLookupOp)
    t0, _ = held.start(s, POINT.format(2))
    builds, c0 = metric.PLAN_CACHE_POOL_BUILDS.value, dispatch.compiles()
    try:
        assert list(_peer(s).execute(POINT.format(12))["qty"]) == [12 % 7]
    finally:
        held.release.set()
        t0.join(60)
    assert metric.PLAN_CACHE_POOL_BUILDS.value == builds + 1
    assert dispatch.compiles() == c0


def test_a_plan_with_state_keeps_one_tree_and_its_sessions_queue(
        monkeypatch):
    s = _session()
    _cache(s).clear()
    want = s.execute(GROUPED.format(30))
    assert _only_entry(s).cap == 1
    held = _Held(monkeypatch, ops.ScanOp)
    t0, box0 = held.start(s, GROUPED.format(30))
    builds, waits = metric.PLAN_CACHE_POOL_BUILDS.value, _waits()
    box1 = {}
    t1 = _sender(_peer(s), GROUPED.format(30), box1)
    t1.start()
    t1.join(0.5)
    assert t1.is_alive()  # in line for the one tree
    held.release.set()
    t0.join(60)
    t1.join(60)
    assert not t0.is_alive() and not t1.is_alive()
    for box in (box0, box1):
        assert sorted(zip(box["res"]["grp"], box["res"]["s"])) == sorted(
            zip(want["grp"], want["s"]))
    assert metric.PLAN_CACHE_POOL_BUILDS.value == builds
    assert _waits() == waits + 1
    e = _only_entry(s)
    assert e._trees == len(e._free) == 1


@pytest.mark.parametrize("text,cls,trees_after", [
    (POINT, ops.PointLookupOp, 0),  # holds nothing: dropped, built anew
    (GROUPED, ops.ScanOp, 1),       # the one tree with state serves again
], ids=["stateless", "with_state"])
def test_a_run_that_raises_leaves_the_entry_serving(monkeypatch, text, cls,
                                                    trees_after):
    s = _session()
    _cache(s).clear()
    want = s.execute(text.format(5))
    real = cls.init
    fail = [True]

    def init(op):
        if fail[0]:
            fail[0] = False
            raise RuntimeError("injected")
        real(op)

    monkeypatch.setattr(cls, "init", init)
    with pytest.raises(Exception, match="injected"):
        s.execute(text.format(6))
    e = _only_entry(s)
    assert e._trees == len(e._free) == trees_after
    got = s.execute(text.format(5))
    assert {n: list(v) for n, v in got.items()} == {
        n: list(v) for n, v in want.items()}
    assert e._trees == len(e._free) == 1


def test_ddl_drops_an_entry_whose_tree_is_out_without_an_error(monkeypatch):
    s = _session()
    c = _cache(s)
    c.clear()
    s.execute(POINT.format(1))
    held = _Held(monkeypatch, ops.PointLookupOp)
    p = _peer(s)
    t0, box0 = held.start(p, POINT.format(11))
    e = _only_entry(s)
    s.execute("CREATE INDEX qty_idx ON items (qty)")
    assert len(c) == 0
    held.release.set()
    t0.join(60)
    assert "err" not in box0 and list(box0["res"]["qty"]) == [11 % 7]
    assert e._trees == len(e._free) == 1  # came back to an entry nobody reads
    assert list(p.execute(POINT.format(12))["qty"]) == [12 % 7]
    assert len(c) == 1 and _only_entry(s) is not e


def test_point_read_storm_loses_no_tree_and_crosses_no_key():
    """16 sessions on a shortened switch interval: a tree lent twice would
    answer one session with another's key; a lost give_back would leave
    `_trees` above what lies free."""
    import sys

    s = _session()
    _cache(s).clear()
    s.execute(POINT.format(0))
    errs = []

    def work(sess, base):
        try:
            for i in range(25):
                k = (base + 5 * i) % 40
                assert list(sess.execute(POINT.format(k))["qty"]) == [k % 7]
        except BaseException as e:  # noqa: BLE001 - asserted below
            errs.append(e)

    ts = [threading.Thread(target=work, args=(_peer(s), b))
          for b in range(16)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not errs and not any(t.is_alive() for t in ts)
    e = _only_entry(s)
    assert 1 <= e._trees == len(e._free) <= 16
