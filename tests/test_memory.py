"""Resource observability plane: the mon.BytesMonitor tree, budget-driven
spills, admission timeout/grant racing, and the serving-load surfaces.

Reference shapes under test: pkg/util/mon (hierarchical byte accounting,
"monitor closed with outstanding bytes" drain discipline), colexecdisk's
disk_spiller (budget exceeded -> external variant, bit-identical results),
and admission's WorkQueue (a grant racing a timeout withdrawal must never
leak the slot).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cockroach_tpu.catalog import Catalog
from cockroach_tpu.flow import memory
from cockroach_tpu.sql import Session
from cockroach_tpu.utils import admission, settings


# ------------------------------------------------------------ monitor tree

def test_monitor_tree_charges_ancestors():
    root = memory.BytesMonitor("test-root", level="root")
    sess = root.child("sess", level="session")
    query = sess.child("query", level="query")
    op = query.child("op", level="operator")

    op.reserve(1000)
    assert (op.used, query.used, sess.used, root.used) == (1000,) * 4
    op.reserve(500)
    assert root.used == 1500 and root.high_water == 1500
    op.release(600)
    assert (op.used, root.used) == (900, 900)
    assert root.high_water == 1500  # peak survives the release

    # close() force-releases the remainder up the chain and reports it
    leaked = op.close()
    assert leaked == 900
    assert query.used == 0 and root.used == 0
    assert op.closed and op.close() == 0  # idempotent


def test_budget_refusal_leaves_chain_untouched():
    root = memory.BytesMonitor("test-root", level="root")
    op = root.child("op", budget=4096)
    op.reserve(4000)
    assert op.would_exceed(100)
    with pytest.raises(memory.BudgetExceededError):
        op.reserve(100)
    # the refused reservation charged NOTHING anywhere
    assert op.used == 4000 and root.used == 4000
    # an ancestor budget refuses too, before any charge lands
    mid = root.child("mid", budget=8192)
    leaf = mid.child("leaf")  # unlimited at this level
    leaf.reserve(8000)
    with pytest.raises(memory.BudgetExceededError):
        leaf.reserve(200)
    assert leaf.used == 8000 and mid.used == 8000
    # force=True skips the check (host-side state that cannot spill) but
    # still accounts the bytes truthfully
    op.reserve(100, force=True)
    assert op.used == 4100 and op.high_water == 4100
    op.close()
    leaf.close()
    mid.close()
    assert root.used == 0


def test_query_scope_joins_and_counts_drain_failures():
    before = memory.drain_failure_count()
    root_used0 = memory.ROOT.used
    with memory.query_scope() as qm:
        # a nested scope (diagnostics re-run shape) JOINS the outer monitor
        with memory.query_scope() as inner:
            assert inner is qm
        # a deliberately leaked operator account: never closed
        alloc = memory.Allocator("leaky op")
        alloc.reserve(2048)
        assert memory.current_query() is qm
        assert qm.used == 2048
    # scope exit force-closed the child, so the node gauge is clean...
    assert memory.ROOT.used == root_used0
    assert memory.current_query() is None
    # ...and the leak was censused with the monitor named
    assert memory.drain_failure_count() == before + 1
    name, leaked = memory.drain_failures(last=1)[0]
    assert leaked == 2048 and name.startswith("query-")
    # undo the deliberate failure so the per-test drain census (conftest
    # autouse fixture) doesn't flag this test — the one place the counter
    # may be rolled back, because the leak was the assertion target
    memory._DRAIN_TOTAL -= 1
    memory._DRAIN_FAILURES.pop()


def test_query_scope_drains_cleanly_when_accounts_close():
    with memory.query_scope() as qm:
        alloc = memory.Allocator("tidy op")
        alloc.reserve(4096)
        alloc.close()
        assert qm.used == 0
    assert qm.high_water == 4096  # peak recorded even after the drain


# ------------------------------------- budget exceeded -> external variant

_SPILL_Q = ("select l_orderkey, sum(l_quantity) as sq from lineitem "
            "group by l_orderkey order by l_orderkey")


def _tpch_session():
    from cockroach_tpu.bench.tpch import gen_tpch_cached

    return Session(catalog=gen_tpch_cached(0.005))


def test_spill_bit_identity_and_query_attribution():
    """disk_spiller contract under the monitor tree: lowering workmem to
    its floor forces the agg/sort spools past budget and into the external
    variants; the result must be BIT-IDENTICAL to the in-memory run, and
    the spill must be attributed to the owning query's fingerprint
    (non-zero spills + peak-memory percentiles in sqlstats)."""
    from cockroach_tpu.sql import sqlstats

    # this statement's own sqlstats row, by its exact fingerprint, and what
    # the row held before: the store is the process's, and a file earlier
    # on the same worker may have left q3's row or q18's subquery ("...
    # from lineitem group by l_orderkey having sum(l_quantity) > _"), which
    # a substring match took for this one (spills 0: the driver's run of
    # PR 37's tree failed here under --dist loadfile)
    key = sqlstats.fingerprint(_SPILL_Q)

    def row_spills():
        return sum(int(r["spills"]) for r in sqlstats.DEFAULT.rows_payload()
                   if r["fingerprint"] == key)

    row_spills_before = row_spills()
    s = _tpch_session()
    ref = s.execute(_SPILL_Q)  # in-memory reference (default workmem)
    assert row_spills() == row_spills_before  # default workmem: no spill

    spills_before = memory.ROOT.spills
    settings.set("sql.distsql.workmem_bytes", 65536)
    try:
        got = s.execute(_SPILL_Q)
    finally:
        settings.reset("sql.distsql.workmem_bytes")
    assert memory.ROOT.spills > spills_before  # the budget actually bit
    assert sorted(ref.keys()) == sorted(got.keys())
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])

    # attribution: the fingerprint's sqlstats row carries the spill count
    # and non-zero peak-memory percentiles next to its latency figures
    res = s.execute(
        "select fingerprint, spills, max_mem_mb, mem_p50_mb, mem_p99_mb "
        "from crdb_internal.node_statement_statistics")
    rows = {str(f): i for i, f in enumerate(res["fingerprint"])}
    i = rows[key]
    assert int(res["spills"][i]) > row_spills_before
    assert float(res["max_mem_mb"][i]) > 0
    assert float(res["mem_p99_mb"][i]) > 0
    s.close()


def test_explain_analyze_prints_memory_and_spill_lines():
    """Acceptance shape: EXPLAIN ANALYZE on a spilling query prints a per-
    operator max-memory figure, marks the spilled operators, and footers
    the query's peak before the kernel-dispatch lines."""
    from cockroach_tpu import sql as sqlmod
    from cockroach_tpu.bench.tpch import gen_tpch_cached

    cat = gen_tpch_cached(0.005)
    settings.set("sql.distsql.workmem_bytes", 65536)
    try:
        txt = sqlmod.explain(cat, "explain analyze " + _SPILL_Q)
    finally:
        settings.reset("sql.distsql.workmem_bytes")
    assert "max mem=" in txt
    assert "spilled" in txt
    lines = txt.splitlines()
    (peak_line,) = [ln for ln in lines if "query peak memory:" in ln]
    assert "(spills:" in peak_line
    # footer ordering: peak memory BEFORE the kernel dispatch/compile pair
    assert lines.index(peak_line) < lines.index(
        next(ln for ln in lines if ln.startswith("kernel dispatches:")))


# --------------------------------------------------- crdb_internal surface

def test_crdb_internal_memory_monitor_and_load_tables():
    s = Session(Catalog())
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values (1, 10), (2, 20), (3, 30)")
    s.execute("select id, v from t order by v")  # reserves a sort spool

    res = s.execute(
        "select name, level, depth, used_bytes, peak_bytes, budget_bytes "
        "from crdb_internal.node_memory_monitors")
    names = [str(n) for n in res["name"]]
    levels = [str(lv) for lv in res["level"]]
    assert names[0] == "root" and int(res["depth"][0]) == 0
    assert "session" in levels  # this session's own monitor is live
    # the statement reading the table sees ITSELF as the open query monitor
    assert "query" in levels
    assert int(res["used_bytes"][0]) >= 0

    res = s.execute(
        "select active_sessions, admission_slots, admission_admitted, "
        "sql_mem_peak_bytes, queries_total from crdb_internal.cluster_load")
    assert len(res["admission_slots"]) == 1
    assert int(res["active_sessions"][0]) >= 1
    assert int(res["admission_slots"][0]) >= 1
    assert int(res["admission_admitted"][0]) >= 1
    assert int(res["sql_mem_peak_bytes"][0]) > 0  # the sort spool peak
    s.close()


# ------------------------------------------------- admission race hammer

def test_admission_timeout_grant_race_hammer():
    """Regression for the admit timeout/grant race: a waiter whose grant
    lands concurrently with its timeout withdrawal must HAND THE SLOT BACK
    instead of leaking it. Hammer with timeouts at the same scale as the
    hold times so the race window is hit constantly; afterwards the queue
    must be fully drained and every slot grantable again."""
    q = admission.WorkQueue(slots=2)
    deadline = time.time() + 2.0
    granted = [0] * 8

    def worker(i: int) -> None:
        rng = np.random.default_rng(i)
        while time.time() < deadline:
            if q.admit(timeout=float(rng.uniform(0.0, 0.002))):
                granted[i] += 1
                if rng.random() < 0.5:
                    time.sleep(float(rng.uniform(0.0, 0.001)))
                q.release()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)

    # the storm must have actually exercised both outcomes
    assert sum(granted) > 0 and q.timeouts > 0
    # post-storm invariants: nothing waiting, nothing held...
    assert q.queue_depth == 0
    assert q.in_use == 0
    assert not q._waiters or all(w.withdrawn for _, _, w in q._waiters)
    # ...and BOTH slots immediately grantable (a leaked slot would make
    # the second of these time out)
    assert q.admit(timeout=1.0)
    assert q.admit(timeout=1.0)
    q.release()
    q.release()
    assert q.in_use == 0


def test_admission_timeout_holds_nothing():
    q = admission.WorkQueue(slots=1)
    assert q.admit()
    t0 = time.perf_counter()
    assert q.admit(timeout=0.05) is False  # queue full: pure timeout
    assert time.perf_counter() - t0 < 5.0
    assert q.timeouts == 1 and q.queue_depth == 0
    q.release()  # the ORIGINAL holder's release must find a free queue
    assert q.in_use == 0
    assert q.admit(timeout=0.5)
    q.release()


def test_sql_slot_is_reentrant_per_thread():
    """A nested statement (internal executor / diagnostics re-run) must
    not deadlock on its own session's slot even at slots=1."""
    saved = admission._SQL_QUEUE
    admission._SQL_QUEUE = admission.WorkQueue(slots=1)
    try:
        with admission.sql_slot() as w0:
            with admission.sql_slot() as w1:  # nested: free pass
                assert w1 == 0.0
            assert w0 >= 0.0
        assert admission._SQL_QUEUE.in_use == 0
    finally:
        admission._SQL_QUEUE = saved
