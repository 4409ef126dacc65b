"""CockroachDB's kv workload (pkg/workload/kv: `SELECT k, v FROM kv WHERE k
IN ($1)` and `UPSERT INTO kv (k, v) VALUES ($1, $2)`) through Sessions over
one Node, held to a plain dict: the preload, then the acknowledged UPSERTs.
What each part of the served path has to do for that:

- a commit resolves its intents where they are (no flush, no re-sort of
  the store), and a flushed intent in its own run only;
- UPSERT parses, binds and overwrites, in and out of a transaction;
- the primary key is a plan route (PointLookup): EXPLAIN shows it, the
  table is never decoded, another key binds the cached plan;
- 64 sessions at once leave no intent behind and lose no acknowledged
  write (ROADMAP D11 (a)).
"""

import threading
import time

import numpy as np
import pytest

from cockroach_tpu.flow import dispatch
from cockroach_tpu.kv import DB, ManualClock
from cockroach_tpu.kv.txn import TransactionRetryError
from cockroach_tpu.server.node import Node
from cockroach_tpu.sql import Session, explain
from cockroach_tpu.sql import parser as P
from cockroach_tpu.storage import mvcc
from cockroach_tpu.storage.lsm import Engine, WriteIntentError
from cockroach_tpu.utils import metric, tracing

ROWS = 2000
ALPHABET = ("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
            "0123456789+/")


def value_of(seed: int, k: int) -> str:
    """The preloaded value of key k: one of 64 characters."""
    return ALPHABET[(k * 2654435761 + seed) % 64]


class KVModel:
    """The reference: a dict. Preload from the seed, then every UPSERT the
    server acknowledged."""

    def __init__(self, seed: int, rows: int):
        self.d = {k: value_of(seed, k) for k in range(rows)}

    def upsert(self, k: int, v: str) -> None:
        self.d[k] = v

    def read(self, k: int):
        return [[k, self.d[k]]] if k in self.d else []


def _rows(res) -> list:
    return [[int(k), str(v)] for k, v in zip(res["k"], res["v"])]


@pytest.fixture
def served():
    """A Node with its default loops, `kv` created through SQL and loaded
    presorted through the AddSSTable path, as the cell's loader does."""
    node = Node().start(pg_port=0)
    sess = Session(catalog=node._sql_catalog, db=node.db, bootstrap=False)
    sess.execute("CREATE TABLE kv (k INT PRIMARY KEY, v STRING)")
    ks = np.arange(ROWS, dtype=np.int64)
    sess.catalog.tables["kv"].bulk_load(
        {"k": ks, "v": np.array([value_of(7, int(k)) for k in ks],
                                dtype=object)}, presorted=True)
    try:
        yield node, sess
    finally:
        sess.close()
        node.stop()


def _session(node) -> Session:
    return Session(catalog=node._sql_catalog, db=node.db, bootstrap=False)


# -- step 1: a commit resolves where the intents are ---------------------


def _engine(**kw) -> Engine:
    return Engine(key_width=16, val_width=16, **kw)


def test_commit_in_the_memtable_flushes_nothing_and_sorts_nothing():
    eng = _engine()
    for i in range(40):
        eng.put(b"k%03d" % i, b"old", ts=10)
    eng.flush()
    runs = list(eng.runs)
    flushes, sorts = eng.stats.flushes, mvcc.KERNEL_CALLS["resolve.sort_block"]
    commits = metric.ENGINE_COMMITS.value
    for txn in (7, 8):
        eng.put(b"k%03d" % txn, b"new%d" % txn, ts=20, txn=txn)
    eng.put(b"k007", b"newer7", ts=20, txn=7)  # rewritten inside the txn
    with pytest.raises(WriteIntentError):
        eng.get(b"k007", ts=30)
    eng.resolve_intents(7, 25, commit=True)
    eng.resolve_intents(8, 0, commit=False)
    # the run set is the same objects: nothing flushed, nothing re-sorted
    assert [id(r) for r in eng.runs] == [id(r) for r in runs]
    assert eng.stats.flushes == flushes
    assert mvcc.KERNEL_CALLS["resolve.sort_block"] == sorts
    assert metric.ENGINE_COMMITS.value == commits + 1
    assert eng.get(b"k007", ts=30) == b"newer7"
    assert eng.get(b"k007", ts=24) == b"old"  # committed AT 25
    assert eng.get(b"k008", ts=30) == b"old"  # the abort dropped the row
    assert len(eng.mem) == 2 and eng.mem.txn == [0, 0]
    assert eng.mem.ts == [25, 25] and not eng.mem.intents
    assert eng.newest_committed_ts(b"k007") == 25
    assert eng.other_intent(b"k007", 99) is None
    assert eng.other_intent(b"k008", 99) is None


def test_a_flushed_intent_resolves_in_its_own_run_only():
    eng = _engine(memtable_size=8, l0_trigger=64)
    for i in range(8):  # one full memtable: a committed run
        eng.put(b"a%03d" % i, b"base", ts=5)
    eng.put(b"b000", b"mine", ts=20, txn=3)
    for i in range(7):  # the flush carries txn 3's intent into a run
        eng.put(b"c%03d" % i, b"fill", ts=21)
    assert len(eng.runs) == 2 and len(eng.mem) == 0
    eng.put(b"b001", b"mine too", ts=20, txn=3)  # and one in the memtable
    held = [r for r in eng.runs if id(r) in eng._run_intents]
    assert len(held) == 1 and eng._run_intents[id(held[0])][1] == {3}
    other = next(r for r in eng.runs if r is not held[0])
    sorts = mvcc.KERNEL_CALLS["resolve.sort_block"]
    launched = metric.ENGINE_RESOLVE_RUN_SORTS.value
    eng.resolve_intents(3, 30, commit=True)
    assert mvcc.KERNEL_CALLS["resolve.sort_block"] == sorts + 1
    assert metric.ENGINE_RESOLVE_RUN_SORTS.value == launched + 1
    assert any(r is other for r in eng.runs)  # untouched, the same object
    assert not any(r is held[0] for r in eng.runs)
    assert not eng._run_intents
    assert eng.get(b"b000", ts=40) == b"mine"
    assert eng.get(b"b001", ts=40) == b"mine too"
    assert eng.get(b"b000", ts=29) is None
    # a second resolution of the same transaction touches nothing
    eng.resolve_intents(3, 30, commit=True)
    assert mvcc.KERNEL_CALLS["resolve.sort_block"] == sorts + 1


def test_a_compaction_carries_flushed_intents_to_its_output():
    eng = _engine(memtable_size=4, l0_trigger=2, compact_width=4)
    eng.put(b"x", b"held", ts=9, txn=11)
    for i in range(16):
        eng.put(b"k%02d" % i, b"v", ts=10)
    assert eng.stats.compactions >= 1
    assert {t for _r, ts in eng._run_intents.values() for t in ts} == {11}
    assert all(any(r is run for run in eng.runs)
               for r, _ in eng._run_intents.values())
    with pytest.raises(WriteIntentError):
        eng.get(b"x", ts=50)
    eng.resolve_intents(11, 12, commit=True)
    assert eng.get(b"x", ts=50) == b"held" and not eng._run_intents


def test_flushed_runs_have_one_capacity_and_compactions_a_ladder():
    eng = _engine(memtable_size=64, l0_trigger=3)
    caps = set()
    for i in range(64 * 9):
        eng.put(b"k%05d" % ((i * 37) % 600), b"v%d" % i, ts=10 + i)
        if len(eng.mem) == 0:
            caps.add(eng.runs[0].capacity)
    assert eng.stats.compactions >= 2
    for r in eng.runs:
        assert r.capacity & (r.capacity - 1) == 0  # a power of two
    assert 1024 in caps  # every flush: _pad(memtable_size)
    assert all(c & (c - 1) == 0 for c in caps)


def test_wal_replay_reads_back_every_acknowledged_upsert(tmp_path):
    """wal_fsync=True is the kv95 configuration's guarantee: the record is
    synced before the acknowledgement, and a new engine over the same log
    reads every acknowledged UPSERT back with no intent left."""
    wal = str(tmp_path / "wal")
    db = DB(Engine(key_width=24, val_width=128, wal_path=wal,
                   wal_fsync=True), ManualClock())
    sess = Session(db=db)
    sess.execute("CREATE TABLE kv (k INT PRIMARY KEY, v STRING)")
    model = {}
    for i in range(40):
        k = (i * 7919) % 23  # keys repeat: later UPSERTs overwrite
        v = ALPHABET[i % 64]
        sess.execute(f"UPSERT INTO kv (k, v) VALUES ({k}, '{v}')")
        model[k] = v
    sess.execute("BEGIN")
    sess.execute("UPSERT INTO kv (k, v) VALUES (999, 'x')")  # never commits
    sess.close()
    db.engine.close()
    db2 = DB(Engine(key_width=24, val_width=128, wal_path=wal,
                    wal_fsync=True), ManualClock(start=1 << 40))
    # the open transaction's intent is replayed as an intent, nothing else
    assert set(db2.engine._locks.values()) == {sess._txn.txn_id}
    db2.engine.resolve_intents(sess._txn.txn_id, 0, commit=False)
    s2 = Session(db=db2)
    for k, v in model.items():
        assert _rows(s2.execute(f"SELECT k, v FROM kv WHERE k IN ({k})")) \
            == [[k, v]]
    assert _rows(s2.execute("SELECT k, v FROM kv WHERE k = 999")) == []
    assert not db2.engine._locks
    s2.close()


# -- step 2: UPSERT -------------------------------------------------------


def test_upsert_parses_beside_insert():
    up = P.parse_statement("UPSERT INTO kv (k, v) VALUES (1, 'a'), (2, 'b')")
    ins = P.parse_statement("INSERT INTO kv (k, v) VALUES (1, 'a'), (2, 'b')")
    assert isinstance(up, P.Insert) and up.upsert and not ins.upsert
    assert (up.table, up.columns, up.rows) == (ins.table, ins.columns,
                                               ins.rows)
    assert P.parse_statement("upsert into kv values (1, 'a');").upsert
    with pytest.raises(SyntaxError):
        P.parse_statement("UPSERT kv (k, v) VALUES (1, 'a')")


def test_upsert_inserts_overwrites_and_binds(served):
    _node, sess = served
    assert sess.execute("UPSERT INTO kv (k, v) VALUES (5000, 'n')") == {
        "rows_affected": 1}
    assert _rows(sess.execute("SELECT k, v FROM kv WHERE k = 5000")) == [
        [5000, "n"]]
    sess.execute("UPSERT INTO kv (k, v) VALUES (5000, 'o'), (3, 'p')")
    assert _rows(sess.execute(
        "SELECT k, v FROM kv WHERE k IN (5000, 3)")) == [[5000, "o"],
                                                          [3, "p"]]
    from cockroach_tpu.sql.binder import BindError

    with pytest.raises(BindError):
        sess.execute("UPSERT INTO kv (k, nope) VALUES (1, 'a')")
    with pytest.raises(BindError):
        sess.execute("UPSERT INTO kv (k) VALUES (1)")
    with pytest.raises(BindError):
        sess.execute("UPSERT INTO kv (k, v) VALUES (NULL, 'a')")


def test_upsert_visibility_in_and_out_of_a_transaction(served):
    node, a = served
    b = _session(node)
    try:
        a.execute("BEGIN")
        a.execute("UPSERT INTO kv (k, v) VALUES (11, 'T')")
        # its own write is visible inside the transaction
        assert _rows(a.execute("SELECT k, v FROM kv WHERE k = 11")) == [
            [11, "T"]]
        a.execute("ROLLBACK")
        assert _rows(b.execute("SELECT k, v FROM kv WHERE k = 11")) == [
            [11, value_of(7, 11)]]
        a.execute("BEGIN")
        a.execute("UPSERT INTO kv (k, v) VALUES (11, 'U')")
        a.execute("COMMIT")
        assert _rows(b.execute("SELECT k, v FROM kv WHERE k = 11")) == [
            [11, "U"]]
        assert not node.db.engine.intent_keys(0) and not [
            k for k in node.db.engine._locks if k.startswith(b"\x02")]
    finally:
        b.close()


def test_a_foreign_intent_is_waited_out_never_read_through(served):
    """An autocommit point read under another transaction's intent retries
    on the server until the intent resolves; only when the retries are
    spent does the conflict reach the client (as 40001)."""
    node, a = served
    b = _session(node)
    try:
        a.execute("BEGIN")
        a.execute("UPSERT INTO kv (k, v) VALUES (21, 'W')")
        retries = metric.TXN_RETRIES.value
        timer = threading.Timer(0.05, lambda: a.execute("COMMIT"))
        timer.start()
        got = _rows(b.execute("SELECT k, v FROM kv WHERE k IN (21)"))
        timer.join()
        assert got == [[21, "W"]]  # waited, then the committed value
        assert metric.TXN_RETRIES.value > retries
        a.execute("BEGIN")
        a.execute("UPSERT INTO kv (k, v) VALUES (22, 'X')")
        with pytest.raises(TransactionRetryError):
            node.db.get_committed(
                next(k for k, t in node.db.engine._locks.items()
                     if t == a._txn.txn_id), max_retries=2)
        from cockroach_tpu.server.pgwire import _sqlstate_for

        assert _sqlstate_for(TransactionRetryError("x")) == "40001"
        # a blind UPSERT of the locked key is retried, then given up
        with pytest.raises(TransactionRetryError):
            node.db.txn(lambda t: node._sql_catalog.tables["kv"].insert(
                t, {"k": 22, "v": "Y"}), max_retries=2)
        a.execute("ROLLBACK")
        assert _rows(b.execute("SELECT k, v FROM kv WHERE k = 22")) == [
            [22, value_of(7, 22)]]
    finally:
        b.close()


# -- step 3: the primary key as a plan route ------------------------------


@pytest.mark.parametrize("where,keys,residual", [
    ("k IN (5)", 1, False),
    ("k = 5", 1, False),
    ("5 = k", 1, False),
    ("k IN (5, 6, 7)", 3, False),
    ("k = 5 AND v = 'x'", 1, True),
    ("v <> 'x' AND k IN (1, 2)", 2, True),
])
def test_explain_shows_the_point_lookup(served, where, keys, residual):
    _node, sess = served
    text = explain(sess.catalog, f"SELECT k, v FROM kv WHERE {where}")
    assert f"point-lookup kv@primary keys={keys}" in text
    assert "scan kv" not in text
    assert ("filter" in text) == residual


@pytest.mark.parametrize("where", ["k > 5", "k <> 5", "v = 'A'",
                                   "k = 5 OR v = 'A'", "k IN (5, 6) OR k > 9"])
def test_other_predicates_keep_the_scan(served, where):
    _node, sess = served
    text = explain(sess.catalog, f"SELECT k, v FROM kv WHERE {where}")
    assert "point-lookup" not in text and "scan kv" in text


def test_the_route_needs_no_setting(served):
    from cockroach_tpu.utils import settings

    _node, sess = served
    settings.set("sql.opt.index_scan.enabled", False)
    try:
        assert "point-lookup" in explain(
            sess.catalog, "SELECT k, v FROM kv WHERE k IN (5)")
    finally:
        settings.set("sql.opt.index_scan.enabled", True)


def test_point_reads_never_decode_the_table_and_bind_one_plan(served):
    _node, sess = served
    decodes = metric.KV_TABLE_DECODES.value
    reads = metric.KV_POINT_READS.value
    assert _rows(sess.execute("SELECT k, v FROM kv WHERE k IN (5)")) == [
        [5, value_of(7, 5)]]
    compiles, hits = dispatch.compiles(), metric.PLAN_CACHE_HITS.value
    for k in (6, 1999, 0, 123456789):
        want = [[k, value_of(7, k)]] if k < ROWS else []
        assert _rows(sess.execute(
            f"SELECT k, v FROM kv WHERE k IN ({k})")) == want
    assert dispatch.compiles() == compiles  # another key: the same plan
    assert metric.PLAN_CACHE_HITS.value == hits + 4
    assert metric.KV_TABLE_DECODES.value == decodes
    assert metric.KV_POINT_READS.value == reads + 5


@pytest.mark.parametrize("sql,want", [
    ("SELECT v FROM kv WHERE k = 9", [value_of(7, 9)]),
    ("SELECT k, v FROM kv WHERE k IN (9, 9, 8)", [9, 8]),
    ("SELECT k FROM kv WHERE k IN (1999, 2000, 2001)", [1999]),
    ("SELECT k FROM kv WHERE k = 9 AND v = '~'", []),
    ("SELECT count(*) AS n FROM kv WHERE k IN (1, 2, 3)", [3]),
])
def test_point_lookup_answers(served, sql, want):
    _node, sess = served
    decodes = metric.KV_TABLE_DECODES.value
    res = sess.execute(sql)
    got = list(next(iter(res.values())))
    assert [g if isinstance(g, str) else int(g) for g in got] == want
    assert metric.KV_TABLE_DECODES.value == decodes


def test_a_point_read_in_a_transaction_notes_its_key_not_the_table(served):
    node, a = served
    b = _session(node)
    try:
        a.execute("BEGIN")
        assert _rows(a.execute("SELECT k, v FROM kv WHERE k = 31")) == [
            [31, value_of(7, 31)]]
        assert [p for _s, _e, p in a._txn._read_spans] == [True]
        # a write elsewhere in the table does not invalidate the read
        b.execute("UPSERT INTO kv (k, v) VALUES (32, 'q')")
        a.execute("UPSERT INTO kv (k, v) VALUES (33, 'r')")
        a.execute("COMMIT")
        # one to the key it read does
        a.execute("BEGIN")
        a.execute("SELECT k, v FROM kv WHERE k = 31")
        b.execute("UPSERT INTO kv (k, v) VALUES (31, 's')")
        a.execute("UPSERT INTO kv (k, v) VALUES (5034, 't')")
        with pytest.raises(TransactionRetryError):
            a.execute("COMMIT")
        assert _rows(b.execute("SELECT k, v FROM kv WHERE k = 5034")) == []
    finally:
        b.close()


# -- the reference against the served path --------------------------------


def _mix(seed: int, n: int):
    """kv95's statements: 95 reads of rows that exist to 5 blind UPSERTs of
    new keys spread over the int64 space."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        if rng.random() < 0.95:
            yield "read", int(rng.integers(0, ROWS)), None
        else:
            yield ("write", int(rng.integers(ROWS, 1 << 62)),
                   ALPHABET[int(rng.integers(64))])


def test_reference_dict_single_threaded_mix(served):
    _node, sess = served
    model = KVModel(7, ROWS)
    written = []
    for kind, k, v in _mix(41, 200):
        if kind == "read":
            got = _rows(sess.execute(f"SELECT k, v FROM kv WHERE k IN ({k})"))
            assert got == model.read(k)
        else:
            sess.execute(f"UPSERT INTO kv (k, v) VALUES ({k}, '{v}')")
            model.upsert(k, v)
            written.append(k)
    assert written
    for k in written + list(range(0, ROWS, 97)):
        assert _rows(sess.execute(
            f"SELECT k, v FROM kv WHERE k IN ({k})")) == model.read(k)


def test_64_sessions_leave_no_intent_and_lose_no_write(served):
    """ROADMAP D11 (a): 64 threads, each a Session of its own over one
    Node, UPSERTs and reads of overlapping keys. Every statement returns;
    when all have, no intent is left and every acknowledged write reads
    back (all writers of a key write the same value, as the cell's clients
    do, so the answer is determined)."""
    node, sess = served
    threads, per = 64, 12
    model = KVModel(7, ROWS)
    # 24 write keys shared by all threads; thread c starts at offset c
    wkeys = [int(k) for k in np.random.default_rng(5).integers(
        ROWS, 1 << 62, size=24)]
    wvals = {k: ALPHABET[i % 64] for i, k in enumerate(wkeys)}
    acked, errors, wrong = [], [], []
    lock = threading.Lock()
    start = threading.Barrier(threads)

    def client(c: int):
        s = _session(node)
        rng = np.random.default_rng([9, c])
        try:
            start.wait()
            for i in range(per):
                if rng.random() < 0.5:
                    k = wkeys[(c + i) % len(wkeys)]
                    s.execute(f"UPSERT INTO kv (k, v) VALUES "
                              f"({k}, '{wvals[k]}')")
                    with lock:
                        acked.append(k)
                elif rng.random() < 0.5:
                    k = wkeys[(c + 2 * i) % len(wkeys)]
                    got = _rows(s.execute(
                        f"SELECT k, v FROM kv WHERE k IN ({k})"))
                    if got not in ([], [[k, wvals[k]]]):
                        wrong.append((k, got))
                else:
                    k = int(rng.integers(0, ROWS))
                    got = _rows(s.execute(
                        f"SELECT k, v FROM kv WHERE k IN ({k})"))
                    if got != model.read(k):
                        wrong.append((k, got))
        except BaseException as e:  # a statement that fails fails the test
            import traceback

            errors.append(f"client {c}: {type(e).__name__}: {e}\n"
                          + traceback.format_exc())
        finally:
            s.close()

    ts = [threading.Thread(target=client, args=(c,)) for c in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert not [t for t in ts if t.is_alive()], "a statement never returned"
    assert not errors, errors[:3]
    assert not wrong, wrong[:3]
    assert len(acked) > threads
    # no intent is left behind in the table's span
    eng = node.db.engine
    table = node._sql_catalog.tables["kv"]
    from cockroach_tpu.storage import rowcodec

    lo, hi = rowcodec.table_span(table.table_id)
    assert not [k for k in eng._locks if lo <= k < hi]
    assert not eng.mem.intents or all(
        eng.mem.keys[i] < lo or eng.mem.keys[i] >= hi
        for rows in eng.mem.intents.values() for i in rows)
    # every acknowledged write is read back, at once
    for k in sorted(set(acked)):
        assert _rows(sess.execute(
            f"SELECT k, v FROM kv WHERE k IN ({k})")) == [[k, wvals[k]]]
    assert metric.KV_TABLE_DECODES.value >= 0


def test_the_wait_for_the_engine_shows_in_a_statements_trace():
    """A contended acquire of the store's mutex inside a traced operation
    opens `storage/engine.lock_wait`; an uncontended one, and the owner's
    re-entry, open nothing."""
    eng = _engine()
    before = tracing.totals().get("storage/engine.lock_wait",
                                  {"count": 0})["count"]
    with tracing.span("sql.execute"):
        eng.put(b"a", b"1", ts=1)
        with eng.mu:
            eng.put(b"b", b"2", ts=2)  # re-entry
    assert tracing.totals().get("storage/engine.lock_wait",
                                {"count": 0})["count"] == before
    held, done = threading.Event(), threading.Event()

    def holder():
        with eng.mu:
            held.set()
            done.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    held.wait(5)
    threading.Timer(0.05, done.set).start()
    with tracing.span("sql.execute") as sp:
        eng.put(b"c", b"3", ts=3)
    t.join()
    waits = [s for s in sp.walk() if s.name == "storage/engine.lock_wait"]
    assert len(waits) == 1 and waits[0].duration >= 0.03
    rec = tracing.totals()["storage/engine.lock_wait"]
    assert rec["count"] == before + 1 and rec["total_s"] >= 0.03


@pytest.mark.parametrize("kw,n,cap", [(16, 300, 1024), (64, 1500, 2048),
                                      (64, 700, 4096)])
def test_the_host_planned_order_is_the_device_sorts(kw, n, cap):
    """The write path plans a run's order with np.lexsort and moves the
    rows with one gather (an 11-operand device sort at 64-byte keys is
    minutes of compile a shape): the same block, row for row, as
    `sort_block`, `merge_blocks` and a shrink give."""
    import jax.numpy as jnp

    rng = np.random.default_rng(kw + n)
    keys = rng.integers(1, 4, size=(n, kw), dtype=np.uint8)  # many ties
    keys[:, : kw - 2] = 1
    blk = mvcc.block_from_host(
        keys, rng.integers(1, 5, n), rng.integers(0, 3, n),
        rng.random(n) < 0.2, rng.integers(0, 255, (n, 8), dtype=np.uint8),
        np.full(n, 8), cap=cap, seq=rng.permutation(n))
    blk = mvcc.KVBlock(**{**{f: getattr(blk, f) for f in (
        "key", "ts", "seq", "txn", "tomb", "value", "vlen")},
        "mask": blk.mask & jnp.asarray(rng.random(cap) < 0.8)})
    want = mvcc.sort_block(blk)
    got = mvcc.sort_block_host(blk)
    live = int(np.asarray(want.mask).sum())
    for f in ("key", "ts", "seq", "txn", "tomb", "value", "vlen", "mask"):
        assert np.array_equal(np.asarray(getattr(got, f))[:live],
                              np.asarray(getattr(want, f))[:live]), f
    assert not np.asarray(got.mask)[live:].any()
    assert not np.asarray(got.key)[live:].any()  # dead rows hold zeros
    # a merge of two halves at a larger capacity, and a shrink to the rows
    half = cap // 2
    a, b = (mvcc.sort_block(mvcc.KVBlock(**{
        f: getattr(blk, f)[s] for f in ("key", "ts", "seq", "txn", "tomb",
                                        "value", "vlen", "mask")}))
        for s in (slice(0, half), slice(half, cap)))
    m_want = mvcc.merge_blocks((a, b), cap=2 * cap)
    m_got = mvcc.merge_blocks_host((a, b), cap=2 * cap)
    assert m_got.capacity == 2 * cap
    for f in ("key", "ts", "seq", "value", "mask"):
        assert np.array_equal(np.asarray(getattr(m_got, f))[:live],
                              np.asarray(getattr(m_want, f))[:live]), f
    small = mvcc.sort_block_host(blk, lambda rows: max(128, rows))
    assert small.capacity == max(128, live)
    assert np.array_equal(np.asarray(small.key)[:live],
                          np.asarray(want.key)[:live])
    with pytest.raises(ValueError):
        mvcc.sort_block_host(blk, live - 1)


# -- PR 43: a point read holds the store's mutex for its snapshot only -----


def _stop_after_snapshot(eng, only: set):
    """Stops a get of the threads in `only` right after its snapshot:
    `_bounded_view` is the first thing a get calls with the mutex released.
    -> (reached, release) events."""
    reached, release = threading.Event(), threading.Event()
    real = eng._bounded_view

    def paused(*a, **kw):
        if threading.current_thread().name in only:
            reached.set()
            assert release.wait(30)
        return real(*a, **kw)

    eng._bounded_view = paused
    return reached, release


def _in_thread(fn, name=None):
    """Run fn on a thread -> (thread, out) with out["value"] or
    out["error"] once it ends."""
    out = {}

    def run():
        try:
            out["value"] = fn()
        except BaseException as e:
            out["error"] = e

    t = threading.Thread(target=run, name=name)
    t.start()
    return t, out


def _mutex_is_free(eng) -> bool:
    """Whether ANOTHER thread could take the store's mutex right now."""
    got = []

    def probe():
        ok = eng.mu.acquire(False)
        got.append(ok)
        if ok:
            eng.mu.release()

    t = threading.Thread(target=probe)
    t.start()
    t.join(5)
    return got == [True]


def test_a_get_waiting_for_the_device_does_not_hold_the_mutex(monkeypatch):
    """(a) While one get waits in its `device_get`, a put and a second get
    from other threads run to their end: the read holds `storage.engine`
    across no launch and no readback."""
    import jax

    eng = _engine()
    for i in range(40):
        eng.put(b"k%03d" % i, b"v%03d" % i, ts=10)
    eng.flush()
    waiting, release = threading.Event(), threading.Event()
    real = jax.device_get

    def held(x):
        if threading.current_thread().name == "held-reader":
            waiting.set()
            assert release.wait(30)
        return real(x)

    monkeypatch.setattr(jax, "device_get", held)
    reader, out = _in_thread(lambda: eng.get(b"k005", ts=20), "held-reader")
    try:
        assert waiting.wait(30)
        assert _mutex_is_free(eng)
        w, wout = _in_thread(lambda: eng.put(b"k005", b"new", ts=30))
        r, rout = _in_thread(lambda: eng.get(b"k006", ts=20))
        w.join(30)
        r.join(30)
        assert not w.is_alive() and not r.is_alive()
        assert "error" not in wout and rout == {"value": b"v006"}
        assert reader.is_alive()  # still inside its device_get
    finally:
        release.set()
        reader.join(30)
    assert not reader.is_alive() and out == {"value": b"v005"}
    assert eng.get(b"k005", ts=40) == b"new"


@pytest.mark.parametrize("old_in", ["run", "memtable"])
def test_a_read_keeps_its_instant_across_a_flush_and_a_compaction(old_in):
    """(b) A reader stopped right after its snapshot, then a put of its key,
    a flush and a compaction: it answers as of its instant (the old value,
    never None: the runs it holds outlive the run set that dropped them),
    and a reader that snapshots afterwards answers the new one."""
    eng = _engine(l0_trigger=64)
    for i in range(40):
        eng.put(b"k%03d" % i, b"old%03d" % i, ts=10)
    eng.flush_mem_only()
    if old_in == "memtable":
        eng.put(b"k005", b"older", ts=11)  # the snapshot takes the memtable
    old = b"older" if old_in == "memtable" else b"old005"
    reached, release = _stop_after_snapshot(eng, {"early"})
    early, out = _in_thread(lambda: eng.get(b"k005", ts=100), "early")
    try:
        assert reached.wait(30)
        gen = eng._runs_gen
        eng.put(b"k005", b"new", ts=20)
        eng.flush_mem_only()
        eng.compact()
        assert eng._runs_gen > gen and len(eng.runs) == 1
        assert eng.get(b"k005", ts=100) == b"new"  # snapshots after
        assert eng.get(b"k005", ts=15) == old
    finally:
        release.set()
        early.join(30)
    assert not early.is_alive() and out == {"value": old}


def test_an_intent_in_the_snapshot_conflicts_even_if_it_resolves_first():
    """(c) The reader's instant holds a foreign intent at or below its
    timestamp: it raises WriteIntentError though the intent commits before
    the reader's launches, and never reads through it; the next read, at
    a fresh instant, sees the committed value."""
    eng = _engine()
    eng.put(b"a", b"base", ts=5)
    eng.flush_mem_only()
    eng.put(b"a", b"mine", ts=10, txn=7)
    reached, release = _stop_after_snapshot(eng, {"early"})
    early, out = _in_thread(lambda: eng.get(b"a", ts=50), "early")
    try:
        assert reached.wait(30)
        eng.resolve_intents(7, 20, commit=True)
        assert eng.get(b"a", ts=50) == b"mine"
    finally:
        release.set()
        early.join(30)
    assert not early.is_alive()
    assert isinstance(out.get("error"), WriteIntentError)
    assert out["error"].txns == [7]


def test_a_get_inside_the_mutex_leaves_its_caller_holding_it():
    """(d) The lock is reentrant: a caller that holds `storage.engine`
    around a get (a transaction's section) still holds it when the get
    returns."""
    eng = _engine()
    eng.put(b"a", b"1", ts=1)
    with eng.mu:
        assert eng.get(b"a", ts=5) == b"1"
        assert not _mutex_is_free(eng)
        assert eng.span_versions_estimate(b"a", b"b") == 1
        assert not _mutex_is_free(eng)
    assert _mutex_is_free(eng)


def test_one_snapshot_a_run_set_generation():
    """A snapshot is rebuilt only when the run set changed: one build a
    generation that a read saw, none for writes that stay in the memtable
    or for an in-memtable commit."""
    eng = _engine(l0_trigger=64)
    builds, reads = (metric.ENGINE_SNAPSHOT_BUILDS.value,
                     metric.ENGINE_SNAPSHOT_READS.value)
    seen = set()

    def read(k, want):
        assert eng.get(k, ts=1000) == want
        seen.add(eng._runs_gen)

    read(b"a", None)  # the empty store's snapshot
    eng.put(b"a", b"1", ts=1)
    read(b"a", b"1")
    eng.put(b"a", b"2", ts=2, txn=9)
    eng.resolve_intents(9, 3, commit=True)  # in the memtable: no new run
    read(b"a", b"2")
    assert len(seen) == 1
    eng.flush_mem_only()
    read(b"a", b"2")
    eng.put(b"b", b"3", ts=4)
    eng.compact()  # flushes, then merges: two generations, one read
    read(b"b", b"3")
    keys = np.zeros((1, 16), np.uint8)
    keys[0, 0] = ord("c")
    eng.ingest(keys, np.full((1, 1), ord("4"), np.uint8), ts=5)
    read(b"c", b"4")
    read(b"c", b"4")
    assert metric.ENGINE_SNAPSHOT_BUILDS.value - builds == len(seen) == 4
    assert metric.ENGINE_SNAPSHOT_READS.value - reads == 7


def test_16_readers_against_flushes_commits_and_compactions():
    """(e) 16 threads x 200 gets against 2 writers (puts, transactional
    writes and their commits, flushes, compactions): every value read is
    one its key held at some instant between the read's start and its end,
    a reader never sees a key go back, every get is counted once,
    snapshots are rebuilt no more often than the run set changed, and the
    lock-free estimate never pairs an old run set with a new memtable (it
    never counts fewer versions than there are keys)."""
    import sys

    eng = _engine(memtable_size=64, l0_trigger=3)
    nkeys, readers, per, writers = 32, 16, 200, 2
    keys = [b"key%03d" % i for i in range(nkeys)]
    clock = iter(range(10, 1 << 40))
    tick = threading.Lock()

    def now() -> int:
        with tick:
            return next(clock)

    for k in keys:
        eng.put(k, b"%08d" % 0, ts=now())
    eng.flush()
    # per key: the newest version whose write has returned, and the newest
    # whose write has begun (each key has one writer)
    done = {k: 0 for k in keys}
    begun = {k: 0 for k in keys}
    stop = threading.Event()
    errors, wrong = [], []
    builds, reads, gen = (metric.ENGINE_SNAPSHOT_BUILDS.value,
                          metric.ENGINE_SNAPSHOT_READS.value, eng._runs_gen)
    gets = [0] * readers

    def writer(w: int):
        rng = np.random.default_rng([43, w])
        mine = keys[w::writers]
        n = 0
        try:
            while not stop.is_set():
                n += 1
                k = mine[int(rng.integers(len(mine)))]
                v = begun[k] = begun[k] + 1
                if n % 3:
                    eng.put(k, b"%08d" % v, ts=now())
                else:
                    txn = 1000 * (w + 1) + n
                    with eng.mu:
                        eng.put(k, b"%08d" % v, ts=now(), txn=txn)
                    eng.resolve_intents(txn, now(), commit=True)
                done[k] = v
                if n % 37 == 0:
                    eng.flush()
                if n % 151 == 0:
                    eng.compact()
                time.sleep(0.002)  # the readers are the test: leave them
                # the interpreter
        except BaseException as e:
            errors.append(f"writer {w}: {type(e).__name__}: {e}")

    def reader(r: int):
        rng = np.random.default_rng([44, r])
        last = {}
        try:
            for _ in range(per):
                k = keys[int(rng.integers(nkeys))]
                lo = done[k]
                gets[r] += 1
                try:
                    got = eng.get(k, ts=1 << 50)
                except WriteIntentError:
                    continue  # its instant held the writer's intent
                hi = begun[k]
                if gets[r] % 16 == 0 and eng.span_versions_estimate(
                        b"key", b"kez") < nkeys:
                    wrong.append(("estimate under the keys", k))
                v = None if got is None else int(got)
                if v is None or not max(lo, last.get(k, 0)) <= v <= hi:
                    wrong.append((k, lo, v, hi, last.get(k)))
                else:
                    last[k] = v
        except BaseException as e:
            errors.append(f"reader {r}: {type(e).__name__}: {e}")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-3)
    try:
        ws = [threading.Thread(target=writer, args=(w,))
              for w in range(writers)]
        rs = [threading.Thread(target=reader, args=(r,))
              for r in range(readers)]
        for t in ws + rs:
            t.start()
        for t in rs:
            t.join(timeout=300)
        stop.set()
        for t in ws:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in ws + rs if t.is_alive()], "a thread never ended"
    assert not errors, errors[:3]
    assert not wrong, wrong[:5]
    assert sum(gets) == readers * per
    assert metric.ENGINE_SNAPSHOT_READS.value - reads == sum(gets)
    changes = eng._runs_gen - gen
    assert changes >= 3, "the writers never changed the run set"
    assert 1 <= metric.ENGINE_SNAPSHOT_BUILDS.value - builds <= changes + 1
    for k in keys:  # quiescent: every key reads its last write
        assert int(eng.get(k, ts=1 << 50)) == done[k] == begun[k]


def test_a_point_read_of_runs_is_one_launch_and_no_eager_put(monkeypatch):
    """A point read's window goes to `mvcc_scan_filter` as it lies in the
    cache (the filter applies the bounds: no `_range_mask` launch), and
    the bounds and timestamps go in as host values (no `jnp.asarray` /
    `jnp.int64` dispatch a read); a key in the memtable seeks the
    memtable's block too (its seek keys are the block's sorted keys): no
    mask, no count sync, no compaction (PR 45)."""
    import jax.numpy as jnp

    from cockroach_tpu.storage import lsm

    eng = _engine(l0_trigger=64)
    for r in range(3):  # three runs, three versions a key
        for i in range(40):
            eng.put(b"k%03d" % i, b"v%d-%03d" % (r, i), ts=10 + r)
        eng.flush_mem_only()
    calls = {"mask": 0, "asarray": 0, "int64": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(lsm, "_range_mask",
                        counted("mask", lsm._range_mask))
    monkeypatch.setattr(jnp, "asarray", counted("asarray", jnp.asarray))
    monkeypatch.setattr(jnp, "int64", counted("int64", jnp.int64))
    assert eng.get(b"k005", ts=100) == b"v2-005"
    assert eng.get(b"k005", ts=11) == b"v1-005"
    assert eng.get(b"k005", ts=9) is None and eng.get(b"zzz", ts=100) is None
    assert calls["mask"] == 0 and calls["int64"] == 0
    eng.compact()  # one run, the cell's shape: its window is the view
    calls.update(mask=0, asarray=0, int64=0)
    assert eng.get(b"k005", ts=100) == b"v2-005"
    assert eng.get(b"k005", ts=11) == b"v1-005"
    assert calls == {"mask": 0, "asarray": 0, "int64": 0}
    eng.put(b"k005", b"mem", ts=20)
    assert eng.get(b"k005", ts=100) == b"mem"
    assert calls["mask"] == 0


def test_the_estimate_off_the_mutex_counts_what_the_locked_one_did():
    """(f) On a quiescent store `span_versions_estimate` (the published
    snapshot, no mutex) counts every version in the span: the same number
    with and without the caller holding the mutex, and the merged view's
    own count; another thread's hold of the mutex does not stop it."""
    eng = _engine(l0_trigger=64)
    for i in range(60):
        eng.put(b"k%03d" % i, b"a", ts=10)
    eng.flush_mem_only()
    for i in range(0, 60, 2):
        eng.put(b"k%03d" % i, b"b", ts=20)
    eng.flush_mem_only()
    eng.delete(b"k007", ts=30)
    eng.put(b"k008", b"c", ts=30, txn=5)
    for lo, hi in ((b"k000", b"k999"), (b"k010", b"k020"), (b"k007", b"k009"),
                   (b"x", b"y")):
        free = eng.span_versions_estimate(lo, hi)
        with eng.mu:
            held = eng.span_versions_estimate(lo, hi)
        assert free == held == eng.span_stats(lo, hi)["versions"]
    assert eng.span_versions_estimate(b"k000", b"k999") == 92
    # the published snapshot is current: the estimate stands in no line
    held, done = threading.Event(), threading.Event()

    def holder():
        with eng.mu:
            held.set()
            done.wait(30)

    t = threading.Thread(target=holder)
    t.start()
    try:
        assert held.wait(30)
        est, out = _in_thread(
            lambda: eng.span_versions_estimate(b"k000", b"k999"))
        est.join(10)
        assert not est.is_alive() and out == {"value": 92}
    finally:
        done.set()
        t.join(30)
    # a new run set: the next estimate builds its snapshot under the mutex
    eng.compact()
    builds = metric.ENGINE_SNAPSHOT_BUILDS.value
    assert eng.span_versions_estimate(b"k000", b"k999") == 92
    assert metric.ENGINE_SNAPSHOT_BUILDS.value == builds + 1


def test_a_traced_statement_carries_the_read_and_its_hold(served):
    """(g) `storage/engine.get` spans the whole read inside a traced
    statement, with the milliseconds it held the mutex in `held_ms`."""
    _, sess = served
    sess.execute("SELECT k, v FROM kv WHERE k IN (5)")  # bind and compile
    before = tracing.totals().get("storage/engine.get",
                                  {"count": 0, "tags": {}})
    with tracing.span("test.statement") as sp:
        assert _rows(sess.execute(
            "SELECT k, v FROM kv WHERE k IN (6)")) == [[6, value_of(7, 6)]]
    gets = [s for s in sp.walk() if s.name == "storage/engine.get"]
    assert len(gets) == 1
    held = gets[0].tags["held_ms"]
    assert 0 <= held <= 1e3 * gets[0].duration
    rec = tracing.totals()["storage/engine.get"]
    assert rec["count"] == before["count"] + 1
    assert rec["tags"]["held_ms"] == pytest.approx(
        before["tags"].get("held_ms", 0) + held)
    # outside a traced operation the read opens no span
    n = tracing.totals()["storage/engine.get"]["count"]
    sess.db.engine.get(b"nokey", ts=1)
    assert tracing.totals()["storage/engine.get"]["count"] == n
