"""The primary-key range route (PR 45): `WHERE pk BETWEEN a AND b` over a
KV-backed table is a plan node of its own (`PKRange`, EXPLAIN's `pk-range`)
that seeks the store for the span and decodes its window on the device:

- the planner: two-sided bounds become the node (strict ones adjusted),
  residual predicates stay Filters, a pinned key stays a PointLookup, a
  one-sided bound stays a scan, secondary indexes change nothing;
- the plan cache: another range is two other arguments, nothing compiles,
  and concurrent sessions each get a tree of the entry's pool;
- the answers, against a plain dict, over an LSM with several runs, a
  non-empty memtable, tombstones, overwritten versions and a neighbouring
  table's keys on both sides of the span; a range wider than a page;
- the transaction: a foreign intent in the span is waited out, never read
  through; inside an explicit transaction the range sees its own writes
  and a conflicting committed write fails the commit's refresh; under a
  writer every answer is one snapshot;
- the engine: the mutex for the snapshot only, both bounds sought on the
  host, no `_range_mask` over a source, no decode of the table.
"""

import threading

import numpy as np
import pytest

from cockroach_tpu.flow import dispatch
from cockroach_tpu.flow import operators as ops
from cockroach_tpu.kv import table as kvtable
from cockroach_tpu.kv.txn import TransactionRetryError
from cockroach_tpu.server.node import Node
from cockroach_tpu.sql import Session, explain
from cockroach_tpu.storage import lsm
from cockroach_tpu.storage.lsm import Engine, WriteIntentError
from cockroach_tpu.utils import metric, tracing

RANGE = "SELECT id, k, c FROM t WHERE id BETWEEN {} AND {}"


def _rows(res) -> list:
    return [(int(i), int(k), str(c))
            for i, k, c in zip(res["id"], res["k"], res["c"])]


def _serve():
    """A Node with its default loops and three tables whose key spans lie
    side by side (`below`, `t`, `above`, created in that order), `t` with a
    secondary index, a CHAR(n) and a STRING column."""
    node = Node().start(pg_port=0)
    sess = Session(catalog=node._sql_catalog, db=node.db, bootstrap=False)
    for name in ("below", "t", "above"):
        sess.execute(f"CREATE TABLE {name} (id INT PRIMARY KEY, k INT, "
                     f"c CHAR(12), s STRING)")
    sess.execute("CREATE INDEX t_k ON t (k)")
    try:
        yield node, sess
    finally:
        sess.close()
        node.stop()


served = pytest.fixture(_serve)


def _session(node) -> Session:
    return Session(catalog=node._sql_catalog, db=node.db, bootstrap=False)


def _fill(sess, model: dict, table: str, ids, tag: str) -> None:
    rows = []
    for i in ids:
        row = (i, (i * 7) % 50, f"{tag}{i % 97:03d}")
        rows.append(f"({row[0]}, {row[1]}, '{row[2]}', 's{i % 3}')")
        if table == "t":
            model[i] = row
    sess.execute(f"UPSERT INTO {table} (id, k, c, s) VALUES "
                 + ", ".join(rows))


def _layer(served):
    """`t` over several runs, a non-empty memtable, tombstones and
    overwritten versions, with the neighbouring tables' keys around it."""
    node, sess = served
    eng = node.db.engine
    model: dict = {}
    for name in ("below", "above"):
        _fill(sess, model, name, range(0, 400, 3), "n")
    _fill(sess, model, "t", range(0, 300), "a")
    eng.flush_mem_only()
    _fill(sess, model, "t", range(100, 400, 2), "b")  # overwrites + new
    for i in range(150, 160):
        sess.execute(f"DELETE FROM t WHERE id = {i}")
        model.pop(i, None)
    eng.flush_mem_only()
    _fill(sess, model, "t", range(140, 170, 3), "c")  # in the memtable
    sess.execute("DELETE FROM t WHERE id = 201")
    model.pop(201, None)
    assert len(eng.runs) >= 2 and len(eng.mem)
    return node, sess, model


@pytest.fixture
def layered(served):
    return _layer(served)


@pytest.fixture(scope="module")
def layered_ro():
    """`layered`, made once: for the tests that only read it."""
    gen = _serve()
    try:
        yield _layer(next(gen))
    finally:
        next(gen, None)


# -- the planner ----------------------------------------------------------


@pytest.mark.parametrize("where,lo,hi,residual", [
    ("id BETWEEN 100 AND 199", 100, 199, False),
    ("id >= 100 AND id <= 199", 100, 199, False),
    ("id > 100 AND id < 199", 101, 198, False),
    ("100 <= id AND 199 >= id", 100, 199, False),
    ("id BETWEEN 100 AND 199 AND k > 3", 100, 199, True),
    ("s <> 'x' AND id >= 5 AND id < 9", 5, 8, True),
    ("id BETWEEN 9 AND 3", 9, 3, False),
])
def test_explain_names_the_range_node(served, where, lo, hi, residual):
    _node, sess = served
    text = explain(sess.catalog, f"SELECT c FROM t WHERE {where}")
    assert f"pk-range t@primary [{lo}, {hi}]" in text
    assert "scan t" not in text and "index-scan" not in text
    assert ("filter" in text) == residual


@pytest.mark.parametrize("where,route", [
    ("id = 5", "point-lookup t@primary keys=1"),
    ("id IN (5, 6)", "point-lookup t@primary keys=2"),
    ("id = 5 AND id BETWEEN 1 AND 9", "point-lookup t@primary keys=1"),
    ("id >= 5", "scan t"),
    ("id < 5 AND k = 1", "scan t"),
    ("k BETWEEN 1 AND 2 AND s <> 'x'", "index-scan t@t_k"),
    ("id BETWEEN 1 AND 5 OR id = 9", "scan t"),
])
def test_other_predicates_keep_their_route(served, where, route):
    _node, sess = served
    text = explain(sess.catalog, f"SELECT c FROM t WHERE {where}")
    assert route in text and "pk-range" not in text


def test_explain_analyze_shows_the_operator_with_its_rows(layered_ro):
    _node, sess, model = layered_ro
    text = explain(sess.catalog,
                   "EXPLAIN ANALYZE " + RANGE.format(100, 120))
    line = next(ln for ln in text.splitlines() if "pk-range" in ln)
    want = sum(1 for i in model if 100 <= i <= 120)
    assert f"rows={want}" in line or f"rows: {want}" in line, text


# -- the plan cache -------------------------------------------------------


def test_another_range_binds_the_same_plan_and_compiles_nothing(layered_ro):
    _node, sess, model = layered_ro
    decodes = metric.KV_TABLE_DECODES.value
    for text in (RANGE, RANGE + " ORDER BY c",
                 "SELECT SUM(k) FROM t WHERE id BETWEEN {} AND {}",
                 "SELECT DISTINCT c FROM t WHERE id BETWEEN {} AND {} "
                 "ORDER BY c"):
        sess.execute(text.format(210, 260))
        sess.execute(text.format(220, 270))  # capacities learned, if any
        c0 = dispatch.compiles()
        sess.execute(text.format(225, 275))  # the same sources hold it
        assert dispatch.compiles() == c0, text
    assert metric.KV_TABLE_DECODES.value == decodes


def test_eight_sessions_at_once_get_trees_from_the_pool(layered_ro,
                                                        monkeypatch):
    node, sess, model = layered_ro
    sess.execute(RANGE.format(1, 2))  # the entry and its first tree
    entered, release = threading.Event(), threading.Event()
    first = threading.Lock()
    real = ops.PKRangeOp.init

    def init(op):
        if first.acquire(blocking=False):  # the first caller stands still
            entered.set()
            assert release.wait(60)
        real(op)

    monkeypatch.setattr(ops.PKRangeOp, "init", init)
    boxes = [{} for _ in range(9)]
    peers = [_session(node) for _ in boxes]

    def send(s, lo, box):
        try:
            box["rows"] = _rows(s.execute(RANGE.format(lo, lo + 20)))
        except Exception as e:  # noqa: BLE001 - asserted below
            box["err"] = e

    held = threading.Thread(target=send, args=(peers[0], 0, boxes[0]))
    held.start()
    assert entered.wait(60)
    runs = metric.PLAN_CACHE_POOL_RUNS.value
    ts = [threading.Thread(target=send, args=(p, 30 * n, box))
          for n, (p, box) in enumerate(zip(peers[1:], boxes[1:]), 1)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        # all eight served while the first tree is still out
        assert not any(t.is_alive() for t in ts) and held.is_alive()
        assert metric.PLAN_CACHE_POOL_RUNS.value >= runs + len(ts)
    finally:
        release.set()
        held.join(60)
    for n, box in enumerate(boxes):
        lo = 30 * n
        assert "err" not in box, box
        assert box["rows"] == sorted(
            model[i] for i in model if lo <= i <= lo + 20)
    for p in peers:
        p.close()


# -- the answers ----------------------------------------------------------


@pytest.mark.parametrize("lo,hi", [
    (0, 99), (100, 199), (140, 170), (150, 159), (195, 260), (290, 399),
    (398, 5000), (-50, 3), (151, 151), (700, 800), (9, 3), (0, 399),
])
def test_ranges_answer_as_a_plain_dict(layered_ro, lo, hi):
    _node, sess, model = layered_ro
    windows = metric.KV_RANGE_WINDOW_ROWS.value
    got = _rows(sess.execute(RANGE.format(lo, hi)))
    assert got == sorted(model[i] for i in model if lo <= i <= hi)
    want_k = sum(model[i][1] for i in model if lo <= i <= hi)
    res = sess.execute(f"SELECT SUM(k) FROM t WHERE id BETWEEN {lo} AND {hi}")
    (s,) = res["sum"]
    assert (None if s is None else int(s)) == (
        want_k if any(lo <= i <= hi for i in model) else None)
    if lo <= hi and any(lo <= i <= hi for i in model):
        assert metric.KV_RANGE_WINDOW_ROWS.value > windows


def test_order_by_and_distinct_over_char_are_bytewise(layered_ro):
    _node, sess, model = layered_ro
    cs = [model[i][2] for i in model if 100 <= i <= 260]
    got = sess.execute("SELECT c FROM t WHERE id BETWEEN 100 AND 260 "
                       "ORDER BY c")
    assert list(got["c"]) == sorted(cs, key=str.encode)
    got = sess.execute("SELECT DISTINCT c FROM t WHERE id BETWEEN 100 AND "
                       "260 ORDER BY c")
    assert list(got["c"]) == sorted(set(cs), key=str.encode)
    # a residual predicate over the range, and the STRING column beside it
    got = sess.execute("SELECT id, s FROM t WHERE id BETWEEN 100 AND 260 "
                       "AND k > 30")
    assert [(int(i), str(s)) for i, s in zip(got["id"], got["s"])] == sorted(
        (i, f"s{i % 3}") for i in model
        if 100 <= i <= 260 and model[i][1] > 30)


def test_a_range_wider_than_a_page_streams_page_by_page(served, monkeypatch):
    """Past the largest window the read goes on page by page from the
    boundary key; it never falls back to a decode of the table."""
    node, sess = served
    monkeypatch.setattr(kvtable, "RANGE_PAGE_ROWS", 256)
    n = 1500
    ids = np.arange(n, dtype=np.int64)
    sess.catalog.tables["t"].bulk_load(
        {"id": ids, "k": ids % 11,
         "c": np.array([f"w{i % 89:04d}" for i in ids], dtype=object),
         "s": np.array([f"s{i % 3}" for i in ids], dtype=object)},
        presorted=True)
    sess.execute("UPSERT INTO t (id, k, c, s) VALUES (700, 5, 'mem', 's0')")
    reads, decodes = (metric.KV_RANGE_READS.value,
                      metric.KV_TABLE_DECODES.value)
    got = _rows(sess.execute(RANGE.format(10, 1400)))
    want = [(i, i % 11, f"w{i % 89:04d}") for i in range(10, 1401)]
    want[700 - 10] = (700, 5, "mem")
    assert got == want
    assert metric.KV_RANGE_READS.value - reads >= 1391 // 256
    assert metric.KV_TABLE_DECODES.value == decodes
    (total,) = sess.execute("SELECT SUM(k) FROM t WHERE id BETWEEN 10 AND "
                            "1400")["sum"]
    assert int(total) == sum(r[1] for r in want)


# -- the transaction ------------------------------------------------------


def test_a_foreign_intent_in_the_span_is_waited_out(layered):
    node, a, model = layered
    b = _session(node)
    try:
        a.execute("BEGIN")
        a.execute("UPSERT INTO t (id, k, c, s) VALUES (120, 1, 'new', 's0')")
        retries = metric.TXN_RETRIES.value
        timer = threading.Timer(0.05, lambda: a.execute("COMMIT"))
        timer.start()
        got = _rows(b.execute(RANGE.format(110, 130)))
        timer.join()
        # it waited, then answered as of its one timestamp, which lies
        # under the commit's: the whole span as it was, never the intent
        assert got == sorted(model[i] for i in model if 110 <= i <= 130)
        assert metric.TXN_RETRIES.value > retries
        model[120] = (120, 1, "new")
        assert _rows(b.execute(RANGE.format(110, 130))) == sorted(
            model[i] for i in model if 110 <= i <= 130)
        # the tries spent, the conflict surfaces; it is never read through
        a.execute("BEGIN")
        a.execute("UPSERT INTO t (id, k, c, s) VALUES (121, 1, 'x', 's0')")
        t = node._sql_catalog.tables["t"]
        from cockroach_tpu.storage import rowcodec

        with pytest.raises(TransactionRetryError):
            node.db.range_committed(
                rowcodec.encode_pk(t.table_id, 110),
                rowcodec.encode_pk(t.table_id, 131),
                node.db.clock.now(), max_retries=2, decode=_filter_only)
        # a span beside the intent reads on
        assert _rows(b.execute(RANGE.format(122, 130))) == sorted(
            model[i] for i in model if 122 <= i <= 130)
        a.execute("ROLLBACK")
        assert _rows(b.execute(RANGE.format(121, 121))) == [model[121]]
    finally:
        b.close()


def test_inside_a_transaction_the_range_sees_its_own_writes(layered):
    node, a, model = layered
    b = _session(node)
    try:
        a.execute("BEGIN")
        a.execute("UPSERT INTO t (id, k, c, s) VALUES (5, 9, 'mine', 's0')")
        a.execute("DELETE FROM t WHERE id = 6")
        mine = dict(model)
        mine[5] = (5, 9, "mine")
        mine.pop(6)
        assert _rows(a.execute(RANGE.format(0, 20))) == sorted(
            mine[i] for i in mine if i <= 20)
        a.execute("COMMIT")
        assert _rows(b.execute(RANGE.format(0, 20))) == sorted(
            mine[i] for i in mine if i <= 20)
    finally:
        b.close()


def test_a_conflicting_committed_write_fails_the_commits_refresh(layered):
    node, a, model = layered
    b = _session(node)
    try:
        a.execute("BEGIN")
        before = _rows(a.execute(RANGE.format(30, 40)))
        assert before == sorted(model[i] for i in model if 30 <= i <= 40)
        b.execute("UPSERT INTO t (id, k, c, s) VALUES (35, 0, 'theirs', 's0')")
        a.execute("UPSERT INTO t (id, k, c, s) VALUES (900, 1, 'mine', 's0')")
        with pytest.raises(TransactionRetryError):
            a.execute("COMMIT")
        assert _rows(b.execute(RANGE.format(900, 900))) == []
        # a write OUTSIDE the span read does not fail the refresh
        a.execute("BEGIN")
        a.execute(RANGE.format(30, 40))
        b.execute("UPSERT INTO t (id, k, c, s) VALUES (41, 0, 'beside', 's0')")
        a.execute("UPSERT INTO t (id, k, c, s) VALUES (901, 1, 'mine', 's0')")
        a.execute("COMMIT")
        assert _rows(b.execute(RANGE.format(901, 901))) == [(901, 1, "mine")]
    finally:
        b.close()


def test_under_a_writer_every_answer_is_one_snapshot(served):
    """A writer rewrites every row of the span in one statement, again and
    again (k := its round); readers of the span only ever see one round."""
    node, sess = served
    ids = list(range(50, 70))

    def write(round_: int, s) -> None:
        s.execute("UPSERT INTO t (id, k, c, s) VALUES " + ", ".join(
            f"({i}, {round_}, 'r{round_}', 's0')" for i in ids))

    write(0, sess)
    stop, errors, seen = threading.Event(), [], []

    def writer():
        s = _session(node)
        try:
            r = 1
            while not stop.is_set():
                write(r, s)
                r += 1
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(e)
        finally:
            s.close()

    def reader():
        s = _session(node)
        try:
            for _ in range(25):
                rows = _rows(s.execute(RANGE.format(40, 80)))
                seen.append(rows)
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(e)
        finally:
            s.close()

    w = threading.Thread(target=writer)
    rs = [threading.Thread(target=reader) for _ in range(3)]
    w.start()
    for t in rs:
        t.start()
    for t in rs:
        t.join(300)
    stop.set()
    w.join(60)
    assert not errors, errors[:3]
    assert len(seen) == 75
    for rows in seen:
        assert [r[0] for r in rows] == ids
        assert len({r[1] for r in rows}) == 1, rows  # one round, whole
        assert {r[2] for r in rows} == {f"r{rows[0][1]}"}
    assert len({rows[0][1] for rows in seen}) > 1, "the writer never ran"


# -- the engine -----------------------------------------------------------


def _engine(**kw) -> Engine:
    return Engine(key_width=16, val_width=16, **kw)


def _three_runs() -> Engine:
    eng = _engine(l0_trigger=64)
    for r in range(3):
        for i in range(0, 200, r + 1):
            eng.put(b"k%03d" % i, b"v%d-%03d" % (r, i), ts=10 + r)
        eng.flush_mem_only()
    return eng


def _filter_only(view, ts, txn, sw, ew):
    """A decode that keeps the rows as they are: the view, its selection."""
    import jax.numpy as jnp

    from cockroach_tpu.storage import mvcc

    sel, conflict = mvcc.mvcc_scan_filter(view, ts, txn, sw, ew)
    return (view, sel), jnp.any(conflict), jnp.sum(sel, dtype=jnp.int32)


def _read(eng, start, end, **kw):
    return eng.range_read(start, end, decode=_filter_only, **kw)


def _selected(eng, got) -> list:
    view, sel = got.out
    idx = np.nonzero(np.asarray(sel))[0]
    keys = [bytes(k).rstrip(b"\x00") for k in np.asarray(view.key)[idx]]
    vals = [bytes(v[:n]) for v, n in zip(np.asarray(view.value)[idx],
                                         np.asarray(view.vlen)[idx])]
    return list(zip(keys, vals))


def test_range_read_seeks_both_bounds_and_masks_no_source(monkeypatch):
    eng = _three_runs()
    eng.put(b"k050", b"mem", ts=20)
    eng.delete(b"k051", ts=20)
    calls = {"mask": 0, "seek": 0}
    me = threading.current_thread()

    def counted(name, fn):  # this thread's calls: a served node has loops
        def wrapper(*a, **kw):
            calls[name] += threading.current_thread() is me
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(lsm, "_range_mask",
                        counted("mask", lsm._range_mask))
    monkeypatch.setattr(lsm, "_seek", counted("seek", lsm._seek))
    got = _read(eng, b"k040", b"k060", ts=100)
    want = {b"k%03d" % i: (b"v2-%03d" % i if i % 3 == 0 else
                           b"v1-%03d" % i if i % 2 == 0 else b"v0-%03d" % i)
            for i in range(40, 60)}
    want[b"k050"] = b"mem"
    del want[b"k051"]
    assert _selected(eng, got) == sorted(want.items())
    assert got.rows == len(want) and got.boundary is None
    assert got.sources == 4 and got.window_rows == 4 * 128
    assert calls == {"mask": 0, "seek": 4}
    # an older timestamp, a span with nothing, a span one source holds
    old = _read(eng, b"k040", b"k044", ts=10)
    assert _selected(eng, old) == [(b"k%03d" % i, b"v0-%03d" % i)
                                   for i in range(40, 44)]
    assert _read(eng, b"x", b"y", ts=100) == lsm.RangeRead(
        None, 0, None, 0, 0)
    one = _read(eng, b"k041", b"k042", ts=100)
    assert one.sources == 1 and _selected(eng, one) == [(b"k041", b"v0-041")]
    assert calls["mask"] == 0


def test_range_read_pages_from_the_boundary():
    eng = _three_runs()
    seen, start, pages = [], b"k000", 0
    while True:
        got = _read(eng, start, b"k999", ts=100, limit_rows=40)
        seen += _selected(eng, got)
        pages += 1
        if got.boundary is None:
            break
        assert got.boundary > start.ljust(16, b"\x00")
        start = got.boundary.rstrip(b"\x00")
    assert pages >= 2
    assert [k for k, _ in seen] == [b"k%03d" % i for i in range(200)]
    # a first key with more versions than the limit: the page grows
    for ts in range(30, 60):
        eng.put(b"k100", b"x%d" % ts, ts=ts)
    eng.flush_mem_only()
    got = _read(eng, b"k100", b"k103", ts=100, limit_rows=8)
    assert [k for k, _ in _selected(eng, got)][0] == b"k100"
    assert dict(_selected(eng, got))[b"k100"] == b"x59"


def test_range_read_raises_on_a_foreign_intent_and_sees_its_own():
    eng = _three_runs()
    eng.put(b"k045", b"intent", ts=50, txn=7)
    with pytest.raises(WriteIntentError) as e:
        _read(eng, b"k040", b"k050", ts=100)
    assert e.value.keys == [b"k045"] and e.value.txns == [7]
    own = _read(eng, b"k045", b"k046", ts=100, txn=7)
    assert _selected(eng, own) == [(b"k045", b"intent")]
    below = _read(eng, b"k040", b"k050", ts=40)  # under the intent
    assert below.rows == 10
    beside = _read(eng, b"k046", b"k050", ts=100)
    assert beside.rows == 4


def test_a_range_read_holds_the_mutex_for_its_snapshot_only():
    """Stopped right after its snapshot, the reader leaves the mutex free:
    a put and a flush run to their end, and the reader still answers as of
    its instant."""
    eng = _three_runs()
    reached, release = threading.Event(), threading.Event()
    real = eng._bounded_view

    def paused(*a, **kw):
        if threading.current_thread().name == "early":
            reached.set()
            assert release.wait(30)
        return real(*a, **kw)

    eng._bounded_view = paused
    out = {}
    t = threading.Thread(
        target=lambda: out.update(got=_read(eng, b"k010", b"k020",
                                                     ts=100)), name="early")
    t.start()
    assert reached.wait(30)
    free = []
    p = threading.Thread(target=lambda: free.append(eng.mu.acquire(False)
                                                    and not eng.mu.release()))
    p.start()
    p.join(5)
    assert free == [True]
    eng.put(b"k015", b"late", ts=30)
    eng.flush()
    release.set()
    t.join(30)
    assert dict(_selected(eng, out["got"]))[b"k015"] == b"v2-015"
    again = _read(eng, b"k010", b"k020", ts=100)
    assert dict(_selected(eng, again))[b"k015"] == b"late"


def test_the_range_read_span_carries_what_it_read():
    eng = _three_runs()
    with tracing.span("statement"):
        t0 = tracing.totals().get("storage/engine.range_read",
                                  {"count": 0, "tags": {}})
        _read(eng, b"k040", b"k060", ts=100)
        t1 = tracing.totals()["storage/engine.range_read"]
    assert t1["count"] == t0["count"] + 1
    tags0 = t0["tags"]
    assert t1["tags"]["window_rows"] - tags0.get("window_rows", 0) == 3 * 128
    assert t1["tags"]["sources"] - tags0.get("sources", 0) == 3
    assert t1["tags"]["held_ms"] >= tags0.get("held_ms", 0)
