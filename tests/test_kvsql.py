"""Storage<->SQL bridge tests: rows written through kv.Txn are readable by
the SQL engine via the direct-columnar-scan path (the cFetcher/col_mvcc
parity point — pkg/sql/colfetcher/cfetcher.go:230,
pkg/storage/col_mvcc.go:25-90)."""

import numpy as np
import pytest

import cockroach_tpu.catalog as catalog_mod
from cockroach_tpu import coldata as cd
from cockroach_tpu.kv import DB, ManualClock, WriteIntentError
from cockroach_tpu.kv.table import create_kv_table
from cockroach_tpu.sql import sql
from cockroach_tpu.storage import rowcodec
from cockroach_tpu.storage.lsm import Engine


SCHEMA = cd.Schema.of(
    id=cd.INT64, qty=cd.INT64, price=cd.DECIMAL(12, 2), day=cd.DATE,
    ratio=cd.FLOAT64, ok=cd.BOOL,
)


def _db():
    return DB(
        Engine(key_width=16, val_width=rowcodec.value_width(SCHEMA),
               memtable_size=64),
        ManualClock(),
    )


def _setup(n=50):
    db = _db()
    cat = catalog_mod.Catalog()
    t = create_kv_table(cat, db, "items", SCHEMA, pk="id")
    rng = np.random.default_rng(3)
    rows = []
    for i in range(n):
        rows.append({
            "id": i, "qty": int(rng.integers(1, 100)),
            "price": int(rng.integers(100, 10000)),
            "day": int(rng.integers(8000, 9000)),
            "ratio": float(rng.random()),
            "ok": bool(rng.integers(0, 2)),
        })

    def ins(txn):
        for r in rows:
            t.insert(txn, r)

    db.txn(ins)
    return db, cat, t, rows


def test_rowcodec_roundtrip():
    row = {"id": -5, "qty": 7, "price": 123456, "day": 8123,
           "ratio": -2.75, "ok": True}
    enc = rowcodec.encode_row(SCHEMA, row)
    dec = rowcodec.decode_row(SCHEMA, enc)
    assert dec["id"] == -5 and dec["qty"] == 7 and dec["price"] == 123456
    assert dec["day"] == 8123 and dec["ratio"] == -2.75 and dec["ok"] is True
    # NULLs
    enc2 = rowcodec.encode_row(SCHEMA, {"id": 1})
    dec2 = rowcodec.decode_row(SCHEMA, enc2)
    assert dec2["qty"] is None and dec2["ratio"] is None


def test_pk_encoding_order_and_nul_free():
    vals = [-(1 << 63), -12345, -1, 0, 1, 77, 1 << 40, (1 << 63) - 1]
    keys = [rowcodec.encode_pk(3, v) for v in vals]
    assert keys == sorted(keys), "key order must follow pk order"
    for k, v in zip(keys, vals):
        assert b"\x00" not in k
        assert rowcodec.decode_pk(k) == v


def test_sql_over_kv_table():
    """Rows written via transactions are visible to SQL aggregates through
    the engine (no preloaded host table anywhere)."""
    db, cat, t, rows = _setup()
    res = sql(cat, """
        select count(*) as n, sum(qty) as s, min(day) as lo, max(day) as hi
        from items where qty > 50
    """).run()
    want = [r for r in rows if r["qty"] > 50]
    assert int(res["n"][0]) == len(want)
    assert int(res["s"][0]) == sum(r["qty"] for r in want)
    assert int(res["lo"][0]) == min(r["day"] for r in want)
    assert int(res["hi"][0]) == max(r["day"] for r in want)
    # decimal + float columns decode correctly through the device path
    res2 = sql(cat, "select sum(price) as p, avg(ratio) as r from items").run()
    np.testing.assert_allclose(
        float(res2["p"][0]), sum(r["price"] for r in rows) / 100.0, rtol=1e-12
    )
    np.testing.assert_allclose(
        float(res2["r"][0]), np.mean([r["ratio"] for r in rows]), rtol=1e-12
    )


def test_kv_table_mvcc_snapshot():
    """read_ts pins a snapshot: updates after the snapshot are invisible."""
    db, cat, t, rows = _setup(10)
    ts0 = db.clock.now()

    def upd(txn):
        t.insert(txn, {**rows[0], "qty": 10_000})

    db.txn(upd)
    res = sql(cat, "select max(qty) as m from items").run()
    assert int(res["m"][0]) == 10_000
    t.read_ts = ts0
    try:
        res0 = sql(cat, "select max(qty) as m from items").run()
        assert int(res0["m"][0]) == max(r["qty"] for r in rows)
    finally:
        t.read_ts = None


def test_kv_table_abort_and_delete():
    db, cat, t, rows = _setup(10)

    class Boom(Exception):
        pass

    def bad(txn):
        t.insert(txn, {"id": 999, "qty": 1, "price": 1, "day": 1,
                       "ratio": 0.0, "ok": False})
        raise Boom()

    with pytest.raises(Boom):
        db.txn(bad)
    db.txn(lambda txn: t.delete_pk(txn, rows[0]["id"]))
    res = sql(cat, "select count(*) as n from items").run()
    assert int(res["n"][0]) == len(rows) - 1  # no aborted row, one deleted


def test_kv_table_null_columns():
    db = _db()
    cat = catalog_mod.Catalog()
    t = create_kv_table(cat, db, "items", SCHEMA, pk="id")

    def ins(txn):
        t.insert(txn, {"id": 1, "qty": 5})
        t.insert(txn, {"id": 2, "price": 300})

    db.txn(ins)
    res = sql(cat, "select count(qty) as cq, count(price) as cp, "
                   "count(*) as n from items").run()
    assert int(res["cq"][0]) == 1 and int(res["cp"][0]) == 1
    assert int(res["n"][0]) == 2


def test_kv_scan_hits_intent_conflict():
    db, cat, t, rows = _setup(5)
    open_txn = db.new_txn()
    t.insert(open_txn, {**rows[2], "qty": 1})
    with pytest.raises(WriteIntentError):
        sql(cat, "select count(*) as n from items").run()
    open_txn.rollback()
    res = sql(cat, "select count(*) as n from items").run()
    assert int(res["n"][0]) == 5


def test_ycsb_e_microbench():
    from cockroach_tpu.bench.ycsb import run_ycsb_e

    out = run_ycsb_e(n_keys=512, ops=8, scan_len=16)
    assert out["ops_per_sec"] > 0
    assert out["rows_scanned"] >= 5 * 16  # scans dominate the mix (a scan
    # starting near the end of the keyspace legitimately returns fewer rows)


def test_q1_over_kv_backed_lineitem():
    """TPC-H Q1 end-to-end over a lineitem that LIVES IN THE ENGINE —
    strings included (the kv/table.py fixed-width restriction is
    gone). Oracle: the same query over the host-resident catalog table."""
    from cockroach_tpu.bench import queries as Q
    from cockroach_tpu.bench import tpch

    host_cat = tpch.gen_tpch(sf=0.002, seed=5)
    want = Q.q1(host_cat).run()

    li = host_cat.get("lineitem")
    db = DB(
        Engine(key_width=16, val_width=rowcodec.value_width(li.schema),
               memtable_size=1 << 14),
        ManualClock(),
    )
    kv_cat = catalog_mod.Catalog()
    kvt = create_kv_table(kv_cat, db, "lineitem", li.schema, pk="l_rowid"
                          if "l_rowid" in li.schema.names else
                          li.schema.names[0])
    # lineitem has no single-column pk; use a synthetic rowid as the key
    n = li.num_rows

    def ins(txn):
        for r in range(n):
            row = {}
            for cname in li.schema.names:
                v = li.columns[cname][r]
                if cname in li.dictionaries:
                    v = li.dictionaries[cname].values[int(v)]
                row[cname] = v
            # key by row index: l_orderkey repeats, so the first column
            # cannot key the row; overwrite the pk encoding input
            row[kvt.pk] = r
            kvt.insert(txn, row)

    db.txn(ins)
    assert kvt.num_rows == n

    got = Q.q1(kv_cat).run()
    assert list(got["l_returnflag"]) == list(want["l_returnflag"])
    assert list(got["l_linestatus"]) == list(want["l_linestatus"])
    for col in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
                "avg_qty", "avg_price", "avg_disc", "count_order"):
        np.testing.assert_allclose(
            np.asarray(got[col], dtype=np.float64),
            np.asarray(want[col], dtype=np.float64), rtol=1e-9,
        )


def test_bulk_load_and_import_job(tmp_path):
    """IMPORT path: vectorized key/value encoding lands CSV data as sorted
    engine runs (AddSSTable discipline); strings dictionary-encode
    vectorized; results query identically to row-at-a-time inserts."""
    import csv

    from cockroach_tpu.kv import DB, ManualClock
    from cockroach_tpu.kv.jobs import Registry, register_import_job
    from cockroach_tpu.sql import sql

    db = DB(Engine(key_width=16, val_width=256, memtable_size=256),
            ManualClock())
    cat = catalog_mod.Catalog()
    schema = cd.Schema.of(id=cd.INT64, qty=cd.INT64,
                          price=cd.DECIMAL(12, 2), tag=cd.STRING)
    t = create_kv_table(cat, db, "items", schema, pk="id")

    path = str(tmp_path / "items.csv")
    n = 5000
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["id", "qty", "price", "tag"])
        w.writeheader()
        for i in range(n):
            w.writerow({"id": i, "qty": i % 97,
                        "price": f"{(i % 1000) + 0.25:.2f}",
                        "tag": f"t{i % 7}"})

    reg = Registry(db)
    register_import_job(reg, cat)
    job = reg.create("import", {"table": "items", "path": path})
    done = reg.adopt_and_resume(job.job_id)
    assert done.state == "succeeded" and done.progress["rows"] == n
    assert t.num_rows == n

    res = sql(cat, "select count(*) as n, sum(qty) as q from items").run()
    assert int(res["n"][0]) == n
    assert int(res["q"][0]) == sum(i % 97 for i in range(n))
    res = sql(cat, "select tag, count(*) as c from items group by tag "
                   "order by tag").run()
    assert list(res["tag"]) == [f"t{i}" for i in range(7)]
    res = sql(cat, "select price from items where id = 1234").run()
    np.testing.assert_allclose(float(res["price"][0]), 234 + 0.25)
    # NULL handling: a row with a missing value
    with open(path, "a", newline="") as f:
        f.write(f"{n},,,t0\n")
    job2 = reg.create("import", {"table": "items", "path": path})
    # re-import at a higher ts: idempotent for existing pks (MVCC versions)
    done2 = reg.adopt_and_resume(job2.job_id)
    assert done2.progress["rows"] == n + 1
    res = sql(cat, f"select qty from items where id = {n}").run()
    assert res["qty"][0] is None


@pytest.mark.slow
def test_sharded_scan_covers_kv_tables():
    """Shard masks select by LIVE-ROW RANK: a KVTable's live rows sit at
    scattered merged-view positions (often past num_rows), so positional
    sharding would silently drop rows (regression)."""
    import numpy as np

    from cockroach_tpu.flow.operators import ScanOp, UnionOp
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.sql import Session

    sess = Session()
    # two tables interleave their keys in the one engine, and updates leave
    # old MVCC versions around — live rows are NOT a position prefix
    sess.execute("create table a (k int primary key, v int)")
    sess.execute("create table b (k int primary key, v int)")
    for i in range(50):
        sess.execute(f"insert into a values ({i}, {i})")
        sess.execute(f"insert into b values ({i}, {1000 + i})")
    sess.execute("update b set v = v + 1 where k < 25")

    tbl = sess.catalog.tables["b"]
    full = run_operator(ScanOp(tbl))
    parts = UnionOp(tuple(
        ScanOp(tbl, shard=(i, 3)) for i in range(3)
    ))
    got = run_operator(parts)
    assert len(got["k"]) == len(full["k"]) == 50
    np.testing.assert_array_equal(np.sort(got["k"]), np.sort(full["k"]))
    np.testing.assert_array_equal(np.sort(got["v"]), np.sort(full["v"]))


def test_sharded_scan_covers_snapshot_beyond_num_rows():
    """The last shard is rank-unbounded: a snapshot can hold MORE live rows
    than num_rows reports at now() (e.g. a snapshot taken before deletes);
    those trailing ranks must not vanish from a sharded scan (regression)."""
    import numpy as np

    from cockroach_tpu.flow.operators import ScanOp, UnionOp
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.sql import Session

    sess = Session()
    sess.execute("create table s (k int primary key, v int)")
    for i in range(60):
        sess.execute(f"insert into s values ({i}, {i})")
    tbl = sess.catalog.tables["s"]
    snap_ts = sess.db.clock.now()
    sess.execute("delete from s where k >= 50")
    assert tbl.num_rows == 50  # newest-visible count
    tbl.read_ts = snap_ts  # scan AT the pre-delete snapshot
    try:
        got = run_operator(UnionOp(tuple(
            ScanOp(tbl, shard=(i, 3)) for i in range(3)
        )))
        assert len(got["k"]) == 60, "sharded snapshot scan dropped rows"
        np.testing.assert_array_equal(np.sort(got["k"]), np.arange(60))
    finally:
        tbl.read_ts = None


def test_distributed_kv_scan_sizes_from_snapshot():
    """The SPMD planner sizes shard capacity from snapshot_live_rows: a
    pre-delete snapshot holding more rows than num_rows must distribute
    completely (regression: sizing from num_rows dropped the tail)."""
    import numpy as np

    from cockroach_tpu.parallel import mesh as mesh_mod
    from cockroach_tpu.sql import Session, sql

    sess = Session()
    sess.execute("create table ds (k int primary key, v int)")
    rows = ", ".join(f"({i}, {i * 2})" for i in range(1200))
    sess.execute(f"insert into ds values {rows}")
    tbl = sess.catalog.tables["ds"]
    snap_ts = sess.db.clock.now()
    sess.execute("delete from ds where k >= 600")
    assert tbl.num_rows == 600
    tbl.read_ts = snap_ts
    try:
        assert tbl.snapshot_live_rows() == 1200
        rel = sql(sess.catalog, "select count(*) as n, sum(v) as s from ds")
        got = rel.run_distributed(mesh_mod.make_mesh(8))
        assert int(got["n"][0]) == 1200
        assert int(got["s"][0]) == sum(i * 2 for i in range(1200))
    finally:
        tbl.read_ts = None


# -- resident scans over tiles: concurrent and sharded ----------------------


def _fact_catalog(n=512, seed=3):
    from cockroach_tpu.catalog import Catalog, Table

    rng = np.random.default_rng(seed)
    cat = Catalog()
    cat.add(Table(
        name="fact",
        schema=cd.Schema(("f_key", "f_val"), (cd.INT64, cd.FLOAT64)),
        columns={"f_key": np.arange(n, dtype=np.int64),
                 "f_val": rng.uniform(0.0, 10.0, n)},
    ))
    return cat


def _drain_rows(op) -> list[tuple]:
    """Live rows of a scan's tile sequence, mask applied, as raw bits."""
    out = []
    op.init()
    while True:
        t = op.next_batch()
        if t is None:
            break
        mask = np.asarray(t.mask)
        cols = [np.asarray(c.data) for c in t.cols]
        for i in np.nonzero(mask)[0]:
            out.append(tuple(c[i].tobytes() for c in cols))
    op.close()
    return out


def test_two_sessions_scanning_one_resident_table_match_a_solo_scan():
    """Two scans of one resident table, tile by tile and at once from two
    threads, each return what a solo scan returns, bit for bit."""
    import threading

    from cockroach_tpu.flow.operators import ScanOp

    table = _fact_catalog().get("fact")
    want = _drain_rows(ScanOp(table, tile=128))
    assert len(want) == 512
    got, errs = {}, []
    barrier = threading.Barrier(2)

    def session(i):
        try:
            op = ScanOp(table, tile=128)
            barrier.wait()
            got[i] = _drain_rows(op)
        except Exception as e:  # noqa: BLE001 - reported by the assert
            errs.append(e)

    ts = [threading.Thread(target=session, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs
    assert got[0] == want and got[1] == want


def test_sharded_resident_scan_tiles_cover_each_row_once():
    """Shards of a tiled resident scan slice their own tiles and between
    them return every row exactly once, each its own rank range."""
    from cockroach_tpu.flow.operators import ScanOp

    table = _fact_catalog().get("fact")
    want = _drain_rows(ScanOp(table, tile=128))
    halves = [_drain_rows(ScanOp(table, tile=128, shard=(i, 2)))
              for i in range(2)]
    assert [len(h) for h in halves] == [256, 256]
    assert halves[0] + halves[1] == want
