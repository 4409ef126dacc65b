"""TPC-H Q13 as a served deployment (PR 34): the text of clause 2.4.13 with
its WORD1 and WORD2 parameters through Session -> parser -> binder -> plan
cache -> flow, held to the benchmark's pandas reference
(benchmarks/oracles/tpch_q13.py). `customer` is the preserved side of the
LEFT OUTER JOIN, so `orders`, whose o_custkey repeats, is the build: the
one TPC-H join that cannot be planned unique-build. It runs
hash_join_general (an emission larger than its probe tile, at a capacity
learned by overflowing), NULL-extends a third of the customers, and feeds
`count(o_orderkey)`, which must skip those rows. The GROUP BY c_custkey
above the join is the dense scatter aggregate, here (1,500 keys) as on the
chip at SF1 (150,001 keys of the accelerator's 524,288 states); a key
domain past that budget sorts the join's output, and the two agree bit for
bit. A new word pair is a plan-cache hit that compiles and re-runs nothing;
the tags the cell's metrics read.
"""

import os
import sys

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.flow import dispatch
from cockroach_tpu.sql import Session, plancache, sql
from cockroach_tpu.utils import settings, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
Q13 = " ".join(TPCH_SQL["q13"].split()).replace(
    "%special%requests%", "%{word1}%{word2}%")
INNER = ("select c_custkey, count(o_orderkey) as c_count from customer "
         "left outer join orders on c_custkey = o_custkey and o_comment "
         "not like '%{word1}%{word2}%' group by c_custkey")
SEED = 2**31 + 34
TAGS = ("join_general_tiles", "join_expanded_tiles", "join_unique_tiles",
        "join_probe_tile_rows", "join_emit_tile_rows", "join_overflow_reruns",
        "agg_ordered_tiles", "agg_streamed_tiles", "agg_merge_rows",
        "join_build_rows", "join_null_extended_tiles",
        "join_build_placed_tiles")


class _Host:
    """What the benchmark's oracle needs of a loader's `Loaded`."""

    def __init__(self, cat):
        if BENCH not in sys.path:
            sys.path.insert(0, BENCH)
        from loaders.tpch import Loaded

        self.tables = dict(cat.tables)
        self.frame = lambda t, cols: Loaded.frame(self, t, cols)


def _reference(host, word1, word2, how="left"):
    from oracles import tpch_q13

    return tpch_q13.answer(host, {"word1": word1, "word2": word2}, how=how)


def _assert_answer(got, want):
    assert list(got) == list(want.columns) == ["c_count", "custdist"]
    for col in want.columns:  # integers, in the reference's row order
        np.testing.assert_array_equal(np.asarray(got[col]).astype(np.int64),
                                      want[col].to_numpy(), err_msg=col)


def _tags():
    pull = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
    return {k: pull.get(k, 0) for k in TAGS}


def _delta(t0):
    return {k: v - t0[k] for k, v in _tags().items()}


def _pulls():
    return tracing.totals().get("flow/pull", {"count": 0})["count"]


@pytest.fixture(scope="module", autouse=True)
def leave_no_statement_statistics():
    yield
    from cockroach_tpu.sql import sqlstats

    sqlstats.DEFAULT.clear()


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.01, seed=SEED)


@pytest.fixture(scope="module")
def host(cat):
    return _Host(cat)


@pytest.fixture(scope="module")
def sess(cat):
    s = Session(cat)
    yield s
    s.close()


@pytest.fixture(scope="module")
def settled(sess):
    """Two word pairs, as the cell's warm-up sends two draws, until a pass
    compiles nothing: the join's emission cap is learned and the plan is in
    the cache."""
    for _ in range(4):
        c0 = dispatch.compiles()
        for w1, w2 in (("special", "requests"), ("express", "deposits")):
            sess.execute(Q13.format(word1=w1, word2=w2))
        if dispatch.compiles() == c0:
            return len(plancache.cache_for(sess.catalog))
    raise AssertionError("q13 still compiles in its fourth pass")


@pytest.mark.parametrize("word1,word2", [
    ("special", "requests"), ("pending", "packages"),
    ("unusual", "accounts"), ("express", "deposits"),
    ("zebra", "quagga")])  # the last matches no comment: no order removed
def test_q13_served_equals_the_reference(sess, host, word1, word2):
    want = _reference(host, word1, word2)
    got = sess.execute(Q13.format(word1=word1, word2=word2))
    _assert_answer(got, want)
    o = host.frame("orders", ["o_comment"]).o_comment.astype(str)
    removed = int(o.str.contains(f"{word1}.*{word2}", regex=True).sum())
    assert (removed == 0) == (word1 == "zebra")
    # every customer is in exactly one row, every kept order counted once
    assert int(np.sum(got["custdist"])) == 1500
    assert int(np.dot(got["c_count"], got["custdist"])) == 15000 - removed


def test_the_zero_row_is_the_customers_without_an_order(sess, host):
    """A third of the customers place no order (clause 4.2.3); a pattern can
    only add to them. An inner join, the benchmark's control, has no such
    row and has to differ by its keys."""
    got = sess.execute(Q13.format(word1="special", word2="requests"))
    o = host.frame("orders", ["o_custkey", "o_comment"])
    kept = o[~o.o_comment.astype(str).str.contains("special.*requests")]
    without = 1500 - kept.o_custkey.nunique()
    assert without >= 500  # c_custkey % 3 == 0 never orders
    at = list(got["c_count"]).index(0)
    assert int(got["custdist"][at]) == without
    from oracles import tpch_q13

    names, rows = list(got), list(zip(*(got[c] for c in got)))
    assert tpch_q13.zero_order_customers(names, rows) == without
    control = _reference(host, "special", "requests", how="inner")
    assert 0 not in set(control.c_count)
    assert len(control) == len(got["c_count"]) - 1


def test_the_plan_is_a_general_left_join_under_a_group_by(cat):
    lines = [ln.strip().split("  [")[0]
             for ln in sql(cat, Q13.format(word1="special",
                                           word2="requests")).explain()
             .splitlines()]
    (join,) = [ln for ln in lines if "hash-join" in ln]
    assert join == "-> hash-join (left) probe=[0] build=[1]"  # NOT unique
    i = lines.index(join)
    # plan/prune.py (PR 38): the join carries 3 columns, not 17; o_comment
    # is read by the NOT LIKE alone and stops above the filter
    assert lines[i + 1] == "-> scan customer columns=['c_custkey']"
    assert lines[i + 2] == "-> project ['o_orderkey', 'o_custkey']"
    assert lines[i + 3].startswith("-> filter Not(arg=CodeLookup(col=2,")
    assert lines[i + 4] == ("-> scan orders columns=['o_orderkey', "
                            "'o_custkey', 'o_comment']")
    inner, outer = [ln for ln in reversed(lines)
                    if ln.startswith("-> group-by")]
    # the ordering customer declares stops at the join: neither is ordered
    assert inner == "-> group-by keys=[0] aggs=['count(1)']"
    assert "(ordered" not in outer


@pytest.mark.parametrize("word1,word2", [  # narrow, wide, narrow
    ("zebra", "quagga"), ("special", "packages"), ("unusual", "requests")])
def test_a_new_word_pair_compiles_nothing_and_runs_once(
        sess, host, settled, word1, word2):
    cache = plancache.cache_for(sess.catalog)
    c0, h0, p0, t0 = dispatch.compiles(), cache.hits, _pulls(), _tags()
    got = sess.execute(Q13.format(word1=word1, word2=word2))
    assert dispatch.compiles() == c0
    assert len(cache) == settled and cache.hits == h0 + 1
    assert _pulls() == p0 + 1 and _delta(t0)["join_overflow_reruns"] == 0
    # its own answer: a stale lookup table would give the settled one's
    _assert_answer(got, _reference(host, word1, word2))


def test_the_tags_the_cells_metrics_read(sess, settled):
    """One tile a table at SF0.01 and the default tile size: customer's one
    tile probes hash_join_general (under the exact packed key the binder
    plans for c_custkey = o_custkey, so by run expansion: PR 36), which
    emits one tile at the learned capacity (15,000 rows: the ladder's
    65,536) into the dense aggregate (no tile is grouped presorted); the
    NOT LIKE table rides as a device argument; the outer aggregate's one
    partial needs no merge."""
    t0 = _tags()
    bound = tracing.totals()["query"]["tags"]["lookup_tables_bound"]
    sess.execute(Q13.format(word1="pending", word2="accounts"))
    d = _delta(t0)
    assert (tracing.totals()["query"]["tags"]["lookup_tables_bound"]
            == bound + 1)
    assert d["join_general_tiles"] == 1 and d["join_unique_tiles"] == 0
    assert d["join_expanded_tiles"] == 1
    assert d["join_probe_tile_rows"] == 8192  # 1,500 customers' rung
    assert d["join_emit_tile_rows"] == 65536
    assert d["join_overflow_reruns"] == 0
    assert d["agg_ordered_tiles"] == 0 and d["agg_streamed_tiles"] == 0
    # PR 39's two tags: the one build (hashjoin_build over 15,000 orders at
    # the ladder's 65,536) is redone every statement; the LEFT join is the
    # general one, so no tile counts as NULL-extended on a unique route
    assert d["join_build_rows"] == 65536
    assert d["join_null_extended_tiles"] == 0
    # PR 40: `orders` comes from under the NOT LIKE Filter, which proves no
    # live prefix, so hashjoin_build compacts its tiles through `concat`
    assert d["join_build_placed_tiles"] == 0
    sess.execute(" ".join(TPCH_SQL["q1"].split()))
    assert _delta(t0) == d  # q1 has no join and no AggregateOp


@pytest.fixture()
def small_tiles():
    settings.set("sql.distsql.tile_size", 1024)
    yield 1024
    settings.reset("sql.distsql.tile_size")


def test_a_first_statement_that_overflows_is_run_again_and_counted(
        small_tiles, host):
    """1,024-row tiles: the first speculation is one output row a probe row
    (4,096 at the least); a tile of 1,024 customers emits about 7,000 rows,
    so the first attempt is cut short, found out at its end, and the
    statement runs again at the canonical step over twice the rows. The
    second statement runs once."""
    cat = tpch.gen_tpch(sf=0.01, seed=SEED)
    s = Session(cat)
    try:
        t0, p0 = _tags(), _pulls()
        got = s.execute(Q13.format(word1="special", word2="requests"))
        d, n = _delta(t0), _pulls() - p0
        t1 = _tags()
        s.execute(Q13.format(word1="pending", word2="deposits"))
        d2 = _delta(t1)
    finally:
        s.close()
    _assert_answer(got, _reference(host, "special", "requests"))
    assert n == 2 and d["join_overflow_reruns"] == 1
    # two customer tiles an attempt: 2 x 4,096, then 2 x 65,536
    assert d["join_general_tiles"] == d["join_expanded_tiles"] == 4
    assert d["join_emit_tile_rows"] == 2 * 4096 + 2 * 65536
    assert d2["join_overflow_reruns"] == 0 and d2["join_general_tiles"] == 2
    assert d2["join_emit_tile_rows"] == 2 * 65536


@pytest.mark.parametrize("tile", [1024, 1 << 20])
def test_the_dense_and_the_sorting_aggregate_agree_bit_for_bit(cat, tile):
    """The same text over the same rows with the dense aggregate's state
    budget at its floor (the route a key domain past 524,288 states takes)
    sorts the join's output to group it (no tile is counted ordered: the
    walk for a clustered input stops at the join) and returns the same
    bits."""
    settings.set("sql.distsql.tile_size", tile)
    try:
        runs = []
        for states in (None, 64):
            if states is not None:
                settings.set("sql.distsql.dense_agg_states", states)
            plancache.cache_for(cat).clear()
            s = Session(cat)
            try:
                t0 = _tags()
                runs.append((s.execute(INNER.format(word1="special",
                                                    word2="requests")),
                             s.execute(Q13.format(word1="special",
                                                  word2="requests")),
                             _delta(t0)))
            finally:
                s.close()
                settings.reset("sql.distsql.dense_agg_states")
    finally:
        settings.reset("sql.distsql.tile_size")
        plancache.cache_for(cat).clear()
    (rows_d, dist_d, tags_d), (rows_s, dist_s, tags_s) = runs
    assert tags_d["agg_ordered_tiles"] == tags_s["agg_ordered_tiles"] == 0
    assert tags_d["join_general_tiles"] == tags_s["join_general_tiles"] > 0
    assert len(rows_d["c_custkey"]) == 1500
    order_d = np.argsort(rows_d["c_custkey"], kind="stable")
    order_s = np.argsort(rows_s["c_custkey"], kind="stable")
    for col in rows_d:
        a = np.asarray(rows_d[col])[order_d]
        b = np.asarray(rows_s[col])[order_s]
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), col
    for col in dist_d:
        a, b = np.asarray(dist_d[col]), np.asarray(dist_s[col])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), col


def test_the_customers_on_an_emitted_tiles_edge_are_counted_once(
        small_tiles, host):
    """A probe row's matches never leave its tile (hash_join_general lays
    a tile's output out by a prefix sum over that tile's probe rows), and
    the aggregate above folds two emitted tiles: each customer's orders
    are counted once, in one group. Customers 1,024 and 1,025 sit on the
    two sides of the first emitted tile's edge; every customer is held to
    pandas."""
    cat = tpch.gen_tpch(sf=0.01, seed=SEED)
    s = Session(cat)
    try:
        got = s.execute(INNER.format(word1="special", word2="requests"))
    finally:
        s.close()
    o = host.frame("orders", ["o_custkey", "o_comment"])
    kept = o[~o.o_comment.astype(str).str.contains("special.*requests")]
    want = kept.groupby("o_custkey").size().reindex(
        np.arange(1, 1501), fill_value=0)
    order = np.argsort(got["c_custkey"])
    np.testing.assert_array_equal(np.asarray(got["c_custkey"])[order],
                                  np.arange(1, 1501))
    np.testing.assert_array_equal(
        np.asarray(got["c_count"])[order].astype(np.int64), want.to_numpy())
    assert want[1024] > 0 and want[1025] > 0 and want[1026] == 0
