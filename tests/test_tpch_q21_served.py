"""TPC-H Q21 as a served deployment (PR 39): the text of clause 2.4.21 with
its NATION parameter through Session -> parser -> binder -> plan cache ->
flow, held to the benchmark's pandas reference
(benchmarks/oracles/tpch_q21.py) and, on a fixture of a dozen orders that
holds each edge once, to a loop written by hand. The binder decorrelates
`EXISTS (... l2.l_suppkey <> l1.l_suppkey)` as `lineitem` grouped by
l_orderkey with min and max of l_suppkey, joined back unique-build and
filtered, and NOT EXISTS as the same aggregate over the late lines,
LEFT-joined and read by IS NULL: five unique-build joins in one probe
pipeline over three passes of `lineitem`. On the chip at SF1 the 1.5M order
keys pass the dense aggregate's state budget, so both aggregates take the
ordered streaming route (the second under a Filter: prefix_live=False); the
`streamed` cases lower `sql.distsql.dense_agg_states` to its floor to run
it here. A new NATION is a plan-cache hit that compiles and re-runs
nothing; the two tags PR 39 added for the cell's metrics.
"""

import os
import sys

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.catalog import Catalog, Table
from cockroach_tpu.coldata.types import DATE, INT64, STRING, Schema
from cockroach_tpu.flow import dispatch
from cockroach_tpu.sql import Session, explain, plancache, sql
from cockroach_tpu.utils import settings, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
Q21 = " ".join(TPCH_SQL["q21"].split()).replace("SAUDI ARABIA", "{nation}")
SEEDS = (2**31 + 39, 2**31 + 3939)
NATIONS = ("SAUDI ARABIA", "CANADA", "FRANCE", "CHINA", "UNITED STATES")
TAGS = ("join_build_rows", "join_null_extended_tiles", "join_unique_tiles",
        "join_general_tiles", "join_probe_tile_rows", "join_overflow_reruns",
        "agg_ordered_tiles", "agg_streamed_tiles", "agg_merge_rows",
        "agg_spills", "join_build_placed_tiles")


class _Host:
    """What the benchmark's oracle needs of a loader's `Loaded`."""

    def __init__(self, cat):
        if BENCH not in sys.path:
            sys.path.insert(0, BENCH)
        from loaders.tpch import Loaded

        self.tables = dict(cat.tables)
        self.frame = lambda t, cols: Loaded.frame(self, t, cols)


def _reference(host, nation, **kw):
    from oracles import tpch_q21

    return tpch_q21.answer(host, {"nation": nation}, **kw)


def _assert_answer(got, want):
    assert list(got) == list(want.columns) == ["s_name", "numwait"]
    assert [str(v) for v in got["s_name"]] == list(want.s_name)
    np.testing.assert_array_equal(
        np.asarray(got["numwait"]).astype(np.int64), want.numwait.to_numpy())


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


@pytest.fixture(scope="module", params=SEEDS, ids=["seed39", "seed3939"])
def cat(request):
    return tpch.gen_tpch(sf=0.01, seed=request.param)


@pytest.fixture(scope="module")
def host(cat):
    return _Host(cat)


@pytest.fixture(scope="module")
def sess(cat):
    s = Session(cat)
    yield s
    s.close()


@pytest.fixture()
def streamed():
    """The route the chip takes at SF1: 15,000 order keys pass a budget of
    64 states, so both decorrelated aggregates are ordered AggregateOps."""
    settings.set("sql.distsql.dense_agg_states", 64)
    yield
    settings.reset("sql.distsql.dense_agg_states")


@pytest.mark.parametrize("nation", NATIONS)
def test_q21_served_equals_the_reference(sess, host, nation):
    want = _reference(host, nation)
    assert len(want) >= 1  # four suppliers a nation at SF0.01
    got = sess.execute(Q21.format(nation=nation))
    _assert_answer(got, want)
    # the control (the frame without its NOT EXISTS) counts more rows
    control = _reference(host, nation, not_exists=False)
    assert int(control.numwait.sum()) > int(want.numwait.sum())


@pytest.mark.parametrize("nation", NATIONS[:4])
def test_q21_streamed_equals_the_reference_and_never_merges(
        cat, host, streamed, nation):
    """Both aggregates stream their one lineitem tile (presorted partial,
    carried group, finalize in one kernel; the second under the late
    filter); nothing is spooled, merged or spilled (each tail is a
    `hashagg_stream_tail`, never a `hashagg_merge`)."""
    plancache.cache_for(cat).clear()
    s = Session(cat)
    try:
        t0 = _tags()
        got = s.execute(Q21.format(nation=nation))
        d = _delta(t0)
    finally:
        s.close()
        plancache.cache_for(cat).clear()
    _assert_answer(got, _reference(host, nation))
    assert d["agg_ordered_tiles"] == d["agg_streamed_tiles"] == 2
    assert d["agg_merge_rows"] == 0 and d["agg_spills"] == 0
    # PR 40: both builds place their aggregate's streamed tile and its tail
    assert d["join_build_placed_tiles"] == 4
    assert d["join_null_extended_tiles"] == 1
    assert d["join_general_tiles"] == 0


def test_the_plan_is_five_unique_joins_over_three_lineitem_scans(
        cat, streamed):
    lines = [ln.strip().split("  [")[0]
             for ln in sql(cat, Q21.format(nation="PERU")).explain()
             .splitlines()]
    joins = [ln for ln in lines if "hash-join" in ln]
    assert [ln.split(")")[0] for ln in joins] == [
        "-> hash-join (left"] + ["-> hash-join (inner"] * 4
    assert all(ln.endswith("(unique build)") for ln in joins)
    assert sum(ln.startswith("-> scan lineitem") for ln in lines) == 3
    groups = [ln for ln in lines if ln.startswith("-> group-by")]
    assert len(groups) == 3
    # the outer GROUP BY s_name is not over clustered input; the two
    # decorrelated aggregates are, and stream
    assert "(ordered" not in groups[0]
    assert all("aggs=['min(1)', 'max(1)']" in g
               and g.endswith("(ordered, streaming)") for g in groups[1:])
    # nation's 25-fold cut comes before either aggregate is joined
    nation_at = next(i for i, ln in enumerate(lines) if "scan nation" in ln)
    assert nation_at < lines.index(groups[1])


# ---- each edge once: a dozen orders against a loop written by hand

_LATE, _ON_TIME = (100, 90), (80, 90)  # (receipt, commit) days
# order -> (status, [(supplier, late?), ...]); suppliers 1-3 are PERU's,
# supplier 4 is KENYA's
_ORDERS = {
    1: ("F", [(1, True), (2, False)]),            # counts 1 for supplier 1
    2: ("F", [(1, True)]),                        # one supplier: EXISTS fails
    3: ("F", [(1, True), (2, True)]),             # two late: NOT EXISTS fails
    4: ("F", [(1, True), (1, True), (3, False)]),  # a lone late supplier,
                                                   # two late lines: counts 2
    5: ("O", [(1, True), (2, False)]),            # status not 'F'
    6: ("F", [(4, True), (1, False)]),            # late, of another nation
    7: ("F", [(2, False), (3, False)]),           # nobody late
    8: ("F", [(2, True), (2, False), (3, False)]),  # late and on time: 1
    9: ("P", [(3, True), (1, False)]),            # status not 'F'
    10: ("F", [(3, True), (3, True)]),            # one supplier, two lines
    11: ("F", [(1, False), (2, False), (3, True)]),  # counts 1 for 3
    12: ("F", [(1, True), (2, False), (3, True)]),  # two of three late
}
_SUPPLIERS = {1: ("Supplier#1", 17), 2: ("Supplier#2", 17),
              3: ("Supplier#3", 17), 4: ("Supplier#4", 14)}
_NATIONS = {17: "PERU", 14: "KENYA", 3: "CANADA"}


def _edge_catalog():
    lines = [(o, s, *(_LATE if late else _ON_TIME))
             for o, (_st, ls) in _ORDERS.items() for s, late in ls]
    ok, sk, rc, cm = (np.array(c) for c in zip(*lines))
    cat = Catalog()
    cat.add(Table.from_strings(
        "lineitem",
        Schema.of(l_orderkey=INT64, l_suppkey=INT64, l_commitdate=DATE,
                  l_receiptdate=DATE),
        {"l_orderkey": ok.astype(np.int64), "l_suppkey": sk.astype(np.int64),
         "l_commitdate": cm.astype(np.int32),
         "l_receiptdate": rc.astype(np.int32)},
        ordering=("l_orderkey",)))
    cat.add(Table.from_strings(
        "orders", Schema.of(o_orderkey=INT64, o_orderstatus=STRING),
        {"o_orderkey": np.array(list(_ORDERS), dtype=np.int64),
         "o_orderstatus": np.array([st for st, _ in _ORDERS.values()],
                                   dtype=object)},
        ordering=("o_orderkey",)))
    cat.add(Table.from_strings(
        "supplier",
        Schema.of(s_suppkey=INT64, s_name=STRING, s_nationkey=INT64),
        {"s_suppkey": np.array(list(_SUPPLIERS), dtype=np.int64),
         "s_name": np.array([n for n, _ in _SUPPLIERS.values()],
                            dtype=object),
         "s_nationkey": np.array([k for _, k in _SUPPLIERS.values()],
                                 dtype=np.int64)},
        ordering=("s_suppkey",)))
    cat.add(Table.from_strings(
        "nation", Schema.of(n_nationkey=INT64, n_name=STRING),
        {"n_nationkey": np.array(list(_NATIONS), dtype=np.int64),
         "n_name": np.array(list(_NATIONS.values()), dtype=object)}))
    return cat


def _by_hand(nation):
    """The text, read line by line: for every l1."""
    counts: dict = {}
    for o, (status, ls) in _ORDERS.items():
        for s1, late1 in ls:
            name, nkey = _SUPPLIERS[s1]
            if status != "F" or not late1 or _NATIONS[nkey] != nation:
                continue
            if not any(s2 != s1 for s2, _ in ls):
                continue  # EXISTS
            if any(s3 != s1 and late3 for s3, late3 in ls):
                continue  # NOT EXISTS
            counts[name] = counts.get(name, 0) + 1
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:100]


@pytest.mark.parametrize("route", ["dense", "streamed"])
def test_each_edge_once_against_a_loop_by_hand(route):
    assert _by_hand("PERU") == [("Supplier#1", 3), ("Supplier#2", 1),
                                ("Supplier#3", 1)]
    assert _by_hand("KENYA") == [("Supplier#4", 1)]
    assert _by_hand("CANADA") == []
    if route == "streamed":
        settings.set("sql.distsql.dense_agg_states", 64)
    cat = _edge_catalog()
    s = Session(cat)
    try:
        for nation in ("PERU", "KENYA", "CANADA", "PERU"):
            got = s.execute(Q21.format(nation=nation))
            rows = list(zip((str(v) for v in got["s_name"]),
                            (int(v) for v in got["numwait"])))
            assert rows == _by_hand(nation), (route, nation)
            # the benchmark's reference reads the same dozen orders alike
            want = _reference(_Host(cat), nation)
            assert list(zip(want.s_name, want.numwait)) == rows
    finally:
        s.close()
        if route == "streamed":
            settings.reset("sql.distsql.dense_agg_states")


# ---- a new NATION on the settled plan; the tags the cell's metrics read

def _late_f_rows_by_nation(host):
    """How many l1 rows reach the nation join, a nation: what moves the
    caps of the joins above it."""
    li = host.frame("lineitem", ["l_orderkey", "l_suppkey", "l_commitdate",
                                 "l_receiptdate"])
    o = host.frame("orders", ["o_orderkey", "o_orderstatus"])
    s = host.frame("supplier", ["s_suppkey", "s_nationkey"])
    n = host.frame("nation", ["n_nationkey", "n_name"])
    f = o.o_orderkey[o.o_orderstatus.astype(str) == "F"]
    l1 = li[(li.l_receiptdate > li.l_commitdate) & li.l_orderkey.isin(f)]
    j = l1.merge(s, left_on="l_suppkey", right_on="s_suppkey").merge(
        n, left_on="s_nationkey", right_on="n_nationkey")
    return j.n_name.astype(str).value_counts()


@pytest.fixture(scope="module")
def settled(sess, host):
    """The two widest nations, until a pass compiles nothing: every learned
    cap then has room for the other 23 (a cap is twice the fullest tile's
    live rows, on a ladder of 1,024 / 8,192 / 65,536)."""
    by = _late_f_rows_by_nation(host)
    widest = list(by.index[:2])
    for _ in range(5):
        c0 = dispatch.compiles()
        for nation in widest:
            sess.execute(Q21.format(nation=nation))
        if dispatch.compiles() == c0:
            return len(plancache.cache_for(sess.catalog)), widest, by
    raise AssertionError("q21 still compiles in its fifth pass")


def test_a_nation_never_sent_before_compiles_and_reruns_nothing(
        sess, host, settled):
    n_plans, widest, by = settled
    cache = plancache.cache_for(sess.catalog)
    others = [n for n in by.index if n not in widest]
    assert len(others) >= 20
    for nation in (others[-1], others[0], others[len(others) // 2]):
        c0, h0, p0, t0 = dispatch.compiles(), cache.hits, _pulls(), _tags()
        got = sess.execute(Q21.format(nation=nation))
        assert dispatch.compiles() == c0, nation
        assert len(cache) == n_plans and cache.hits == h0 + 1
        assert _pulls() == p0 + 1
        assert _delta(t0)["join_overflow_reruns"] == 0
        # its own answer: a stale nation code would give the settled one's
        _assert_answer(got, _reference(host, nation))


def test_the_two_tags_pr39_added(sess, settled):
    """One tile a table at SF0.01 and the default tile size. The two builds
    made from an aggregate's whole output are `hashjoin_lut`s at the rung of
    15,000 groups (65,536) and of the late lines' groups (65,536): orders,
    supplier and nation are dense keys probed by arithmetic and build
    nothing. The LEFT join's one probe tile is NULL-extended in place."""
    t0 = _tags()
    sess.execute(Q21.format(nation="JAPAN"))
    d = _delta(t0)
    assert d["join_build_rows"] == 2 * 65536
    assert d["join_null_extended_tiles"] == 1
    assert d["join_unique_tiles"] == 5 and d["join_general_tiles"] == 0
    assert d["agg_streamed_tiles"] == 0  # the dense aggregate, on the CPU
    assert d["join_build_placed_tiles"] == 0  # which proves no live prefix
    sess.execute(" ".join(TPCH_SQL["q1"].split()))
    assert _delta(t0) == d  # q1 has no join
    out = explain(sess.catalog, "explain analyze (debug) "
                  + Q21.format(nation="JAPAN"))
    assert "join_build_rows" in out and "join_null_extended_tiles" in out


def test_a_slow_query_bundle_carries_the_two_tags(sess, settled):
    from cockroach_tpu.sql import diagnostics

    settings.set("sql.log.slow_query.latency_threshold", 1e-9)
    try:
        sess.execute(Q21.format(nation="INDIA"))
        listing = diagnostics.bundles()
        assert listing and listing[0]["trigger"] == "slow_query"
        text = repr(diagnostics.get(listing[0]["id"])["trace"])
    finally:
        settings.reset("sql.log.slow_query.latency_threshold")
    assert "join_build_rows" in text and "join_null_extended_tiles" in text


# ---- PR 40: the two builds place their aggregates' tiles

@pytest.fixture(scope="module")
def tiny():
    return tpch.gen_tpch(sf=0.001, seed=SEEDS[0])


@pytest.fixture()
def six_tiles(streamed):
    """The chip's shape at SF1 in small: `lineitem` in six tiles."""
    settings.set("sql.distsql.tile_size", 1024)
    yield
    settings.reset("sql.distsql.tile_size")


@pytest.mark.parametrize("nation", NATIONS)
def test_q21_on_the_placed_route_equals_the_reference(cat, host, streamed,
                                                      nation):
    """Four lineitem tiles a pass: each build places four streamed tiles
    and a tail at their running offsets, the last short of its capacity."""
    settings.set("sql.distsql.tile_size", 1 << 14)
    plancache.cache_for(cat).clear()
    s = Session(cat)
    try:
        t0 = _tags()
        got = s.execute(Q21.format(nation=nation))
        d = _delta(t0)
    finally:
        s.close()
        plancache.cache_for(cat).clear()
        settings.reset("sql.distsql.tile_size")
    _assert_answer(got, _reference(host, nation))
    assert d["agg_streamed_tiles"] == 8 and d["agg_merge_rows"] == 0
    assert d["join_build_placed_tiles"] == 10
    assert d["join_build_rows"] == 2 * 65536


def test_the_tag_pr40_added_reads_fourteen_on_the_streamed_route(
        tiny, six_tiles):
    """Two builds of seven tiles each (six streamed and the tail), as the
    chip's at SF1; the count is host-known and needs the tile's producer
    only, so EXPLAIN ANALYZE's batch-by-batch pull reads the same, and a
    slow-query bundle carries it."""
    from cockroach_tpu.sql import diagnostics

    s = Session(tiny)
    try:
        t0 = _tags()
        s.execute(Q21.format(nation="JAPAN"))
        d = _delta(t0)
        assert d["agg_streamed_tiles"] == 12
        assert d["join_build_placed_tiles"] == 14
        assert d["join_build_rows"] == 2 * 8192
        out = explain(tiny, "explain analyze (debug) "
                      + Q21.format(nation="JAPAN"))
        assert "'join_build_placed_tiles': 14" in out
        settings.set("sql.log.slow_query.latency_threshold", 1e-9)
        s.execute(Q21.format(nation="INDIA"))
        listing = diagnostics.bundles()
        assert listing and listing[0]["trigger"] == "slow_query"
        text = repr(diagnostics.get(listing[0]["id"])["trace"])
        assert "'join_build_placed_tiles': 14" in text
    finally:
        settings.reset("sql.log.slow_query.latency_threshold")
        s.close()
        plancache.cache_for(tiny).clear()
