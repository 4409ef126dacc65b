"""TPC-H Q18 as a served deployment (PR 32): the text of clause 2.4.18 with
its QUANTITY parameter through Session -> parser -> binder -> plan cache ->
flow, held to the benchmark's float64 pandas reference
(benchmarks/oracles/tpch_q18.py). The IN-subquery's semi-join filters
`orders` below both joins, so `lineitem` is joined with the kept orders
only; the subquery's GROUP BY l_orderkey takes the ordered (sort-free)
route over the clustered `lineitem` and streams (PR 33: a tile's closed
groups leave at once, the group on the tile's edge is carried, nothing is
spooled or merged); a new QUANTITY is a plan-cache hit that compiles
nothing; the tags the cell's per-layer metrics read.

The dense scatter aggregate is what the CPU picks for 15,000 order keys;
the chip at SF1 (1.5M keys over a budget of 524,288 states) takes
AggregateOp's sort-based route, so every case here lowers
`sql.distsql.dense_agg_states` to its floor to run the route the cell runs.
"""

import os
import sys

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.flow import dispatch, operators
from cockroach_tpu.sql import Session, plancache, sql
from cockroach_tpu.utils import settings, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
Q18 = " ".join(TPCH_SQL["q18"].split()).replace("> 300", "> {quantity}")
SEED = 2**31 + 32
TAGS = ("agg_ordered_tiles", "agg_streamed_tiles", "agg_merge_rows",
        "agg_spills", "semijoin_probe_tile_rows", "join_probe_tile_rows",
        "join_unique_tiles", "join_general_tiles", "join_late_emit_tiles",
        "join_build_placed_tiles")


class _Host:
    """What the benchmark's oracle needs of a loader's `Loaded`."""

    def __init__(self, cat):
        if BENCH not in sys.path:
            sys.path.insert(0, BENCH)
        from loaders.tpch import Loaded

        self.tables = dict(cat.tables)
        self.frame = lambda t, cols: Loaded.frame(self, t, cols)


def _reference(host, quantity):
    from oracles import tpch_q18

    return tpch_q18.answer(host, {"quantity": quantity})


def _assert_answer(got, want):
    assert list(got) == list(want.columns)
    assert [str(v) for v in got["c_name"]] == list(want.c_name)
    for col in ("c_custkey", "o_orderkey", "o_orderdate"):
        np.testing.assert_array_equal(np.asarray(got[col]),
                                      want[col].to_numpy(), err_msg=col)
    for col in ("o_totalprice", "sum_qty"):
        np.testing.assert_allclose(np.asarray(got[col], np.float64),
                                   want[col].to_numpy(), rtol=1e-9, atol=0,
                                   err_msg=col)


def _tags():
    pull = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
    return {k: pull.get(k, 0) for k in TAGS}


def _delta(t0):
    return {k: v - t0[k] for k, v in _tags().items()}


@pytest.fixture(scope="module", autouse=True)
def the_route_the_chip_takes():
    settings.set("sql.distsql.dense_agg_states", 64)
    yield
    settings.reset("sql.distsql.dense_agg_states")
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
    """Both ends of the served range, as the cell's warm-up sends them,
    until a pass compiles nothing: the joins' caps are learned at the wide
    end (68 orders, 472 lines: the ladder's lowest rung, as the chip's
    dozen orders at SF1) and the plan is in the cache."""
    for _ in range(4):
        c0 = dispatch.compiles()
        for quantity in (250, 300):
            sess.execute(Q18.format(quantity=quantity))
        if dispatch.compiles() == c0:
            return len(plancache.cache_for(sess.catalog))
    raise AssertionError("q18 still compiles in its fourth pass")


@pytest.mark.parametrize("quantity,rows", [(250, 68), (200, 100), (280, 7),
                                           (300, 1), (330, 0)])
def test_q18_served_equals_the_reference(sess, host, quantity, rows):
    """250 first: 68 orders pass, the joins built from them start compact at
    the lowest rung; 200 keeps 842 orders, overflows the lineitem join's
    rung, and is answered by its re-run."""
    want = _reference(host, quantity)
    assert len(want) == rows  # 330: nothing passes, an empty answer
    got = sess.execute(Q18.format(quantity=quantity))
    assert len(got["o_orderkey"]) == rows
    _assert_answer(got, want)


def test_the_semi_join_filters_orders_below_both_joins(cat):
    lines = [ln.strip().split("  [")[0]
             for ln in sql(cat, Q18.format(quantity=300)).explain()
             .splitlines()]
    joins = [ln for ln in lines if "hash-join" in ln]
    assert [ln.split(")")[0] for ln in joins] == [
        "-> hash-join (inner", "-> hash-join (inner", "-> hash-join (semi"]
    assert all(ln.endswith("(unique build)") for ln in joins)
    # lineitem probes the kept orders; orders probes the subquery's keys
    i = lines.index(joins[1])
    assert lines[i + 1].startswith("-> scan lineitem")
    assert lines[i + 2] == joins[2]
    assert lines[i + 3].startswith("-> scan orders")
    assert lines[i + 4] == "-> project ['l_orderkey']"
    assert lines[i + 5].startswith("-> filter Cmp(op='gt'")
    # the subquery groups the clustered lineitem without a key sort and
    # without a spool; the outer group-by, over joined rows, does not
    groups = [ln for ln in lines if ln.startswith("-> group-by")]
    assert [ln.endswith("(ordered, streaming)") for ln in groups] == [
        False, True]
    assert "(ordered" not in groups[0]
    assert lines[-1].startswith("-> scan customer")


@pytest.mark.parametrize("quantity", [290, 255, 270])  # narrow, wide, narrow
def test_a_new_quantity_compiles_nothing(sess, host, settled, quantity):
    cache = plancache.cache_for(sess.catalog)
    c0, h0, p0 = dispatch.compiles(), cache.hits, _pulls()
    got = sess.execute(Q18.format(quantity=quantity))
    assert dispatch.compiles() == c0
    assert len(cache) == settled and cache.hits == h0 + 1
    assert _pulls() == p0 + 1  # inside the warmed range nothing overflows
    # its own answer: a stale threshold would give the settled one's
    _assert_answer(got, _reference(host, quantity))


def _pulls():
    return tracing.totals().get("flow/pull", {"count": 0})["count"]


def test_the_tags_the_cells_metrics_read(sess, settled):
    """One tile a table at SF0.01 and the default tile size: the subquery's
    one lineitem tile is grouped presorted, streamed and never merged (the
    outer aggregate's one partial needs no merge either); the semi-join
    is handed orders' one tile; the lineitem join cuts its tile to the cap
    learned from a dozen orders' lines before it gathers a build column."""
    t0 = _tags()
    sess.execute(Q18.format(quantity=260))
    d = _delta(t0)
    assert d["agg_ordered_tiles"] == 1 and d["agg_streamed_tiles"] == 1
    assert d["agg_merge_rows"] == 0 and d["agg_spills"] == 0
    orders_tile = d["semijoin_probe_tile_rows"]
    assert orders_tile >= sess.catalog.get("orders").num_rows
    # three probes a statement, all by a unique-build strategy: orders into
    # the subquery's keys, lineitem into the kept orders, those rows into
    # customer (composed into the aggregate at the lineitem join's cap)
    assert d["join_unique_tiles"] == 3 and d["join_general_tiles"] == 0
    assert d["join_late_emit_tiles"] == 1
    assert d["join_probe_tile_rows"] > orders_tile
    # PR 40: the streamed aggregate reaches its join through the HAVING's
    # Filter, which leaves holes: both hashjoin_lut builds keep `concat`
    assert d["join_build_placed_tiles"] == 0
    sess.execute(" ".join(TPCH_SQL["q1"].split()))
    assert _delta(t0) == d  # q1 has no AggregateOp spool and no join


@pytest.fixture()
def small_tiles():
    settings.set("sql.distsql.tile_size", 1024)
    yield 1024
    settings.reset("sql.distsql.tile_size")
    settings.reset("sql.distsql.workmem_rows")


@pytest.fixture()
def fresh(small_tiles):
    """The module's data in a catalog of its own: no plan cached, no table
    resident at another tile size's padding."""
    cat = tpch.gen_tpch(sf=0.01, seed=SEED)
    return cat, _Host(cat)


def _spy_merge_caps(monkeypatch):
    """The static `cap` of every hashagg_merge launch, in order."""
    caps = []
    real = operators.AggregateOp.init

    def init(self):
        real(self)
        fn = self._merge_fn
        if not getattr(fn, "_spied", False):
            def merge(tiles, cap):
                caps.append(cap)
                return fn(tiles, cap=cap)

            merge._spied = True
            self._merge_fn = merge

    monkeypatch.setattr(operators.AggregateOp, "init", init)
    return caps


def test_the_tags_count_every_tile_and_every_merge(fresh, small_tiles,
                                                   monkeypatch):
    """1,024-row tiles: 59 lineitem tiles grouped presorted and streamed,
    15 orders tiles handed to the semi-join, and `agg_merge_rows` is the sum
    of the static caps the hashagg_merge launches ran at: the subquery's
    15,000 groups are never merged (the parent of PR 33 merged them once,
    at the shape ladder's 65,536), only the outer aggregate's few hundred
    rows, at 1,024."""
    cat, host = fresh
    caps = _spy_merge_caps(monkeypatch)
    s = Session(cat)
    try:
        t0 = _tags()
        got = s.execute(Q18.format(quantity=250))
        d = _delta(t0)
    finally:
        s.close()
    _assert_answer(got, _reference(host, 250))
    tiles = -(-cat.get("lineitem").num_rows // small_tiles)
    assert d["agg_ordered_tiles"] == tiles == 59
    assert d["agg_streamed_tiles"] == 59
    assert d["semijoin_probe_tile_rows"] == 15 * small_tiles
    assert caps == [1024] and d["agg_merge_rows"] == sum(caps)
    assert d["agg_spills"] == 0 and d["join_general_tiles"] == 0
    assert d["join_build_placed_tiles"] == 0  # 60 tiles under the HAVING


@pytest.mark.parametrize("clustered", [False, True],
                         ids=["unordered_spills", "ordered_streams"])
def test_a_spool_over_its_budget_spills_and_is_counted(fresh, clustered,
                                                       monkeypatch):
    """15,000 groups against 8,192 rows of work memory. With
    `Table.ordering` cleared the merged partials do not fit, the spool goes
    to the Grace aggregator, the answer stands and `agg_spills` says so.
    Clustered, the aggregate holds one tile and one row whatever the
    budget: no spool is opened, nothing spills, every tile streams."""
    cat, host = fresh
    if not clustered:
        cat.get("lineitem").ordering = ()
    spooled = []
    real = operators.AggregateOp._spool
    monkeypatch.setattr(
        operators.AggregateOp, "_spool",
        lambda self: (spooled.append(self.ordered), real(self))[1])
    settings.set("sql.distsql.workmem_rows", 8192)
    s = Session(cat)
    try:
        t0 = _tags()
        got = s.execute(Q18.format(quantity=250))
        d = _delta(t0)
    finally:
        s.close()
    _assert_answer(got, _reference(host, 250))
    if clustered:
        assert d["agg_spills"] == 0 and d["agg_streamed_tiles"] == 59
        assert spooled == [False]  # the outer aggregate's, not the subquery's
    else:
        assert d["agg_spills"] == 1 and spooled == [False, False]
        assert d["agg_ordered_tiles"] == d["agg_streamed_tiles"] == 0


@pytest.mark.parametrize("tile", [1024, 1 << 20])
def test_ordered_and_unordered_aggregates_agree_bit_for_bit(cat, tile):
    """The same text over the same rows with `Table.ordering` cleared takes
    the sorting route (no tile is counted ordered) and returns the same
    bits."""
    import copy

    from cockroach_tpu.catalog import Catalog

    plain = Catalog()
    for name, table in cat.tables.items():
        if name == "lineitem":
            table = copy.copy(table)
            table.ordering = ()
        plain.add(table)
    settings.set("sql.distsql.tile_size", tile)
    try:
        runs = []
        for c in (cat, plain):
            s = Session(c)
            try:
                t0 = _tags()
                runs.append((s.execute(Q18.format(quantity=240)),
                             _delta(t0)["agg_ordered_tiles"]))
            finally:
                s.close()
    finally:
        settings.reset("sql.distsql.tile_size")
    (ordered, n_ordered), (unordered, n_unordered) = runs
    assert n_ordered > 0 and n_unordered == 0
    assert len(ordered["o_orderkey"]) > 50
    for col in ordered:
        a, b = np.asarray(ordered[col]), np.asarray(unordered[col])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), col


def test_a_group_that_straddles_a_tile_edge_is_summed_whole(small_tiles):
    """An order whose lines lie on both sides of a 1,024-row tile edge, each
    side's quantity under the threshold and their sum over it: its left half
    leaves the first tile's kernel as the carried row and meets the right
    half in slot 0 of the next tile's (ops/aggregation.py
    stitch_ordered_partial; before PR 33 the two partial groups met in
    hashagg_merge), so the HAVING above sees the group once, whole, and the
    order is in the answer."""
    cat = tpch.gen_tpch(sf=0.01, seed=SEED)
    li = cat.get("lineitem")
    keys = np.asarray(li.columns["l_orderkey"])
    edges = np.arange(small_tiles, len(keys), small_tiles)
    straddlers = [int(keys[e]) for e in edges if keys[e] == keys[e - 1]]
    order = max(straddlers, key=lambda k: int((keys == k).sum()))
    rows = np.nonzero(keys == order)[0]
    edge = next(e for e in edges if rows[0] < e <= rows[-1])
    qty = np.array(li.columns["l_quantity"])
    qty[rows] = 5000  # 50.00 a line, the most a line holds
    li.columns["l_quantity"] = qty
    left, right = int((rows < edge).sum()) * 50, int((rows >= edge).sum()) * 50
    assert len(rows) >= 5 and left and right
    quantity = left + right - 1
    assert max(left, right) <= quantity
    host = _Host(cat)
    want = _reference(host, quantity)
    assert order in set(want.o_orderkey) and len(want) < 100
    s = Session(cat)
    try:
        got = s.execute(Q18.format(quantity=quantity))
    finally:
        s.close()
    _assert_answer(got, want)
    at = list(got["o_orderkey"]).index(order)
    assert float(got["sum_qty"][at]) == left + right


def test_the_filtered_source_is_estimated_from_the_subquery():
    """With ANALYZE statistics the semi-joined `orders` is estimated at the
    subquery's rows (l_orderkey's distinct values, a third of them past the
    HAVING) over o_orderkey's distinct values; without them at the
    unknown-selectivity constant. Either way it is a reducing build side."""
    from cockroach_tpu.sql import binder as binder_mod, parser

    cat = tpch.gen_tpch(sf=0.001, seed=SEED)
    seen = []
    real = binder_mod.Binder._semi_kept_fraction

    def spy(self, s, pos, sub):
        seen.append((real(self, s, pos, sub), self._plan_est_rows(sub.plan)))
        return seen[-1][0]

    binder_mod.Binder._semi_kept_fraction = spy
    try:
        text = Q18.format(quantity=300)
        binder_mod.Binder(cat).bind(parser.parse_statement(text))
        s = Session(cat)
        for table in ("lineitem", "orders"):
            s.execute(f"analyze {table}")
        s.close()
        binder_mod.Binder(cat).bind(parser.parse_statement(text))
    finally:
        binder_mod.Binder._semi_kept_fraction = real
    (frac0, rows0), (frac1, rows1) = seen
    assert rows0 is None and frac0 == pytest.approx(1 / 3)
    orders = cat.get("orders").num_rows
    assert rows1 == pytest.approx(orders / 3, rel=0.05)
    assert frac1 == pytest.approx(1 / 3, rel=0.05)


@pytest.mark.parametrize("quantity,pulls,probe_rows", [
    # 68 kept orders: the semi-join and the lineitem join start compact at
    # 1,024 rows, customer's join is handed that rung: no full-tile learn run
    (250, 1, 65536 + 65536 + 1024),
    # 842 kept orders fit the rung, their 5,651 lines do not: the first
    # attempt overflows, the re-run counts at full tiles and answers
    (200, 2, None),
])
def test_a_plans_first_statement_starts_compact_on_a_small_build(
        host, quantity, pulls, probe_rows):
    cat = tpch.gen_tpch(sf=0.01, seed=SEED)
    s = Session(cat)
    try:
        t0, p0 = _tags(), _pulls()
        got = s.execute(Q18.format(quantity=quantity))
        d, n = _delta(t0), _pulls() - p0
    finally:
        s.close()
    _assert_answer(got, _reference(host, quantity))
    assert n == pulls
    assert d["join_late_emit_tiles"] >= 1  # cut to the rung in its first run
    if probe_rows is not None:
        assert d["join_probe_tile_rows"] == probe_rows


def test_a_reload_of_the_schema_keeps_the_programs_constants():
    """A DECIMAL column's bounds reach a sort key's packing as constants of
    the program; o_totalprice's extremes move with the seed, the widened
    pair the catalog hands out does not (it keys the compile cache), and
    still holds every value. Integer keys keep their exact domain."""
    stats = []
    for seed in (SEED, SEED + 1):
        orders = tpch.gen_tpch(sf=0.01, seed=seed).get("orders")
        price = np.asarray(orders.columns["o_totalprice"])
        lo, hi = orders.col_stats()["o_totalprice"]
        assert lo == 0 and hi == (1 << int(price.max()).bit_length()) - 1
        assert lo <= price.min() and price.max() <= hi
        assert orders.col_stats()["o_orderkey"] == (1, orders.num_rows)
        stats.append((int(price.min()), int(price.max()), lo, hi))
    (lo0, hi0, *canon0), (lo1, hi1, *canon1) = stats
    assert (lo0, hi0) != (lo1, hi1) and canon0 == canon1
