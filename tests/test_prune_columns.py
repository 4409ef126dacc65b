"""The column-pruning pass (plan/prune.py, PR 38): scans hand up, joins emit
and builds compact only the columns the statement reads above them.

(a) all 22 TPC-H texts answer the same, row for row, pruned and unpruned;
(b) the five served texts' scans list exactly the columns read, and EXPLAIN
prints them; (c) Rel pipelines of every operator the pass has a rule for,
and of every barrier, pruned against unpruned; (d) count(*) keeps one
column and a node the pass does not know keeps all its inputs' columns;
(e) the tag `join_output_columns` the benchmark's
`flow.join_output_columns_per_stmt` reads."""

import dataclasses
import math

import numpy as np
import pytest

from cockroach_tpu import catalog as catalog_mod
from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.coldata.types import INT64, STRING, Schema
from cockroach_tpu.flow import operators
from cockroach_tpu.flow.runtime import run_plan
from cockroach_tpu.ops import expr as ex
from cockroach_tpu.plan import spec as S
from cockroach_tpu.plan.prune import expr_refs, prune_columns, remap_expr
from cockroach_tpu.sql import Session, sql
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import settings, tracing


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.001, seed=3)  # a seed all 22 texts bind on


def _same(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (math.isnan(a) and math.isnan(b))
    return a == b


def _assert_same_answer(got, want):
    assert list(got) == list(want)
    for col in want:
        g, w = np.asarray(got[col]).tolist(), np.asarray(want[col]).tolist()
        assert len(g) == len(w), f"{col}: {len(g)} vs {len(w)} rows"
        bad = [i for i, (x, y) in enumerate(zip(g, w)) if not _same(x, y)]
        assert not bad, f"{col}: rows {bad[:5]} differ"


def _nodes(plan, kind) -> list:
    """Every node of ``kind`` in the plan, in pre-order."""
    out = [plan] if isinstance(plan, kind) else []
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        for c in v if isinstance(v, tuple) else (v,):
            if isinstance(c, S.PlanNode):
                out += _nodes(c, kind)
    return out


def _scans(plan) -> dict[str, list]:
    """table -> the columns of each of its scans, in plan order."""
    out: dict[str, list] = {}
    for n in _nodes(plan, (S.TableScan, S.IndexScan)):
        out.setdefault(n.table, []).append(n.columns)
    return out


# -- (a) ---------------------------------------------------------------------


@pytest.mark.parametrize("qname", sorted(TPCH_SQL, key=lambda q: int(q[1:])))
def test_tpch_text_answers_the_same_pruned_and_unpruned(cat, qname):
    rel = sql(cat, TPCH_SQL[qname])
    pruned = rel.optimized_plan()
    assert pruned != rel.plan  # every text reads fewer columns than it scans
    _assert_same_answer(run_plan(pruned, cat), run_plan(rel.plan, cat))


# -- (b) ---------------------------------------------------------------------

SERVED_SCANS = {
    "q1": {"lineitem": [("l_quantity", "l_extendedprice", "l_discount",
                         "l_tax", "l_returnflag", "l_linestatus",
                         "l_shipdate")]},
    "q3": {"lineitem": [("l_orderkey", "l_extendedprice", "l_discount",
                         "l_shipdate")],
           "orders": [("o_orderkey", "o_custkey", "o_orderdate",
                       "o_shippriority")],
           "customer": [("c_custkey", "c_mktsegment")]},
    "q9": {"lineitem": [("l_orderkey", "l_partkey", "l_suppkey",
                         "l_quantity", "l_extendedprice", "l_discount")],
           "part": [("p_partkey", "p_name")],
           "supplier": [("s_suppkey", "s_nationkey")],
           "partsupp": [("ps_partkey", "ps_suppkey", "ps_supplycost")],
           "orders": [("o_orderkey", "o_orderdate")],
           "nation": [("n_nationkey", "n_name")]},
    "q13": {"customer": [("c_custkey",)],
            "orders": [("o_orderkey", "o_custkey", "o_comment")]},
    "q18": {"lineitem": [("l_orderkey", "l_quantity"),
                         ("l_orderkey", "l_quantity")],
            "orders": [("o_orderkey", "o_custkey", "o_totalprice",
                        "o_orderdate")],
            "customer": [("c_custkey", "c_name")]},
}


@pytest.mark.parametrize("qname", sorted(SERVED_SCANS))
def test_served_text_scans_exactly_the_columns_it_reads(cat, qname):
    rel = sql(cat, TPCH_SQL[qname])
    assert all(set(cols[0]) == set(cat.get(t).schema.names)
               for t, cols in _scans(rel.plan).items())  # the binder's
    got = _scans(rel.optimized_plan())
    assert got == SERVED_SCANS[qname]
    text = rel.explain()
    for table, scans in SERVED_SCANS[qname].items():
        for cols in scans:
            assert f"-> scan {table} columns={list(cols)}" in text


def test_a_column_only_a_predicate_reads_stops_above_its_filter(cat):
    """q13's o_comment (NOT LIKE), q3's l_shipdate and c_mktsegment, q9's
    p_name: a narrowing Project sits directly above the filter, so the
    column does not ride into the join; o_orderdate, which q3's select list
    reads too, rides on."""
    def above_filters(q):
        plan = sql(cat, TPCH_SQL[q]).optimized_plan()
        return {p.names for p in _nodes(plan, S.Project)
                if isinstance(p.input, S.Filter)}

    assert ("o_orderkey", "o_custkey") in above_filters("q13")
    q3 = above_filters("q3")
    assert ("l_orderkey", "l_extendedprice", "l_discount") in q3
    assert ("c_custkey",) in q3
    assert not any("o_orderdate" in names and "o_custkey" not in names
                   for names in q3)
    assert ("p_partkey",) in above_filters("q9")
    # what each join carries: the issue's 3 of 17, 8 of 33
    def join_widths(q):
        return sorted(_width(cat, j) for j in _nodes(
            sql(cat, TPCH_SQL[q]).optimized_plan(), S.HashJoin))

    assert join_widths("q13") == [3] and join_widths("q3") == [7, 8]


def _width(cat, node) -> int:
    from cockroach_tpu.plan.distribute import schema_of

    return len(schema_of(node, cat))


# -- (c) ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def small():
    rng = np.random.default_rng(38)
    n, m = 500, 120
    c = catalog_mod.Catalog()
    c.add(catalog_mod.Table.from_strings(
        "f", Schema.of(k=INT64, a=INT64, b=INT64, s=STRING, d=INT64),
        {"k": rng.integers(0, m + 20, n).astype(np.int64),
         "a": rng.integers(0, 50, n).astype(np.int64),
         "b": rng.integers(-5, 5, n).astype(np.int64),
         "s": rng.choice(["red", "green", "blue", "plum"], n).astype(object),
         "d": np.arange(n, dtype=np.int64)}))
    c.add(catalog_mod.Table.from_strings(
        "u", Schema.of(uk=INT64, v=INT64, w=INT64, t=STRING),
        {"uk": np.arange(m, dtype=np.int64),
         "v": rng.integers(0, 9, m).astype(np.int64),
         "w": rng.integers(0, 1000, m).astype(np.int64),
         "t": rng.choice(["x", "y", "z"], m).astype(object)}))
    c.add(catalog_mod.Table.from_strings(
        "g", Schema.of(gk=INT64, x=INT64, y=INT64),
        {"gk": rng.integers(0, m, 300).astype(np.int64),
         "x": rng.integers(0, 7, 300).astype(np.int64),
         "y": rng.integers(0, 1000, 300).astype(np.int64)}))
    return c


def _f(c):
    return Rel.scan(c, "f")


def _joined(c, how, unique=True):
    build = Rel.scan(c, "u" if unique else "g")
    on = [("k", "uk" if unique else "gk")]
    return _f(c).filter(ex.Cmp("gt", _f(c).c("b"), ex.lit(-3))).join(
        build, on=on, how=how, build_unique=unique)


PIPELINES = {
    "filter_project": lambda c: _f(c).filter(
        ex.Cmp("lt", _f(c).c("a"), ex.lit(25))).select("k", "d"),
    "project_expr": lambda c: _f(c).project(
        [("z", ex.BinOp("+", _f(c).c("a"), _f(c).c("b"))),
         ("k", _f(c).c("k"))]).select("z"),
    "string_predicate": lambda c: _f(c).filter(
        _f(c).str_in("s", ["red", "plum"])).select("d"),
    "string_transform": lambda c: (
        lambda r, e_d: r.project([("s2", e_d[0]), ("d", r.c("d")),
                                  ("a", r.c("a"))])
        .with_dict("s2", e_d[1]).select("d", "s2"))(
            _f(c), _f(c).str_transform("s", lambda v: v[:1])),
    **{f"join_{how}": (lambda c, how=how: _joined(c, how).select(
        *(("d", "w") if how in ("inner", "left") else ("d", "a"))))
       for how in ("inner", "left", "semi", "anti")},
    **{f"join_general_{how}": (
        lambda c, how=how: _joined(c, how, unique=False).select(
            *(("d", "y") if how in ("inner", "left") else ("d",))))
       for how in ("inner", "left", "semi", "anti")},
    "join_right": lambda c: _joined(c, "right").select("d", "w", "t"),
    "join_full": lambda c: _joined(c, "full").select("a", "uk"),
    "join_then_filter_on_build": lambda c: (
        lambda j: j.filter(ex.Cmp("lt", j.c("v"), ex.lit(4)))
        .select("d", "t"))(_joined(c, "inner")),
    "two_joins": lambda c: (
        lambda j: j.join(Rel.scan(c, "g"), on=[("uk", "gk")], how="inner",
                         build_unique=False).select("d", "y", "t"))(
            _joined(c, "inner")),
    "groupby": lambda c: _f(c).groupby(
        ["s"], [("n", "count_rows", None), ("sa", "sum", "a")]),
    "groupby_over_join": lambda c: _joined(c, "left").groupby(
        ["v"], [("sw", "sum", "w"), ("mx", "max", "d")]).sort(
            [("v", False)]),
    "scalar_agg": lambda c: _f(c).scalar_agg(
        [("n", "count_rows", None), ("mb", "min", "b")]),
    "sort": lambda c: _f(c).select("d", "a", "b").sort(
        [("a", True), ("d", False)]).select("d"),
    "sort_limit_topk": lambda c: _f(c).sort(
        [("a", True), ("d", False)]).limit(7, 2).select("d", "s"),
    "limit": lambda c: _f(c).limit(9).select("k"),
    "distinct": lambda c: _f(c).distinct(["s", "b"]).sort(
        [("s", False), ("b", False)]),
    "distinct_under_join": lambda c: _f(c).distinct(["k"]).join(
        Rel.scan(c, "u"), on=[("k", "uk")], how="inner").select("k", "w"),
    # barriers: every input keeps all its columns
    "window": lambda c: _f(c).window(
        ["s"], [("d", False)], [("rn", "row_number", None)]).select(
            "d", "rn"),
    "union_all": lambda c: _f(c).select("k", "a").union_all(
        _f(c).select("d", "b")).select("k"),
    "merge_join": lambda c: _f(c).merge_join(
        Rel.scan(c, "u"), ("k", "uk")).select("d", "w"),
    "cross_join": lambda c: Rel.scan(c, "u").filter(
        ex.Cmp("lt", Rel.scan(c, "u").c("uk"), ex.lit(3))).cross_join(
            Rel.scan(c, "g").limit(4)).select("uk", "y"),
}
# pipelines whose scans the pass must have cut (the others sit under a
# barrier that needs every column, or read them all)
NARROWER = {
    "filter_project": {"f": [("k", "a", "d")]},
    "project_expr": {"f": [("a", "b")]},
    "string_predicate": {"f": [("s", "d")]},
    "string_transform": {"f": [("s", "d")]},
    "join_inner": {"f": [("k", "b", "d")], "u": [("uk", "w")]},
    "join_left": {"f": [("k", "b", "d")], "u": [("uk", "w")]},
    "join_semi": {"f": [("k", "a", "b", "d")], "u": [("uk",)]},
    "join_anti": {"f": [("k", "a", "b", "d")], "u": [("uk",)]},
    "join_general_inner": {"f": [("k", "b", "d")], "g": [("gk", "y")]},
    "join_general_semi": {"f": [("k", "b", "d")], "g": [("gk",)]},
    "join_then_filter_on_build": {"f": [("k", "b", "d")],
                                  "u": [("uk", "v", "t")]},
    "two_joins": {"f": [("k", "b", "d")], "u": [("uk", "t")],
                  "g": [("gk", "y")]},
    "groupby": {"f": [("a", "s")]},
    "groupby_over_join": {"f": [("k", "b", "d")], "u": [("uk", "v", "w")]},
    "scalar_agg": {"f": [("b",)]},
    "sort": {"f": [("a", "d")]},
    "sort_limit_topk": {"f": [("a", "s", "d")]},
    "limit": {"f": [("k",)]},
    "distinct": {"f": [("b", "s")]},
    "distinct_under_join": {"f": [("k",)], "u": [("uk", "w")]},
    "window": {"f": None},
    "union_all": {"f": [("k", "a"), ("b", "d")]},
    "merge_join": {"f": None, "u": None},
}


@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_rel_pipeline_answers_the_same_pruned_and_unpruned(small, name):
    rel = PIPELINES[name](small)
    pruned = rel.optimized_plan()
    want = NARROWER.get(name)
    if want is not None:
        got = _scans(pruned)
        whole = {t: [small.get(t).schema.names] * len(got[t])
                 for t, cols in want.items() if cols is None}
        assert got == {**want, **whole}
    _assert_same_answer(run_plan(pruned, small), run_plan(rel.plan, small))


def test_every_position_follows_its_column(small):
    """Sort keys, group columns, aggregate inputs, join keys, a string
    lookup's column and a Project's dictionary overrides are remapped in
    the same walk."""
    f = _f(small)
    e, d = f.str_transform("s", lambda v: v.upper())
    rel = (f.project([("a", f.c("a")), ("s2", e), ("d", f.c("d"))])
           .with_dict("s2", d))
    rel = rel.join(Rel.scan(small, "u"), on=[("a", "uk")], how="inner")
    rel = rel.select("w", "s2", "d").sort([("d", True)])
    plan = rel.optimized_plan()
    (join,) = _nodes(plan, S.HashJoin)
    assert (join.probe_keys, join.build_keys) == ((0,), (0,))
    inner = join.probe
    assert isinstance(inner, S.Project) and inner.names == ("a", "s2", "d")
    assert [i for i, _d in inner.dict_overrides] == [1]
    assert isinstance(inner.exprs[1], ex.CodeLookup) and inner.exprs[1].col == 1
    assert _scans(plan) == {"f": [("a", "s", "d")], "u": [("uk", "w")]}
    (srt,) = _nodes(plan, S.Sort)
    assert [k.col for k in srt.keys] == [2]
    got = run_plan(plan, small)
    _assert_same_answer(got, run_plan(rel.plan, small))
    assert set(got["s2"]) <= {"RED", "GREEN", "BLUE", "PLUM"}


def test_expression_walkers_see_every_positional_leaf():
    table = np.array([True, False])
    e = ex.Case(
        ((ex.Cmp("eq", ex.ColRef(4), ex.Param(0, INT64)),
          ex.CodeLookup(col=2, table=table)),),
        ex.Coalesce((ex.ParamLookup(7, 1, 2, "bool"),
                     ex.IsNull(ex.ColRef(4)))))
    assert expr_refs(e) == {2, 4, 7}
    m = {2: 0, 4: 1, 7: 2}
    assert expr_refs(remap_expr(e, m)) == {0, 1, 2}
    assert remap_expr(e, {2: 2, 4: 4, 7: 7}) is e  # a lookup table keeps its identity


# -- (d) ---------------------------------------------------------------------


def test_count_star_keeps_one_column(cat):
    rel = sql(cat, "select count(*) from lineitem")
    assert _scans(rel.optimized_plan()) == {"lineitem": [("l_orderkey",)]}
    assert rel.run()["count"].tolist() == [cat.get("lineitem").num_rows]
    rel = sql(cat, "select count(*) from orders where o_custkey < 40")
    assert _scans(rel.optimized_plan()) == {"orders": [("o_custkey",)]}


@pytest.mark.parametrize("wrap", [
    lambda n: S.Exchange(n, (0,)), S.Broadcast, S.Gather,
    lambda n: S.HashBucket(n, (0,), 2, 0), lambda n: S.Union((n, n)),
    lambda n: S.StreamUnion((n,)),
    lambda n: S.Window(n, (0,), (), ()),
    lambda n: S.Aggregate(n, (0,), (), mode="partial"),
    lambda n: S.Distinct(n, None)],
    ids=["exchange", "broadcast", "gather", "hashbucket", "union",
         "streamunion", "window", "partial_aggregate", "distinct_all"])
def test_a_node_without_a_rule_requires_all_its_inputs_columns(small, wrap):
    """Above the barrier only `k` is read; below it the scan keeps every
    column, and the subtree under the barrier is still pruned on its own
    (the filter's join below keeps what the barrier's input hands up)."""
    f = _f(small)
    below = f.join(Rel.scan(small, "u"), on=[("k", "uk")], how="semi")
    barrier = wrap(below.plan)
    plan = S.Project(barrier, (ex.ColRef(0),), ("k",))
    got = prune_columns(plan, small)
    assert isinstance(got, S.Project) and type(got.input) is type(barrier)
    scans = _scans(got)
    assert set(scans["f"]) == {small.get("f").schema.names}  # untouched
    assert set(scans["u"]) == {("uk",)}  # the semi join's own rule


def test_a_remote_stream_and_a_virtual_table_are_left_alone(small):
    leaf = S.RemoteStream(("h", 1), "flow", 0, small.get("u").schema)
    plan = S.Project(S.Filter(leaf, ex.Cmp("lt", ex.ColRef(1), ex.lit(3))),
                     (ex.ColRef(2),), ("w",))
    got = prune_columns(plan, small)
    assert got.input.input == leaf
    assert got.input.predicate == plan.input.predicate
    rel = sql(small, "select name from crdb_internal.node_metrics")
    assert (_nodes(rel.optimized_plan(), S.TableScan)
            == _nodes(rel.plan, S.TableScan))


def test_a_plan_with_nothing_to_cut_comes_back_as_it_was(small):
    rel = _f(small).filter(ex.Cmp("lt", _f(small).c("a"), ex.lit(25)))
    plan = S.TableScan("f", None)
    assert prune_columns(plan, small) == plan
    assert prune_columns(rel.plan, small) == rel.plan


# -- (e) ---------------------------------------------------------------------


def _tag(name):
    return tracing.totals().get("flow/pull", {"tags": {}})["tags"].get(name, 0)


@pytest.mark.parametrize("qname,widths", [("q13", [3]), ("q3", [7, 8])])
def test_join_output_columns_is_written_once_a_probe_tile(
        cat, qname, widths, monkeypatch):
    seen = []
    real = operators.HashJoinOp._note_probe_tile

    def spy(self, t, src=None, composed=False):
        seen.append(len(self.output_schema))
        return real(self, t, src, composed)

    monkeypatch.setattr(operators.HashJoinOp, "_note_probe_tile", spy)
    settings.set("sql.distsql.tile_size", 1024)
    sess = Session(cat)
    try:
        sess.execute(" ".join(TPCH_SQL[qname].split()))  # learns its caps
        seen.clear()
        c0, t0 = _tag("join_output_columns"), (
            _tag("join_unique_tiles") + _tag("join_general_tiles"))
        sess.execute(" ".join(TPCH_SQL[qname].split()))
        cols = _tag("join_output_columns") - c0
        tiles = _tag("join_unique_tiles") + _tag("join_general_tiles") - t0
    finally:
        sess.close()
        settings.reset("sql.distsql.tile_size")
    assert sorted(set(seen)) == widths
    assert len(seen) == tiles and cols == sum(seen)
