"""TPC-H Q9 as a served deployment (PR 28): the text of clause 2.4.9 with
its COLOR parameter through Session -> parser -> binder -> plan cache ->
flow, held to the benchmark's float64 pandas reference
(benchmarks/oracles/tpch_q9.py); a new colour is a plan-cache hit that
rebinds the pattern's lookup table and compiles nothing; the default join
order places the filtered `part` join first; the tags the cell's
per-layer metrics read; the four joins above `part`, handed tiles already
cut to their own cap, compose into the aggregate (PR 29); the `part` join's
own emit, and q3's `orders` join's, cut the tile to the learned cap before
they gather a build column (PR 31: `join_late_emit_tiles`)."""

import json
import os
import sys

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.flow import dispatch, operators
from cockroach_tpu.sql import Session, binder as binder_mod, plancache, sql
from cockroach_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
Q9 = " ".join(TPCH_SQL["q9"].split()).replace("%green%", "%{color}%")
# plans the reducing-first default order changed (PR 28), and plans whose
# IN-subquery's semi-join went below the joins, onto the one source its
# column belongs to (PR 32); the other 15 TPC-H texts keep the parent's plan
# byte for byte
REORDERED = {"q2", "q5", "q8", "q9", "q21"}
SEMI_PLACED = {"q18", "q20"}
with open(os.path.join(ROOT, "tests", "data", "tpch_explain_pr27.json")) as f:
    GOLDEN = json.load(f)  # explain() of the 22 texts on the parent (a4c6576)


class _Host:
    """What the benchmark's oracle needs of a loader's `Loaded`."""

    def __init__(self, cat):
        if BENCH not in sys.path:
            sys.path.insert(0, BENCH)
        from loaders.tpch import Loaded

        self.tables = dict(cat.tables)
        self.frame = lambda t, cols: Loaded.frame(self, t, cols)


def _reference(host, color):
    from oracles import tpch_q9

    return tpch_q9.answer(host, {"color": color})


def _assert_answer(got, want):
    assert list(got) == ["nation", "o_year", "sum_profit"]
    assert [str(v) for v in got["nation"]] == list(want.nation)
    np.testing.assert_array_equal(np.asarray(got["o_year"]),
                                  want.o_year.to_numpy())
    np.testing.assert_allclose(np.asarray(got["sum_profit"], np.float64),
                               want.sum_profit.to_numpy(), rtol=1e-9, atol=0)


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.01, seed=2**31 + 28)


@pytest.fixture(scope="module")
def host(cat):
    return _Host(cat)


@pytest.fixture(scope="module")
def sess(cat):
    s = Session(cat)
    yield s
    s.close()
    from cockroach_tpu.sql import sqlstats

    sqlstats.DEFAULT.clear()


@pytest.fixture(scope="module")
def settled(sess):
    """One colour until a run compiles nothing: the emission caps are
    learned and the plan is in the cache."""
    for _ in range(4):
        c0 = dispatch.compiles()
        sess.execute(Q9.format(color="green"))
        if dispatch.compiles() == c0:
            return len(plancache.cache_for(sess.catalog))
    raise AssertionError("q9 still compiles in its fourth run")


@pytest.mark.parametrize("color", ["green", "red", "ivory"])
def test_q9_served_equals_the_reference(sess, host, color):
    want = _reference(host, color)
    assert 100 < len(want) <= 175
    _assert_answer(sess.execute(Q9.format(color=color)), want)


@pytest.mark.parametrize("color", ["salmon", "thistle", "no such colour"])
def test_a_new_colour_compiles_nothing(sess, host, settled, color):
    cache = plancache.cache_for(sess.catalog)
    c0, h0 = dispatch.compiles(), cache.hits
    got = sess.execute(Q9.format(color=color))
    assert dispatch.compiles() == c0
    assert len(cache) == settled and cache.hits == h0 + 1
    # its own answer: a stale table would give the settled colour's
    _assert_answer(got, _reference(host, color))
    if color == "no such colour":
        assert len(got["nation"]) == 0


# a settled q9 at SF0.01, one lineitem tile: PR 28 (a57b729) issued 14
# programs a statement, five of them `hashjoin_emit`; since PR 29 the four
# joins above `part` ride in the aggregate's fold kernel, and since PR 31
# the one `hashjoin_emit` left (the `part` join's) takes the compaction's
# index from the probe and gathers `part`'s columns once, at its cap: the
# same 10 programs, one tile counted into `join_late_emit_tiles`
PARENT_SETTLED_DISPATCHES = 14


@pytest.mark.parametrize("color", ["orchid", "navy", "linen"])
def test_settled_q9_composes_the_upper_joins_into_the_aggregate(
        sess, host, settled, color):
    t0, d0 = _tags(), dispatch.total()
    got = sess.execute(Q9.format(color=color))
    t1, d1 = _tags(), dispatch.total()
    _assert_answer(got, _reference(host, color))
    assert t1["passed"] - t0["passed"] == 4  # four joins, one tile each
    assert t1["late"] - t0["late"] == 1  # the `part` join's one tile
    assert t1["unique"] - t0["unique"] == 5
    assert d1 - d0 == PARENT_SETTLED_DISPATCHES - 4


def test_another_columns_dictionary_keys_a_new_plan(cat):
    def key(text):
        pplan, values, types = plancache.parameterize(
            sql(cat, text).optimized_plan())
        return plancache.plan_key(pplan), values, types

    base = "select count(*) as n from part where {}"
    k1, v1, t1 = key(base.format("p_name like '%green%'"))
    k2, v2, _ = key(base.format("p_name like '%red%'"))
    k3, _, t3 = key(base.format("p_type like '%BRASS%'"))
    assert k1 == k2 and not np.array_equal(v1[0], v2[0])
    assert len(t1) == 1 and t1[0].size == len(v1[0]) and t1[0].dtype == "bool"
    assert k1 != k3 and t1 != t3
    # a standing view's slots are scalars: its tables stay baked in the key
    _p, values, _t = plancache.parameterize(
        sql(cat, base.format("p_name like '%green%'")).optimized_plan(),
        tables=False)
    assert values == ()


def _join_lines(text):
    return [ln.strip() for ln in text.splitlines()
            if "hash-join" in ln or "-> scan" in ln or "-> filter" in ln]


def test_q9_joins_the_filtered_part_first(cat):
    lines = _join_lines(sql(cat, Q9.format(color="green")).explain())
    joins = [ln for ln in lines if "hash-join" in ln]
    assert len(joins) == 5 and all("(unique build)" in ln for ln in joins)
    i = lines.index(joins[-1])  # the innermost join
    assert lines[i + 1].startswith("-> scan lineitem")
    assert lines[i + 2].startswith("-> filter CodeLookup")
    assert lines[i + 3].startswith("-> scan part")


@pytest.fixture(scope="module")
def gcat():
    return tpch.gen_tpch(sf=0.002, seed=11)  # the golden strings' catalog


def _binders_plan(rel):
    """EXPLAIN of what the binder chose (join order, semi-join placement),
    as the golden strings hold it: before plan/prune.py (PR 38) cuts every
    scan to the columns read, which tests/test_prune_columns.py holds."""
    from cockroach_tpu.plan.explain import explain_plan
    from cockroach_tpu.plan.indexopt import use_indexes
    from cockroach_tpu.plan.topkopt import push_topk

    return explain_plan(push_topk(use_indexes(rel.plan, rel.catalog)),
                        rel.catalog)


@pytest.mark.parametrize("qname", sorted(TPCH_SQL, key=lambda q: int(q[1:])))
def test_tpch_plans_against_the_parents(gcat, qname, monkeypatch):
    now = sql(gcat, TPCH_SQL[qname])
    if qname not in REORDERED | SEMI_PLACED:
        assert _binders_plan(now) == GOLDEN[qname]
        return
    assert _binders_plan(now) != GOLDEN[qname]
    with monkeypatch.context() as m:
        m.setattr(binder_mod.Binder, "_build_rank",
                  staticmethod(lambda s: (1, 0.0)))
        m.setattr(binder_mod.Binder, "_semi_filter_source",
                  lambda self, sub_join, scope: False)
        parent = sql(gcat, TPCH_SQL[qname])
    assert _binders_plan(parent) == GOLDEN[qname]
    got, want = now.run(), parent.run()
    assert list(got) == list(want)
    for col in want:
        g, w = got[col], want[col]
        assert len(g) == len(w), f"{col}: {len(g)} vs {len(w)} rows"
        if w.dtype.kind == "f":
            np.testing.assert_allclose(g, w, rtol=1e-9, err_msg=col)
        else:
            np.testing.assert_array_equal(g, w, err_msg=col)


def _tags():
    t = tracing.totals()
    pull = t.get("flow/pull", {"tags": {}})["tags"]
    query = t.get("query", {"tags": {}})["tags"]
    return {"rows": pull.get("join_probe_tile_rows", 0),
            "unique": pull.get("join_unique_tiles", 0),
            "general": pull.get("join_general_tiles", 0),
            "passed": pull.get("join_passthrough_tiles", 0),
            "late": pull.get("join_late_emit_tiles", 0),
            "tables": query.get("lookup_tables_bound", 0)}


def test_the_tags_the_cells_metrics_read(sess, settled, monkeypatch):
    handed = []
    real = operators.HashJoinOp._note_probe_tile

    def spy(self, t, src=None, composed=False):
        cap = getattr(t, "capacity", None)
        if cap is None:  # a resident scan's (table batch, offset) token
            while not hasattr(src, "_res_tile"):
                src = src.src
            cap = src._res_tile
        handed.append((int(cap), composed))
        return real(self, t, src, composed)

    monkeypatch.setattr(operators.HashJoinOp, "_note_probe_tile", spy)
    t0 = _tags()
    sess.execute(Q9.format(color="plum"))
    t1 = _tags()
    assert len(handed) == 5  # one tile a join at SF0.01
    caps = [cap for cap, _composed in handed]
    assert t1["rows"] - t0["rows"] == sum(caps)
    # the `part` join emits (full tile in, its cap out); the four above
    # are handed that cap, which is theirs too, and compose
    assert [composed for _cap, composed in handed] == [False] + [True] * 4
    assert caps[0] == max(caps) and set(caps[1:]) == {min(caps)}
    assert min(caps) < max(caps)
    assert t1["passed"] - t0["passed"] == 4
    # the one tile that was emitted was cut to the cap before the build
    # side was gathered: the first join's tile count
    assert t1["late"] - t0["late"] == 1
    assert t1["unique"] - t0["unique"] == 5
    assert t1["general"] - t0["general"] == 0
    assert t1["tables"] - t0["tables"] == 1
    sess.execute(" ".join(TPCH_SQL["q1"].split()))
    t2 = _tags()
    assert t2["tables"] - t1["tables"] == 0
    assert t2["rows"] - t1["rows"] == 0
    assert t2["passed"] - t1["passed"] == 0
    assert t2["late"] - t1["late"] == 0


def _pull_tags():
    pull = tracing.totals()["flow/pull"]
    return (pull["count"], pull["tags"].get("join_probe_tile_rows", 0),
            pull["tags"].get("join_passthrough_tiles", 0),
            pull["tags"].get("join_late_emit_tiles", 0))


def _run(sess, color, passed=None, late=None):
    """(answer, attempts, probe-tile rows, programs compiled) of one q9;
    ``passed``: the probe tiles its compact-mode joins composed into their
    consumer must number this; ``late``: the tiles a join cut to its cap
    before it gathered its build side, this."""
    (p0, r0, t0, l0), c0 = _pull_tags(), dispatch.compiles()
    got = sess.execute(Q9.format(color=color))
    p1, r1, t1, l1 = _pull_tags()
    if passed is not None:
        assert t1 - t0 == passed
    if late is not None:
        assert l1 - l0 == late
    return got, p1 - p0, r1 - r0, dispatch.compiles() - c0


def test_a_wide_pattern_overflows_to_the_right_answer_and_the_caps_come_back(
        sess, host, settled):
    """'%a%' keeps nearly every part: the joins' emission caps were learned
    on a colour that keeps a twentieth, so the run overflows and re-runs
    once, every join counting at full tiles, and answers exactly. The plan
    is every colour's: the next narrow colour runs once at full tiles,
    the one after it on the caps learned before, the four upper joins
    composed into the aggregate again, and none of it compiles: the
    aggregate's kernel over the composed chain and its kernel over the
    joins' own tiles are both kept."""
    cache = plancache.cache_for(sess.catalog)
    entries = len(cache)
    _got, pulls, steady, compiled = _run(sess, "green", passed=4, late=1)
    assert (pulls, compiled) == (1, 0)
    # the first attempt still composes, above the `part` join's late emit;
    # the re-run counts at every join, at full tiles (learn: no cap to cut
    # to, so the aligned emission)
    got, pulls, _rows, compiled = _run(sess, "a", passed=4, late=1)
    want = _reference(host, "a")
    assert len(want) > 150
    _assert_answer(got, want)
    assert pulls == 2  # it overflowed, and one re-run was enough
    assert compiled == 0  # the full-tile programs are the first run's
    got, pulls, full, compiled = _run(sess, "green", passed=0, late=0)
    _assert_answer(got, _reference(host, "green"))
    assert (pulls, compiled) == (1, 0) and full > steady
    got, pulls, rows, compiled = _run(sess, "red", passed=4, late=1)
    _assert_answer(got, _reference(host, "red"))
    assert (pulls, rows, compiled) == (1, steady, 0)
    assert len(cache) == entries


def test_an_overflow_below_recounts_every_join_above_in_one_rerun():
    """Tiles wide enough (262,144 rows over a 65,536 cap) that a join above
    an overflowed one could double its cap on the tiles that were cut
    short and overflow in the re-run, one join an attempt: five joins would
    use up the runtime's four attempts. They count again together."""
    from cockroach_tpu.utils import settings

    cat = tpch.gen_tpch(sf=0.05, seed=2**31 + 29)
    settings.set("sql.distsql.tile_size", 262144)
    s = Session(cat)
    try:
        for _ in range(2):
            s.execute(Q9.format(color="green"))
        # the four joins above the one that will overflow pass its tiles
        # through: they hold no count of their own to notice it by
        _got, pulls, steady, _c = _run(s, "green", passed=2 * 4)
        assert pulls == 1 and steady == 2 * (262144 + 4 * 65536)  # 2 tiles
        got, pulls, _rows, _c = _run(s, "a", passed=2 * 4)
        assert pulls == 2
        _assert_answer(got, _reference(_Host(cat), "a"))
        _got, pulls, rows, _c = _run(s, "green", passed=0)
        assert (pulls, rows) == (1, 2 * 5 * 262144)
        _got, pulls, rows, _c = _run(s, "green", passed=2 * 4)
        assert (pulls, rows) == (1, steady)
    finally:
        s.close()
        settings.reset("sql.distsql.tile_size")


def test_the_cells_loader_holds_this_program_to_its_plans_guarantee():
    """`benchmarks/loaders/tpch_rebind.py`, the loader of the configuration
    `tpch_sf1_q9`, ends a run on a program that compiles for a new string
    pattern (the parent compiles 2 programs there): this one compiles 0."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from loaders import tpch_rebind

    assert tpch_rebind.compiles_for_a_new_pattern(2**31 + 30) == 0


Q3 = " ".join(TPCH_SQL["q3"].split()).replace("1995-03-15", "{date}")


def test_q3s_first_join_cuts_its_tile_before_it_gathers_orders(cat, host):
    """The other cell's text (clause 2.4.3) at the same scale: the learn
    run emits probe-aligned and counts nothing late; every statement after
    it cuts the `orders` join's one lineitem tile to the learned cap
    first. At this scale the `customer` join above really shrinks what it
    is handed (8,192 rows into 1,024), so it still drives its own emit
    (PR 29) and that emit is late as well; at SF1 it is handed its own cap
    and composes, and only the first join's six tiles count. Each answer
    is the float64 pandas oracle's (benchmarks/oracles/tpch_q3.py)."""
    import datetime

    from oracles import tpch_q3

    epoch = datetime.date(1970, 1, 1)
    s = Session(cat)
    try:
        for i, date in enumerate(["1995-03-15", "1995-03-04", "1995-03-28"]):
            t0, d0 = _tags(), dispatch.total()
            got = s.execute(Q3.format(date=date))
            t1, issued = _tags(), dispatch.total() - d0
            want = tpch_q3.answer(host, {"date": date})
            assert len(want) == 10
            assert list(got) == ["l_orderkey", "revenue", "o_orderdate",
                                 "o_shippriority"]
            np.testing.assert_array_equal(np.asarray(got["l_orderkey"]),
                                          want.l_orderkey.to_numpy())
            np.testing.assert_allclose(
                np.asarray(got["revenue"], np.float64),
                want.revenue.to_numpy(), rtol=1e-9, atol=0)
            days = [(d - epoch).days if isinstance(d, datetime.date) else
                    int(d) for d in np.asarray(got["o_orderdate"]).tolist()]
            assert days == want.o_orderdate.tolist()
            # one lineitem tile at SF0.01: two joins, two probe tiles
            assert t1["unique"] - t0["unique"] == 2
            assert t1["passed"] - t0["passed"] == 0
            assert t1["late"] - t0["late"] == (0 if i == 0 else 2)
            if i == 1:
                settled = issued
            elif i == 2:
                assert issued == settled
    finally:
        s.close()
