"""A guard against a wrong claim (PR 40): an operator that says
`emits_live_prefix` has its tiles placed by a join build at a running offset
(coldata/batch.py `concat_prefix`), with no index to find the live rows, so
every tile such an operator hands out, through `next_batch` or through a
join's build spool, must have its mask equal to ``arange < sum(mask)``.

Checked on the served TPC-H texts that put an aggregate on a build side
(q21, q18, and q17, q20, whose aggregates do not stream today) on the route
the chip takes at SF1 (ordered, streaming
aggregates: `sql.distsql.dense_agg_states` at its floor, 1,024-row tiles so
a build spools several), and on a hand-built plan each for
Project-over-aggregate (claims, passes the mask on) and
Filter-over-aggregate (must not claim). Each text's answer on the placed
route equals its answer with every claim withdrawn (the gather route).
"""

import collections

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.catalog import Catalog, Table
from cockroach_tpu.coldata.types import INT64, Schema
from cockroach_tpu.flow import operators
from cockroach_tpu.flow.operator import Operator
from cockroach_tpu.flow.runtime import run_operator
from cockroach_tpu.ops import expr as ex
from cockroach_tpu.ops.aggregation import AggSpec
from cockroach_tpu.ops.join import JoinSpec
from cockroach_tpu.plan import builder, spec as S
from cockroach_tpu.sql import Session, plancache
from cockroach_tpu.utils import settings, tracing

TILE = 1024


@pytest.fixture(scope="module", autouse=True)
def the_route_the_chip_takes():
    settings.set("sql.distsql.dense_agg_states", 64)
    settings.set("sql.distsql.tile_size", TILE)
    yield
    settings.reset("sql.distsql.dense_agg_states")
    settings.reset("sql.distsql.tile_size")
    from cockroach_tpu.sql import sqlstats

    sqlstats.DEFAULT.clear()


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.001, seed=2**31 + 40)


@pytest.fixture()
def checked(monkeypatch):
    """{operator class: tiles checked}: every tile a claimant hands out,
    pulled batch by batch or spooled by a join's build, is held to the
    claim as it passes."""
    seen = collections.Counter()

    def check(op, b):
        if b is None or not op.emits_live_prefix:
            return
        mask = np.asarray(b.mask)
        assert np.array_equal(mask, np.arange(len(mask)) < mask.sum()), (
            f"{op.label or type(op).__name__} claims live-prefix tiles and "
            f"handed out a mask with holes ({int(mask.sum())} live)")
        seen[type(op).__name__] += 1

    pull = Operator.next_batch
    spool = operators._consume_op

    def next_batch(self):
        b = pull(self)
        check(self, b)
        return b

    def consume_op(op, tag):
        for b in spool(op, tag):
            check(op, b)
            yield b

    monkeypatch.setattr(Operator, "next_batch", next_batch)
    monkeypatch.setattr(operators, "_consume_op", consume_op)
    return seen


def _placed():
    pull = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
    return pull.get("join_build_placed_tiles", 0)


def _run(cat, text):
    """(answer, tiles placed) of one statement on a plan of its own."""
    plancache.cache_for(cat).clear()
    s = Session(cat)
    try:
        p0 = _placed()
        return s.execute(text), _placed() - p0
    finally:
        s.close()
        plancache.cache_for(cat).clear()


def _assert_same(got, want):
    assert list(got) == list(want)
    for c in want:
        np.testing.assert_array_equal(np.asarray(got[c]),
                                      np.asarray(want[c]), err_msg=c)


# text -> (build tiles placed, claimants' tiles checked) a statement at
# SF0.001 in 1,024-row tiles: q21's two aggregates are the build sides
# themselves (six streamed tiles and the tail each, checked as the
# aggregate hands them out and again as the join spools them); q18's streaming
# aggregate sits under the HAVING's Filter, so its seven tiles are checked
# and its join must not place them; q17 and q20 group by l_partkey, which
# `lineitem` is not clustered on, so nothing streams and nobody claims
# (the cases hold a planner that one day streams them to the same check)
_TEXTS = {"q21": (14, 28), "q18": (0, 7), "q17": (0, 0), "q20": (0, 0)}


@pytest.mark.parametrize("name", list(_TEXTS))
def test_every_claimant_of_a_served_text_hands_out_prefix_tiles(
        cat, checked, monkeypatch, name):
    text = " ".join(TPCH_SQL[name].split())
    got, placed = _run(cat, text)
    assert (placed, sum(checked.values())) == _TEXTS[name]
    # every claim withdrawn: the same statement through `concat`
    monkeypatch.setattr(operators.AggregateOp, "emits_live_prefix", False)
    want, gathered = _run(cat, text)
    assert gathered == 0
    _assert_same(got, want)


# ---- hand-built: what passes the claim on and what must not

ROWS, GROUPS = 5000, 1700


def _catalog():
    rng = np.random.default_rng(40)
    g = np.sort(rng.integers(1, GROUPS + 1, ROWS)).astype(np.int64)
    cat = Catalog()
    cat.add(Table.from_strings(
        "fact", Schema.of(g=INT64, v=INT64),
        {"g": g, "v": rng.integers(0, 1000, ROWS)}, ordering=("g",)))
    cat.add(Table.from_strings(
        "probe", Schema.of(k=INT64, p=INT64),
        {"k": rng.integers(-3, GROUPS + 9, 3000), "p": np.arange(3000)}))
    return cat, g, cat.get("fact").columns["v"], cat.get("probe").columns["k"]


def _join_over(build):
    agg = S.Aggregate(S.TableScan("fact"), (0,),
                      (AggSpec("min", 1, "lo"), AggSpec("max", 1, "hi")))
    return S.HashJoin(S.TableScan("probe"), build(agg), (0,), (0,),
                      JoinSpec(join_type="inner", build_unique=True))


def _find(op, cls):
    if isinstance(op, cls):
        return op
    return next((f for c in op.children()
                 if (f := _find(c, cls)) is not None), None)


def test_a_project_over_a_streaming_aggregate_passes_the_claim_on(checked):
    cat, g, v, k = _catalog()
    root = builder.build(_join_over(lambda agg: S.Project(
        agg, (ex.ColRef(0), ex.BinOp("-", ex.ColRef(2), ex.ColRef(1))),
        ("g", "spread"))), cat)
    join = _find(root, operators.HashJoinOp)
    assert isinstance(join.build, operators.ProjectOp)
    assert _find(join.build, operators.AggregateOp).streaming
    assert join.build.emits_live_prefix
    p0 = _placed()
    with tracing.span("test.statement"):  # `flow/pull` is a leaf span
        got = run_operator(root)
    assert join._places_build
    tiles = -(-ROWS // TILE) + 1  # one a streamed tile and the tail
    assert _placed() - p0 == tiles
    assert checked["ProjectOp"] == tiles and checked["AggregateOp"] == tiles
    spread = {key: v[g == key].max() - v[g == key].min()
              for key in np.unique(g)}
    hit = np.isin(k, g)
    np.testing.assert_array_equal(np.sort(np.asarray(got["p"])),
                                  np.nonzero(hit)[0])
    by_p = dict(zip(np.asarray(got["p"]).tolist(),
                    np.asarray(got["spread"]).tolist()))
    assert by_p == {int(p): int(spread[k[p]]) for p in np.nonzero(hit)[0]}


def test_a_filter_over_a_streaming_aggregate_claims_nothing(checked):
    cat, g, v, k = _catalog()
    keep = ex.Cmp("ge", ex.ColRef(2), ex.lit(500))  # max(v) >= 500
    root = builder.build(_join_over(lambda agg: S.Filter(agg, keep)), cat)
    join = _find(root, operators.HashJoinOp)
    assert isinstance(join.build, operators.FilterOp)
    agg = _find(join.build, operators.AggregateOp)
    assert agg.streaming and agg.emits_live_prefix
    assert not join.build.emits_live_prefix
    p0 = _placed()
    with tracing.span("test.statement"):  # `flow/pull` is a leaf span
        got = run_operator(root)
    assert not join._places_build and _placed() == p0
    assert checked["AggregateOp"] > 1 and "FilterOp" not in checked
    kept = {key for key in np.unique(g) if v[g == key].max() >= 500}
    assert 0 < len(kept) < len(np.unique(g))  # the filter leaves holes
    np.testing.assert_array_equal(
        np.sort(np.asarray(got["p"])),
        np.nonzero(np.isin(k, sorted(kept)))[0])
