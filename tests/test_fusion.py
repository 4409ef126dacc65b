"""Fusion-equivalence sweep + pull-loop readback tests.

`sql.distsql.fusion.enabled=off` degrades the engine to classic
one-jit-per-operator pulls (both the plan-build pass in flow/fuse.py and
the consumer-driven spool fusion in flow/operators.py) — the oracle every
fused run must match bit-for-bit, including the speculative-capacity retry
path; the pull loop's one-tile readback lag returns every tile in order.

A representative subset runs tier-1; the full TPC-H + TPC-DS corpus is
marked slow (compile-bound: each fused chain jits per query)."""

import numpy as np
import pytest

from cockroach_tpu.bench import queries as Q
from cockroach_tpu.bench import tpcds, tpch
from cockroach_tpu.utils import settings

# tier-1 representatives: dense group-by (q1), join chain + top-k (q3),
# scalar agg (q6), 5-way join + expr group (q9), semi-join style agg (q18)
_FAST_TPCH = {"q1", "q3", "q6", "q9", "q18"}
_FAST_TPCDS = {"q3", "q42"}


@pytest.fixture(scope="module")
def hcat():
    return tpch.gen_tpch(sf=0.005, seed=7)


@pytest.fixture(scope="module")
def dcat():
    return tpcds.gen_tpcds(sf=0.01)


def _run(rel, fusion: bool):
    settings.set("sql.distsql.fusion.enabled", fusion)
    try:
        return rel.run()
    finally:
        settings.reset("sql.distsql.fusion.enabled")


def _assert_identical(got, want):
    assert set(got) == set(want)
    for name in want:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape, name
        if g.dtype == object or w.dtype == object:
            assert list(g) == list(w), name
        else:
            # bit-identical, not allclose: fusion must not reassociate
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize(
    "qname",
    [pytest.param(q, marks=() if q in _FAST_TPCH else (pytest.mark.slow,))
     for q in sorted(Q.QUERIES)],
)
def test_tpch_fusion_equivalence(hcat, qname):
    rel = Q.QUERIES[qname](hcat)
    _assert_identical(_run(rel, fusion=True), _run(rel, fusion=False))


@pytest.mark.parametrize(
    "qname",
    [pytest.param(q, marks=() if q in _FAST_TPCDS else (pytest.mark.slow,))
     for q in sorted(tpcds.QUERIES)],
)
def test_tpcds_fusion_equivalence(dcat, qname):
    rel = tpcds.QUERIES[qname](dcat)
    _assert_identical(_run(rel, fusion=True), _run(rel, fusion=False))


@pytest.mark.parametrize("tiles", [0, 1, 3])
def test_pull_loop_returns_every_tile_in_order(tiles):
    """The pull loop materializes tile k while the root computes tile
    k+1: the lag must neither drop the last tile nor reorder any, for a
    root that yields no tile, one tile, and several."""
    from cockroach_tpu.catalog import Table
    from cockroach_tpu.coldata.types import INT64, Schema
    from cockroach_tpu.flow import runtime
    from cockroach_tpu.flow.operators import ScanOp

    tile = 128

    class FirstTiles(ScanOp):
        def _next(self):
            if self._offset >= tiles * tile:
                return None
            return super()._next()

    table = Table(name="seq", schema=Schema(("k",), (INT64,)),
                  columns={"k": np.arange(4 * tile, dtype=np.int64)})
    got = runtime.run_operator(FirstTiles(table, tile=tile))
    assert list(got) == ["k"]
    np.testing.assert_array_equal(
        got["k"], np.arange(tiles * tile, dtype=np.int64))


def test_retry_path_equivalence(hcat):
    """Speculative-capacity overflow under fusion: shrinking a learned
    join emission capacity must trigger the post_run_update -> re-run path
    and still produce the unfused oracle's exact results."""
    from cockroach_tpu.flow import runtime
    from cockroach_tpu.flow.operators import HashJoinOp
    from cockroach_tpu.plan import builder as plan_builder

    rel = Q.QUERIES["q3"](hcat)
    oracle = _run(rel, fusion=False)
    settings.set("sql.distsql.fusion.enabled", True)
    try:
        root = plan_builder.build(rel.optimized_plan(), rel.catalog)
        runtime.run_operator(root)  # learn emission capacities

        joins = []

        def walk(op):
            if isinstance(op, HashJoinOp):
                joins.append(op)
            for c in op.children():
                walk(c)

        walk(root)
        assert joins, "q3 plan lost its hash joins"
        for j in joins:
            j._emit_mode = "compact"
            j._emit_cap = 16  # guaranteed overflow at sf 0.005

        inits = 0
        orig_init = root.init

        def counting_init():
            nonlocal inits
            inits += 1
            orig_init()

        root.init = counting_init
        res = runtime.run_operator(root)
        assert inits >= 2, "overflow did not trigger the re-run path"
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    _assert_identical(res, oracle)


def test_readback_shrink_overflow_patch():
    """_ReadbackShrink speculation: a large tile compacts to capacity/64
    with NO host sync; when the deferred count shows the compaction
    truncated live rows, finish() re-materializes from the retained
    original — no rows lost."""
    from cockroach_tpu.coldata.batch import from_host, to_host
    from cockroach_tpu.coldata.types import INT64, Schema
    from cockroach_tpu.flow.runtime import _ReadbackShrink

    schema = Schema(("v",), (INT64,))
    cap = _ReadbackShrink.MIN_CAP  # 64k tile
    live = cap // 2  # far more live rows than the cap/64 shrink target
    b = from_host(schema, {"v": np.arange(live, dtype=np.int64)},
                  capacity=cap)

    shrink = _ReadbackShrink()
    small = shrink.shrink(b)
    assert small.capacity == cap >> 6  # speculation actually engaged
    outs = [to_host(small, schema, {})]
    assert len(outs[0]["v"]) < live  # truncated pre-patch
    shrink.finish(outs, schema, {})
    np.testing.assert_array_equal(outs[0]["v"],
                                  np.arange(live, dtype=np.int64))

    # small tiles pass through untouched (no compact dispatch to pay)
    tiny = from_host(schema, {"v": np.arange(10, dtype=np.int64)},
                     capacity=1024)
    assert shrink.shrink(tiny) is tiny


def test_explain_shows_pipeline_groups(hcat):
    rel = Q.QUERIES["q1"](hcat)
    settings.set("sql.distsql.fusion.enabled", True)
    try:
        fused = rel.explain()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    assert "[pipeline" in fused
    settings.set("sql.distsql.fusion.enabled", False)
    try:
        plain = rel.explain()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    assert "[pipeline" not in plain


def test_explain_analyze_reports_dispatches(hcat):
    rel = Q.QUERIES["q1"](hcat)
    settings.set("sql.distsql.fusion.enabled", True)
    try:
        text, res = rel.explain_analyze()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    dispatches, compiles = text.splitlines()[-2:]
    assert dispatches.startswith("kernel dispatches: ")
    assert int(dispatches.split(": ")[1]) > 0
    assert compiles.startswith("kernel compiles: ")
    assert "[pipeline" in text
    assert len(res["l_returnflag"]) > 0


def test_general_probe_fusion_equivalence(hcat):
    """Non-unique (fan-out) inner probes fuse as speculative streaming
    emitters under sql.distsql.fusion.general_probe; the gated-off run —
    the probe breaking the chain like pre-fusion engines — is the oracle."""
    from cockroach_tpu.sql.rel import Rel

    rel = (Rel.scan(hcat, "orders")
           .join(Rel.scan(hcat, "lineitem"),
                 on=[("o_orderkey", "l_orderkey")], how="inner",
                 build_unique=False)
           .groupby(["o_orderkey"], [("n", "count_rows", None)]))
    settings.set("sql.distsql.fusion.general_probe", False)
    try:
        want = _run(rel, fusion=True)
    finally:
        settings.reset("sql.distsql.fusion.general_probe")
    _assert_identical(_run(rel, fusion=True), want)


@pytest.mark.parametrize("qname", ["q9", "q18"])
def test_spill_and_skew_forced_tpch_equivalence(hcat, qname):
    """The join-plane escape hatches must not change a single bit: q9/q18
    re-run with workmem forced down (Grace spill + hybrid partition
    degrade) and the skew sampler armed aggressively, against the
    in-memory fused oracle."""
    from cockroach_tpu.utils import metric

    rel = Q.QUERIES[qname](hcat)
    want = _run(rel, fusion=True)
    spills0 = metric.GRACE_JOIN_SPILLS.value
    settings.set("sql.distsql.workmem_bytes", 1 << 16)
    settings.set("sql.distsql.grace_skew_frac", 0.02)
    try:
        got = _run(rel, fusion=True)
    finally:
        settings.reset("sql.distsql.workmem_bytes")
        settings.reset("sql.distsql.grace_skew_frac")
    assert metric.GRACE_JOIN_SPILLS.value > spills0, "never spilled"
    _assert_identical(got, want)


def _emit_state(join, mode, cap):
    join._emit_mode, join._emit_cap = mode, cap
    join._emit_cap_seen = cap or join._emit_cap_seen


@pytest.mark.parametrize("feeder, passes", [
    (("compact", 1024), True),   # tiles arrive at the upper join's own cap
    (("compact", 512), True),    # and under it
    (("compact", 2048), False),  # over it: compacting shrinks them
    (("learn", None), False),    # full tiles
])
def test_a_join_fed_tiles_at_its_own_cap_composes_into_its_consumer(
        hcat, feeder, passes):
    """A unique-build join in compact mode (cap 1,024) above another: fed
    tiles a compact join already cut to no more than 1,024 rows it drives
    no `hashjoin_emit` of its own (its probe rides in the aggregate's
    kernel) and counts `join_passthrough_tiles`; fed wider tiles it
    compacts as it always did. Same answer either way, and its learned
    mode and cap stay. Every tile a compact join does emit, at a cap under
    the tile's capacity, is cut to the cap before the build side is
    gathered and counts into `join_late_emit_tiles` (PR 31); a learn run
    has no cap and counts none."""
    from cockroach_tpu.flow import dispatch, runtime
    from cockroach_tpu.flow.operators import HashJoinOp
    from cockroach_tpu.ops import expr as ex
    from cockroach_tpu.plan import builder as plan_builder
    from cockroach_tpu.sql.rel import Rel
    from cockroach_tpu.utils import tracing

    li = Rel.scan(hcat, "lineitem", ("l_orderkey", "l_quantity"))
    li = li.filter(ex.Cmp("lt", li.c("l_quantity"),
                          ex.Const(3.0, li.type_of("l_quantity"))))
    rel = (li.join(Rel.scan(hcat, "orders", ("o_orderkey", "o_custkey")),
                   on=[("l_orderkey", "o_orderkey")], how="inner")
           .join(Rel.scan(hcat, "customer", ("c_custkey", "c_nationkey")),
                 on=[("o_custkey", "c_custkey")], how="inner")
           .groupby(["c_nationkey"], [("n", "count_rows", None)]))
    want = _run(rel, fusion=False)
    settings.set("sql.distsql.fusion.enabled", True)
    settings.set("sql.distsql.tile_size", 8192)
    try:
        root = plan_builder.build(rel.optimized_plan(), hcat)
        runtime.run_operator(root)
        upper = root
        while not isinstance(upper, HashJoinOp):
            upper = upper.child
        lower = upper.child
        while not isinstance(lower, HashJoinOp):
            lower = lower.child
        assert upper.spec.build_unique and lower.spec.build_unique

        drove = []
        real = HashJoinOp.stream_tiles

        def spy(self):
            drove.append(self)
            return real(self)

        def run(feeder):
            """(answer, programs issued, probe tiles a join, those the
            upper join passed through, those a join cut before it gathered
            its build side)"""
            _emit_state(lower, *feeder)
            _emit_state(upper, "compact", 1024)
            del drove[:]
            with tracing.span("query"):
                p0 = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
                d0 = dispatch.total()
                got = runtime.run_operator(root)
                d = dispatch.total() - d0
                p1 = tracing.totals()["flow/pull"]["tags"]
            return (got, d, *(
                p1.get(k, 0) - p0.get(k, 0)
                for k in ("join_unique_tiles", "join_passthrough_tiles",
                          "join_late_emit_tiles")))

        HashJoinOp.stream_tiles = spy
        try:
            got, issued, probed, passed, late = run(feeder)
            drove_upper = upper in drove
            # against the same tree with the upper join emitting
            _got, compacting, _probed, _passed, _late = run(("compact", 2048))
        finally:
            HashJoinOp.stream_tiles = real
    finally:
        settings.reset("sql.distsql.fusion.enabled")
        settings.reset("sql.distsql.tile_size")
    _assert_identical(got, want)
    tiles = probed // 2  # two joins
    assert tiles > 1 and lower in drove
    assert drove_upper is not passes
    assert passed == (tiles if passes else 0)
    assert issued == compacting - (tiles if passes else 0)
    # the lower join's emit when it has a cap, the upper's when it emits
    assert late == tiles * ((feeder[0] == "compact") + (not passes))
    assert upper._emit_mode == "compact" and upper._emit_cap_seen >= 1024
