"""A compact-mode join's emission cuts the tile to its cap BEFORE it gathers
a build column (PR 31; ops/join.py `emit_unique_compact`, HashJoinOp.
_emit_kernel): equal to `compact(emit_unique(...), cap)` for every
unique-build strategy, and in the kernel's jaxpr no build column is
gathered at the probe tile's capacity."""

import jax
import numpy as np
import pytest

from cockroach_tpu import coldata as cd
from cockroach_tpu.bench import tpch
from cockroach_tpu.coldata.batch import compact
from cockroach_tpu.flow import runtime
from cockroach_tpu.flow.operators import HashJoinOp
from cockroach_tpu.ops import expr as ex
from cockroach_tpu.ops import join as jn
from cockroach_tpu.plan import builder as plan_builder
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import settings, tracing

TILE = 256  # probe capacity of the kernel-level cases
BUILD_CAP = 128
KEY_LO = 1000
BSCHEMA = cd.Schema.of(bk0=cd.INT64, bk1=cd.INT32, bv=cd.FLOAT64,
                       bs=cd.BYTES(5), bn=cd.INT32)
PSCHEMA = cd.Schema.of(pk0=cd.INT64, pk1=cd.INT32, pv=cd.INT64)
STRATEGIES = ["analytic", "analytic_fanout4", "lut", "sorted_exact",
              "sorted_hashed"]


def _tables(rng, fanout):
    """A build whose first key is KEY_LO + row // fanout (dense, clustered:
    the analytic strategies address it by arithmetic; with the second key,
    unique for the others), with NULLs in a carried column (`bv`), a 2-D
    BYTES column (`bs`) and rows filtered out by the mask; a probe with
    misses, NULL keys and dead rows."""
    nb = 96
    rows = np.arange(nb)
    bk0 = KEY_LO + rows // fanout
    bk1 = (rows % fanout) * 7
    build = cd.from_host(
        BSCHEMA,
        {"bk0": bk0, "bk1": bk1, "bv": rng.random(nb),
         "bs": rng.integers(1, 255, (nb, 5)).astype(np.uint8),
         "bn": rng.integers(0, 1 << 20, nb)},
        valids={"bv": rng.random(nb) > 0.3},
        capacity=BUILD_CAP,
    )
    build = build.with_mask(build.mask & np.asarray(
        np.pad(rng.random(nb) > 0.2, (0, BUILD_CAP - nb))))
    n_p = 230
    pk0 = KEY_LO + rng.integers(-8, nb // fanout + 8, n_p)
    pk1 = rng.integers(0, fanout, n_p) * 7
    probe = cd.from_host(
        PSCHEMA, {"pk0": pk0, "pk1": pk1, "pv": np.arange(n_p)},
        valids={"pk0": rng.random(n_p) > 0.1}, capacity=TILE,
    )
    probe = probe.with_mask(probe.mask & np.asarray(
        np.pad(rng.random(n_p) > 0.15, (0, TILE - n_p))))
    stats = {0: (KEY_LO - 8, KEY_LO + nb + 8), 1: (0, 7 * fanout)}
    return build, probe, stats


def _probe_pair(strategy, rng):
    """(probe, build, found_idx, found) of one strategy, as HashJoinOp.
    _set_probe wires them."""
    fanout = 4 if strategy == "analytic_fanout4" else 1
    build, probe, stats = _tables(rng, fanout)
    keys = (0, 1)
    if strategy.startswith("analytic"):
        info = jn.DenseAnalytic(key_lo=KEY_LO, fanout=fanout, build_rows=96)
        return (probe, build, *jn.dense_analytic_probe(
            probe, keys, build, keys, info))
    layout = jn.plan_exact_key(PSCHEMA, keys, BSCHEMA, keys, stats, stats,
                               None, have_remaps=True)
    assert layout is not None
    if strategy == "lut":
        lut = jn.build_dense_lut(build, keys, layout)
        return probe, build, *jn.dense_lut_probe(probe, keys, layout, lut)
    if strategy == "sorted_hashed":
        layout = None
    index = jn.build_index(build, BSCHEMA, keys, exact_layout=layout)
    return (probe, build, *jn.probe_unique(
        probe, PSCHEMA, keys, build, BSCHEMA, keys, index=index,
        exact_layout=layout))


@pytest.mark.parametrize("cap_is", ["fits", "overflows", "wider_than_tile"])
@pytest.mark.parametrize("join_type", ["inner", "left"])
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_the_compacting_emission_equals_compact_of_the_aligned_one(
        rng, strategy, join_type, cap_is):
    probe, build, found_idx, found = _probe_pair(strategy, rng)
    spec = jn.JoinSpec(join_type, True)
    aligned = jn.emit_unique(probe, build, spec, found_idx, found)
    live = int(np.asarray(aligned.mask).sum())
    matched = int(np.asarray(probe.mask & found).sum())
    assert 20 < matched < int(np.asarray(probe.mask).sum()) - 20
    bv = aligned.cols[len(PSCHEMA) + 2]  # matched rows whose value is NULL
    assert int(np.asarray(probe.mask & found & ~bv.valid).sum()) > 5
    cap = {"fits": -(-live // 32) * 32, "overflows": live // 2,
           "wider_than_tile": 2 * TILE}[cap_is]
    assert (cap < TILE) == (cap_is != "wider_than_tile")
    want = compact(aligned, capacity=cap)
    got, count = jn.emit_unique_compact(probe, build, spec, found_idx, found,
                                        cap)
    # the TRUE total of the whole tile, whatever the cut kept: what
    # post_run_update compares with the cap
    assert int(count) == live and count.dtype == np.int64
    assert (live > cap) == (cap_is == "overflows")
    mask = np.asarray(want.mask)
    np.testing.assert_array_equal(np.asarray(got.mask), mask)
    assert mask.shape == (cap,) and mask.sum() == min(live, cap)
    assert len(got.cols) == len(PSCHEMA) + len(BSCHEMA) == len(want.cols)
    for i, (g, w) in enumerate(zip(got.cols, want.cols)):
        assert g.data.shape == w.data.shape and g.data.dtype == w.data.dtype
        np.testing.assert_array_equal(np.asarray(g.valid),
                                      np.asarray(w.valid), err_msg=str(i))
        assert not np.asarray(g.valid)[~mask].any()  # dead rows stay NULL
        np.testing.assert_array_equal(np.asarray(g.data)[mask],
                                      np.asarray(w.data)[mask],
                                      err_msg=str(i))
    assert got.cols[len(PSCHEMA) + 3].data.shape == (cap, 5)  # BYTES


# -- the kernel HashJoinOp launches ----------------------------------------

ORDERS9 = ("o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
           "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
           "o_comment")
KERNEL_TILE = 8192
KERNEL_CAP = 1024


@pytest.fixture(scope="module")
def hcat():
    return tpch.gen_tpch(sf=0.005, seed=2**31 + 31)


def _lineitem_join_orders(hcat, build_cols, how="inner"):
    li = Rel.scan(hcat, "lineitem", ("l_orderkey", "l_quantity"))
    li = li.filter(ex.Cmp("lt", li.c("l_quantity"),
                          ex.Const(3.0, li.type_of("l_quantity"))))
    return li.join(Rel.scan(hcat, "orders", build_cols),
                   on=[("l_orderkey", "o_orderkey")], how=how)


def _gathers(jaxpr, out):
    """Leading dimension of every `gather` equation's output, nested
    programs (pjit, while, cond) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append(eqn.outvars[0].aval.shape[0])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _gathers(inner, out)
    return out


def _emit_gathers(hcat, build_cols):
    """Gathers of the compact-mode `hashjoin_emit` over a lineitem tile, by
    the leading dimension of what they produce."""
    rel = _lineitem_join_orders(hcat, build_cols)
    join = plan_builder.build(rel.optimized_plan(), hcat)
    while not isinstance(join, HashJoinOp):  # the root's FusedPipeline
        join = join.child
    assert join.spec.build_unique
    join.init()
    join._ensure_built()
    join._emit_mode, join._emit_cap = "compact", KERNEL_CAP
    src, cfn, cargs = join.child.stream_parts()
    kern = join._emit_kernel(cfn, len(cargs))
    tile = next(iter(src.stream_tiles()))
    jaxpr = jax.make_jaxpr(kern._jitted)(
        tile, *cargs, join._build_batch, join._index)
    assert "hashjoin_emit" in str(jaxpr)
    dims = _gathers(jaxpr.jaxpr, [])
    join.close()
    return dims, len(join.child.output_schema), join._probe_kind


def test_no_build_column_is_gathered_at_the_probe_tiles_capacity(hcat):
    settings.set("sql.distsql.tile_size", KERNEL_TILE)
    try:
        two, n_probe, kind2 = _emit_gathers(hcat, ORDERS9[:2])
        nine, _n, kind9 = _emit_gathers(hcat, ORDERS9)
    finally:
        settings.reset("sql.distsql.tile_size")
    assert kind2 == kind9 == "analytic"
    # at the tile: the probe's own gathers (the build's liveness), whatever
    # the build carries
    assert two.count(KERNEL_TILE) == nine.count(KERNEL_TILE) > 0
    # at the cap: data and valid bits of every column once, and the
    # probe's (found_idx, found) moved through the index
    for dims, n_build in ((two, 2), (nine, 9)):
        assert dims.count(KERNEL_CAP) == 2 * (n_probe + n_build) + 2
        assert set(dims) <= {KERNEL_TILE, KERNEL_CAP}


@pytest.mark.parametrize("how", ["inner", "left"])
def test_a_cap_below_the_live_count_overflows_and_the_rerun_is_right(
        hcat, how):
    """The late emission's count is the whole tile's, so a cap that cut
    rows off is seen at the end of the run (post_run_update) and the
    statement runs again at the corrected cap: two pulls, the unfused
    answer."""
    rel = (_lineitem_join_orders(hcat, ORDERS9[:5], how)
           .groupby(["o_orderstatus"], [("n", "count_rows", None),
                                        ("s", "sum", "o_totalprice")]))
    settings.set("sql.distsql.fusion.enabled", False)
    try:
        want = rel.run()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    settings.set("sql.distsql.tile_size", KERNEL_TILE)
    try:
        root = plan_builder.build(rel.optimized_plan(), hcat)
        join = root
        while not isinstance(join, HashJoinOp):
            join = join.child

        def run():
            t = tracing.totals().get("flow/pull", {"count": 0, "tags": {}})
            with tracing.span("query"):
                got = runtime.run_operator(root)
            t1 = tracing.totals()["flow/pull"]
            return (got, t1["count"] - t["count"],
                    t1["tags"].get("join_late_emit_tiles", 0)
                    - t["tags"].get("join_late_emit_tiles", 0))

        got, pulls, late = run()  # learn: aligned emission, counted
        assert (pulls, late) == (1, 0)
        _same(got, want)
        assert join._emit_mode == "compact" and join._emit_cap == 1024
        got, pulls, late = run()
        tiles = late
        assert pulls == 1 and tiles > 1
        _same(got, want)
        join._emit_cap = 64  # under what a tile keeps (about 300)
        got, pulls, late = run()
        # both attempts cut first: the overflowed join corrects its own cap
        # (a join ABOVE one that overflowed is what goes back to learn)
        assert (pulls, late) == (2, 2 * tiles)
        _same(got, want)
        assert join._emit_mode == "compact" and join._emit_cap == 1024
    finally:
        settings.reset("sql.distsql.tile_size")


def _same(got, want):
    assert set(got) == set(want)
    order_g = np.argsort(np.asarray(got["o_orderstatus"], dtype=object)
                         .astype(str), kind="stable")
    order_w = np.argsort(np.asarray(want["o_orderstatus"], dtype=object)
                         .astype(str), kind="stable")
    for name in want:
        g, w = np.asarray(got[name])[order_g], np.asarray(want[name])[order_w]
        if w.dtype.kind == "f":
            np.testing.assert_allclose(g, w, rtol=1e-12, err_msg=name)
        else:
            assert list(g) == list(w), name
