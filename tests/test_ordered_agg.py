"""Ordered (sort-free) aggregation over clustered scans — the colexec
orderedAggregator specialization (reference: pkg/sql/colexec/
ordered_aggregator.go). Parity vs the general sort path and the plan-level
clustering detection."""

import numpy as np
import pytest

from cockroach_tpu.catalog import Catalog, Table
from cockroach_tpu.coldata.types import INT64, STRING, Schema
from cockroach_tpu.plan import builder as plan_builder
from cockroach_tpu.sql.rel import Rel


def _clustered_cat(rng, n=5000, groups=700, with_null=True):
    """A fact table clustered by grp (equal keys adjacent, like TPC-H
    lineitem by l_orderkey), with NULLs in the value column. Group ids
    are SPARSE over a huge range so the planner's dense-scatter path
    (bounded key spaces) stays out and the general aggregate — where the
    ordered specialization lives — is what's under test."""
    sizes = rng.integers(1, 12, groups)
    grp = np.repeat(
        rng.permutation(groups).astype(np.int64) * 12_345_678 + 10, sizes
    )[:n]
    n = len(grp)
    val = rng.integers(-50, 50, n).astype(np.int64)
    valid = rng.random(n) > 0.1 if with_null else np.ones(n, bool)
    cat = Catalog()
    cat.add(Table.from_strings(
        "fact",
        Schema.of(grp=INT64, val=INT64, tag=STRING),
        {
            "grp": grp,
            "val": val,
            "tag": np.array(["abcdef"[int(x) % 6] for x in grp],
                            dtype=object),
        },
        valids={"val": valid},
        ordering=("grp",),
    ))
    return cat, grp, val, valid


@pytest.mark.parametrize("seed", [0, 1])
def test_ordered_agg_matches_oracle(rng, seed):
    rng = np.random.default_rng(seed)
    cat, grp, val, valid = _clustered_cat(rng)
    r = Rel.scan(cat, "fact", ("grp", "val"))
    g = r.groupby(["grp"], [("s", "sum", "val"), ("c", "count", "val"),
                            ("mn", "min", "val"), ("mx", "max", "val")])
    # detection: pure scan chain -> ordered AND prefix-live
    op = plan_builder.build(g.plan, cat)
    assert getattr(op, "ordered", False), type(op).__name__
    assert getattr(op, "prefix_live", False)
    got = g.sort([("grp", False)]).run()

    import pandas as pd

    df = pd.DataFrame({"grp": grp, "val": np.where(valid, val, np.nan)})
    g = df.groupby("grp").val
    # SQL semantics: sum/min/max over an all-NULL group are NULL (pandas
    # sum would say 0 — min_count=1 restores the SQL answer)
    want = pd.DataFrame({
        "s": g.sum(min_count=1), "c": g.count(),
        "mn": g.min(), "mx": g.max(),
    }).reset_index().sort_values("grp")

    def col(series):
        return [None if pd.isna(x) else int(x) for x in series]

    np.testing.assert_array_equal(np.asarray(got["grp"]), want.grp)
    for name in ("s", "c", "mn", "mx"):
        a = [None if x is None else int(x) for x in got[name]]
        assert a == col(want[name]), name


def test_ordered_agg_with_filter_compacts(rng):
    """A filter below the aggregate interleaves dead rows: the ordered path
    must still group correctly (compaction sort) and detection must report
    prefix_live=False."""
    cat, grp, val, valid = _clustered_cat(rng, with_null=False)
    r = Rel.scan(cat, "fact", ("grp", "val"))
    from cockroach_tpu.ops import expr as ex

    f = r.filter(ex.Cmp("gt", r.c("val"), ex.lit(0)))
    g = f.groupby(["grp"], [("s", "sum", "val")])
    op = plan_builder.build(g.plan, cat)
    assert getattr(op, "ordered", False)
    assert not getattr(op, "prefix_live", True)
    got = g.sort([("grp", False)]).run()

    import pandas as pd

    df = pd.DataFrame({"grp": grp, "val": val})
    df = df[df.val > 0]
    want = df.groupby("grp").val.sum().reset_index().sort_values("grp")
    np.testing.assert_array_equal(np.asarray(got["grp"]), want.grp)
    np.testing.assert_array_equal(np.asarray(got["s"]), want.val)


def test_detection_negative_cases(rng):
    """Grouping by a non-prefix (or through a join) must NOT claim order."""
    cat, *_ = _clustered_cat(rng)
    r = Rel.scan(cat, "fact")
    g = r.groupby(["val"], [("c", "count_rows", None)])
    op = plan_builder.build(g.plan, cat)
    assert not getattr(op, "ordered", False)
    # group by (grp, val): grp is an ordering prefix but val breaks
    # adjacency within a run
    g2 = r.groupby(["grp", "val"], [("c", "count_rows", None)])
    op2 = plan_builder.build(g2.plan, cat)
    assert not getattr(op2, "ordered", False)


def test_ordered_agg_distributed_matches_local(rng):
    cat, *_ = _clustered_cat(rng)
    r = Rel.scan(cat, "fact", ("grp", "val"))
    g = r.groupby(["grp"], [("s", "sum", "val")]).sort([("grp", False)])
    local = g.run()
    dist = Rel.scan(cat, "fact", ("grp", "val")).groupby(
        ["grp"], [("s", "sum", "val")]).sort([("grp", False)]
                                             ).run_distributed()
    np.testing.assert_array_equal(np.asarray(local["grp"]),
                                  np.asarray(dist["grp"]))
    np.testing.assert_array_equal(np.asarray(local["s"]),
                                  np.asarray(dist["s"]))


# -- a hash join's emission order, and the group-by above a join (PR 34) ----


_SPARSE = 12_345_678  # keys far apart: the dense scatter path stays out


def _join_cat(rng, n=3000, keys=400, fanout=1):
    """`probe` sorted on k (every key 0 to 14 times, so some keys have no
    row) and `build` with each of the odd keys `fanout` times, shuffled:
    half the probe rows find no match."""
    k = np.sort(rng.integers(0, keys, n)).astype(np.int64) * _SPARSE
    bk = rng.permutation(np.repeat(np.arange(1, keys, 2), fanout)
                         ).astype(np.int64) * _SPARSE
    cat = Catalog()
    cat.add(Table.from_strings(
        "probe", Schema.of(k=INT64, v=INT64),
        {"k": k, "v": rng.integers(0, 100, n).astype(np.int64)},
        ordering=("k",)))
    cat.add(Table.from_strings(
        "build", Schema.of(bk=INT64, w=INT64),
        {"bk": bk, "w": rng.integers(0, 1000, len(bk)).astype(np.int64)}))
    return cat, k, bk


def _join_op(root):
    from cockroach_tpu.flow import operators
    from cockroach_tpu.flow.fuse import unwrap

    op = unwrap(root)
    while not isinstance(op, operators.HashJoinOp):
        op = unwrap(op.children()[0])
    return op


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("fanout", [1, 3], ids=["unique", "repeated"])
@pytest.mark.parametrize("tile", [512, 1 << 20])
def test_a_hash_join_emits_in_probe_order(rng, how, fanout, tile):
    """An inner or left hash join's output keeps the probe's order, tile
    after tile, in every emission the CPU reaches (hash_join_general lays
    its rows out by an exclusive prefix sum over the probe rows; the
    unique-build emits are probe-aligned or go through the stable
    `live_index`). No plan relies on it yet: plan/builder._clustered_input
    stops at a join. One operator tree run three times, so a
    unique-build join goes learn -> compact (a build side filtered to a
    few keys) or learn -> transparent (unfiltered), and a join whose build
    keys repeat runs general mode at its first and at its learned cap."""
    from cockroach_tpu.flow import runtime
    from cockroach_tpu.ops import expr as ex
    from cockroach_tpu.utils import settings

    cat, k, bk = _join_cat(rng, fanout=fanout)
    settings.set("sql.distsql.tile_size", tile)
    try:
        for keep in (None, 40 * _SPARSE):  # every build key, or a tenth
            b = Rel.scan(cat, "build")
            if keep is not None:
                b = b.filter(ex.Cmp("lt", b.c("bk"), ex.lit(keep)))
            j = Rel.scan(cat, "probe").join(b, on=[("k", "bk")], how=how,
                                            build_unique=fanout == 1)
            root = plan_builder.build(j.plan, cat)
            modes = []
            for _ in range(3):
                modes.append(_join_op(root)._emit_mode)
                got = runtime.run_operator(root)
                assert (np.diff(np.asarray(got["k"])) >= 0).all(), modes
                kept = bk if keep is None else bk[bk < keep]
                hits = np.isin(k, kept)
                want = (int(hits.sum()) * fanout
                        + (0 if how == "inner" else int((~hits).sum())))
                assert len(got["k"]) == want
            if fanout > 1:
                assert set(modes) == {"general"}
            else:
                # a left join keeps every probe row: nothing to compact
                assert modes[0] == "learn" and modes[2] == (
                    "compact" if keep and how == "inner" and tile > 512
                    else "transparent")
    finally:
        settings.reset("sql.distsql.tile_size")


@pytest.mark.parametrize("how,fanout", [
    ("inner", 1), ("inner", 3), ("left", 1), ("left", 3), ("right", 3),
    ("full", 3), ("semi", 1)])
def test_a_group_by_above_a_join_sorts_and_answers_as_pandas(rng, how,
                                                             fanout):
    """A group-by on the probe's clustering key above a hash join does not
    take the ordered route (plan/builder._clustered_input walks Project and
    Filter down to a TableScan and stops at a join), and answers as pandas
    does: unique and repeated build keys, NULL-extended rows skipped by
    `count(w)`, the build side's unmatched rows of a right or full join in
    a NULL group."""
    import pandas as pd

    cat, k, bk = _join_cat(rng, fanout=fanout)
    j = Rel.scan(cat, "probe").join(Rel.scan(cat, "build"),
                                    on=[("k", "bk")], how=how,
                                    build_unique=fanout == 1)
    aggs = [("n", "count_rows", None), ("s", "sum", "v")]
    if how != "semi":
        aggs.append(("m", "count", "w"))
    g = j.groupby(["k"], aggs)
    op = plan_builder.build(g.plan, cat)
    assert not getattr(op, "ordered", False), type(op).__name__
    assert not getattr(op, "streaming", False)
    got = g.sort([("k", False)]).run()
    p = pd.DataFrame({"k": k, "v": np.asarray(
        cat.get("probe").columns["v"])})
    b = pd.DataFrame({"bk": bk, "w": np.asarray(
        cat.get("build").columns["w"])})
    if how == "semi":
        m = p[p.k.isin(bk)]
    else:
        m = p.merge(b, how={"full": "outer"}.get(how, how), left_on="k",
                    right_on="bk")
    want = m.groupby("k", dropna=False).agg(
        n=("k", "size"), s=("v", "sum"),
        **({} if how == "semi" else {"m": ("w", "count")})).reset_index()
    want = want.sort_values("k", na_position="last")
    assert len(got["k"]) == len(want)
    live = want.k.notna().to_numpy()
    np.testing.assert_array_equal(
        np.asarray(got["k"])[live].astype(np.int64),
        want.k[live].to_numpy().astype(np.int64))
    for name in ("n", "m"):
        if name in want:
            np.testing.assert_array_equal(
                np.asarray(got[name]).astype(np.int64), want[name])
    np.testing.assert_array_equal(
        np.asarray(got["s"])[live].astype(np.int64),
        want.s[live].to_numpy().astype(np.int64))


@pytest.mark.parametrize("seed", [19920101, 2**31 + 34])
@pytest.mark.parametrize("via_arrow", [True, False],
                         ids=["arrow", "generated"])
def test_every_ordering_gen_tpch_declares_holds(seed, via_arrow):
    """A declared ordering that is false makes an ordered aggregate
    silently wrong: every table that declares one is non-decreasing on it
    (lexicographically), as generated and through the Arrow round trip."""
    from cockroach_tpu.bench import tpch

    cat = tpch.gen_tpch(sf=0.01, seed=seed, via_arrow=via_arrow)
    declared = {n: t.ordering for n, t in cat.tables.items() if t.ordering}
    assert declared == {"lineitem": ("l_orderkey",),
                        "orders": ("o_orderkey",),
                        "customer": ("c_custkey",)}
    for name, ordering in declared.items():
        t = cat.get(name)
        cols = [np.asarray(t.columns[c]) for c in ordering]
        order = np.lexsort(cols[::-1])
        assert (order == np.arange(t.num_rows)).all() or all(
            (c[order] == c).all() for c in cols), name
