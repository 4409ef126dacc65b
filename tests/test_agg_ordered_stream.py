"""The streaming ordered GROUP BY (PR 33; colexec's orderedAggregator, which
carries the open group across batches and never spools): a complete
aggregate over input clustered on its group keys emits one output tile an
input tile, the group a tile's edge cuts is carried as one row into the next
tile's kernel (ops/aggregation.py stitch_ordered_partial), and the last open
group leaves as a tile of its own. Every case is held bit for bit to the
sorting route: the same rows in a table whose `Table.ordering` is cleared.
1,024-row tiles, CPU."""

import numpy as np
import pytest

from cockroach_tpu.catalog import Catalog, Table
from cockroach_tpu.coldata.types import BOOL, INT64, STRING, Schema
from cockroach_tpu.flow import dispatch, operators
from cockroach_tpu.flow.runtime import run_operator
from cockroach_tpu.ops import expr as ex
from cockroach_tpu.plan import builder as plan_builder
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import settings, tracing

TILE = 1024
SCHEMA = Schema.of(grp=INT64, val=INT64, keep=INT64, flag=BOOL)
# every merge the state layout knows: sum / count through "sum", min, max,
# any_not_null, bool_and, bool_or; avg and stddev through their partial
# layouts (sum + count; sum, sum of squares + count)
EVERY_SPEC = [("s", "sum", "val"), ("c", "count", "val"),
              ("n", "count_rows", None), ("mn", "min", "val"),
              ("mx", "max", "val"), ("a", "avg", "val"),
              ("any", "any_not_null", "val"), ("ba", "bool_and", "flag"),
              ("bo", "bool_or", "flag"), ("sd", "stddev", "val")]


@pytest.fixture(autouse=True)
def small_tiles():
    settings.set("sql.distsql.tile_size", TILE)
    yield
    settings.reset("sql.distsql.tile_size")


def _sparse(ids):
    """Group ids spread over a huge range: the planner's dense aggregate
    (bounded key spaces) stays out, AggregateOp is what runs."""
    return np.asarray(ids, np.int64) * 12_345_678 + 10


def _catalogs(grp, *, grp_valid=None, keep=None, seed=0):
    """The same rows twice: clustered on grp (ordering kept) and plain."""
    rng = np.random.default_rng(seed)
    n = len(grp)
    raw = {
        "grp": np.asarray(grp, np.int64),
        "val": rng.integers(-50, 50, n).astype(np.int64),
        "keep": (np.ones(n, np.int64) if keep is None
                 else np.asarray(keep, np.int64)),
        "flag": rng.random(n) > 0.3,
    }
    valids = {"val": rng.random(n) > 0.2, "flag": rng.random(n) > 0.2}
    if grp_valid is not None:
        valids["grp"] = np.asarray(grp_valid, bool)
    cats = []
    for ordering in (("grp",), ()):
        cat = Catalog()
        cat.add(Table.from_strings("fact", SCHEMA, dict(raw), valids=valids,
                                   ordering=ordering))
        cats.append(cat)
    return cats


def _query(cat, aggs=EVERY_SPEC, filtered=False):
    r = Rel.scan(cat, "fact")
    if filtered:
        r = r.filter(ex.Cmp("gt", r.c("keep"), ex.lit(0)))
    return r.groupby(["grp"], aggs)


def _bits(col):
    a = np.asarray(col)
    return [repr(v) for v in a] if a.dtype == object else a.tobytes()


def _assert_same_bits(grp, aggs=EVERY_SPEC, filtered=False, **kw):
    """Ordered (streaming) against unordered (spool + merge), sorted by key;
    returns the ordered answer."""
    clustered, plain = _catalogs(grp, **kw)
    op = plan_builder.build(_query(clustered, aggs, filtered).plan, clustered)
    assert op.ordered and op.streaming and op.prefix_live != filtered
    assert not plan_builder.build(_query(plain, aggs, filtered).plan,
                                  plain).ordered
    got, want = (_query(c, aggs, filtered).sort([("grp", False)]).run()
                 for c in (clustered, plain))
    assert list(got) == list(want)
    for name in got:
        assert np.asarray(got[name]).dtype == np.asarray(want[name]).dtype
        assert _bits(got[name]) == _bits(want[name]), name
    return got


def _groups_of(size, n):
    return _sparse(np.repeat(np.arange(-(-n // size)), size)[:n])


@pytest.mark.parametrize("size", [7, 1, 300])
def test_groups_cut_by_tile_edges_are_summed_whole(size):
    """3,000 rows in groups of 7 (1,024 is no multiple: every edge cuts a
    group), of 1 (no edge cuts one: the carried row never continues) and of
    300 (a tile holds three and a half)."""
    got = _assert_same_bits(_groups_of(size, 3000))
    assert len(got["grp"]) == -(-3000 // size)
    assert int(np.sum(got["n"])) == 3000


@pytest.mark.parametrize("rows", [[100, 3500, 50], [4096], [1024, 1024, 5],
                                  [1023, 2, 2047, 1]])
def test_one_group_spans_three_and_more_tiles(rows):
    """Tiles that hold a single group which continues the carried one hand
    the whole running state on: 3,500 rows of one key over tiles 0-3
    between two small groups; one group alone over four full tiles (only
    the tail tile holds a row); groups that end exactly on an edge; a group
    of two rows cut one and one."""
    got = _assert_same_bits(_sparse(np.repeat(np.arange(len(rows)), rows)))
    assert sorted(int(v) for v in got["n"]) == sorted(rows)


def test_an_all_dead_tile_passes_the_carry_through():
    """A filter kills every row of tile 1; the group that tile 0 leaves open
    (rows 1,000-2,099: its middle lies in the dead tile) meets its end in
    tile 2's slot 0."""
    n = 3000
    ids = np.arange(n) // 10
    ids[1000:2100] = 100
    keep = np.ones(n, np.int64)
    keep[TILE:2 * TILE] = 0
    got = _assert_same_bits(_sparse(ids), filtered=True, keep=keep)
    at = list(got["grp"]).index(int(_sparse([100])[0]))
    assert int(got["n"][at]) == (TILE - 1000) + (2100 - 2 * TILE)


@pytest.mark.parametrize("first,last", [(1000, 1050), (1024, 2048),
                                        (0, 2500), (2990, 3000)])
def test_a_null_key_group_on_an_edge_is_one_group(first, last):
    """NULL keys are one group whatever garbage their data holds, across an
    edge as inside a tile: NULL = NULL in the carried row's comparison as in
    sort_groupby's (packed words, validity counted)."""
    n = 3000
    ids = np.arange(n) // 9  # the garbage under the NULLs differs row to row
    valid = np.ones(n, bool)
    valid[first:last] = False
    got = _assert_same_bits(_sparse(ids), grp_valid=valid)
    nulls = [i for i, g in enumerate(got["grp"]) if g is None]
    assert len(nulls) == 1 and int(got["n"][nulls[0]]) == last - first


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_filter_below_interleaves_dead_rows(seed):
    """`prefix_live=False`: the partial compacts dead rows (one u8 sort) and
    the carry meets the first LIVE group; groups the filter empties are no
    groups."""
    rng = np.random.default_rng(seed)
    n = 5000
    keep = (rng.random(n) > 0.4).astype(np.int64)
    keep[2 * TILE - 3:2 * TILE + 40] = 0  # a dead stretch over an edge
    got = _assert_same_bits(_groups_of(5, n), filtered=True, keep=keep,
                            seed=seed)
    assert int(np.sum(got["n"])) == int(keep.sum())


@pytest.mark.parametrize("n", [0, 1, TILE, TILE + 1])
def test_empty_input_one_row_and_exact_tiles(n):
    """No row: no group (a grouped aggregate over nothing returns nothing);
    one row: one group, which only the tail tile holds."""
    got = _assert_same_bits(_sparse(np.arange(n) // 3))
    assert len(got["grp"]) == -(-n // 3)


@pytest.mark.parametrize("spec", EVERY_SPEC, ids=[s[0] for s in EVERY_SPEC])
def test_every_merge_spec_alone(spec):
    """Each aggregate as the only state column, over groups of 700 rows (one
    and a half to a tile: every group is cut, most carried rows continue)."""
    _assert_same_bits(_groups_of(700, 5000), aggs=[spec])


@pytest.mark.parametrize("filtered", [False, True])
def test_the_chips_branch_of_the_partial_streams_too(monkeypatch, filtered):
    """On an accelerator sort_groupby reduces with segmented scans and takes
    its slots by a stable sort (ops/segscan.py), which leaves dead rows'
    data behind differently from the CPU's scatters: the carried row and
    the vacated slot must not depend on it. `use_scans` is read at trace
    time, so steering it here runs that branch on the CPU."""
    from cockroach_tpu.ops import segscan

    monkeypatch.setattr(segscan, "use_scans", lambda: True)
    n = 3000
    ids = np.arange(n) // 7
    ids[1000:2040] = 500  # one group over tile 0's end and most of tile 1
    valid = np.ones(n, bool)
    valid[2040:2060] = False  # a NULL group over the next edge
    keep = (np.arange(n) % 5 > 0).astype(np.int64)
    got = _assert_same_bits(_sparse(ids), filtered=filtered, grp_valid=valid,
                            keep=keep)
    assert int(np.sum(got["n"])) == (int(keep.sum()) if filtered else n)


def test_two_key_columns_one_a_string():
    """A clustered (a, b) prefix with a dictionary-coded column: the carried
    row's keys are compared over both, through the same packing."""
    n = 4000
    a = np.arange(n) // 40
    b = np.array(["xyz"[(i // 13) % 3] for i in range(n)], dtype=object)
    # (a, b) runs: equal pairs adjacent, as a two-column ordering gives them
    order = np.lexsort((b.astype(str), a))
    a, b = _sparse(a[order]), b[order]
    val = np.arange(n, dtype=np.int64)
    runs = []
    for ordering in (("a", "b"), ()):
        cat = Catalog()
        cat.add(Table.from_strings(
            "fact", Schema.of(a=INT64, b=STRING, val=INT64),
            {"a": a, "b": b, "val": val}, ordering=ordering))
        q = Rel.scan(cat, "fact").groupby(
            ["a", "b"], [("s", "sum", "val"), ("n", "count_rows", None)])
        op = plan_builder.build(q.plan, cat)
        assert getattr(op, "streaming", False) == bool(ordering)
        runs.append(q.sort([("a", False), ("b", False)]).run())
    got, want = runs
    assert len(got["a"]) == 300 and int(np.sum(got["n"])) == n
    for name in got:
        assert _bits(got[name]) == _bits(want[name]), name


def _spy(monkeypatch):
    """Every hashagg_merge launch's cap, and every spool's one host sync."""
    seen = {"merge_caps": [], "live_total_syncs": 0, "spools": 0}
    real_init = operators.AggregateOp.init
    real_live, real_spool = operators._live_total, operators.AggregateOp._spool

    def init(self):
        real_init(self)
        fn = self._merge_fn
        if not getattr(fn, "_spied", False):
            def merge(tiles, cap):
                seen["merge_caps"].append(cap)
                return fn(tiles, cap=cap)

            merge._spied = True
            self._merge_fn = merge

    def live_total(tiles):
        seen["live_total_syncs"] += 1
        return real_live(tiles)

    def spool(self):
        seen["spools"] += 1
        return real_spool(self)

    monkeypatch.setattr(operators.AggregateOp, "init", init)
    monkeypatch.setattr(operators.AggregateOp, "_spool", spool)
    monkeypatch.setattr(operators, "_live_total", live_total)
    return seen


def _run_traced(op):
    with tracing.span("test"):  # flow/pull is a leaf span: it needs a root
        return run_operator(op)


def _pull_tags():
    tags = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
    return {k: tags.get(k, 0) for k in (
        "agg_ordered_tiles", "agg_streamed_tiles", "agg_merge_rows",
        "agg_spills")}


@pytest.mark.parametrize("filtered", [False, True])
def test_one_dispatch_a_tile_no_merge_no_sync(monkeypatch, filtered):
    """5 input tiles: 5 `hashagg_stream_fused` launches (scan, filter,
    partial, stitch and finalize in one) + the tail tile's, no
    `hashagg_merge`, no spool, and not one of the spool's host syncs; the
    sorting route over the same rows pays 5 partials + merge + finalize and
    counts its live rows on the host."""
    n = 5 * TILE - 100
    clustered, plain = _catalogs(_groups_of(7, n), keep=np.ones(n, np.int64))
    seen = _spy(monkeypatch)
    for cat, streams in ((clustered, True), (plain, False)):
        op = plan_builder.build(_query(cat, filtered=filtered).plan, cat)
        run_operator(op)  # compiles; the counted run is the settled one
        for k in seen:
            seen[k] = [] if k == "merge_caps" else 0
        d0, c0, t0 = dispatch.total(), dispatch.compiles(), _pull_tags()
        out = _run_traced(op)
        tags = {k: v - t0[k] for k, v in _pull_tags().items()}
        assert dispatch.compiles() == c0
        assert len(out["grp"]) == -(-n // 7)
        if streams:
            assert dispatch.total() - d0 == 5 + 1
            assert seen == {"merge_caps": [], "live_total_syncs": 0,
                            "spools": 0}
            assert tags == {"agg_ordered_tiles": 5, "agg_streamed_tiles": 5,
                            "agg_merge_rows": 0, "agg_spills": 0}
        else:
            assert dispatch.total() - d0 == 5 + 2
            assert seen["spools"] == 1 and seen["live_total_syncs"] == 1
            assert seen["merge_caps"] == [tags["agg_merge_rows"]]
            assert tags["agg_ordered_tiles"] == 0
            assert tags["agg_streamed_tiles"] == 0


def test_the_unfused_pull_streams_too():
    """`sql.distsql.fusion.enabled` off (the per-operator oracle): the same
    tiles through `hashagg_stream`, one launch an input tile."""
    grp = _groups_of(7, 3000)
    want = _assert_same_bits(grp)
    settings.set("sql.distsql.fusion.enabled", False)
    try:
        clustered, _ = _catalogs(grp)
        got = _query(clustered).sort([("grp", False)]).run()
    finally:
        settings.reset("sql.distsql.fusion.enabled")
    for name in want:
        assert _bits(got[name]) == _bits(want[name]), name


def test_partial_mode_still_spools(monkeypatch):
    """An ordered `partial` aggregate feeds an Exchange whose `final` side
    merges duplicates: it keeps its spool (presorted partials, one merge by
    key) and emits the state layout once; string_agg, whose strings are
    gathered on the host, keeps it too."""
    n = 3000
    clustered, plain = _catalogs(_groups_of(7, n))
    seen = _spy(monkeypatch)
    aggs = _query(clustered).plan.aggs
    outs = []
    for cat, ordered in ((clustered, True), (plain, False)):
        scan = plan_builder.build(Rel.scan(cat, "fact").plan, cat)
        op = operators.AggregateOp(scan, (0,), aggs, mode="partial",
                                   ordered=ordered, prefix_live=ordered)
        assert not op.streaming
        t0 = _pull_tags()
        outs.append(_run_traced(op))
        tags = {k: v - t0[k] for k, v in _pull_tags().items()}
        assert tags["agg_streamed_tiles"] == 0
        assert tags["agg_ordered_tiles"] == (3 if ordered else 0)
    assert seen["spools"] == 2 and len(seen["merge_caps"]) == 2
    got, want = outs
    assert list(got) == list(op.output_schema.names)
    order_g, order_w = np.argsort(got["grp"]), np.argsort(want["grp"])
    for name in got:
        assert _bits(np.asarray(got[name])[order_g]) == _bits(
            np.asarray(want[name])[order_w]), name
    sagg = Rel.scan(clustered, "fact").groupby(
        ["grp"], [("c", "count", "val"), ("t", "string_agg", "val", "-")])
    op = plan_builder.build(sagg.plan, clustered)
    assert op.ordered and not op.streaming
