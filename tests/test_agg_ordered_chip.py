"""The streaming ordered GROUP BY on the ACCELERATOR's branch (PR 47), a
sibling of tests/test_agg_ordered_stream.py (whose cases run the CPU's
`segment_*` route) kept in a file of its own so that the two share the
suite's workers: `ops/aggregation.py` `_ordered_groupby` groups a presorted
tile where its rows lie (`segscan.seg_scan_multi`, the groups to the front by
`segscan.rows_to_front`), and an unsorted tile after its key sort.
`segscan.use_scans` is read at trace time, so steering it runs that kernel
on the CPU. 1,024-row tiles."""

import numpy as np
import pytest

from cockroach_tpu.flow import dispatch, operators
from cockroach_tpu.ops import expr as ex
from cockroach_tpu.plan import builder as plan_builder
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import settings
from test_agg_ordered_stream import (
    EVERY_SPEC, INT64, TILE, Schema, _assert_same_bits, _bits, _catalogs,
    _groups_of, _query, _sparse)

# the cases about a tile's shape need no more states than tell groups apart
# (every state lane is 10 more unrolled rounds for XLA:CPU to compile)
FEW_SPECS = [("s", "sum", "val"), ("n", "count_rows", None),
             ("mn", "min", "val")]


@pytest.fixture(autouse=True)
def small_tiles():
    settings.set("sql.distsql.tile_size", TILE)
    yield
    settings.reset("sql.distsql.tile_size")


@pytest.fixture
def chips_branch(monkeypatch):
    from cockroach_tpu.ops import segscan

    monkeypatch.setattr(segscan, "use_scans", lambda: True)


_UNORDERED: dict = {}


def _spec_rows():
    """3,000 rows in groups of 700 (every tile edge cuts one), the first 40
    under a NULL key, a third of the rows and one whole group failing the
    Filter."""
    n = 3000
    valid = np.ones(n, bool)
    valid[:40] = False
    keep = (np.arange(n) % 3 > 0).astype(np.int64)
    keep[1400:2100] = 0  # one whole group dead: no group
    return _catalogs(_groups_of(700, n), grp_valid=valid, keep=keep)


def _unordered_answer(filtered):
    """What the unordered plan (unsorted partial, merge) answers for EVERY
    spec at once, run one time a Filter case for the twenty cases below: a
    state's column does not depend on the states beside it."""
    if filtered not in _UNORDERED:
        _, plain = _spec_rows()
        q = _query(plain, EVERY_SPEC, filtered)
        assert not plan_builder.build(q.plan, plain).ordered
        _UNORDERED[filtered] = q.sort([("grp", False)]).run()
    return _UNORDERED[filtered]


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
@pytest.mark.parametrize("spec", EVERY_SPEC, ids=[s[0] for s in EVERY_SPEC])
def test_every_merge_spec_on_the_chips_branch(chips_branch, spec, filtered):
    """Each state the layout knows, alone in a streaming plan, with and
    without a Filter below (dead rows stay where they are and add their
    lane's identity), bit for bit against the unordered plan."""
    clustered, _ = _spec_rows()
    q = _query(clustered, [spec], filtered)
    op = plan_builder.build(q.plan, clustered)
    assert op.ordered and op.streaming and op.prefix_live != filtered
    got = q.sort([("grp", False)]).run()
    want = _unordered_answer(filtered)
    assert list(got) == ["grp", spec[0]]
    assert len(got["grp"]) == (5 if filtered else 6)
    for name in got:
        assert np.asarray(got[name]).dtype == np.asarray(want[name]).dtype
        assert _bits(got[name]) == _bits(want[name]), name


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
def test_single_row_groups_at_a_tiles_first_and_last_row(chips_branch,
                                                         filtered):
    """Rows 0, 1,023, 1,024 and 2,047 are groups of their own (a tile's
    first and last row: the scan's forced start, the last row's forced
    end), the rest in groups of 6; under the Filter three of the four
    stay live and row 1,024's group is emptied."""
    n = 2 * TILE + 100
    ids = np.arange(n) // 6 + 10
    for i, at in enumerate((0, TILE - 1, TILE, 2 * TILE - 1)):
        ids[at] = 5_000 + i
    ids = np.cumsum(np.concatenate([[0], ids[1:] != ids[:-1]]))  # adjacent
    keep = (np.arange(n) % 4 > 0).astype(np.int64)
    keep[[0, TILE - 1, 2 * TILE - 1]] = 1
    keep[TILE] = 0
    got = _assert_same_bits(_sparse(ids), aggs=FEW_SPECS, filtered=filtered,
                            keep=keep)
    ones = sum(int(c) == 1 for c in got["n"])
    assert ones >= (3 if filtered else 4)


def test_every_row_of_a_tile_ends_a_group(chips_branch):
    """num_groups == capacity: 2,048 distinct keys fill two tiles' outputs
    to the last slot (nothing past the count is read as a group, and the
    one group the edge could cut does not continue)."""
    got = _assert_same_bits(_sparse(np.arange(2 * TILE)), aggs=FEW_SPECS)
    assert len(got["grp"]) == 2 * TILE and set(map(int, got["n"])) == {1}


def test_a_float_sum_keeps_the_bits_it_had(chips_branch):
    """A FLOAT sum is not regrouped: its lane still runs the strided
    recursion (`jax.lax.associative_scan`, inside `segscan.seg_scan_multi`),
    so a presorted tile's totals are, bit for bit, what that recursion
    leaves at each group's last row: the parent's kernel, which gathered
    them from there. The integer lanes of the same tile go through the
    shifted passes."""
    import jax
    import jax.numpy as jnp

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.coldata.types import FLOAT64
    from cockroach_tpu.ops import aggregation as agg_ops

    rng = np.random.default_rng(5)
    n = TILE
    key = np.repeat(np.arange(n), rng.integers(1, 9, n))[:n].astype(np.int64)
    x = rng.random(n) * 1e3 + rng.random(n) * 1e-3  # sums round
    live = np.arange(n) < n - 17                    # a padded tail
    ok = rng.random(n) > 0.1
    schema = Schema.of(k=INT64, x=FLOAT64)
    b = Batch(cols=(Column(jnp.asarray(key), jnp.ones((n,), bool)),
                    Column(jnp.asarray(x), jnp.asarray(ok))),
              mask=jnp.asarray(live))
    out, ng = agg_ops.sort_groupby(
        b, schema, (0,), (agg_ops.AggSpec("sum", 1, "s"),
                          agg_ops.AggSpec("count", 1, "c")),
        out_capacity=n, presorted=True, compact=False)
    boundary = np.concatenate([[True], key[1:] != key[:-1]])
    def combine(a, c):
        return a[0] | c[0], jnp.where(c[0], c[1], a[1] + c[1])

    _, scanned = jax.lax.associative_scan(
        combine, (jnp.asarray(boundary),
                  jnp.asarray(np.where(live & ok, x, 0.0))))
    ends = np.concatenate([boundary[1:], [True]])
    has_live = np.maximum.reduceat(live, np.nonzero(boundary)[0])
    want = np.asarray(scanned)[ends][has_live]
    g = int(ng)
    assert g == int(has_live.sum())
    assert np.asarray(out.cols[1].data)[:g].tobytes() == want.tobytes()
    # and they are not the left-to-right sums: the order is the scan's own
    seq = np.array([np.sum(np.where(live & ok, x, 0.0)[s:e + 1])
                    for s, e in zip(np.nonzero(boundary)[0],
                                    np.nonzero(ends)[0])])[has_live]
    assert np.allclose(want, seq, rtol=1e-12)


def test_an_ordered_aggregate_over_dead_rows_refuses_a_rewritten_key():
    """`_ordered_groupby` never moves a dead row out of the way, so a dead
    row inside a group has to carry the group's key. A Filter and a
    Project of bare column references promise it (what the builder calls
    ordered); a chain that computes the key, or anything that is no scan
    at the bottom, does not, and the operator says so when it is built."""
    clustered, _ = _catalogs(_groups_of(7, 100))
    scan = plan_builder.build(Rel.scan(clustered, "fact").plan, clustered)
    kept = operators.FilterOp(scan, ex.Cmp("gt", ex.ColRef(2), ex.lit(0)))
    passed = operators.ProjectOp(
        kept, (ex.ColRef(1), ex.ColRef(0), ex.ColRef(3)), ("v", "g", "f"))
    op = operators.AggregateOp(passed, (1,), (), ordered=True)
    assert op.streaming and not op.prefix_live
    computed = operators.ProjectOp(
        kept, (ex.BinOp("+", ex.ColRef(0), ex.lit(1)), ex.ColRef(1)),
        ("g1", "v"))
    for child, cols in ((computed, (0,)),
                        (operators.LimitOp(kept, 10), (0,))):
        with pytest.raises(ValueError, match="as the table stored them"):
            operators.AggregateOp(child, cols, (), ordered=True)
    # live-prefix tiles carry their dead rows in the tail only: any chain
    operators.AggregateOp(computed, (0,), (), ordered=True, prefix_live=True)


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
def test_the_chips_unsorted_partial_and_merge_against_the_cpus(monkeypatch,
                                                                filtered):
    """The unordered plan is what the cases above are held to, and on the
    accelerator's branch it runs the same kernel after its key sort (the
    columns follow the permutation, valid bitmaps and mask in one shared
    word). So it is held, bit for bit, to the CPU's segment_* route: every
    state, NULL keys, a dead stretch, rows in no key order."""
    from cockroach_tpu.ops import segscan

    n = 3000
    rng = np.random.default_rng(11)
    grp = _sparse(rng.integers(0, 40, n))
    valid = rng.random(n) > 0.05
    keep = (rng.random(n) > 0.3).astype(np.int64)
    keep[900:1300] = 0
    _, plain = _catalogs(grp, grp_valid=valid, keep=keep, seed=3)
    q = _query(plain, filtered=filtered).sort([("grp", False)])
    want = q.run()
    monkeypatch.setattr(segscan, "use_scans", lambda: True)
    dispatch.clear_kernel_cache()
    _, again = _catalogs(grp, grp_valid=valid, keep=keep, seed=3)
    got = _query(again, filtered=filtered).sort([("grp", False)]).run()
    assert list(got) == list(want) and len(got["grp"]) > 30
    for name in got:
        assert _bits(got[name]) == _bits(want[name]), name
