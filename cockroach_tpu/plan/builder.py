"""Plan -> operator tree — the colbuilder.NewColOperator analog
(reference: pkg/sql/colexec/colbuilder/execplan.go:736, core dispatch at
:153-270). Walks the PlanNode tree and instantiates flow operators, threading
catalog tables and host-side dictionary bridges."""

from __future__ import annotations

from ..catalog import Catalog
from ..flow import operators as ops
from ..flow.operator import Operator
from ..utils import settings
from . import spec as S


def _plan_dense_agg(child: Operator, group_cols, aggs):
    """(key_sizes, key_lows) for the dense scatter aggregation when every
    group key is bounded — by catalog/ANALYZE stats (integer families) or
    dictionary size (strings) — and the packed code space fits the
    sql.distsql.dense_agg_states budget. The dense code replaces the hash
    table slot (reference: colexechash hashtable.go:215) collision-free."""
    from ..coldata.types import Family
    from ..ops.aggregation import STAT_FUNCS

    if not settings.get("sql.distsql.dense_agg.enabled"):
        return None
    for spec in aggs:
        # dense states cover the decomposable aggregates; avg/var decompose
        # in partial_layout, so only truly unsupported funcs bail
        if spec.func not in ("sum", "count", "count_rows", "min", "max",
                             "avg", "any_not_null") + STAT_FUNCS:
            return None
    sizes, lows = [], []
    G = 1
    budget = settings.get("sql.distsql.dense_agg_states")
    import jax

    if jax.default_backend() != "cpu":
        # scatters serialize on the TPU VPU: big-G dense states lose to
        # sort+segscan there (q18's 6M-wide orderkey space is the prime
        # suspect in its 4.0s-TPU vs 0.31s-CPU gap; .drive_q18ab.py A/Bs
        # the two paths on the chip)
        budget = min(
            budget, settings.get("sql.distsql.dense_agg.accel_max_states")
        )
    for gi in group_cols:
        t = child.output_schema.types[gi]
        if t.family is Family.STRING and gi in child.dictionaries:
            if getattr(child.dictionaries[gi], "_runtime", False):
                return None  # fills at runtime: size unknown at plan time
            size, lo = len(child.dictionaries[gi]), 0
        elif t.family in (Family.FLOAT, Family.BYTES, Family.JSON,
                          Family.STRING):
            return None
        else:
            st = child.col_stats.get(gi)
            if st is None:
                return None
            lo, hi = int(st[0]), int(st[1])
            size = hi - lo + 1
            if size <= 0:
                return None
        sizes.append(size)
        lows.append(lo)
        G *= size + 1  # +1: the per-key NULL code (dense_layout)
        if G > budget:
            return None
    return tuple(sizes), tuple(lows)


def _clustered_input(plan: S.PlanNode, group_cols, catalog: Catalog):
    """(ordered, prefix_live) for an Aggregate's input chain: ordered when
    the walk down Project/Filter reaches a TableScan whose Table.ordering
    prefix IS the group key set — equal keys then arrive adjacent and the
    grouping can skip its key sort (colexec orderedAggregator role).
    prefix_live when no Filter interleaves dead rows (pure scan tiles are
    live-prefix), dropping the compaction sort too."""
    from ..ops import expr as ex

    cols = list(group_cols)
    prefix_live = True
    node = plan
    while True:
        if isinstance(node, S.Project):
            mapped = []
            for c in cols:
                e = node.exprs[c]
                if not isinstance(e, ex.ColRef):
                    return False, False
                mapped.append(e.idx)
            cols = mapped
            node = node.input
        elif isinstance(node, S.Filter):
            prefix_live = False
            node = node.input
        elif isinstance(node, S.TableScan):
            table = catalog.get(node.table)
            ordering = tuple(getattr(table, "ordering", ()) or ())
            if not ordering or len(cols) > len(ordering):
                return False, False
            names = tuple(node.columns or table.schema.names)
            try:
                keynames = {names[c] for c in cols}
            except IndexError:
                return False, False
            if keynames == set(ordering[: len(cols)]):
                return True, prefix_live
            return False, False
        else:
            return False, False


def build(plan: S.PlanNode, catalog: Catalog, params=None) -> Operator:
    """Instantiate the operator tree for `plan`, then collapse contiguous
    stateless per-tile chains into single-kernel FusedPipeline segments
    (flow/fuse.py) unless sql.distsql.fusion.enabled is off.

    ``params`` (a sql/plancache.ParamStore) reaches FilterOps whose
    predicates carry ex.Param leaves, so cached plans rebind literals as
    jit arguments instead of retracing (the prepared-plan fast path)."""
    op = _build(plan, catalog, params)
    if settings.get("sql.distsql.fusion.enabled"):
        from ..flow import fuse

        op = fuse.fuse_operators(op)
    _label_operators(op)
    return op


def _label_operators(root: Operator) -> None:
    """Give every operator of the tree as built its ``label``,
    ``<KERNEL>.<n>`` with n the pre-order position, and its ``what``, the
    plan's own words for it: what a statement's operator record and the
    profiler's ``flow.dispatch`` regions name it by (flow/dispatch.py). A
    dot and not ``#``, ``,`` or ``=``: the profiler's annotations delimit
    their arguments by those, and ``op=join#1`` arrives as ``join``.
    Once a tree, so once a plan-cache entry; EXPLAIN prints neither. A
    wrapper the fusion pass put in goes by the operator it wraps."""
    from ..flow.fuse import unwrap

    n = 0

    def walk(op: Operator) -> None:
        nonlocal n
        inner = unwrap(op)
        if inner is not op:
            walk(inner)
            while op is not inner:  # every wrapper on the way down
                op.label, op.what = inner.label, inner.what
                (op,) = op.children()
            return
        op.label = f"{op.KERNEL}.{n}"
        n += 1
        for c in op.children():
            walk(c)
        op.what = _what(op)  # its sources are labelled by now

    walk(root)


def _source(op: Operator) -> str:
    """What feeds a join's side: the table its chain of per-tile links
    scans, else the label of the operator the chain ends at."""
    from ..flow.fuse import unwrap

    op = unwrap(op)
    while isinstance(op, (ops.FilterOp, ops.ProjectOp, ops.HashBucketOp)):
        op = unwrap(op.child)
    if isinstance(op, (ops.ScanOp, ops.IndexScanOp, ops.PointLookupOp,
                       ops.PKRangeOp)):
        return op.table.name
    return op.label


def _what(op: Operator) -> str:
    if isinstance(op, ops.ScanOp):
        return op.table.name
    if isinstance(op, ops.IndexScanOp):
        return f"{op.table.name}@{op.ix.name}"
    if isinstance(op, (ops.PointLookupOp, ops.PKRangeOp)):
        return f"{op.table.name}@primary"
    if isinstance(op, (ops.HashJoinOp, ops.MergeJoinOp)):
        unique = " unique" if op.spec.build_unique else ""
        return (f"{op.spec.join_type} probe={_source(op.child)} "
                f"build={_source(op.build)}{unique}")
    if isinstance(op, ops.AggregateOp):
        route = (" streaming" if op.streaming
                 else " ordered" if op.ordered else "")
        return f"mode={op.mode} keys={op.num_keys}{route}"
    if isinstance(op, ops.SmallGroupAggregateOp):
        return f"dense keys={len(op.group_cols)}"
    if isinstance(op, ops.TopKOp):
        return f"k={op.k}"
    return ""


def _build(plan: S.PlanNode, catalog: Catalog, params=None) -> Operator:
    if isinstance(plan, S.TableScan):
        return ops.ScanOp(
            catalog.get(plan.table), plan.columns,
            tile=settings.get("sql.distsql.tile_size"),
            shard=plan.shard,
        )
    if isinstance(plan, S.IndexScan):
        return ops.IndexScanOp(
            catalog.get(plan.table), plan.index, plan.lo, plan.hi,
            plan.columns,
        )
    if isinstance(plan, S.PointLookup):
        return ops.PointLookupOp(catalog.get(plan.table), plan.keys,
                                 plan.columns, params=params)
    if isinstance(plan, S.PKRange):
        return ops.PKRangeOp(catalog.get(plan.table), plan.lo, plan.hi,
                             plan.columns, params=params)
    if isinstance(plan, S.HashBucket):
        return ops.HashBucketOp(_build(plan.input, catalog, params), plan.keys,
                                plan.n_parts, plan.part)
    if isinstance(plan, S.RemoteStream):
        return ops.RemoteStreamOp(plan.addr, plan.flow_id, plan.stream_id,
                                  plan.schema)
    if isinstance(plan, S.StreamUnion):
        return ops.ParallelUnorderedSyncOp(
            tuple(_build(p, catalog, params) for p in plan.inputs))
    if isinstance(plan, S.Filter):
        return ops.FilterOp(_build(plan.input, catalog, params),
                            plan.predicate, params=params)
    if isinstance(plan, S.Project):
        return ops.ProjectOp(_build(plan.input, catalog, params), plan.exprs,
                             plan.names, plan.dict_overrides)
    if isinstance(plan, S.Aggregate):
        child = _build(plan.input, catalog, params)
        if plan.key_sizes is not None and plan.mode == "complete":
            return ops.SmallGroupAggregateOp(
                child, plan.group_cols, plan.aggs, plan.key_sizes
            )
        if plan.mode == "complete":
            dense = _plan_dense_agg(child, plan.group_cols, plan.aggs)
            if dense is not None:
                sizes, lows = dense
                return ops.SmallGroupAggregateOp(
                    child, plan.group_cols, plan.aggs, sizes, key_lows=lows
                )
        ordered, prefix_live = (
            _clustered_input(plan.input, plan.group_cols, catalog)
            if plan.mode in ("complete", "partial") else (False, False)
        )
        return ops.AggregateOp(child, plan.group_cols, plan.aggs, plan.mode,
                               ordered=ordered, prefix_live=prefix_live)
    if isinstance(plan, S.ScalarAggregate):
        return ops.ScalarAggregateOp(_build(plan.input, catalog, params), plan.aggs)
    if isinstance(plan, S.Sort):
        return ops.SortOp(_build(plan.input, catalog, params), plan.keys)
    if isinstance(plan, S.TopK):
        return ops.TopKOp(_build(plan.input, catalog, params), plan.keys,
                          plan.k)
    if isinstance(plan, S.Limit):
        return ops.LimitOp(_build(plan.input, catalog, params), plan.limit, plan.offset)
    if isinstance(plan, S.Distinct):
        return ops.DistinctOp(_build(plan.input, catalog, params), plan.cols)
    if isinstance(plan, S.Window):
        return ops.WindowOp(
            _build(plan.input, catalog, params), plan.partition_cols,
            plan.order_keys, plan.specs,
        )
    if isinstance(plan, S.MergeJoin):
        return ops.MergeJoinOp(
            _build(plan.probe, catalog, params),
            _build(plan.build, catalog, params),
            plan.probe_key,
            plan.build_key,
            plan.spec,
        )
    if isinstance(plan, S.HashJoin):
        return ops.HashJoinOp(
            _build(plan.probe, catalog, params),
            _build(plan.build, catalog, params),
            plan.probe_keys,
            plan.build_keys,
            plan.spec,
        )
    if isinstance(plan, S.Union):
        return ops.UnionOp(tuple(_build(p, catalog, params) for p in plan.inputs))
    if isinstance(plan, S.Exchange):
        # single-device build: the shuffle is the identity; the multi-device
        # path lives in parallel/shuffle.py and is planned by parallel/dist.py
        return _build(plan.input, catalog, params)
    raise TypeError(f"unknown plan node {plan}")
