"""EXPLAIN / EXPLAIN ANALYZE — plan pretty-printing + ComponentStats folding.

Reference: EXPLAIN renders the optimizer plan tree; EXPLAIN ANALYZE runs the
query with per-processor ComponentStats collection and folds the stats into
the rendered tree (pkg/sql/execstats/traceanalyzer.go over
execinfrapb/component_stats.proto). Here the operator tree mirrors the plan
tree one-to-one, so stats attach directly to plan lines.
"""

from __future__ import annotations

from . import spec as S


def _node_label(n: S.PlanNode, op=None) -> str:
    if isinstance(n, S.TableScan):
        cols = f" columns={list(n.columns)}" if n.columns else ""
        return f"scan {n.table}{cols}"
    if isinstance(n, S.IndexScan):
        lo = "-inf" if n.lo is None else n.lo
        hi = "+inf" if n.hi is None else n.hi
        return f"index-scan {n.table}@{n.index} [{lo}, {hi}]"
    if isinstance(n, S.PointLookup):
        cols = f" columns={list(n.columns)}" if n.columns else ""
        return f"point-lookup {n.table}@primary keys={len(n.keys)}{cols}"
    if isinstance(n, S.PKRange):
        lo, hi = (getattr(b, "value", b) for b in (n.lo, n.hi))
        cols = f" columns={list(n.columns)}" if n.columns else ""
        return f"pk-range {n.table}@primary [{lo}, {hi}]{cols}"
    if isinstance(n, S.Filter):
        return f"filter {n.predicate}"
    if isinstance(n, S.Project):
        return f"project {list(n.names)}"
    if isinstance(n, S.Aggregate):
        aggs = [f"{a.func}({a.col if a.col is not None else '*'})"
                for a in n.aggs]
        mode = f" mode={n.mode}" if n.mode != "complete" else ""
        dense = " dense" if n.key_sizes else ""
        # the route the operator takes: its input arrives clustered on the
        # group keys, so a tile is grouped without a key sort, and (a
        # complete aggregate) leaves at once with one open group carried
        ordered = ("" if not getattr(op, "ordered", False)
                   else " (ordered, streaming)" if op.streaming
                   else " (ordered)")
        return (f"group-by keys={list(n.group_cols)} aggs={aggs}{mode}{dense}"
                f"{ordered}")
    if isinstance(n, S.ScalarAggregate):
        aggs = [f"{a.func}({a.col if a.col is not None else '*'})"
                for a in n.aggs]
        return f"scalar-group-by aggs={aggs}"
    if isinstance(n, S.HashJoin):
        u = " (unique build)" if n.spec.build_unique else ""
        return (f"hash-join ({n.spec.join_type}) "
                f"probe={list(n.probe_keys)} build={list(n.build_keys)}{u}")
    if isinstance(n, S.Sort):
        keys = [f"{k.col}{' desc' if k.desc else ''}" for k in n.keys]
        return f"sort keys={keys}"
    if isinstance(n, S.Limit):
        off = f" offset={n.offset}" if n.offset else ""
        return f"limit {n.limit}{off}"
    if isinstance(n, S.TopK):
        keys = [f"{k.col}{' desc' if k.desc else ''}" for k in n.keys]
        return f"top-k k={n.k} keys={keys}"
    if isinstance(n, S.Distinct):
        return f"distinct on={list(n.cols) if n.cols else 'all'}"
    if isinstance(n, S.Exchange):
        return f"exchange (all-to-all) keys={list(n.keys)}"
    if isinstance(n, S.Union):
        return f"union-all ({len(n.inputs)} inputs)"
    if isinstance(n, S.Broadcast):
        return "broadcast (all-gather)"
    if isinstance(n, S.Gather):
        return "gather (all-gather)"
    if isinstance(n, S.MergeJoin):
        return (f"merge-join ({n.spec.join_type}) "
                f"probe={n.probe_key} build={n.build_key}")
    if isinstance(n, S.Window):
        fns = [s.func for s in n.specs]
        return (f"window {fns} partition={list(n.partition_cols)} "
                f"order={[k.col for k in n.order_keys]}")
    return type(n).__name__


def _children(n: S.PlanNode) -> list[S.PlanNode]:
    if isinstance(n, (S.HashJoin, S.MergeJoin)):
        return [n.probe, n.build]
    if isinstance(n, S.Union):
        return list(n.inputs)
    if hasattr(n, "input"):
        return [n.input]
    return []


def _fusion_groups(plan: S.PlanNode) -> dict[int, int]:
    """id(plan node) -> fused pipeline group (empty when fusion is off).
    Members of one group collapse into a single per-tile kernel at
    execution (flow/fuse.py + the spool fusion in flow/operators.py)."""
    from ..utils import settings

    if not settings.get("sql.distsql.fusion.enabled"):
        return {}
    from ..flow.fuse import plan_fusion_groups

    return plan_fusion_groups(plan)


def _group_tag(groups: dict[int, int], n: S.PlanNode) -> str:
    g = groups.get(id(n))
    return f"  [pipeline {g}]" if g is not None else ""


def _operators_of(plan: S.PlanNode, root_op) -> dict[int, object]:
    """id(plan node) -> its operator, by the walk EXPLAIN ANALYZE makes;
    where the two trees part, the nodes below stay without one."""
    from ..flow.fuse import unwrap

    found: dict[int, object] = {}

    def walk(n: S.PlanNode, op):
        if isinstance(n, S.Exchange):  # single-device builds elide it
            walk(n.input, op)
            return
        op = unwrap(op)
        found[id(n)] = op
        kids, kid_ops = _children(n), op.children()
        if len(kids) == len(kid_ops):
            for c, co in zip(kids, kid_ops):
                walk(c, co)

    walk(plan, root_op)
    return found


def explain_plan(plan: S.PlanNode, catalog=None) -> str:
    """Render the plan tree (EXPLAIN). With the catalog, the operator tree
    is built (not run) beside it, so a line can name the route its
    operator takes (a group-by's `(ordered)` or `(ordered, streaming)`)."""
    lines: list[str] = []
    groups = _fusion_groups(plan)
    operators: dict[int, object] = {}
    if catalog is not None:
        from . import builder

        operators = _operators_of(plan, builder.build(plan, catalog))

    def walk(n: S.PlanNode, depth: int):
        lines.append(
            "  " * depth + "-> " + _node_label(n, operators.get(id(n)))
            + _group_tag(groups, n))
        for c in _children(n):
            walk(c, depth + 1)

    walk(plan, 0)
    return "\n".join(lines)


def explain_analyze_mesh(root_op) -> str:
    """EXPLAIN ANALYZE of a statement that ran as one program across the
    node's devices (parallel/planner.py MeshOp, run with
    collect_stats(True)): the distributed plan, each all-to-all stage with
    what the program's counts said of it (live rows delivered, those that
    left their chip, the send cap a bucket and the slots moved), then the
    statement's rows, time and dispatches. The stages between exchanges are
    traced compute of the one program and have no clock of their own."""
    q = root_op.query
    by_node = {s["node"]: s for s in root_op.exchange_stages}
    st = root_op.stats
    lines = [f"distribution: mesh ({q.D} devices), one program "
             f"[rows={st.rows} time={st.time_s*1e3:.1f}ms "
             f"overflow re-runs={q.reruns}]"]

    def walk(n: S.PlanNode, depth: int):
        s = by_node.get(id(n))
        ran = ("" if s is None else
               f"  [rows={s['rows']} offchip_rows={s['offchip_rows']} "
               f"send_cap={s['send_cap']} send_slots={s['send_slots']}]")
        lines.append("  " * depth + "-> " + _node_label(n, None) + ran)
        for c in _children(n):
            walk(c, depth + 1)

    walk(q.dplan, 0)
    tsp = getattr(root_op, "_trace_span", None)
    if tsp is not None:
        lines.append("trace:")
        lines.append(tsp.tree(indent=1))
    kd = getattr(st, "kernel_dispatches", 0)
    if kd:
        lines.append(f"kernel dispatches: {kd}")
        kc = getattr(st, "kernel_compiles", 0)
        lines.append(f"kernel compiles: {kc} (cached: {kd - kc})")
    return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    """Human byte figure for EXPLAIN ANALYZE memory lines (KiB below one
    MiB, else MiB — mirroring the reference's humanizeutil sizes)."""
    n = int(n)
    if n < 1 << 20:
        return f"{n / 1024:.1f} KiB"
    return f"{n / (1 << 20):.1f} MiB"


def explain_analyze(plan: S.PlanNode, root_op) -> str:
    """Render the plan tree with executed ComponentStats (EXPLAIN ANALYZE).
    `root_op` must have been run with collect_stats(True)."""
    from ..flow.fuse import unwrap

    lines: list[str] = []
    groups = _fusion_groups(plan)

    def walk(n: S.PlanNode, op, depth: int):
        if isinstance(n, S.Exchange):
            # single-device builds elide the exchange operator
            walk(n.input, op, depth)
            return
        # fusion-pass wrappers sit between plan nodes; see through them so
        # the plan-node/operator walk stays one-to-one
        op = unwrap(op)
        st = op.stats
        excl = st.exclusive(op.children())
        # memory-account annotations (mon.BoundAccount high-water): only
        # buffering operators open accounts, so most lines carry neither
        mem = (f" max mem={_fmt_bytes(st.max_mem_bytes)}"
               if getattr(st, "max_mem_bytes", 0) else "")
        spill = " spilled" if getattr(st, "spilled", False) else ""
        lines.append(
            "  " * depth + "-> " + _node_label(n, op)
            + f"  [rows={st.rows} batches={st.batches} "
            f"bytes={st.bytes} "
            f"time={st.time_s*1e3:.1f}ms self={excl*1e3:.1f}ms{mem}{spill}]"
            + _group_tag(groups, n)
        )
        for c, co in zip(_children(n), op.children()):
            walk(c, co, depth + 1)

    walk(plan, root_op, 0)
    # span tree from the traced run (flow/runtime.py attaches it): operator
    # wall times plus the seams ComponentStats cannot see (pull attempts,
    # readback, KV round-trips grafted from remote nodes); the plan tree
    # keeps its root on line 1 and the dispatch footer its last two lines
    # (consumers parse both)
    tsp = getattr(root_op, "_trace_span", None)
    if tsp is not None:
        lines.append("trace:")
        lines.append(tsp.tree(indent=1))
    # query peak-memory footer (the statement monitor's high water, set by
    # flow/runtime.py) BEFORE the dispatch lines, which stay last
    peak = getattr(root_op, "_query_mem_peak", 0)
    if peak:
        spills = getattr(root_op, "_query_mem_spills", 0)
        suffix = f" (spills: {spills})" if spills else ""
        lines.append(f"query peak memory: {_fmt_bytes(peak)}{suffix}")
    kd = getattr(getattr(root_op, "stats", None), "kernel_dispatches", 0)
    if kd:
        lines.append(f"kernel dispatches: {kd}")
        kc = getattr(root_op.stats, "kernel_compiles", 0)
        lines.append(f"kernel compiles: {kc} (cached: {kd - kc})")
    return "\n".join(lines)
