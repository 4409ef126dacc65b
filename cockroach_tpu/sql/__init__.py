"""SQL front end: parser (pkg/sql/parser analog), binder (optbuilder analog),
and the Rel fluent plan builder. ``sql(catalog, text)`` parses + plans a
SELECT into an executable Rel."""

from .binder import BindError, sql
from .rel import Rel
from .session import Session


def explain(catalog, text: str) -> str:
    """EXPLAIN / EXPLAIN ANALYZE [(DEBUG)] / EXPLAIN (DISTSQL) over SQL text.
    Accepts the statement with or without the leading EXPLAIN keywords.
    ANALYZE (DEBUG) additionally captures a statement diagnostics bundle
    (sql/diagnostics.py) and reports its id."""
    t = text.strip()
    low = t.lower()
    analyze = False
    distsql = False
    debug = False
    if low.startswith("explain"):
        t = t[len("explain"):].lstrip()
        if t.lower().startswith("(distsql)"):
            distsql = True
            t = t[len("(distsql)"):].lstrip()
        if t.lower().startswith("analyze"):
            analyze = True
            t = t[len("analyze"):].lstrip()
            if t.lower().startswith("(debug)"):
                debug = True
                t = t[len("(debug)"):].lstrip()
    rel = sql(catalog, t)
    from . import matview

    note = matview.explain_note(catalog, rel)
    prefix = (note + "\n") if note else ""
    if distsql:
        # on a node that spans devices: what a session's default mode
        # would run; on any other catalog: the plan every device jax shows
        # would run (sql/distsql.py decides both)
        served = getattr(catalog, "mesh", None) is not None
        return prefix + rel.explain_distributed(
            mode="auto" if served else "on")
    if analyze:
        import time as _time
        from types import SimpleNamespace

        from . import plancache

        t0 = _time.perf_counter()
        rendered, _ = rel.explain_analyze()
        elapsed = _time.perf_counter() - t0
        # status a NORMAL execution of this statement would see (analyze
        # itself always runs a fresh instrumented tree)
        from ..storage import blockcache

        out = rendered + f"\nplan cache: {plancache.probe(rel)}"
        # storage read-path health alongside the plan status: how much of
        # this node's point/seek traffic the block cache absorbed
        out += f"\nblock cache: {blockcache.node_cache().describe()}"
        # serving-plane health: what admission a normal execution of this
        # statement would face right now (its lane, the queue, shed state)
        from ..utils import admission

        aq = admission.sql_queue()
        pri = admission.classify_statement(t)
        lanes = aq.lane_depths()
        out += (f"\nadmission: lane={admission.lane_for(pri)} "
                f"slots={aq.in_use}/{aq.slots} "
                f"queued={lanes[admission.LANE_INTERACTIVE]}i"
                f"+{lanes[admission.LANE_ANALYTICAL]}a "
                f"shed_floor={admission.shed_floor()} "
                f"rejected={aq.rejected}")
        if debug:
            from . import diagnostics
            from ..flow.runtime import last_trace_span

            bundle = diagnostics.capture(
                SimpleNamespace(catalog=catalog), t, elapsed_s=elapsed,
                span=last_trace_span(), trigger="explain_analyze_debug",
            )
            out += f"\ndiagnostics bundle: {bundle['id']}"
        return prefix + out
    return prefix + rel.explain()


__all__ = ["BindError", "Rel", "Session", "explain", "sql"]
