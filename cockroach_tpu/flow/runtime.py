"""Flow execution — the FlowCoordinator/Materializer pull loop.

Reference: distsql_running.go:710 Run drives the root operator;
colexec/materializer.go:30 converts the final columnar batches to rows for
pgwire. Here run_plan pulls every tile from the root operator and materializes
live rows to host numpy columns (decoding string dictionaries).

The pull loop is double-buffered: tile k's
device->host copies are kicked off asynchronously as soon as the tile is
dispatched, and the blocking materialization of tile k happens while the
root computes tile k+1 — so the device->host readback (bandwidth not
measured on an attached chip) overlaps compute instead of serializing after
it.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..catalog import Catalog
from ..coldata.batch import to_host
from ..plan import builder as plan_builder
from ..plan.spec import PlanNode
from ..utils import tracing


def _start_readback(b) -> None:
    """Begin the device->host copy of every array in an already-dispatched
    tile (jax.Array.copy_to_host_async); the np.asarray calls inside
    to_host then find the bytes already landing instead of starting the
    transfer at block time."""
    import jax

    for leaf in jax.tree_util.tree_leaves(b):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # crlint: allow-broad-except(best-effort async prefetch; to_host still blocks correctly)
                return  # best-effort: to_host still blocks correctly


class _ReadbackShrink:
    """Device-side output compaction before materialization. A top-10
    result living in a 2M-row padded tile would spend the query on
    reading padding back, so large tiles compact to capacity/64 on-device.

    The decision is SPECULATIVE — no host sync in the pull loop: each
    compaction keeps a deferred device live-count and retains the original
    tile; finish() fetches all counts in one stacked sync at query end and
    re-materializes any tile the compaction truncated from its retained
    original (no recompute, no query re-run)."""

    MIN_CAP = 1 << 16

    def __init__(self):
        self._checks = []  # (output index, original tile, cap, count future)
        self._n = 0

    def shrink(self, b):
        import jax.numpy as jnp

        from ..coldata.batch import compact
        from . import dispatch

        i = self._n
        self._n += 1
        if b.capacity < self.MIN_CAP:
            return b
        cap = max(1024, b.capacity >> 6)
        count = jnp.sum(b.mask, dtype=jnp.int32)  # deferred device scalar
        out = compact(b, capacity=cap)
        dispatch.note()  # compact is a shared jitted kernel
        self._checks.append((i, b, cap, count))
        return out

    def finish(self, outs, schema, dictionaries) -> None:
        """ONE stacked count fetch; patch truncated tiles from their
        retained originals. Call only on the attempt whose output is kept
        (after _post_run_updates decides no re-run)."""
        if not self._checks:
            return
        import jax.numpy as jnp

        # crlint: allow-host-sync(deferred shrink counts: ONE stacked sync at query end by design)  # crlint: allow-mem-accounting(one int32 per shrunk tile — bounded by the query's tile count)
        counts = np.asarray(jnp.stack([c for *_, c in self._checks]))
        for (i, orig, cap, _), n in zip(self._checks, counts):
            if int(n) > cap:
                outs[i] = to_host(orig, schema, dictionaries)
        self._checks = []


def _readback(b, root, psp) -> dict[str, np.ndarray]:
    """One tile to host columns: the wait for the device, the copy and the
    dictionary decode, timed into the pull span's ``readback_ms`` and, on
    the profiler's clock, a ``flow.readback`` region."""
    r0 = time.perf_counter()
    with tracing.annotation("flow.readback"):
        out = to_host(b, root.output_schema, root.dictionaries)
    if psp is not None:
        psp.inc_tag("readback_ms",
                    round((time.perf_counter() - r0) * 1e3, 3))
    return out


def _fold_operator_spans(parent_span, op) -> None:
    """Fold the operator tree's ComponentStats into synthetic child spans
    (the execstats/traceanalyzer.go fold): inclusive wall time per
    operator, nesting mirroring the operator tree, so the trace tree shows
    where query latency went without per-tile span overhead in the pull
    loop. Exclusive times telescope: summing (self - children) over the
    whole subtree recovers the root operator's wall time."""
    st = getattr(op, "stats", None)
    if st is None:
        child = parent_span
    else:
        child = tracing.synthetic_span(
            parent_span, f"operator/{type(op).__name__}",
            float(getattr(st, "time_s", 0.0) or 0.0),
            rows=int(getattr(st, "rows", 0)),
            batches=int(getattr(st, "batches", 0)))
    for c in op.children():
        _fold_operator_spans(child, c)


def _post_run_updates(op) -> bool:
    """Give every operator its end-of-query adaptive update (deferred
    device-counter fetch — the ONE host sync speculative execution pays per
    query). Returns True when any operator invalidated this run's output
    (speculative emission capacity overflowed) and the query must re-run."""
    below = False
    for c in op.children():
        below = _post_run_updates(c) or below
    # children first: an overflow below cut this operator's inputs short
    return op.post_run_update(truncated=below) or below


def run_operator(root) -> dict[str, np.ndarray]:
    from ..utils import metric, settings
    from ..utils.errors import QueryError, _PASSTHROUGH
    from . import dispatch

    from . import memory

    metric.QUERIES.inc()
    t0 = time.perf_counter()
    d0 = dispatch.total()
    c0 = dispatch.compiles()
    # joins the session's statement monitor when sql/session.py opened one;
    # otherwise (direct rel-API use) an ephemeral query monitor under ROOT.
    # Entered manually so the exit lands AFTER root.close() in the finally:
    # operators drain their accounts in close(), and only then is the query
    # monitor judged for drain failures.
    _scope = memory.query_scope()
    qmon = _scope.__enter__()
    try:
        # speculative-capacity retry loop: operators run with sticky learned
        # shapes and validate their deferred counters after the pull; an
        # overflow (rare: first run after a data change) re-runs the query
        # with corrected capacities rather than paying a sync per tile
        # on the profiler's clock while sql.trace.xla_profile is on (the
        # benchmark's trace reduction counts statements by this name)
        with tracing.annotation("cockroach_tpu.query"):
            for attempt in range(4):
                outs: list[dict[str, np.ndarray]] = []
                shrink = _ReadbackShrink()
                # the span carries the attempt's operator rows as one
                # record (flow/dispatch.py)
                with tracing.leaf_span("flow/pull", attempt=attempt) as psp, \
                        dispatch.operator_record(psp):
                    if attempt and psp is not None:
                        # only a join's emission cap (general or compact)
                        # that overflowed sends a statement round again
                        # (HashJoinOp.post_run_update)
                        psp.add_tag("join_overflow_reruns", 1)
                    root.init()
                    # one-tile lag: materialize tile k (blocking host
                    # copy) while the root's async dispatches compute
                    # tile k+1
                    prev = None
                    while True:
                        b = root.next_batch()
                        if b is not None:
                            b = shrink.shrink(b)
                            _start_readback(b)
                        if prev is not None:
                            outs.append(_readback(prev, root, psp))
                        prev = b
                        if b is None:
                            break
                    if psp is not None:
                        psp.add_tag("tiles", len(outs))
                if not _post_run_updates(root):
                    shrink.finish(outs, root.output_schema,
                                  root.dictionaries)
                    break
            else:
                raise RuntimeError(
                    "speculative emission capacities failed to converge"
                )
    except _PASSTHROUGH:
        raise
    except Exception as e:
        # the colexecerror boundary: engine/kernel failures surface as a
        # typed query error, never a raw JAX traceback mid-flow
        from ..utils import log

        log.error(log.SQL_EXEC, "query failed",
                  operator=type(root).__name__, error=str(e))
        raise QueryError(f"operator {type(root).__name__}", e) from e
    finally:
        metric.QUERY_SECONDS.observe(time.perf_counter() - t0)
        st = getattr(root, "stats", None)
        if st is not None:
            # per-query dispatch attribution (EXPLAIN ANALYZE header);
            # dispatches are process-global so they land on the root
            st.kernel_dispatches += dispatch.total() - d0
            st.kernel_compiles += dispatch.compiles() - c0
        root.close()
        _scope.__exit__(None, None, None)
        # peak/spills survive monitor close — EXPLAIN ANALYZE's query
        # footer and sqlstats read them off the root operator
        root._query_mem_peak = qmon.high_water
        root._query_mem_spills = qmon.spills
    if not outs:
        return {n: np.array([]) for n in root.output_schema.names}
    return {
        n: np.concatenate([o[n] for o in outs])
        for n in root.output_schema.names
    }


def run_plan_with_stats(plan: PlanNode, catalog: Catalog, root=None):
    """Run with ComponentStats collection; returns (results, root operator).
    The stats land on the active tracing span. ``root``: the tree to run
    where the caller placed the plan itself (sql/distsql.py)."""
    if root is None:
        root = plan_builder.build(plan, catalog)
    root.collect_stats(True)
    with tracing.span("query") as sp:
        res = run_operator(root)
        sp.record(root.stats)
        _fold_operator_spans(sp, root)
    root._trace_span = sp  # EXPLAIN ANALYZE renders the tree from here
    _LAST_TRACE.span = sp
    return res, root


_LAST_TRACE = threading.local()


def last_trace_span():
    """This thread's most recent run_plan_with_stats root span — EXPLAIN
    ANALYZE (DEBUG) reads it for bundle capture after the rel API has
    already discarded the root operator."""
    return getattr(_LAST_TRACE, "span", None)


def run_plan(plan: PlanNode, catalog: Catalog) -> dict[str, np.ndarray]:
    from ..utils import settings

    if settings.get("sql.stats.collect_execution_stats"):
        res, _ = run_plan_with_stats(plan, catalog)
        return res
    return run_operator(plan_builder.build(plan, catalog))
