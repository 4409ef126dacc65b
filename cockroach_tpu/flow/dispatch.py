"""Kernel-dispatch accounting and the process-global kernel cache.

Every jitted call the engine issues is one XLA executable dispatch, and each
dispatch has a fixed host-side cost (not measured on an attached chip), so
dispatch COUNT (not FLOP count) is what short queries pay for. The fusion work
(flow/fuse.py, the _consume composition in flow/operators.py) exists to
drive that count down to ~one per tile; this module makes the count
observable so the win is measurable and regressions are catchable:

- ``jit`` wraps ``jax.jit`` so every *call* of the compiled function bumps
  one process-global counter (thread-safe: ParallelUnorderedSyncOp calls
  kernels from puller threads). All flow-layer kernels are jitted through
  it, each under a ``name`` (``<operator>_<role>``: ``hashjoin_build``,
  ``groupagg_fold_step``, ``pipe_filter``): the XLA module is
  ``jit_<name>`` and the body runs under ``jax.named_scope(name)``, so a
  profiler trace and the persistent compile cache name the program by what
  it does, not by the Python closure that built it. A name is static: it
  never holds a per-query value (the caches key on the module).
- ``flow/runtime.py`` snapshots ``total()`` around a query and attributes
  the delta to the root's ``ComponentStats.kernel_dispatches`` (surfaced
  by EXPLAIN ANALYZE).
- ``scripts/check_dispatch_budget.py`` turns the per-query count into a
  tier-1 regression budget.

Which operator a dispatch belongs to: ``section(op)`` is open around the
code in which a plan operator drives kernels (``Operator.next_batch``, and
through ``sectioned`` every resume of a tile source's generator); sections
nest as operators pull each other, on the thread that runs the statement.
While ``flow/runtime.run_operator`` holds a ``flow/pull`` span open
(``operator_record``), every section and every counted call adds to that
statement's row for the operator's ``label`` (plan/builder.py): wall,
what nested sections and jitted calls covered, seconds inside jitted
calls, calls by kernel name. A call outside any section goes to the row
``none``. At the span's close the rows become ONE record on the span
(``Span.to_dict`` carries it to bundles, /_status/spans and the debug zip)
and each row's wall and self seconds go into ``tracing.totals()`` under
``flow.op.<KERNEL>``. Nothing here grows a span tree, enters a profiler
annotation or takes a lock a tile; with no ``flow/pull`` span open a
section is a null context.

Compile-wall accounting (the L1 cache of the plan/kernel cache hierarchy —
see README "Cache hierarchy"):

- every trace bumps ``compiles()``: the wrapped function body is plain
  Python, so it executes exactly once per jax trace — and a trace is a new
  executable specialization (one XLA compile, or one persistent-cache
  deserialize). ``scripts/check_recompiles.py`` holds repeat queries to a
  ZERO delta on this counter.
- ``jit(fn, key=...)`` routes through a process-global kernel cache: two
  structurally identical kernels (same ``key``) share ONE jitted wrapper,
  so the second query's filter/project/slice reuses the first's traced
  executables instead of re-tracing an identical closure. jax.jit itself
  keys on shapes/dtypes/static args beneath each wrapper, so the composite
  key is (function identity via ``key``) x (canonical shapes) — the T5X
  PjittedFnWithContext shape. Keys must be hashable and must fully
  determine the traced computation; ``kernel_key`` returns None (= no
  sharing) for unhashable parts.
"""

from __future__ import annotations

import functools
import re
import threading
import time
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar

import jax

from ..utils import metric, tracing

_NAME = re.compile(r"[a-z][a-z0-9_]{0,47}")
_lock = threading.Lock()
_total = 0
_compiles = 0
_kernel_cache: dict = {}

NO_OPERATOR = "none"  # the row of a dispatch outside every section
_NULL = nullcontext()


class _Row:
    """One operator label's sums over one ``flow/pull``. ``child_s`` and
    ``tags`` are what ``Tracer._account`` reads of a span: the seconds
    that nested sections and the jitted calls covered, and no tag sums
    (the span's record carries the counts)."""

    __slots__ = ("label", "what", "kernel", "wall_s", "child_s", "jit_s",
                 "compile_s", "dispatches", "kernels")
    tags: dict = {}

    def __init__(self, label: str, what: str, kernel: str):
        self.label, self.what, self.kernel = label, what, kernel
        self.wall_s = self.child_s = self.jit_s = self.compile_s = 0.0
        self.dispatches = 0
        self.kernels: dict[str, int] = {}

    def self_s(self) -> float:
        """Wall seconds inside this operator's sections that neither a
        nested section nor a jitted call covers."""
        return max(0.0, self.wall_s - self.child_s)


class _Section:
    """One open operator section: the record's innermost row while open;
    on exit its wall (``wall_s``, which EXPLAIN ANALYZE's operator time
    reads too) goes to the row and to the enclosing row's ``child_s``. An
    operator nested in itself is counted right by the same sums: the inner
    wall is both in its ``wall_s`` and its ``child_s``. With no record it
    only keeps its wall."""

    __slots__ = ("_rec", "_row", "_prev", "_t0", "wall_s")

    def __init__(self, rec: "OperatorRecord | None", row: "_Row | None"):
        self._rec, self._row = rec, row

    def __enter__(self) -> "_Section":
        rec = self._rec
        if rec is not None:
            self._prev = rec.cur
            rec.cur = self._row
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = self.wall_s = time.perf_counter() - self._t0
        rec = self._rec
        if rec is None:
            return False
        prev = rec.cur = self._prev
        self._row.wall_s += dt
        if prev is None:
            rec.top_s += dt
        else:
            prev.child_s += dt
        return False


class OperatorRecord:
    """The operator rows of one ``flow/pull`` span, written by the one
    thread that runs the statement (a thread an operator starts has a
    context of its own and finds no record)."""

    def __init__(self):
        self.rows: dict[str, _Row] = {}
        self.cur: _Row | None = None  # the innermost open section's row
        self.top_s = 0.0  # wall of the sections no other section encloses

    def row(self, op) -> _Row:
        label = op.label or op.KERNEL
        row = self.rows.get(label)
        if row is None:
            row = self.rows[label] = _Row(label, op.what, op.KERNEL)
        return row

    def open_row(self) -> _Row:
        """Where a dispatch lands: the innermost open section's row, else
        the row ``none``."""
        row = self.cur
        if row is None:
            row = self.rows.get(NO_OPERATOR)
            if row is None:
                row = self.rows[NO_OPERATOR] = _Row(NO_OPERATOR, "",
                                                    NO_OPERATOR)
        return row

    def close(self, span) -> None:
        """The rows as one record on ``span``, and each operator's wall
        and self seconds into the totals under its class's name, as a
        span's close is. On the span's own clock the wall so far is tiled
        by the rows' ``host_self_ms`` + ``jit_ms``, the readback and
        ``pull_self_ms``: the pull loop's own time, what no section, no
        readback and no dispatch outside a section covers."""
        wall_s = time.perf_counter() - span.start
        out = []
        outside = self.rows.get(NO_OPERATOR)
        for r in self.rows.values():
            d = {"label": r.label, "what": r.what,
                 "dispatches": r.dispatches, "kernels": r.kernels,
                 "jit_ms": round(r.jit_s * 1e3, 3),
                 "host_self_ms": round(r.self_s() * 1e3, 3)}
            if r.compile_s:
                d["compile_ms"] = round(r.compile_s * 1e3, 3)
            out.append(d)
            if r is not outside:
                tracing.account(f"flow.op.{r.kernel}", r.wall_s, r)
        pull_self_s = (wall_s - self.top_s
                       - span.tags.get("readback_ms", 0.0) / 1e3
                       - (outside.jit_s if outside is not None else 0.0))
        span.record({"operators": out,
                     "pull_self_ms": round(max(0.0, pull_self_s) * 1e3, 3)})


_record: ContextVar[OperatorRecord | None] = ContextVar(
    "crdb_tpu_operator_record", default=None)


@contextmanager
def operator_record(span):
    """Collect operator rows while ``span`` (a ``flow/pull``) is open;
    nothing where ``span`` is None (no statement is being traced)."""
    if span is None:
        yield None
        return
    rec = OperatorRecord()
    token = _record.set(rec)
    try:
        yield rec
    finally:
        _record.reset(token)
        rec.close(span)


def section(op, timed: bool = False):
    """The context in which ``op`` drives kernels (module docstring).
    ``timed``: the caller reads the section's ``wall_s``, so it keeps its
    clock where no statement is being traced too."""
    rec = _record.get()
    if rec is None:
        return _Section(None, None) if timed else _NULL
    return _Section(rec, rec.row(op))


def sectioned(op, tiles):
    """``tiles`` (a tile source's generator) resumed inside ``op``'s
    section every time, the section closed while the consumer holds the
    tile: a generator's body runs between its consumer's lines."""
    try:
        while True:
            with section(op):
                try:
                    t = next(tiles)
                except StopIteration:
                    return
            yield t
    finally:
        tiles.close()  # a consumer may stop early (LIMIT)


def note(n: int = 1) -> None:
    """Record n dispatches issued outside a ``jit`` wrapper (direct calls
    of a shared jitted kernel, e.g. coldata.batch.compact)."""
    global _total
    with _lock:
        _total += n
    metric.KERNEL_DISPATCHES.inc(n)


def total() -> int:
    """Process-lifetime dispatch count (monotonic — snapshot before/after
    a query for per-query attribution)."""
    return _total


def note_compile(n: int = 1) -> None:
    """Record n new traces/compiles (called from inside the traced body)."""
    global _compiles
    with _lock:
        _compiles += n
    metric.KERNEL_COMPILES.inc(n)


def compiles() -> int:
    """Process-lifetime trace/compile count (monotonic — snapshot around a
    query to assert the zero-recompile serving path). Read under the
    counter lock: sessions read it while others' note_compile writes."""
    with _lock:
        return _compiles


def kernel_cache_hits() -> int:
    """Process-lifetime kernel-cache hits (jit(key=...) lookups answered
    by an already-built wrapper)."""
    return int(metric.KERNEL_CACHE_HITS.value)


def clear_kernel_cache() -> None:
    """Drop all shared wrappers (tests; frees the underlying executables
    only once operator trees release their references)."""
    with _lock:
        _kernel_cache.clear()


def kernel_key(*parts):
    """Build a kernel-cache key from hashable parts, or None (no sharing)
    when any part is unhashable. The key must fully determine the traced
    computation: callers put the op kind, schema, and the full expression
    tree in — and keep runtime-varying values (params, row counts) OUT."""
    try:
        hash(parts)
    except TypeError:
        return None
    return parts


def jit(fn=None, key=None, name=None, **jit_kwargs):
    """``jax.jit`` with a stable program name, per-call dispatch
    accounting, per-trace compile accounting, and optional process-global
    sharing under ``key``. Usable like jax.jit, both directly and via
    ``functools.partial(jit, name=..., ...)`` as a decorator."""
    if fn is None:
        return functools.partial(jit, key=key, name=name, **jit_kwargs)
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(
            f"dispatch.jit needs a static name=<operator>_<role> "
            f"(lower case, digits, '_'), got {name!r}")
    if key is not None:
        with _lock:
            cached = _kernel_cache.get(key)
        if cached is not None:
            metric.KERNEL_CACHE_HITS.inc()
            return cached

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        # plain-Python body: runs once per jax trace == one new compile
        note_compile()
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    # jax names the XLA module jit_<__name__>
    traced.__name__ = traced.__qualname__ = name
    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        note()
        sp = tracing.current()
        if sp is None:
            return jitted(*args, **kwargs)
        # traced call: the wall time of a call that compiled nothing goes
        # into the enclosing span's tags (tracing.totals() sums them over a
        # window); every call goes to its operator's row of the statement's
        # record, a call under which a trace happened as compile time, and
        # on the profiler's clock the region names kernel and operator
        rec = _record.get()
        row = None if rec is None else rec.open_row()
        # crlint: allow-race-coverage(_compiles is a monotonic counter: every write holds _lock; these lockless GIL-atomic snapshot reads only split telemetry into compile-vs-dispatch buckets — taking _lock per dispatch on the serving hot path buys nothing a stale-by-one read can break)
        c0 = _compiles
        t0 = time.perf_counter()
        with tracing.annotation(
                "flow.dispatch", kernel=name,
                op=NO_OPERATOR if row is None else row.label):
            out = jitted(*args, **kwargs)
        dt = time.perf_counter() - t0
        compiled = _compiles > c0
        if not compiled:
            sp.inc_tag("jit_dispatches", 1)
            sp.inc_tag("jit_dispatch_ms", round(dt * 1e3, 3))
        if row is not None:
            row.dispatches += 1
            row.jit_s += dt
            row.child_s += dt
            row.kernels[name] = row.kernels.get(name, 0) + 1
            if compiled:
                row.compile_s += dt
        return out

    counted._jitted = jitted  # uncounted handle (AOT lowering/inspection)
    if key is not None:
        with _lock:
            # racing builders: first insert wins so every caller shares it
            counted = _kernel_cache.setdefault(key, counted)
    return counted
