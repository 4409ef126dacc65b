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

import jax

from ..utils import metric, tracing

_NAME = re.compile(r"[a-z][a-z0-9_]{0,47}")
_lock = threading.Lock()
_total = 0
_compiles = 0
_cache_hits = 0
_kernel_cache: dict = {}


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
    counter lock: warm-menu workers poll this for their budget check
    concurrently with serving-path note_compile writes."""
    with _lock:
        return _compiles


def kernel_cache_hits() -> int:
    """Process-lifetime kernel-cache hits (jit(key=...) lookups answered
    by an already-built wrapper)."""
    return _cache_hits


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
        global _cache_hits
        with _lock:
            cached = _kernel_cache.get(key)
        if cached is not None:
            with _lock:
                _cache_hits += 1
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
        # traced call: split wall time into compile (trace happened under
        # this call) vs execute, folded into the enclosing span's tags so
        # EXPLAIN ANALYZE (DEBUG) shows where dispatch time went, and
        # tracing.totals() sums them over a window
        # crlint: allow-race-coverage(_compiles is a monotonic counter: every write holds _lock; these lockless GIL-atomic snapshot reads only split telemetry into compile-vs-dispatch buckets — taking _lock per dispatch on the serving hot path buys nothing a stale-by-one read can break)
        c0 = _compiles
        t0 = time.perf_counter()
        with tracing.annotation("flow.dispatch", kernel=name):
            out = jitted(*args, **kwargs)
        dt_ms = (time.perf_counter() - t0) * 1e3
        if _compiles > c0:
            sp.inc_tag("jit_compiles", _compiles - c0)
            sp.inc_tag("jit_compile_ms", round(dt_ms, 3))
        else:
            sp.inc_tag("jit_dispatches", 1)
            sp.inc_tag("jit_dispatch_ms", round(dt_ms, 3))
        return out

    counted._jitted = jitted  # uncounted handle (AOT lowering/inspection)
    counted._kernel_key = key
    if key is not None:
        with _lock:
            # racing builders: first insert wins so every caller shares it
            counted = _kernel_cache.setdefault(key, counted)
    return counted
