"""Tracing — the pkg/util/tracing analog (Tracer tracer.go:289, Span
span.go:46): always-cheap structured spans forming a tree per operation,
with structured payloads. DistSQL propagates spans through flows and folds
per-processor ComponentStats into EXPLAIN ANALYZE via
execstats/traceanalyzer.go; here every layer seam opens a span (parse/bind/
plan-cache, flow pull, KV batch send, WAL append, compaction) and remote
recordings graft back into the caller's tree (the snowball-trace shape).

Concurrency model: the "current span" lives in a ``contextvars.ContextVar``
so concurrent sessions — one thread per pgwire connection — keep disjoint
span trees. A new thread starts with an empty context, so its first span is
a new root; nothing ever needs to lock a shared stack. The inflight-span
registry (crdb_internal.node_inflight_trace_spans / tracing/service's
inflight collection) and the finished-root ring are the only shared state,
each under its own lock.

Wire shape: ``context()`` exports the Dapper-style ``(trace_id, span_id)``
pair; a server opens its span with ``remote_span(name, ctx)`` and ships the
finished recording (``Span.to_dict``) back in its response; the client
calls ``graft(payload)`` to attach the remote subtree to its own span.

Creation discipline (enforced by the crlint ``tracing-api`` pass): spans
are only born through ``Tracer.span``/``remote_span``/``synthetic_span`` —
no direct Span() construction or current-context mutation outside this
module, so every span is guaranteed to close, unregister from the inflight
table, and land in exactly one tree.

Whole-window accounting: every span that closes adds its count, seconds,
self seconds and numeric tags to a per-name record (``totals()``); a reader
snapshots before and after a window and takes the difference, so nothing
depends on which roots the ring still holds. ``timed(name)`` is the entry
for work that must NOT grow a tree — the node's background loops, the wire
— it feeds the same totals and names an *owner* for the compiles that
happen inside it (``compiles_by_owner()``), but is never ``current()``, in
the in-flight table or in the ring. Sections that keep their own times
(the flow layer's operator sections, flow/dispatch.py) close into the same
totals a statement at a time (``account``). ``compile_seconds()``
holds what JAX reported of every backend compile by program name, a load
from the persistent cache told apart from a compile.

The profiler's clock: while ``sql.trace.xla_profile`` is on, every span
and timed section also enters a ``jax.profiler.TraceAnnotation`` of its
name on its own thread (``annotation()`` is the same for sites too hot
for a span), so a profiler trace places the device's idle time against
the layer the host was in.
"""

from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar
from dataclasses import dataclass, field, is_dataclass
from typing import Any

from . import settings

_ids = itertools.count(1)
_id_lock = threading.Lock()


def _next_id() -> int:
    with _id_lock:
        return next(_ids)


def _jsonable(v: Any):
    """Best-effort JSON projection for tags/records (ComponentStats and
    friends carry __slots__; unknown objects degrade to repr)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(i) for i in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    slots = getattr(type(v), "__slots__", None)
    if slots:
        return {s: _jsonable(getattr(v, s, None)) for s in slots}
    if is_dataclass(v) and not isinstance(v, type):
        import dataclasses

        return {f.name: _jsonable(getattr(v, f.name))
                for f in dataclasses.fields(v)}
    return repr(v)


@dataclass
class Span:
    name: str
    trace_id: int = 0
    span_id: int = 0
    parent_id: int = 0
    start: float = 0.0       # perf_counter seconds (durations)
    start_wall: float = 0.0  # epoch seconds (cross-process alignment)
    duration: float = 0.0
    tags: dict[str, Any] = field(default_factory=dict)
    records: list[Any] = field(default_factory=list)
    children: list["Span"] = field(default_factory=list)
    remote: bool = False     # grafted from another node's recording
    error: str | None = None
    child_s: float = 0.0     # seconds its closed children covered (self time)

    def record(self, payload: Any) -> None:
        """Attach a structured payload (ComponentStats etc.)."""
        self.records.append(payload)

    def add_tag(self, key: str, value: Any) -> None:
        self.tags[key] = value

    def inc_tag(self, key: str, delta: float) -> None:
        """Accumulate a numeric tag (per-call costs folded into one
        number: jit dispatch time, readback time, retry counts)."""
        self.tags[key] = self.tags.get(key, 0) + delta

    def tree(self, indent: int = 0) -> str:
        mark = " [remote]" if self.remote else ""
        err = f" error={self.error}" if self.error else ""
        out = [f"{'  ' * indent}{self.name}: {self.duration*1e3:.2f}ms"
               + mark + (f" {self.tags}" if self.tags else "") + err]
        for c in self.children:
            out.append(c.tree(indent + 1))
        return "\n".join(out)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def to_dict(self) -> dict:
        """JSON-serializable recording (the wire/bundle shape)."""
        d = {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "startWallMs": round(self.start_wall * 1e3, 3),
            "durationMs": round(self.duration * 1e3, 4),
            "tags": _jsonable(self.tags),
            "children": [c.to_dict() for c in self.children],
        }
        if self.records:
            d["records"] = _jsonable(self.records)
        if self.remote:
            d["remote"] = True
        if self.error:
            d["error"] = self.error
        return d

    @staticmethod
    def from_dict(d: dict) -> "Span":
        s = Span(
            name=str(d.get("name", "?")),
            trace_id=int(d.get("traceId", 0)),
            span_id=int(d.get("spanId", 0)),
            parent_id=int(d.get("parentId", 0)),
            start_wall=float(d.get("startWallMs", 0.0)) / 1e3,
            duration=float(d.get("durationMs", 0.0)) / 1e3,
            tags=dict(d.get("tags") or {}),
            records=list(d.get("records") or ()),
            remote=True,
            error=d.get("error"),
        )
        s.children = [Span.from_dict(c) for c in d.get("children", ())]
        return s


MAX_FINISHED = 1024  # ring of recent root spans (the span registry's cap)
MAX_CHILDREN = 128  # per-span child cap (hot leaf sites: WAL appends)

# JAX's own event for one backend compile (the benchmark's
# kernels.xla_compiles_in_window counts the same event)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# reported on the compiling thread, inside the compile event's stretch, only
# where the persistent cache answered (jax/_src/compiler.py)
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
OWNER_STATEMENT = "statement"  # a span was current on the compiling thread
OWNER_OTHER = "other"          # neither a timed section nor a span

_NULL = nullcontext()
# sql.trace.xla_profile, kept current by _on_setting so that no span looks
# the setting up
_mirror = False


def _on_setting(name: str, _value) -> None:
    global _mirror
    if name == "sql.trace.xla_profile":
        _mirror = bool(settings.get("sql.trace.xla_profile"))


settings.on_change(_on_setting)


def _profiler_annotation(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` (not yet entered); a null
    context where the profiler cannot be had — a query must run without
    it."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # crlint: allow-broad-except(profiler optional; query must run without it)
        return _NULL
    return TraceAnnotation(name, **args)


def annotation(name: str, **args):
    """Profiler-only region for sites too hot for an always-on span (each
    kernel dispatch, each readback): on the profiler's clock while
    ``sql.trace.xla_profile`` is on, nothing otherwise — no span, no
    totals."""
    if not _mirror:
        return _NULL
    return _profiler_annotation(name, **args)


class _Timed:
    """One timed section (``Tracer.timed``). Besides its wall seconds it
    records the thread's own CPU seconds (``time.thread_time``): a loop
    body's wall time is mostly waiting — for the interpreter lock, the
    engine's mutex, the device's queue behind a statement's kernels — and
    only the CPU seconds say what the section itself took from the host."""

    __slots__ = ("_tracer", "_name", "_t0", "_c0", "_token", "_ann")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer, self._name = tracer, name

    def __enter__(self) -> "_Timed":
        self._token = self._tracer._owner.set(self._name)
        self._ann = None
        if _mirror:
            self._ann = _profiler_annotation(self._name)
            self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        cpu = time.thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._owner.reset(self._token)
        self._tracer._account(self._name, dt, None, None, cpu)
        return False


class Tracer:
    """Per-process tracer; the current span rides a ContextVar so every
    thread (pgwire session, flow server conn, background queue) nests its
    own tree. Finished root spans are kept in a bounded ring; open spans
    are visible through ``inflight()`` for crdb_internal."""

    def __init__(self):
        self._current: ContextVar[Span | None] = ContextVar(
            f"crdb_tpu_trace_{id(self)}", default=None)
        self.finished: list[Span] = []
        self._fin_lock = threading.Lock()
        self._inflight: dict[int, Span] = {}
        self._if_lock = threading.Lock()
        # the innermost open timed section's name on this thread/context
        self._owner: ContextVar[str | None] = ContextVar(
            f"crdb_tpu_trace_owner_{id(self)}", default=None)
        # name -> [count, total_s, self_s, {numeric tag: sum}, cpu_s], and
        # backend compiles by owner; one lock for both, taken once per close
        self._totals: dict[str, list] = {}
        self._compiles: dict[str, int] = {}
        # program name -> [compiles, compile_s, cache_loads, cache_load_s]
        self._programs: dict[str, list] = {}
        self._cache_load = threading.local()  # .seen: a load precedes
        self._tot_lock = threading.Lock()

    # -- span lifecycle ----------------------------------------------------

    @contextmanager
    def span(self, name: str, **tags):
        yield from self._run_span(Span(name=name, tags=dict(tags)), None)

    @contextmanager
    def remote_span(self, name: str, ctx: dict | None, **tags):
        """Server-side half of propagation: open a span whose parent is
        the REMOTE caller's span (``ctx`` from :func:`context`). With
        ``ctx=None`` this is a no-op context yielding None — so wire
        handlers stay unconditional. The finished recording (``to_dict``)
        is what the server ships back for grafting."""
        if ctx is None:
            yield None
            return
        s = Span(name=name, tags=dict(tags))
        remote = (int(ctx.get("traceId", 0)), int(ctx.get("spanId", 0)))
        yield from self._run_span(s, remote)

    @contextmanager
    def leaf_span(self, name: str, **tags):
        """A span that only exists when an operation is already being
        traced (hot sites: WAL appends, KV sends from background threads
        must not flood the finished ring with root spans). Yields None
        when no span is active."""
        if self._current.get() is None:
            yield None
            return
        yield from self._run_span(Span(name=name, tags=dict(tags)), None)

    def timed(self, name: str) -> _Timed:
        """A timed section: feeds ``totals()`` and the profiler mirror and
        owns the compiles inside it, but grows no tree — it is never
        ``current()``, never in ``inflight()`` or ``finished``, and a
        ``leaf_span`` inside it still yields None. For work that runs
        many times a second outside any statement (the node's loops, the
        wire): root spans there would evict the ring's statement sample
        and switch on every leaf site under them."""
        return _Timed(self, name)

    def _account(self, name: str, total_s: float, span: Span | None,
                 parent: Span | None, cpu_s: float = 0.0) -> None:
        """One close: the per-name totals, and the parent's running
        ``child_s`` (so children dropped past MAX_CHILDREN still count
        against its self time)."""
        with self._tot_lock:
            rec = self._totals.get(name)
            if rec is None:
                rec = self._totals[name] = [0, 0.0, 0.0, {}, 0.0]
            rec[0] += 1
            rec[1] += total_s
            if span is None:
                rec[2] += total_s
                rec[4] += cpu_s
                return
            rec[2] += max(0.0, total_s - span.child_s)
            if parent is not None:
                parent.child_s += total_s
            sums = rec[3]
            for k, v in span.tags.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    sums[k] = sums.get(k, 0) + v

    def _run_span(self, s: Span, remote_parent: tuple[int, int] | None):
        parent = self._current.get()
        s.span_id = _next_id()
        s.start = time.perf_counter()
        s.start_wall = time.time()
        if remote_parent is not None:
            s.trace_id, s.parent_id = remote_parent
        elif parent is not None:
            s.trace_id = parent.trace_id
            s.parent_id = parent.span_id
            if len(parent.children) < MAX_CHILDREN:
                parent.children.append(s)
            else:
                parent.inc_tag("dropped_children", 1)
        else:
            s.trace_id = s.span_id
        with self._if_lock:
            self._inflight[s.span_id] = s
        token = self._current.set(s)
        ann = None
        if _mirror:
            ann = _profiler_annotation(s.name)
            ann.__enter__()
        try:
            yield s
        except BaseException as e:
            if s.error is None:
                s.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            s.duration = time.perf_counter() - s.start
            if ann is not None:
                ann.__exit__(None, None, None)
            self._current.reset(token)
            self._account(s.name, s.duration, s,
                          parent if remote_parent is None else None)
            with self._if_lock:
                self._inflight.pop(s.span_id, None)
            if parent is None:
                with self._fin_lock:
                    self.finished.append(s)
                    if len(self.finished) > MAX_FINISHED:
                        del self.finished[: -MAX_FINISHED]

    def synthetic_span(self, parent: Span, name: str, duration_s: float,
                       **tags) -> Span:
        """Attach an already-measured child span (execstats folding: per-
        operator ComponentStats become spans after the pull loop ran).
        The ONE sanctioned way to make a span without entering it."""
        s = Span(name=name, trace_id=parent.trace_id,
                 span_id=_next_id(), parent_id=parent.span_id,
                 start_wall=parent.start_wall, duration=duration_s,
                 tags=dict(tags))
        parent.children.append(s)
        return s

    # -- context + recordings ----------------------------------------------

    def current(self) -> Span | None:
        return self._current.get()

    def context(self) -> dict | None:
        """The wire-propagated (trace_id, span_id) of the current span —
        None when nothing is being traced (callers then skip the field)."""
        s = self._current.get()
        if s is None:
            return None
        return {"traceId": s.trace_id, "spanId": s.span_id}

    def graft(self, payload: dict | None,
              into: Span | None = None) -> Span | None:
        """Attach a remote recording (a ``to_dict`` dict shipped back by
        a server) under the current span — or under ``into``, for streams
        whose trailer arrives on a different thread than the span owner
        (flow inboxes pulled by puller threads). No-op outside a span or
        for a None/bad payload — error paths call this unconditionally."""
        if not payload:
            return None
        cur = into if into is not None else self._current.get()
        if cur is None:
            return None
        try:
            s = Span.from_dict(payload)
        except (TypeError, ValueError, KeyError):
            return None
        cur.children.append(s)
        return s

    def inflight(self) -> list[Span]:
        """Open spans, oldest first (node_inflight_trace_spans). The
        returned Span objects are live — readers must not mutate them."""
        with self._if_lock:
            return sorted(self._inflight.values(), key=lambda s: s.start)

    # -- whole-window accounting -------------------------------------------

    def totals(self) -> dict[str, dict]:
        """Snapshot of every closed span and timed section since the
        process started, by name: ``count``, ``total_s``, ``self_s`` (a
        span's seconds minus what its closed children covered; a timed
        section's are its own), ``tags`` (sums of numeric tags) and
        ``cpu_s`` (timed sections only: the thread's own CPU seconds
        inside them, waits left out; 0 for a span). A reader takes the
        difference of two snapshots over its window."""
        with self._tot_lock:
            return {n: {"count": r[0], "total_s": r[1], "self_s": r[2],
                        "tags": dict(r[3]), "cpu_s": r[4]}
                    for n, r in self._totals.items()}

    def compiles_by_owner(self) -> dict[str, int]:
        """Backend compiles JAX reported since the listener went in, by
        who asked: the innermost timed section open on the compiling
        thread, else ``statement`` where a span was current, else
        ``other``."""
        with self._tot_lock:
            return dict(self._compiles)

    def compile_seconds(self) -> dict[str, dict]:
        """What JAX reported of every backend compile since the listener
        went in, by program name as JAX gives it (``jit(<kernel>)``): ``compiles`` and
        ``compile_s`` for programs XLA compiled, ``cache_loads`` and
        ``cache_load_s`` for those the persistent cache answered (the
        seconds are the compile event's either way: what the caller
        waited)."""
        with self._tot_lock:
            return {n: {"compiles": r[0], "compile_s": r[1],
                        "cache_loads": r[2], "cache_load_s": r[3]}
                    for n, r in self._programs.items()}

    def _on_duration(self, event: str, seconds: float, fun_name=None,
                     **_kw) -> None:
        # runs on the compiling thread, so both contextvars and the
        # thread-local are its own
        if event == CACHE_LOAD_EVENT:
            self._cache_load.seen = True
            return
        if event != COMPILE_EVENT:
            return
        loaded = getattr(self._cache_load, "seen", False)
        self._cache_load.seen = False
        owner = self._owner.get()
        if owner is None:
            owner = (OWNER_STATEMENT if self._current.get() is not None
                     else OWNER_OTHER)
        with self._tot_lock:
            self._compiles[owner] = self._compiles.get(owner, 0) + 1
            rec = self._programs.setdefault(str(fun_name), [0, 0.0, 0, 0.0])
            at = 2 if loaded else 0
            rec[at] += 1
            rec[at + 1] += seconds


# process-global default tracer (the reference hangs one off every Server)
DEFAULT = Tracer()


def span(name: str, **tags):
    return DEFAULT.span(name, **tags)


def remote_span(name: str, ctx: dict | None, **tags):
    return DEFAULT.remote_span(name, ctx, **tags)


def leaf_span(name: str, **tags):
    return DEFAULT.leaf_span(name, **tags)


def current() -> Span | None:
    return DEFAULT.current()


def context() -> dict | None:
    return DEFAULT.context()


def graft(payload: dict | None, into: Span | None = None) -> Span | None:
    return DEFAULT.graft(payload, into)


def inflight() -> list[Span]:
    return DEFAULT.inflight()


def timed(name: str) -> _Timed:
    return DEFAULT.timed(name)


def totals() -> dict[str, dict]:
    return DEFAULT.totals()


def account(name: str, total_s: float, covered) -> None:
    """One close of a section that kept its own clock (an operator's row
    of a statement, flow/dispatch.py) into the totals as a span's close
    is. ``covered`` holds what that reads of a span: ``child_s``, the
    seconds nested sections and calls covered, and ``tags``."""
    DEFAULT._account(name, total_s, covered, None)


_listener_lock = threading.Lock()
_listening = False


def install_compile_listener() -> None:
    """Register the default tracer's one ``jax.monitoring`` duration
    listener (idempotent). ``utils/backend.enable_compile_cache`` — which
    every entry point calls — and ``compiles_by_owner`` both come here."""
    global _listening
    with _listener_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            DEFAULT._on_duration)
        _listening = True


def compiles_by_owner() -> dict[str, int]:
    install_compile_listener()
    return DEFAULT.compiles_by_owner()


def compile_seconds() -> dict[str, dict]:
    install_compile_listener()
    return DEFAULT.compile_seconds()


def synthetic_span(parent: Span, name: str, duration_s: float,
                   **tags) -> Span:
    return DEFAULT.synthetic_span(parent, name, duration_s, **tags)
