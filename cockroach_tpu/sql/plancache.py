"""Prepared-plan cache — the zero-recompile serving path (L2 of the cache
hierarchy; see README "Compile-avoidance cache hierarchy").

Reference shape: pkg/sql's query cache (plan_opt.go / querycache) keys
memoized plans on statement + placeholder types + catalog descriptor
versions, so the conn executor skips optbuild on repeat statements. Here
the expensive phase is not optimization but the build->fuse->XLA-compile
pipeline, so the cache holds the BUILT operator tree:

- ``parameterize`` rewrites numeric literals in Filter predicates into
  ``ex.Param`` slots, and the host-built tables of string predicates
  (LIKE, IN, =: ``ex.CodeLookup``) into ``ex.ParamLookup`` slots, so a
  repeat statement with different literals or another pattern maps to the
  same structural plan; the values are rebound per execution as jit
  ARGUMENTS (ops/expr.param_scope), never retraced.
- ``plan_key`` derives a stable structural key from the parameterized
  plan (frozen dataclasses all the way down). Anything it cannot key
  byte-stably (runtime-filled dictionaries, unknown objects) raises
  ``_Unkeyable`` and the statement simply is not cached — conservative
  misses, never wrong hits.
- Entries are LRU-bounded (``sql.plan_cache.size``) and keyed on the
  catalog schema version + the settings signature, so DDL (CREATE/DROP
  INDEX, ALTER) and tuning changes can never serve a stale plan; the
  session's DDL handlers additionally sweep dead-version entries out
  eagerly (``invalidate``).
- Operator trees hold mutable pull state, so two sessions never drive
  the same tree at once. An entry keeps the trees built for its plan and
  lends a session one no other session holds. Where every operator of
  the plan keeps nothing between runs (``Operator.stateless_between_runs``:
  a primary-key point read and the per-tile wrappers above it), a session
  that finds every tree out builds one more from the entry's parameterized
  plan, each tree with a ``ParamStore`` of its own, up to what the process
  admits at once (``admission.sql.slots``); a further tree compiles
  nothing, its kernels are shared by ``dispatch.kernel_key``. Any other
  plan (spools, join build sides, learned emission caps) keeps exactly
  ONE tree and its sessions queue for it under the
  ``sql.plancache.entry_wait`` span: a second tree would hold the build
  sides again and learn its caps again. Distinct statements run in
  parallel either way.

Execution-stats collection (EXPLAIN ANALYZE / the cluster setting)
bypasses the cache: stats need a fresh per-operator tree, and cached
trees deliberately skip the instrumented path.
"""

from __future__ import annotations

import dataclasses
import enum
import threading
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Dictionary
from ..coldata.types import Family
from ..ops import expr as ex
from ..plan import spec as S
from ..utils import metric, settings, tracing

# literal families rewritten into Param slots: everything whose device
# representation is a plain numeric scalar. A STRING literal never reaches
# here as a Const: string predicates lower to host-built CodeLookup tables,
# which ride as table slots (_TableSlot). BOOL stays literal (structural
# TRUE/FALSE branches), NULL stays literal (its valid-mask shape differs
# from any bound value). A BYTES literal (a string compared with a raw
# CHAR(n) column) is a slot of one zero-padded row of the type's width
_PARAM_FAMILIES = (Family.INT, Family.FLOAT, Family.DECIMAL, Family.DATE,
                   Family.TIMESTAMP, Family.INTERVAL, Family.BYTES)


class _Unkeyable(Exception):
    """The plan holds an object with no stable structural key; the
    statement runs uncached (conservative — a miss is always correct)."""


@dataclasses.dataclass(frozen=True)
class _TableSlot:
    """The slot type of a string predicate's lookup table: length (the
    column's dictionary) and dtype are pinned, the bytes are the value."""

    size: int
    dtype: str


class ParamStore:
    """Positional parameter values for one cached plan, shared by every
    operator the plan's builder created with ``params=``.

    ``args()`` is re-read at each run's ``stream_parts`` fetch, so
    rebinding values between runs flows into the jitted kernels as fresh
    arguments — dtypes are pinned per slot at parameterize time, so no
    value change can force a retrace."""

    def __init__(self, types):
        self._types = tuple(types)
        self._values: tuple | None = None
        # lookup tables among the slots (the `query` span's
        # lookup_tables_bound tag)
        self.tables = sum(isinstance(t, _TableSlot) for t in self._types)

    def set_values(self, values) -> None:
        if len(values) != len(self._types):
            raise ValueError(
                f"expected {len(self._types)} parameter values, "
                f"got {len(values)}")
        out = []
        for v, t in zip(values, self._types):
            if isinstance(t, _TableSlot):
                a = np.asarray(v)
                if a.shape != (t.size,) or str(a.dtype) != t.dtype:
                    raise ValueError(
                        f"lookup table {a.dtype}{a.shape} bound to a slot "
                        f"of {t.dtype}({t.size},)")
                # on the device once a run, not once a kernel call
                out.append(jnp.asarray(a))
                continue
            if t.family is Family.DECIMAL:
                # the same host-side fixed-point scaling Const evaluation
                # applies (ops/expr.py) — device kernels see scaled ints
                v = int(round(float(v) * 10 ** t.scale))
            if t.family is Family.BYTES:
                v = np.frombuffer(bytes(v).ljust(t.width, b"\0"), np.uint8)
            out.append(np.asarray(v, dtype=t.dtype))
        self._values = tuple(out)

    def args(self) -> tuple:
        if self._values is None:
            raise RuntimeError("ParamStore.args() before set_values()")
        return self._values


def parameterize(plan, tables: bool = True):
    """Rewrite numeric Filter-predicate literals, PointLookup keys and
    PKRange bounds into Param slots and string-predicate lookup tables into ParamLookup slots.

    Returns ``(pplan, values, types)``: the parameterized plan (shared
    across every statement with the same shape), the extracted literal
    values in slot order, and their SQL types. Runs AFTER index
    selection (plan/indexopt.py), so IndexScan lo/hi bounds stay
    literal — different index bounds are different plans by design.
    ``tables=False`` leaves lookup tables baked and content-keyed (standing
    views vmap their slots over views: scalars only)."""
    values: list = []
    types: list = []

    def walk_expr(e):
        if isinstance(e, ex.Const):
            if (e.value is not None
                    and e.type.family in _PARAM_FAMILIES
                    and not isinstance(e.value, (tuple, list, np.ndarray))):
                p = ex.Param(len(values), e.type)
                values.append(e.value)
                types.append(e.type)
                return p
            return e
        if isinstance(e, ex.CodeLookup):
            t = np.asarray(e.table)
            if not tables or t.ndim != 1:
                return e
            slot = _TableSlot(int(t.shape[0]), str(t.dtype))
            p = ex.ParamLookup(e.col, len(values), slot.size, slot.dtype,
                               e.out_type)
            values.append(t)
            types.append(slot)
            return p
        if not isinstance(e, ex.Expr):
            return e
        if isinstance(e, ex.Func2) and e.func == "round2":
            # round2's digit count is read with .value at trace time
            # ("binder guarantees a literal") — it must stay a Const
            left = walk_expr(e.left)
            return (e if left is e.left
                    else dataclasses.replace(e, left=left))
        changes = {}
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            nv = walk_field(v)
            if nv is not v:
                changes[f.name] = nv
        return dataclasses.replace(e, **changes) if changes else e

    def walk_field(v):
        if isinstance(v, ex.Expr):
            return walk_expr(v)
        if isinstance(v, tuple):
            nv = tuple(walk_field(i) for i in v)
            return nv if any(a is not b for a, b in zip(nv, v)) else v
        return v

    def walk_plan(n):
        if not dataclasses.is_dataclass(n):
            return n
        changes = {}
        for f in dataclasses.fields(n):
            v = getattr(n, f.name)
            if isinstance(n, S.Filter) and f.name == "predicate":
                nv = walk_expr(v)
            elif isinstance(n, S.PointLookup) and f.name == "keys":
                nv = walk_field(v)
            elif isinstance(n, S.PKRange) and f.name in ("lo", "hi"):
                nv = walk_field(v)
            elif isinstance(v, S.PlanNode):
                nv = walk_plan(v)
            elif (isinstance(v, tuple) and v
                    and isinstance(v[0], S.PlanNode)):
                nv = tuple(walk_plan(i) for i in v)
                if not any(a is not b for a, b in zip(nv, v)):
                    nv = v
            else:
                nv = v
            if nv is not v:
                changes[f.name] = nv
        return dataclasses.replace(n, **changes) if changes else n

    return walk_plan(plan), tuple(values), tuple(types)


def plan_key(pplan):
    """Stable structural key of a (parameterized) plan tree. Raises
    ``_Unkeyable`` for objects without byte-stable content."""
    return _key_of(pplan)


def _key_of(x):
    if x is None or isinstance(x, (bool, int, float, str, bytes)):
        return x
    if isinstance(x, enum.Enum):
        return ("enum", type(x).__name__, x.name)
    if isinstance(x, np.generic):
        return ("np", str(x.dtype), x.item())
    if isinstance(x, np.ndarray):
        return ("nd", str(x.dtype), x.shape, x.tobytes())
    if isinstance(x, ex.CodeLookup):
        # outside Filter predicates (projections, CASE arms) the table
        # stays a baked constant: eq=False dataclass (identity semantics
        # for jit keys); the plan key compares the host table's CONTENT so
        # two binds of the same string expression share an entry
        t = np.asarray(x.table)
        return ("codelookup", x.col, _key_of(x.out_type), str(t.dtype),
                t.shape, t.tobytes())
    if isinstance(x, Dictionary):
        if getattr(x, "_runtime", False):
            raise _Unkeyable("runtime-filled dictionary")
        return ("dict", tuple(str(v) for v in x.values))
    if isinstance(x, (tuple, list)):
        return ("seq", tuple(_key_of(i) for i in x))
    if dataclasses.is_dataclass(x):
        return ((type(x).__name__,)
                + tuple(_key_of(getattr(x, f.name))
                        for f in dataclasses.fields(x)))
    raise _Unkeyable(type(x).__name__)


def _table_names(plan) -> list[str]:
    names: set[str] = set()

    def walk(n):
        if isinstance(n, (S.TableScan, S.IndexScan, S.PointLookup,
                          S.PKRange)):
            names.add(n.table)
        for f in ("input", "probe", "build"):
            c = getattr(n, f, None)
            if c is not None:
                walk(c)
        for c in getattr(n, "inputs", ()) or ():
            walk(c)

    walk(plan)
    return sorted(names)


def _dict_gen(catalog, plan) -> tuple:
    """Per-table string-dictionary generations (column -> value count).
    Built operators capture dictionary SNAPSHOTS (flow/operators.py
    _wire_source_metadata), so an INSERT that mints a new string value
    must re-key the plan — decoding through the stale snapshot would
    mislabel the new codes. Row-count changes alone keep hitting."""
    return _dict_gen_for(catalog, _table_names(plan))


def _dict_gen_for(catalog, names) -> tuple:
    out = []
    for name in names:
        t = catalog.tables.get(name)
        if t is None:
            continue
        d = t.dictionaries  # KVTable property returns fresh snapshots
        out.append((name, tuple(sorted(
            (c, len(dd.values)) for c, dd in d.items()))))
    return tuple(out)


def _settings_sig() -> tuple:
    """Current values of every registered setting. Conservative: ANY
    settings change re-keys the cache (a stale tile size or fusion mode
    must never serve), at the cost of misses on unrelated toggles."""
    reg = settings.all_settings()
    return tuple((n, str(reg[n].get())) for n in sorted(reg))


def _stateless(op) -> bool:
    return op.stateless_between_runs and all(
        _stateless(c) for c in op.children())


class _Entry:
    """One cached plan: what a tree is built from, and the trees built,
    each a ``(root, ParamStore)`` pair lent to one session at a time.

    ``cap`` is how many trees the entry may hold, read off the first one:
    the process's admission slots where every operator keeps nothing
    between runs, else 1 (the one tree IS the entry's lock then). The
    settings signature is part of an entry's key, so the slots it was
    created under are the slots it lives under."""

    __slots__ = ("pplan", "types", "catalog", "distsql", "version",
                 "fingerprint", "hits", "first", "cap", "_free", "_trees",
                 "_cond")

    def __init__(self, pplan, types, catalog, fingerprint, tree,
                 distsql: str = "auto"):
        self.pplan = pplan
        self.types = types
        self.catalog = catalog
        self.distsql = distsql
        self.version = catalog.version
        self.fingerprint = fingerprint
        self.hits = 0
        self.first = tree[0]
        self.cap = (int(settings.get("admission.sql.slots"))
                    if _stateless(tree[0]) else 1)
        self._free = [tree]
        self._trees = 1  # in `_free` or out with a session
        self._cond = threading.Condition(threading.Lock())

    def take(self):
        """A tree no other session drives: a free one, else one more
        where the entry may grow, else the wait for one to come back."""
        with self._cond:
            if not self._free and self._trees >= self.cap:
                # sessions sending one statement shape queue here, and a
                # traced statement says for how long
                with tracing.leaf_span("sql.plancache.entry_wait"):
                    while not self._free and self._trees >= self.cap:
                        self._cond.wait()
            if self._free:
                return self._free.pop()
            self._trees += 1
        try:
            tree = _build_tree(self.pplan, self.types, self.catalog,
                               self.distsql)
        except BaseException:
            self.give_back(None)
            raise
        metric.PLAN_CACHE_POOL_BUILDS.inc()
        return tree

    def give_back(self, tree) -> None:
        """Return a tree taken, or with None its place: a tree that holds
        nothing is not kept after a run that raised."""
        with self._cond:
            if tree is None:
                self._trees -= 1
            else:
                self._free.append(tree)
            self._cond.notify()


def _build_tree(pplan, types, catalog, distsql: str = "auto"):
    """What an entry runs: the local operator tree, or on a node that
    spans devices the mesh program as a one-operator tree, as
    sql/distsql.py `place` decides (once an entry; the mode keys it)."""
    from . import distsql as distsql_mod

    store = ParamStore(types)
    return distsql_mod.place(pplan, catalog, distsql, params=store), store


def _placement_key(catalog, distsql: str):
    """The session's `distsql` where it can change where a plan runs."""
    return distsql if getattr(catalog, "mesh", None) is not None else None


class PlanCache:
    """Size-capped LRU of built plans, one per Catalog (``cache_for``).
    ``hits``/``misses`` counters are per-cache (tests); the process
    metrics (sql_plan_cache_*) aggregate across catalogs."""

    def __init__(self):
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self._memo: OrderedDict = OrderedDict()   # exact text -> (key, values)
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def lookup(self, key):
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                metric.PLAN_CACHE_MISSES.inc()
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            e.hits += 1
            metric.PLAN_CACHE_HITS.inc()
            return e

    def peek(self, key):
        with self._lock:
            return self._entries.get(key)

    def insert(self, key, entry) -> "_Entry":
        cap = int(settings.get("sql.plan_cache.size"))
        with self._lock:
            cur = self._entries.get(key)
            if cur is not None:
                return cur  # concurrent first executions: first wins
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > cap:
                self._entries.popitem(last=False)
                self.evictions += 1
                metric.PLAN_CACHE_EVICTIONS.inc()
            return entry

    def invalidate(self, version: int) -> int:
        """Eagerly drop entries built against a dead catalog version
        (DDL). Version is part of the key, so stale entries could never
        HIT again — this sweep just frees them immediately."""
        with self._lock:
            dead = [k for k, e in self._entries.items()
                    if e.version != version]
            for k in dead:
                del self._entries[k]
                self.evictions += 1
                metric.PLAN_CACHE_EVICTIONS.inc()
            self._memo.clear()
            return len(dead)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._memo.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- exact-text memo (skips parse/bind/optimize on verbatim repeats) --

    _MEMO_CAP = 512

    def memo_get(self, text):
        with self._lock:
            v = self._memo.get(text)
            if v is not None:
                self._memo.move_to_end(text)
            return v

    def memo_put(self, text, key, values, tables) -> None:
        with self._lock:
            self._memo[text] = (key, values, tables)
            self._memo.move_to_end(text)
            while len(self._memo) > self._MEMO_CAP:
                self._memo.popitem(last=False)


def cache_for(catalog) -> PlanCache:
    pc = getattr(catalog, "_plan_cache", None)
    if pc is None:
        pc = catalog._plan_cache = PlanCache()
    return pc


# -- the serving path --------------------------------------------------------


def _cacheable() -> bool:
    return (settings.get("sql.plan_cache.enabled")
            and not settings.get("sql.stats.collect_execution_stats"))


_VOLATILE = ("now(", "current_date", "current_timestamp")


def _is_virtual_plan(plan) -> bool:
    from . import crdb_internal

    return any(crdb_internal.is_virtual(n) for n in _table_names(plan))


def run_cached(rel, text: str | None = None):
    """Execute a bound Rel through the plan cache; see
    :func:`run_cached_ex` (this keeps the original 2-tuple shape)."""
    res, status, _ = run_cached_ex(rel, text)
    return res, status


def run_cached_ex(rel, text: str | None = None, distsql: str = "auto"):
    """Execute a bound Rel through the plan cache.

    Returns ``(results, status, fingerprint)`` with status one of ``hit``
    (literals rebound into a cached tree, zero new builds), ``miss``
    (built fresh and cached), ``uncacheable`` (no stable key), ``bypass``
    (cache off, stats collection on, or crdb_internal virtual tables —
    those materialize fresh per statement, so a cached plan would freeze
    a snapshot). ``fingerprint`` is the serving entry's structural
    fingerprint (the first text that built it — sqlstats uses it so
    literal variants collapse to one row), or '' when no entry served."""
    from ..flow import runtime

    if not _cacheable():
        return rel.run(), "bypass", ""
    cache = cache_for(rel.catalog)
    plan = rel.optimized_plan()
    if _is_virtual_plan(plan):
        return runtime.run_plan(plan, rel.catalog), "bypass", ""
    try:
        with tracing.leaf_span("sql.plancache.lookup"):
            pplan, values, types = parameterize(plan)
            key = (plan_key(pplan), rel.catalog.version, _settings_sig(),
                   _dict_gen(rel.catalog, pplan),
                   _placement_key(rel.catalog, distsql))
            entry = cache.lookup(key)
    except _Unkeyable:
        return runtime.run_plan(plan, rel.catalog), "uncacheable", ""
    status = "hit"
    if entry is None:
        status = "miss"
        entry = _Entry(pplan, types, rel.catalog, _fingerprint(text),
                       _build_tree(pplan, types, rel.catalog, distsql),
                       distsql)
        # run BEFORE publishing: a plan whose first execution fails never
        # enters the cache (concurrent first executions may both build;
        # insert keeps whichever published first)
        res = _run_entry(entry, values, "miss")
        entry = cache.insert(key, entry)
    else:
        res = _run_entry(entry, values, "hit")
    if text is not None:
        low = text.lower()
        if not any(tok in low for tok in _VOLATILE):
            # verbatim repeats can skip parse/bind next time; statements
            # with per-bind folded volatiles (now()) must re-bind
            cache.memo_put(text, key, values, tuple(_table_names(pplan)))
    return res, status, entry.fingerprint


def _run_entry(entry, values, status: str):
    """Bind ``values`` and run a prepared plan. The one site that opens a
    cached statement's ``query`` span (an instrumented, uncached run opens
    it in flow/runtime.run_plan_with_stats; a statement has one or the
    other)."""
    from ..flow import runtime

    tree = entry.take()
    root, store = tree
    if root is not entry.first:
        metric.PLAN_CACHE_POOL_RUNS.inc()
    keep = entry.cap == 1  # the one tree of a plan with state serves again
    try:
        store.set_values(values)
        with tracing.leaf_span("query", cache=status,
                               lookup_tables_bound=store.tables):
            res = runtime.run_operator(root)
        keep = True
        return res
    finally:
        # after a run that raised, a tree that holds nothing is cheaper
        # built anew than trusted half-pulled
        entry.give_back(tree if keep else None)


def run_memoized(catalog, text: str):
    """Exact-text fast path; see :func:`run_memoized_ex` (this keeps the
    original results-or-None shape)."""
    m = run_memoized_ex(catalog, text)
    return None if m is None else m[0]


def run_memoized_ex(catalog, text: str, distsql: str = "auto"):
    """Exact-text fast path: if this verbatim statement ran before and
    its entry is still live (same catalog version + settings, placed under
    the same `distsql`), execute it without parsing or binding. Returns
    (results, entry fingerprint) or None (fall through to the normal
    path)."""
    if not _cacheable():
        return None
    cache = cache_for(catalog)
    m = cache.memo_get(text)
    if m is None:
        return None
    key, values, tables = m
    # key embeds (version, settings sig, dict gens); ALL must still hold
    # — the entry itself may still live under the old key, so a stale
    # dictionary generation has to be rejected here, not left to lookup
    if (key[1] != catalog.version or key[2] != _settings_sig()
            or key[3] != _dict_gen_for(catalog, tables)
            or key[4] != _placement_key(catalog, distsql)):
        return None
    entry = cache.lookup(key)
    if entry is None:
        return None
    return _run_entry(entry, values, "memo"), entry.fingerprint


def probe(rel) -> str:
    """Cache status a statement WOULD see, without executing — the
    EXPLAIN ANALYZE "plan cache:" line (stats collection itself always
    runs the instrumented fresh tree)."""
    if not settings.get("sql.plan_cache.enabled"):
        return "disabled"
    if _is_virtual_plan(rel.optimized_plan()):
        return "uncacheable"
    try:
        pplan, _, _ = parameterize(rel.optimized_plan())
        key = (plan_key(pplan), rel.catalog.version, _settings_sig(),
               _dict_gen(rel.catalog, pplan),
               _placement_key(rel.catalog, "auto"))
    except _Unkeyable:
        return "uncacheable"
    hit = cache_for(rel.catalog).peek(key) is not None
    return "hit" if hit else "miss"


def _fingerprint(text: str | None) -> str:
    if text is None:
        return ""
    from . import sqlstats

    return sqlstats.fingerprint(text)


# -- L3: on-disk XLA compilation cache ---------------------------------------

_compile_cache_on = False


def maybe_enable_compile_cache() -> None:
    """Idempotently turn on JAX's persistent compilation cache unless
    ``sql.compile_cache.enabled`` is off — process restarts then reload
    executables from disk instead of recompiling the kernel fleet. Every
    Session and Node calls this at construction."""
    global _compile_cache_on
    if _compile_cache_on or not settings.get("sql.compile_cache.enabled"):
        return
    from ..utils.backend import enable_compile_cache

    enable_compile_cache()
    _compile_cache_on = True
