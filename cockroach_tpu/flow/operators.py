"""Flow operators — the colexec operator set over the Operator contract.

Two execution paths per operator:

- **Fused streaming segments** (the TPU-first hot path): every streaming
  operator exposes ``stream_parts()`` — a pure per-tile device function plus
  its device arguments. Buffering consumers (aggregation, sort, join build)
  compose the whole streaming chain beneath them (scan slice -> filter ->
  project -> unique/semi/anti join probes -> their own per-tile work) into
  ONE jitted function, so a TPC-H probe pipeline costs one XLA dispatch per
  tile instead of one per operator. This matters doubly on TPU: XLA fuses
  elementwise work into single HBM passes, and dispatch+sync latency
  (not measured on an attached chip) stops scaling with plan depth.
  The reference gets pipelining from goroutine-per-processor batch pulls
  (flowinfra); here the pipeline is a traced program.
- **Per-operator jits** (fallback): general joins (dynamic output capacity),
  exchanges, and any non-fusible child keep the classic pull loop, one jit
  per operator, mirroring colexecop.Operator Next() semantics.

Buffering operators size their spools by LIVE row count (one host sync per
spool, not per tile), so downstream kernels compile at the smallest pow2
capacity that fits the data, and capacity-bucketing keeps the set of compiled
shapes tiny. Aggregation decomposes into partial/merge/finalize exactly like
CRDB's local/final aggregation around a shuffle (distsql_physical_planner.go).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog import SHAPE_BUCKETS, Table
from ..coldata.batch import (
    Batch, Column, Dictionary, concat, concat_prefix, empty_batch, from_host,
    pad_rows,
)
from ..coldata.types import FLOAT64, Family, Schema
from ..ops import aggregation as agg_ops
from ..ops.aggregation import partial_layout
from ..ops import expr as ex
from ..ops import join as join_ops
from ..ops import sort as sort_ops
from ..utils import tracing
from . import dispatch
from .operator import OneInputOperator, Operator, SourceOperator


def _next_pow2(n: int) -> int:
    p = 1024
    while p < n:
        p *= 2
    return p


def _canonical_cap(n: int) -> int:
    """Canonical capacity for DATA-DEPENDENT intermediates (spools, learned
    emission caps, join output growth). With shape bucketing on, snaps to
    the catalog.SHAPE_BUCKETS rung ladder so a repeat run whose literals
    select a somewhat different row count still lands on the kernel shapes
    the first run compiled — pure pow2 would mint a fresh specialization at
    every doubling boundary. Stays pow2 (spool consumers assume it): rungs
    are pow2, and above the top rung pow2 growth IS the coarse ladder.
    Falls back to plain pow2 with bucketing off."""
    from ..utils import settings

    if settings.get("sql.distsql.shape_buckets.enabled"):
        for b in SHAPE_BUCKETS:
            if n <= b:
                return b
    return _next_pow2(n)


_LOWEST_RUNG = SHAPE_BUCKETS[0]  # 1,024: the floor of a learned cap


def _emission_cap(n: int) -> int:
    """Learned compact-emission cap for ``n`` rows (2 x the live rows a
    join's fullest tile kept): the canonical rung up to 65,536, plain pow2
    past it. The next rung is 8x away, and a cap 8x the live rows makes
    every join downstream carry seven dead rows for each live one (q9 at
    SF1: 55.7k live rows a tile, rung 524,288, pow2 131,072). Caps only
    grow once learned (post_run_update), so the finer steps mint at most
    one specialization a doubling."""
    cap = _canonical_cap(n)
    return cap if cap <= (1 << 16) else _next_pow2(n)


def _live_total(tiles: list[Batch]) -> int:
    """Total live rows across spooled tiles — ONE host sync for the spool."""
    if not tiles:
        return 0
    # crlint: allow-host-sync(one stacked sync per spool finalize, not per tile)
    return int(sum(jnp.sum(t.mask, dtype=jnp.int64) for t in tiles))


def _spool_cap(tiles: list[Batch]) -> int:
    """Canonical capacity fitting the spool's LIVE rows: every spool
    compacts into it through `concat`, but a join build whose producer
    proves its tiles live-prefix, which places them (`concat_prefix`)."""
    return _canonical_cap(max(1, _live_total(tiles)))


class _FusedPull:
    """Drives a fused streaming chain: one jit over (consumer tile fn o
    chain fn), pulled from the chain's source. Cached on the consumer so the
    composition traces once per operator instance."""

    def __init__(self, parts, tile_fn, kernel: str):
        src, chain_fn, _ = parts
        self.src = src
        self._fn = dispatch.jit(
            lambda t, *a: tile_fn(chain_fn(t, *a)), name=f"{kernel}_fused"
        )

    def pull(self, parts):
        _, _, args = parts
        for t in self.src.stream_tiles():
            yield self._fn(t, *args)


def _per_chain(op, attr: str, cfn, make):
    """``op``'s cached composition over the chain function ``cfn``, made
    once a chain. A few are kept, not one: a join below alternates between
    driving its own kernel and composing its probe into this one as its
    literals change (HashJoinOp._composes), and every chain it has
    run must find its traced programs again."""
    cache = op.__dict__.setdefault(attr, {})
    got = cache.get(cfn)
    if got is None:
        if len(cache) >= 4:
            cache.pop(next(iter(cache)))
        got = cache[cfn] = make()
    return got


def _fusion_enabled() -> bool:
    # sql.distsql.fusion.enabled=off degrades EVERY fusion path (the
    # plan-build pass in flow/fuse.py AND these consumer-driven spool
    # compositions) to classic one-jit-per-operator pulls — the unfused
    # oracle the fusion-equivalence sweep compares against
    from ..utils import settings

    return settings.get("sql.distsql.fusion.enabled")


def _consume(op: OneInputOperator, tile_fn_name: str, tile_fn,
             fallback_fn=None):
    """Iterate tile_fn over the child's tiles, fused into one jit with the
    child's streaming chain when possible. fallback_fn (a jitted version of
    tile_fn) serves the classic per-operator pull path.

    tile_fn_name keys the cached composition on the consumer instance.

    Stats collection (EXPLAIN ANALYZE) forces the per-operator path so every
    operator's batch/row counts stay observable — the reference equivalently
    pays for its stats wrappers (colflow/stats.go)."""
    parts = (None if (op._collect or not _fusion_enabled())
             else op.child.stream_parts())
    if parts is None:
        fn = fallback_fn if fallback_fn is not None else tile_fn
        while True:
            b = op.child.next_batch()
            if b is None:
                return
            yield fn(b)
        return
    cached = _per_chain(
        op, f"_fused_{tile_fn_name}", parts[1],
        lambda: _FusedPull(parts, tile_fn, f"{op.KERNEL}_{tile_fn_name}"))
    yield from cached.pull(parts)


def _fold(op: OneInputOperator, tag: str, tile_raw, tile_jit, merge_raw,
          merge_jit):
    """Reduce tile_raw over the child's tiles, merging into an accumulator
    with merge_raw. The fused path composes (merge o tile o chain) into ONE
    step kernel carrying the accumulator — folding consumers (scalar/dense
    aggregation) then pay exactly one dispatch per tile instead of
    tile + merge. Returns the final accumulator (None on empty input)."""
    parts = (None if (op._collect or not _fusion_enabled())
             else op.child.stream_parts())
    if parts is None:
        acc = None
        while True:
            b = op.child.next_batch()
            if b is None:
                return acc
            st = tile_jit(b)
            acc = st if acc is None else merge_jit(acc, st)
    src, cfn, args = parts
    nc = len(args)
    seed, step = _per_chain(op, f"_fold_{tag}", cfn, lambda: (
        dispatch.jit(lambda t, *a: tile_raw(cfn(t, *a[:nc])),
                     name=f"{op.KERNEL}_fold_seed"),
        dispatch.jit(
            lambda acc, t, *a: merge_raw(acc, tile_raw(cfn(t, *a[:nc]))),
            donate_argnums=0, name=f"{op.KERNEL}_fold_step",
        )))
    acc = None
    for t in src.stream_tiles():
        acc = seed(t, *args) if acc is None else step(acc, t, *args)
    return acc


# ---------------------------------------------------------------------------
# Scan


def _wire_source_metadata(op, table, names: tuple[str, ...]) -> None:
    """Install the plan-static metadata every table source carries:
    output_schema, per-column dictionaries, and (lo, hi) column stats —
    shared by ScanOp and IndexScanOp so the downstream contract has one
    definition."""
    idxs = tuple(table.schema.index(n) for n in names)
    op.col_idxs = idxs
    op.output_schema = table.schema.select(idxs)
    full_dicts = table.dict_by_index()
    op.dictionaries = {
        i: full_dicts[ci] for i, ci in enumerate(idxs) if ci in full_dicts
    }
    stats_fn = getattr(table, "col_stats", None)
    if callable(stats_fn):
        by_name = stats_fn()
        op.col_stats = {
            i: by_name[n]
            for i, n in enumerate(op.output_schema.names)
            if n in by_name
        }


class ScanOp(SourceOperator):
    """Tile-granular scan (cFetcher analog). Two modes:

    - resident: the table materializes once in HBM (warm block-cache model;
      KV decode happened at load) and BOUNDED tiles slice from it — the
      table capacity is padded to a multiple of the tile (catalog._pad_cap),
      so no downstream kernel ever compiles at full-table shape.
    - streaming: tables over `sql.distsql.scan_stream_rows` never fully
      occupy HBM — tiles upload host->device with DOUBLE BUFFERING (the
      next tile's async transfer is issued before the current one is
      consumed, so transfer overlaps downstream compute — SURVEY §7's
      pipelining host<->device hard part).

    In fused mode the slice itself is traced into the consumer's kernel
    (stream_tiles yields (resident_batch, offset) tokens), so a probe
    pipeline's scan costs zero extra dispatches.
    """

    def __init__(self, table: Table, columns: tuple[str, ...] | None = None,
                 tile: int | None = None,
                 shard: tuple[int, int] | None = None):
        super().__init__()
        self.table = table
        self.shard = shard  # (i, n): emit only rows [i*rows//n, (i+1)*rows//n)
        _wire_source_metadata(self, table, columns or table.schema.names)
        self._batch = None
        self.tile = tile
        self._offset = 0
        self.streaming = False

    def init(self):
        from ..utils import settings

        stream_rows = settings.get("sql.distsql.scan_stream_rows")
        self.streaming = (
            hasattr(self.table, "columns")  # KV-backed tables decode whole
            and self.table.num_rows > stream_rows
        )
        if self.streaming:
            self._init_streaming()
        else:
            self._init_resident()
        self._offset = 0
        super().init()

    # -- resident mode ------------------------------------------------------

    def _shard_bounds(self) -> tuple[int, int | None] | None:
        """Rank range [lo, hi) for this shard; the LAST shard is unbounded
        (hi None): num_rows is the newest-visible count at now(), but the
        scan's snapshot can hold MORE live rows (older snapshot before
        deletes, or a txn's own inserts) — trailing ranks must still land
        in some shard or a distributed scan silently drops them."""
        if self.shard is None:
            return None
        i, n = self.shard
        rows = self.table.num_rows
        return (i * rows // n,
                None if i == n - 1 else (i + 1) * rows // n)

    def _init_resident(self):
        self._batch = self.table.device_batch(self.output_schema.names)
        bounds = self._shard_bounds()
        if bounds is not None:
            # shard by LIVE-ROW RANK, not raw position: KV-backed tables'
            # live rows sit at scattered merged-view positions (often past
            # num_rows), so a positional mask would silently drop rows.
            # For host tables live rows are a prefix, so rank == position.
            # Positions stay stable either way (dense-key addressing holds).
            lo, hi = bounds
            rank = jnp.cumsum(self._batch.mask.astype(jnp.int32)) - 1
            keep = self._batch.mask & (rank >= lo)
            if hi is not None:
                keep = keep & (rank < hi)
            self._batch = self._batch.with_mask(keep)
        cap = self._batch.capacity
        tile = self.tile
        if tile is None or tile <= 0 or cap % tile != 0:
            tile = cap  # small tables: one tile
        self._res_tile = min(tile, cap)
        if getattr(self, "_slice_tile", None) != self._res_tile:
            res_tile = self._res_tile
            # the slice kernel takes (batch, offset) as arguments, so one
            # wrapper per tile size serves EVERY resident table
            self._slice = dispatch.jit(
                functools.partial(_slice_tile, res_tile),
                key=("slice_tile", res_tile), name="scan_slice")
            self._slice_tile = res_tile

    # -- streaming mode -----------------------------------------------------

    def _init_streaming(self):
        t = self.table
        names = self.output_schema.names
        # crlint: allow-host-sync(catalog columns are host-resident numpy)
        self._host_cols = {n: np.asarray(t.columns[n]) for n in names}
        self._host_valids = {n: t.valids[n] for n in names if n in t.valids}
        self._nrows = t.num_rows
        bounds = self._shard_bounds()
        if bounds is not None:
            lo, hi = bounds
            self._host_cols = {n: a[lo:hi] for n, a in self._host_cols.items()}
            self._host_valids = {
                n: v[lo:hi] for n, v in self._host_valids.items()
            }
            self._nrows = (hi if hi is not None else self._nrows) - lo
        # big tiles amortize dispatch (bounded so two in-flight double-
        # buffered tiles stay far under HBM); ~64 tiles per table keeps the
        # pipeline busy at any scale
        auto = _next_pow2(max(1 << 12, min(1 << 20, self._nrows // 64)))
        self._stream_tile = max(self.tile or 0, auto)
        self._prefetched = None

    def _upload(self, off: int) -> Batch:
        """Async host->device transfer of one tile (device_put returns
        before the copy completes — that is the overlap)."""
        from ..coldata.batch import from_host

        hi = min(off + self._stream_tile, self._nrows)
        arrays = {n: a[off:hi] for n, a in self._host_cols.items()}
        valids = {n: v[off:hi] for n, v in self._host_valids.items()}
        return from_host(self.output_schema, arrays, valids=valids,
                         capacity=self._stream_tile)

    def stream_parts(self):
        if not self._initialized:
            self.init()
        if self.streaming:
            self._parts_key = ("scan_stream",)
            return self, _identity_fn, ()
        self._parts_key = ("scan_slice", self._res_tile)
        # one chain head per tile size, shared by every resident scan:
        # stable identity keeps consumer compositions cached across runs
        # AND across queries (the closure is immutable, so a re-init with
        # a different tile gets a different fn, never a stale one)
        return self, _slice_parts_for(self._res_tile), ()

    def stream_tiles(self):
        """Yield raw tile tokens for the fused path (reset scan position)."""
        return dispatch.sectioned(self, self._stream_tiles())

    def _stream_tiles(self):
        self._offset = 0
        if self.streaming:
            self._prefetched = None
            while True:
                t = self._next_streaming()
                if t is None:
                    return
                yield t
            return
        cap = self._batch.capacity
        # advance the shared scan position so a consumer that stops mid-way
        # and falls back to next_batch() (e.g. SortOp's spill handoff)
        # resumes after the tiles already delivered instead of re-reading
        while self._offset < cap:
            off = self._offset
            self._offset += self._res_tile
            yield (self._batch, jnp.int32(off))

    def _next_streaming(self):
        if self._offset >= self._nrows:
            return None
        cur = self._prefetched
        if cur is None:
            cur = self._upload(self._offset)
        nxt = self._offset + self._stream_tile
        # issue the next transfer BEFORE handing the current tile to
        # the consumer: its device work overlaps this upload
        self._prefetched = self._upload(nxt) if nxt < self._nrows else None
        self._offset = nxt
        return cur

    def _next(self):
        if self.streaming:
            return self._next_streaming()
        cap = self._batch.capacity
        if self._offset >= cap:
            return None
        if self._res_tile == cap:
            self._offset = cap
            return self._batch
        out = self._slice(self._batch, jnp.int32(self._offset))
        self._offset += self._res_tile
        return out


class IndexScanOp(SourceOperator):
    """Index-backed read (plan/spec.IndexScan): resolve matching primary
    keys from the secondary-index keyspace, then fetch the rows in one
    Streamer pass (joinreader.go + kvstreamer/streamer.go:517 roles). The
    output batch's capacity is sized by the MATCH COUNT — downstream
    kernels compile at lookup-result shape, not table shape."""

    def __init__(self, table, index_name: str, lo: int | None,
                 hi: int | None, columns: tuple[str, ...] | None = None):
        super().__init__()
        self.table = table
        self.ix = next(i for i in table.indexes if i.name == index_name)
        self.lo, self.hi = lo, hi
        self.names = tuple(columns or table.schema.names)
        _wire_source_metadata(self, table, self.names)
        self._batch = None

    def init(self):
        from ..kv import index as ixm

        pks = ixm.scan_pks(self.table, self.ix, self.lo, self.hi)
        self._batch = ixm.Streamer(self.table).fetch(pks, self.names)
        super().init()

    def _next(self):
        b, self._batch = self._batch, None
        return b


class PointLookupOp(SourceOperator):
    """Primary-key point read (plan/spec.PointLookup): each key is read
    through the transaction the statement runs in (``KVTable.point_rows``:
    kv.Txn.Get inside one, the retrying autocommit read outside), the
    values decode on the host (storage/rowcodec.py) and land as one batch
    of a fixed capacity. The keys are host values: literals, or the plan
    cache's ``Param`` slots read at init, so another key is another
    argument to the same operator and compiles nothing."""

    stateless_between_runs = True  # init() reads the keys' rows anew

    def __init__(self, table, keys, columns: tuple[str, ...] | None = None,
                 params=None):
        super().__init__()
        self.table = table
        self.keys = tuple(keys)
        self.names = tuple(columns or table.schema.names)
        self._params = params
        _wire_source_metadata(self, table, self.names)
        self._batch = None

    def _pks(self) -> list[int]:
        args = self._params.args() if self._params is not None else ()
        return [int(args[k.slot]) if isinstance(k, ex.Param)
                else int(k.value) for k in self.keys]

    def init(self):
        from ..plan.spec import PointLookup

        arrays, valids = self.table.point_rows(self._pks(), self.names)
        self._batch = from_host(self.output_schema, arrays, valids,
                                capacity=PointLookup.MAX_KEYS)
        super().init()

    def _next(self):
        b, self._batch = self._batch, None
        return b


class PKRangeOp(SourceOperator):
    """Primary-key range read (plan/spec.PKRange): the rows whose key is in
    [lo, hi] come from the store as DEVICE tiles (``KVTable.range_batches``:
    the span sought on the host, one window a source, MVCC filter and row
    decode in one program), through the transaction the statement runs in.
    A tile's capacity is the window's: a power of two from 128 rows (a
    range of sysbench's 100 ids, one version each, in one run: 128, the
    engine's smallest candidate tile and the shape a point read's window
    already has; 256 only where versions or a second source fill it) up to
    a page of a few thousand; a wider range streams page by page and never
    falls back to a decode of the table. The bounds are host values:
    literals, or the plan cache's ``Param`` slots read at init, so another
    range is two other arguments to the same operator and compiles
    nothing."""

    stateless_between_runs = True  # init() starts the read anew

    def __init__(self, table, lo, hi, columns: tuple[str, ...] | None = None,
                 params=None):
        super().__init__()
        self.table = table
        self.lo, self.hi = lo, hi
        self.names = tuple(columns or table.schema.names)
        self._params = params
        _wire_source_metadata(self, table, self.names)
        self._pages = None

    def _bound(self, b) -> int:
        if isinstance(b, ex.Param):
            return int(self._params.args()[b.slot])
        return int(b.value)

    def init(self):
        self._pages = self.table.range_batches(
            self._bound(self.lo), self._bound(self.hi), self.names)
        super().init()

    def _next(self):
        return next(self._pages, None)


def _identity_fn(b):
    return b


_slice_parts_fns: dict[int, object] = {}


def _slice_parts_for(res_tile: int):
    fn = _slice_parts_fns.get(res_tile)
    if fn is None:
        def fn(token):
            b, off = token
            return _slice_tile(res_tile, b, off)

        fn = _slice_parts_fns.setdefault(res_tile, fn)
    return fn


def _slice_tile(tile: int, b: Batch, off) -> Batch:
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, off, tile, axis=0), b
    )


# ---------------------------------------------------------------------------
# Streaming ops


class HashBucketOp(OneInputOperator):
    """One outgoing stream of a HashRouter (colflow/routers.go:420): mask
    away rows whose key-hash bucket is not `part` of `n_parts`. A producer
    runs one HashBucketOp per consumer over the same scan — together they
    partition the input exactly (same splitmix64 the join/agg hash paths
    use, so co-partitioned sides land on the same peer)."""

    def __init__(self, child: Operator, keys: tuple[int, ...],
                 n_parts: int, part: int):
        super().__init__(child)
        self.output_schema = child.output_schema
        from ..coldata.types import Family
        from ..ops import hashing

        schema = child.output_schema
        for k in keys:
            if schema.types[k].family is Family.STRING:
                raise TypeError(
                    "cross-host repartition on STRING keys is not "
                    "supported (dictionary codes are per-process)"
                )

        def raw(b: Batch) -> Batch:
            h = hashing.hash_columns(
                [b.cols[k] for k in keys],
                [schema.types[k] for k in keys],
            )
            return b.with_mask(
                b.mask & (hashing.bucket(h, n_parts) == part))

        self._key = dispatch.kernel_key(
            "hashbucket", schema, keys, n_parts, part)
        self._raw = raw
        self._fn = dispatch.jit(raw, key=self._key, name="hashbucket_tile")

    def stream_parts(self):
        return _compose_parts(self, self.child, self._raw, key=self._key)

    def _next(self):
        b = self.child.next_batch()
        return None if b is None else self._fn(b)


class RemoteStreamOp(SourceOperator):
    """Leaf that attaches to a peer host's registered flow stream at init
    and pulls its batches — the Inbox half of a host-to-host stream
    (colrpc/inbox.go:48; plan/spec.RemoteStream)."""

    def __init__(self, addr, flow_id: str, stream_id: int, schema):
        super().__init__()
        self.addr = tuple(addr)
        self.flow_id = flow_id
        self.stream_id = stream_id
        self.output_schema = schema
        self._inbox = None

    def init(self):
        from .disthost import attach_stream

        self._inbox = attach_stream(self.addr, self.flow_id,
                                    self.stream_id, self.output_schema)
        super().init()

    def _next(self):
        return self._inbox.next_batch()

    def close(self):
        if self._inbox is not None:
            self._inbox.close()


class FilterOp(OneInputOperator):
    """Predicate mask. With ``params`` (a plancache.ParamStore), the
    predicate's ex.Param leaves read their values from jit ARGUMENTS
    instead of baked constants, so a cached plan rebinds literals with
    zero new traces (the prepared-plan fast path)."""

    _passes_tiles = True
    stateless_between_runs = True

    def __init__(self, child: Operator, predicate: ex.Expr, params=None):
        super().__init__(child)
        self.output_schema = child.output_schema
        schema = child.output_schema
        self.predicate = predicate
        self._params = params
        if params is None:
            def raw(b: Batch) -> Batch:
                return b.with_mask(ex.filter_mask(b, schema, predicate))
        else:
            def raw(b: Batch, *pv) -> Batch:
                with ex.param_scope(pv):
                    return b.with_mask(ex.filter_mask(b, schema, predicate))

        self._key = dispatch.kernel_key(
            "filter", schema, predicate, params is not None)
        self._raw = raw
        self._fn = dispatch.jit(raw, key=self._key, name="filter_tile")

    def stream_parts(self):
        extra = () if self._params is None else self._params.args()
        return _compose_parts(self, self.child, self._raw, key=self._key,
                              extra=extra)

    def _next(self):
        b = self.child.next_batch()
        if b is None:
            return None
        if self._params is None:
            return self._fn(b)
        return self._fn(b, *self._params.args())


_chain_cache: dict = {}


def _compose_parts(op, child, raw_fn, key=None, extra=()):
    """Chain raw_fn onto the child's fused streaming function (args
    pass-through; composition cached per operator instance).

    When both the child's chain and this op carry structural kernel keys,
    the composed chain function is ALSO shared process-globally (keyed on
    the key pair), so two queries with identical fused prefixes reuse one
    traced chain — the cross-query half of the kernel cache. ``extra``
    appends this op's runtime arguments (param values) after the child's;
    the chain splits them back out positionally, so values stay jit
    ARGUMENTS (re-read every run) rather than baked constants."""
    parts = child.stream_parts()
    if parts is None:
        return None
    src, cfn, cargs = parts
    ckey = getattr(child, "_parts_key", None)
    chain_key = (("chain", ckey, key, len(cargs))
                 if ckey is not None and key is not None else None)
    def make():
        chain = (_chain_cache.get(chain_key)
                 if chain_key is not None else None)
        if chain is None:
            nc = len(cargs)

            def chain(t, *a):
                return raw_fn(cfn(t, *a[:nc]), *a[nc:])

            if chain_key is not None:
                chain = _chain_cache.setdefault(chain_key, chain)
        return chain

    chain = _per_chain(op, "_chain_fns", cfn, make)
    op._parts_key = chain_key
    return src, chain, tuple(cargs) + tuple(extra)


class ProjectOp(OneInputOperator):
    _passes_tiles = True
    stateless_between_runs = True

    def __init__(self, child: Operator, exprs: tuple[ex.Expr, ...],
                 names: tuple[str, ...], dict_overrides: tuple = ()):
        super().__init__(child)
        self.exprs = exprs  # JoinOp's dense-build walk maps keys through these
        schema = child.output_schema
        types = tuple(ex.expr_type(e, schema) for e in exprs)
        self.output_schema = Schema(tuple(names), types)
        # dictionaries survive through bare column references; host-side
        # string transforms attach theirs via dict_overrides
        self.dictionaries = {
            i: self.child.dictionaries[e.idx]
            for i, e in enumerate(exprs)
            if isinstance(e, ex.ColRef) and e.idx in self.child.dictionaries
        }
        for i, d in dict_overrides:
            self.dictionaries[i] = d
        # bounds propagate through computed columns (EXTRACT/arithmetic),
        # not just bare references — keeps dense-key planning alive
        self.col_stats = {}
        for i, e in enumerate(exprs):
            b = ex.expr_bounds(e, schema, self.child.col_stats)
            if b is not None:
                self.col_stats[i] = b

        def raw(b: Batch) -> Batch:
            cols = []
            for e in exprs:
                d, v = ex.eval_expr(e, b.cols, schema)
                cols.append(Column(data=d, valid=v))
            return Batch(cols=tuple(cols), mask=b.mask)

        self._key = dispatch.kernel_key("project", schema, exprs)
        self._raw = raw
        self._fn = dispatch.jit(raw, key=self._key, name="project_tile")

    def stream_parts(self):
        return _compose_parts(self, self.child, self._raw, key=self._key)

    @property
    def emits_live_prefix(self) -> bool:
        return self.child.emits_live_prefix  # `raw` hands the mask on

    def _next(self):
        b = self.child.next_batch()
        return None if b is None else self._fn(b)


class LimitOp(OneInputOperator):
    stateless_between_runs = True  # init() starts the count again

    def __init__(self, child: Operator, limit: int, offset: int = 0):
        super().__init__(child)
        self.output_schema = child.output_schema
        self.limit = limit
        self.offset = offset
        self._seen = 0

        def fn(b: Batch, seen):
            pos = seen + jnp.cumsum(b.mask.astype(jnp.int32)) - 1
            keep = b.mask & (pos >= offset) & (pos < offset + limit)
            return b.with_mask(keep), seen + jnp.sum(b.mask, dtype=jnp.int32)

        self._fn = dispatch.jit(
            fn, key=dispatch.kernel_key("limit", offset, limit),
            name="limit_tile")

    def init(self):
        super().init()
        self._seen = jnp.int32(0)
        self._done = False

    def _next(self):
        if self._done:
            return None
        b = self.child.next_batch()
        if b is None:
            return None
        out, self._seen = self._fn(b, self._seen)
        if int(self._seen) >= self.offset + self.limit:
            self._done = True
        return out


# ---------------------------------------------------------------------------
# Aggregation


def _stored_keys_below(op: Operator, cols) -> bool:
    """Whether every row `op` hands on, dead or live, still holds in
    ``cols`` what its table stored there: the chain down to a ScanOp is
    Filters (which clear mask bits) and Projects that pass ``cols`` through
    as bare column references."""
    cols = list(cols)
    while True:
        if isinstance(op, FilterOp):
            op = op.child
        elif isinstance(op, ProjectOp):
            if not all(isinstance(op.exprs[c], ex.ColRef) for c in cols):
                return False
            cols = [op.exprs[c].idx for c in cols]
            op = op.child
        else:
            return isinstance(op, ScanOp)


class AggregateOp(OneInputOperator):
    """GROUP BY aggregation (hashAggregator analog). mode:
    - complete: input rows -> final results
    - partial:  input rows -> state columns (feeds an Exchange)
    - final:    state columns (partial layout) -> final results

    A complete aggregate over input clustered on its group keys
    (``ordered``) STREAMS, as colexec's orderedAggregator does: a tile's
    groups are final but the one its edge may cut, so each input tile
    leaves at once as one output tile and one open group is carried to the
    next (_stream); nothing is spooled, merged or spilled. Every other
    aggregate spools its tiles' partial states and merges them (_spool).
    """

    KERNEL = "hashagg"

    def __init__(
        self,
        child: Operator,
        group_cols: tuple[int, ...],
        aggs: tuple[agg_ops.AggSpec, ...],
        mode: str = "complete",
        input_schema: Schema | None = None,
        ordered: bool = False,
        prefix_live: bool = False,
    ):
        super().__init__(child)
        self.mode = mode
        self.group_cols = group_cols
        self.aggs = aggs
        # ordered: equal group keys arrive adjacent (clustered scan —
        # Table.ordering); the per-tile grouping skips its key sort
        # (orderedAggregator role). prefix_live additionally asserts tiles
        # are live-prefix (no filters in the fused chain below), dropping
        # the dead-row compaction sort too.
        self.ordered = ordered
        self.prefix_live = prefix_live
        if ordered and not prefix_live and not _stored_keys_below(
                child, group_cols):
            # ops/aggregation.py `_ordered_groupby` groups a tile where its
            # rows lie: a dead row inside a group must still carry the
            # group's key, which only a chain that never rewrites the
            # stored key can promise (plan/builder.py `_clustered_input`)
            raise ValueError(
                "an ordered aggregate over dead rows needs its group keys "
                "as the table stored them: Scan -> Filter -> "
                "Project(ColRef) chains only")
        # string_agg runs OUTSIDE the device state pipeline: per-row
        # (group key, string code) pairs are collected host-side during
        # the spool and concatenated at finalize (the reference's concat
        # agg accumulates variable-width bytes, which has no fixed-tile
        # device representation). The device pipeline runs a count
        # placeholder in its slot; _attach_saggs overwrites the column.
        self._sagg = [(j, s) for j, s in enumerate(aggs)
                      if s.func == "string_agg"]
        if self._sagg:
            if mode != "complete":
                raise ValueError(
                    "string_agg runs in complete mode only (distributed "
                    "plans fall back to local execution, parallel/"
                    "planner.py _needs_local)"
                )
            aggs = tuple(
                agg_ops.AggSpec("count", s.col, s.name)
                if s.func == "string_agg" else s
                for s in aggs
            )
        # the schema over which aggs/group_cols were written
        base = input_schema if input_schema is not None else child.output_schema
        self.base_schema = base
        self.partial_specs, self.state_schema, self.final_map = partial_layout(
            base, group_cols, aggs
        )
        k = len(group_cols)
        self.num_keys = k
        # merge aggregation over the state layout
        self.merge_group_cols = tuple(range(k))
        self.merge_specs = agg_ops.merge_specs_for(self.partial_specs, k)
        final_schema = self._final_schema(base)
        self.output_schema = (
            self.state_schema if mode == "partial" else final_schema
        )
        keep = {
            gi: self.child.dictionaries[gi]
            for gi in group_cols
            if gi in self.child.dictionaries
        }
        if mode == "final":
            # child emits state layout; group keys are 0..k-1 already
            keep = {
                i: self.child.dictionaries[i]
                for i in range(k)
                if i in self.child.dictionaries
            }
            self.dictionaries = keep
            self.key_stats = {
                i: self.child.col_stats[i]
                for i in range(k)
                if i in self.child.col_stats
            }
        else:
            self.dictionaries = {
                group_cols.index(gi): d for gi, d in keep.items()
            }
            self.key_stats = {
                group_cols.index(gi): s
                for gi, s in self.child.col_stats.items()
                if gi in group_cols
            }
        # group keys (and their stats) survive to the output positions
        self.col_stats = dict(self.key_stats)
        # STRING group keys without numeric stats still pack tight: the
        # dictionary size bounds the code range
        for pos, d in self.dictionaries.items():
            self.col_stats.setdefault(pos, (0, max(0, len(d) - 1)))
            self.key_stats.setdefault(pos, (0, max(0, len(d) - 1)))
        # string_agg outputs get an empty Dictionary NOW (parents copy the
        # reference at construction) and fill it in place at finalize.
        # _runtime marks it: consumers whose PLAN depends on dictionary
        # contents (sort ranks, dense-agg sizing) must refuse it — at init
        # time it is still empty and would silently produce garbage
        for j, _ in self._sagg:
            d = Dictionary(np.array([], dtype=object))
            d._runtime = True
            self.dictionaries[len(group_cols) + j] = d
        # conversely, grouping BY a runtime-filled string column cannot
        # work: the group codes would be computed against an empty dict
        for gi in group_cols:
            if getattr(self.child.dictionaries.get(gi), "_runtime", False):
                raise ValueError(
                    "grouping by a string_agg result is not supported"
                )
        # decided by the plan (the builder's ordered, the mode, the
        # aggregates), never by a setting; EXPLAIN prints it
        self.streaming = ordered and mode == "complete" and not self._sagg
        self._acc = None
        self._emitted = False
        self._spool_alloc = None

    @property
    def emits_live_prefix(self) -> bool:
        """A streaming aggregate's tiles: `stitch_ordered_partial` sets the
        mask to the closed groups' prefix whatever lay below, `_finalize`
        hands it on, and the tail is one padded row."""
        return self.streaming

    def _close_spool(self) -> None:
        if self._spool_alloc is not None:
            self._spool_alloc.close()
            self._spool_alloc = None

    def _final_schema(self, base: Schema) -> Schema:
        return agg_ops.agg_output_schema(
            base, self.group_cols, self.aggs,
            "final" if self.mode == "final" else "complete",
        )

    def init(self):
        super().init()
        self._tiles: list[Batch] = []
        self._emitted = False
        self._external = None
        self._streamed = None
        self._close_spool()  # cached-plan re-run: prior account is dead
        self._sagg_rows = {j: {} for j, _ in self._sagg}
        if hasattr(self, "_partial_fn"):
            return
        schema = self.base_schema
        gcols = self.group_cols
        pspecs = self.partial_specs
        sschema = self.state_schema
        mcols = self.merge_group_cols
        mspecs = self.merge_specs
        in_stats = {
            gi: s for gi, s in self.child.col_stats.items() if gi in gcols
        } if self.mode != "final" else {}
        for gi in gcols:
            if gi in self.child.dictionaries:
                in_stats.setdefault(
                    gi, (0, max(0, len(self.child.dictionaries[gi]) - 1))
                )
        merge_stats = {
            i: s for i, s in self.key_stats.items() if i < len(mcols)
        }

        ordered = self.ordered
        prefix_live = self.prefix_live

        def grouped(b):
            # out_capacity == input capacity: groups <= live rows, so this
            # CANNOT overflow — no device->host sync on the hot tile loop
            return agg_ops.sort_groupby(
                b, schema, gcols, pspecs, out_capacity=b.capacity,
                col_stats=in_stats,
                presorted=ordered, compact=not prefix_live,
            )

        def partial_fn(b):
            return grouped(b)[0]

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="hashagg_merge")
        def merge_fn(tiles, cap):
            both = concat(list(tiles), capacity=cap)
            return agg_ops.sort_groupby(both, sschema, mcols, mspecs,
                                        out_capacity=cap,
                                        col_stats=merge_stats)

        def stream_fn(b, carry):
            # the presorted partial, the carried group met with it, closed
            # groups finalized: one kernel a tile
            closed, carry = agg_ops.stitch_ordered_partial(
                *grouped(b), carry, sschema, mcols, mspecs, merge_stats)
            return self._finalize(closed), carry

        def stream_tail_fn(carry):
            # the group still open after the last tile, at the ladder's
            # lowest rung
            return self._finalize(jax.tree_util.tree_map(
                lambda x: pad_rows(x, _LOWEST_RUNG), carry))

        self._partial_raw = partial_fn
        self._partial_fn = dispatch.jit(partial_fn, name="hashagg_partial")
        self._merge_fn = merge_fn
        self._finalize_fn = dispatch.jit(self._finalize,
                                         name="hashagg_finalize")
        if self.streaming:
            self._stream_raw = stream_fn
            self._stream_fn = dispatch.jit(stream_fn, name="hashagg_stream")
            self._stream_tail_fn = dispatch.jit(stream_tail_fn,
                                                name="hashagg_stream_tail")
            self._no_carry = empty_batch(sschema, 1)

    def _finalize(self, state: Batch) -> Batch:
        return agg_ops.finalize_states(state, self.final_map, self.num_keys)

    def _stream(self):
        """One output tile an input tile (fused with the streaming chain
        beneath, as _consume fuses a partial), the open group threaded
        through as a device argument: no spool, no merge, no host sync.
        Then the group still open, as one last tile; nothing on no input.
        Output tiles claim no order among themselves."""
        parts = (None if (self._collect or not _fusion_enabled())
                 else self.child.stream_parts())
        if parts is None:
            step, args = self._stream_fn, ()
            tiles = iter(self.child.next_batch, None)
        else:
            src, cfn, args = parts
            raw = self._stream_raw
            step = _per_chain(
                self, "_fused_stream", cfn,
                lambda: dispatch.jit(
                    lambda t, carry, *a: raw(cfn(t, *a), carry),
                    name="hashagg_stream_fused"))
            tiles = src.stream_tiles()
        # the pull span's tags (host-known, no sync): `agg_ordered_tiles`
        # as _spool counts it; `agg_streamed_tiles`, input tiles that left
        # as an output tile at once
        sp = tracing.current()
        carry = self._no_carry
        for t in tiles:
            out, carry = step(t, carry, *args)
            if sp is not None:
                sp.inc_tag("agg_ordered_tiles", 1)
                sp.inc_tag("agg_streamed_tiles", 1)
            yield out
        if carry is not self._no_carry:
            yield self._stream_tail_fn(carry)

    def _spool(self):
        """Spool per-tile partial states (fused with the streaming chain
        beneath); merge down only when the spool exceeds workmem (rows or
        the monitor-tree byte account — the colmem.Allocator discipline)."""
        from ..utils import settings
        from .memory import Allocator, batch_bytes, note_spill

        budget = settings.get("sql.distsql.workmem_rows")
        alloc = Allocator("aggregation spool", stats=self.stats)
        self._spool_alloc = alloc
        if self.mode == "final":
            tile_raw, tile_jit = _identity_fn, _identity_fn
        else:
            tile_raw, tile_jit = self._partial_raw, self._partial_fn
        spooled = 0
        if self._sagg:
            # plain pull (no fused chain): every input tile materializes
            # its (group key, string code) pairs host-side before the
            # device partial — the host collect cannot live inside a jit
            def gen():
                while True:
                    b = self.child.next_batch()
                    if b is None:
                        return
                    self._collect_sagg(b)
                    yield tile_jit(b)

            source = gen()
        else:
            source = _consume(self, "partial", tile_raw, tile_jit)
        source_it = iter(source)
        # the pull span's tags (host-known, no sync; tracing.totals() sums
        # them over a window): `agg_ordered_tiles`, input tiles grouped
        # without a key sort; `agg_merge_rows` (_merge_down), the static
        # cap of every hashagg_merge launch; `agg_spills`, a spool handed
        # to the Grace aggregator
        sp = tracing.current()
        presorted = self.ordered and self.mode != "final"
        for part in source_it:
            if sp is not None and presorted:
                sp.inc_tag("agg_ordered_tiles", 1)
            self._tiles.append(part)
            spooled += part.capacity
            nb = batch_bytes(part)
            over = alloc.would_exceed(nb)
            # the tile is resident whether or not the budget likes it, so
            # account it truthfully (forcing past the refusal): a spilling
            # operator's max-mem must show the footprint that tripped the
            # budget, and string_agg (which cannot spill — host-side
            # state) keeps over-budget accounting rather than none
            alloc.reserve(nb, force=over)
            if spooled > budget or over:
                self._tiles = [self._merge_down()]
                spooled = self._tiles[0].capacity
                alloc.release()
                mb = batch_bytes(self._tiles[0])
                over = alloc.would_exceed(mb)
                alloc.reserve(mb, force=over)
                if (spooled > budget or over) and not self._sagg:
                    # merge-down didn't shrink below budget: the GROUP
                    # COUNT itself exceeds memory. Hand the spooled state
                    # tiles + the rest of the partial stream to the Grace
                    # external aggregator (disk_spiller.go's swap;
                    # external_hash_aggregator.go role), attributed to the
                    # owning query's monitor
                    from .external import ChainOp, GraceAggregateOp

                    note_spill("agg")
                    if sp is not None:
                        sp.inc_tag("agg_spills", 1)
                    self.stats.spilled = True
                    alloc.close()
                    self._spool_alloc = None

                    class _Rest:
                        def next_batch(_self):
                            return next(source_it, None)

                        def close(_self):
                            pass

                    chain = ChainOp(self._tiles, self.state_schema,
                                    self.dictionaries, _Rest())
                    chain.init()
                    self._external = GraceAggregateOp(chain, self)
                    self._external.init()
                    self._tiles = []
                    return

    # -- string_agg host path ------------------------------------------------

    # crlint: allow-host-sync(string_agg host path: object-dtype strings cannot live on device)
    def _collect_sagg(self, b: Batch) -> None:
        """Append (group key tuple -> string values) for every live row of
        one input tile, in row order."""
        mask = np.asarray(b.mask)
        idx = np.nonzero(mask)[0]
        if not len(idx):
            return
        keys = self._host_group_keys(b, idx)
        for j, spec in self._sagg:
            col = b.cols[spec.col]
            data = np.asarray(col.data)[idx]
            valid = np.asarray(col.valid)[idx]
            d = self.child.dictionaries.get(spec.col)
            store = self._sagg_rows[j]
            for key, code, ok in zip(keys, data, valid):
                if not ok:
                    continue
                v = (str(d.values[int(code)]) if d is not None
                     else str(code))
                store.setdefault(key, []).append(v)

    # crlint: allow-host-sync(string_agg host path: hashable python keys)
    def _host_group_keys(self, b: Batch, idx: np.ndarray) -> list[tuple]:
        """Hashable per-row group keys (None for NULL key columns) over the
        rows at `idx` — for SOURCE-schema batches (complete mode)."""
        parts = []
        for gi in self.group_cols:
            c = b.cols[gi]
            data = np.asarray(c.data)[idx]
            valid = np.asarray(c.valid)[idx]
            parts.append([
                (None if not ok else data[i].item())
                for i, ok in enumerate(valid)
            ])
        return list(zip(*parts)) if parts else [()] * len(idx)

    # crlint: allow-host-sync(string_agg host path: runs once at finalize)
    def _attach_saggs(self, final: Batch) -> Batch:
        """Overwrite each string_agg placeholder column with codes into a
        runtime-built Dictionary of per-group concatenations."""
        k = self.num_keys
        mask = np.asarray(final.mask)
        idx = np.nonzero(mask)[0]
        # final batch group keys are at positions 0..k-1 (output schema)
        gcols_saved = self.group_cols
        try:
            self.group_cols = tuple(range(k))
            keys = self._host_group_keys(final, idx)
        finally:
            self.group_cols = gcols_saved
        cols = list(final.cols)
        for j, spec in self._sagg:
            store = self._sagg_rows[j]
            joined = [
                spec.sep.join(store[key]) if store.get(key) else None
                for key in keys
            ]
            uniq = sorted({v for v in joined if v is not None})
            self.dictionaries[k + j].reset(np.array(uniq, dtype=object))
            code_of = {v: c for c, v in enumerate(uniq)}
            codes = np.zeros(final.capacity, np.int32)
            valid = np.zeros(final.capacity, bool)
            for row, v in zip(idx, joined):
                if v is not None:
                    codes[row] = code_of[v]
                    valid[row] = True
            cols[k + j] = Column(
                data=jnp.asarray(codes),
                valid=jnp.asarray(valid) & final.mask,
            )
        return Batch(cols=tuple(cols), mask=final.mask)

    def _merge_down(self) -> Batch:
        sp = tracing.current()
        cap = _spool_cap(self._tiles)
        while True:  # one bounded retry loop per merge-down, not per tile
            if sp is not None:
                sp.inc_tag("agg_merge_rows", cap)
            merged, ng = self._merge_fn(tuple(self._tiles), cap=cap)
            if int(ng) <= cap:
                return merged
            cap = _canonical_cap(int(ng))

    def _next(self):
        if self.streaming:
            if self._streamed is None:
                self._streamed = self._stream()
            return next(self._streamed, None)
        if self._external is not None:
            return self._external.next_batch()  # spilled: stream partitions
        if self._emitted:
            return None
        self._spool()
        if self._external is not None:
            return self._external.next_batch()
        self._emitted = True
        if not self._tiles:
            self._close_spool()
            return None
        # a single tile is already fully grouped UNLESS it came from a
        # "final"-mode child (exchanged state rows may repeat group keys)
        if len(self._tiles) == 1 and self.mode != "final":
            acc = self._tiles[0]
        else:
            acc = self._merge_down()
        self._tiles = []
        self._close_spool()  # spool tiles are dead; the account drains
        if self.mode == "partial":
            return acc
        out = self._finalize_fn(acc)
        if self._sagg:
            out = self._attach_saggs(out)
        return out

    def close(self):
        super().close()
        self._streamed = None  # a consumer may stop early (LIMIT)
        self._close_spool()


class ScalarAggregateOp(OneInputOperator):
    """Aggregation without GROUP BY — exactly one output row, even on empty
    input (SQL scalar aggregate semantics)."""

    KERNEL = "scalaragg"

    def __init__(self, child: Operator, aggs: tuple[agg_ops.AggSpec, ...]):
        super().__init__(child)
        self.aggs = aggs
        base = child.output_schema
        self.base_schema = base
        names, types = [], []
        for spec in aggs:
            names.append(spec.name or spec.func)
            types.append(
                FLOAT64 if spec.func == "avg"
                else agg_ops.agg_output_type(spec, base)
            )
        self.output_schema = Schema(tuple(names), tuple(types))
        self.dictionaries = {}
        self.col_stats = {}
        self._tile_raw = lambda b: agg_ops.scalar_tile_states(b, aggs, base)
        self._tile_fn = dispatch.jit(self._tile_raw, name="scalaragg_tile")
        self._merge_raw = (
            lambda acc, new: agg_ops.scalar_merge_states(aggs, acc, new)
        )
        self._merge_fn = dispatch.jit(self._merge_raw,
                                      name="scalaragg_merge")
        self._emitted = False

    def init(self):
        super().init()
        self._emitted = False

    def _next(self):
        if self._emitted:
            return None
        acc = _fold(self, "scalar", self._tile_raw, self._tile_fn,
                    self._merge_raw, self._merge_fn)
        self._emitted = True
        return agg_ops.scalar_result_batch(
            self.aggs, self.base_schema, self.output_schema, acc
        )


# ---------------------------------------------------------------------------
# Sort / Distinct


class SortOp(OneInputOperator):
    """Buffering sorter (NewSorter analog): spool all tiles, one device sort
    at the pow2 capacity fitting the spool's LIVE rows."""

    def __init__(self, child: Operator, keys: tuple[sort_ops.SortKey, ...]):
        super().__init__(child)
        self.output_schema = child.output_schema
        self.keys = keys
        self._emitted = False
        self._spool_alloc = None

    def close(self):
        super().close()
        if self._spool_alloc is not None:
            self._spool_alloc.close()
            self._spool_alloc = None

    def init(self):
        super().init()
        self._emitted = False
        self._external = None
        if self._spool_alloc is not None:  # cached-plan re-run
            self._spool_alloc.close()
            self._spool_alloc = None
        if hasattr(self, "_fn"):
            return
        rank_tables = {
            k.col: self.child.dictionaries[k.col].ranks
            for k in self.keys
            if k.col in self.child.dictionaries
        }
        for k in self.keys:
            if getattr(self.child.dictionaries.get(k.col), "_runtime",
                       False):
                # the dict fills at the child's finalize — its ranks here
                # are empty and would sort garbage
                raise ValueError(
                    "ORDER BY a string_agg result is not supported"
                )
        schema = self.output_schema
        keys = self.keys
        col_stats = dict(self.child.col_stats)

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="sort_spool")
        def fn(batches, cap):
            big = concat(list(batches), capacity=cap)
            return sort_ops.sort_batch(big, schema, keys, rank_tables,
                                       col_stats)

        self._fn = fn

    def _next(self):
        from ..utils import settings
        from .memory import Allocator, batch_bytes, note_spill

        if self._emitted:
            return None
        if getattr(self, "_external", None) is not None:
            return self._external.next_batch()
        tiles = []
        total = 0
        budget = settings.get("sql.distsql.workmem_rows")
        alloc = self._spool_alloc = Allocator("sort spool", stats=self.stats)
        for b in _consume(self, "spool", _identity_fn):
            nb = batch_bytes(b)
            tiles.append(b)
            total += b.capacity
            over = alloc.would_exceed(nb)
            # account the tile even past the budget (it is resident, and
            # the spilling operator's max-mem must reflect it)
            alloc.reserve(nb, force=over)
            if total > budget or over:
                # spill: hand the spooled tiles + the rest of the input to
                # the external range-partitioned sort (disk_spiller swap) —
                # triggered by the ROW budget or the byte ACCOUNT,
                # attributed to the owning query's monitor
                from .external import ChainOp, ExternalSortOp

                note_spill("sort")
                self.stats.spilled = True
                alloc.close()
                self._spool_alloc = None
                chain = ChainOp(tiles, self.output_schema,
                                self.child.dictionaries, self.child)
                self._external = ExternalSortOp(
                    chain, self.keys, budget_rows=budget
                )
                self._external.init()
                return self._external.next_batch()
        self._emitted = True
        alloc.close()  # the one-shot device sort consumes the spool
        self._spool_alloc = None
        if not tiles:
            return None
        return self._fn(tuple(tiles), cap=_spool_cap(tiles))


class TopKOp(OneInputOperator):
    """Device top-k (sorttopk.go analog): fold a per-tile stable
    k-selection over the input — each step keeps the first k rows of the
    stable sort order at a static accumulator capacity — so ORDER BY ...
    LIMIT k neither spools the input nor sorts more than O(k) rows per
    tile. The accumulator merge rides inside the fused step kernel
    (_fold), so a fused chain still pays ONE dispatch per tile. Output is
    the single sorted top-k tile, bit-identical to SortOp + LimitOp (the
    oracle plan/topkopt.py rewrites away)."""

    def __init__(self, child: Operator, keys: tuple[sort_ops.SortKey, ...],
                 k: int):
        super().__init__(child)
        self.output_schema = child.output_schema
        self.keys = keys
        self.k = int(k)
        self._emitted = False

    def init(self):
        super().init()
        self._emitted = False
        if hasattr(self, "_tile_raw"):
            return
        rank_tables = {
            k.col: self.child.dictionaries[k.col].ranks
            for k in self.keys
            if k.col in self.child.dictionaries
        }
        for k in self.keys:
            if getattr(self.child.dictionaries.get(k.col), "_runtime",
                       False):
                raise ValueError(
                    "ORDER BY a string_agg result is not supported"
                )
        schema = self.output_schema
        keys = self.keys
        col_stats = dict(self.child.col_stats)
        kk = self.k
        cap = self._acc_cap = _canonical_cap(kk)

        def tile_raw(b):
            return sort_ops.topk_batch(b, schema, keys, kk, cap,
                                       rank_tables, col_stats)

        def merge_raw(acc, new):
            # concat compacts acc's live rows BEFORE new's, so the stable
            # re-selection keeps earlier-tile rows first among equal keys
            # — global stable order survives the fold
            big = concat([acc, new], capacity=2 * cap)
            return sort_ops.topk_batch(big, schema, keys, kk, cap,
                                       rank_tables, col_stats)

        self._tile_raw = tile_raw
        self._tile_fn = dispatch.jit(tile_raw, name="topk_tile")
        self._merge_raw = merge_raw
        self._merge_fn = dispatch.jit(merge_raw, name="topk_merge")

    def _next(self):
        from .memory import Allocator, batch_bytes

        if self._emitted:
            return None
        acc = _fold(self, "topk", self._tile_raw, self._tile_fn,
                    self._merge_raw, self._merge_fn)
        self._emitted = True
        if acc is None:
            return None
        # the accumulator is the operator's whole resident state — O(k),
        # but account it so EXPLAIN ANALYZE max-mem tells the truth
        alloc = Allocator("topk accumulator", stats=self.stats)
        alloc.reserve(batch_bytes(acc), force=True)
        alloc.close()
        return acc


class DistinctOp(OneInputOperator):
    """DISTINCT via grouped aggregation with no aggregates."""

    def __init__(self, child: Operator, cols: tuple[int, ...] | None = None):
        super().__init__(child)
        self.cols = cols or tuple(range(len(child.output_schema)))
        self.output_schema = child.output_schema.select(self.cols)
        self.dictionaries = {
            self.cols.index(i): d
            for i, d in child.dictionaries.items()
            if i in self.cols
        }
        self.col_stats = {
            self.cols.index(i): s
            for i, s in child.col_stats.items()
            if i in self.cols
        }
        self._inner = AggregateOp(child, self.cols, (), mode="complete")

    def init(self):
        self._inner.init()
        self._initialized = True

    def _next(self):
        return self._inner._next()


# ---------------------------------------------------------------------------
# Join


class HashJoinOp(OneInputOperator):
    """hashJoiner analog: spool+index the build side once, stream probe tiles.

    Unique-build and semi/anti probes have static output shapes and fuse into
    the consumer's streaming segment (the build batch + sorted hash index ride
    along as device arguments). General duplicate-key joins keep the
    capacity-bucketing retry loop and act as a fusion barrier."""

    def __init__(
        self,
        probe: Operator,
        build: Operator,
        probe_keys: tuple[int, ...],
        build_keys: tuple[int, ...],
        spec: join_ops.JoinSpec,
    ):
        super().__init__(probe)
        self.build = build
        self.spec = spec
        # existence probes (semi/anti) and unique-build probes have static
        # probe-aligned output shapes: fusable, and eligible for the dense
        # direct-addressing strategies picked in _ensure_built
        self._fusable = (
            spec.build_unique or spec.join_type in ("semi", "anti")
        )
        if self._fusable and len(build_keys) > 1:
            # the analytic route addresses the build by its FIRST key: the
            # order the SQL text wrote the equalities in decides nothing
            # (q9: ps_suppkey = l_suppkey and ps_partkey = l_partkey, the
            # clustered ps_partkey second)
            lead = next((j for j, k in enumerate(build_keys)
                         if self._dense_key(k) is not None), 0)
            order = (lead, *(j for j in range(len(build_keys)) if j != lead))
            probe_keys = tuple(probe_keys[j] for j in order)
            build_keys = tuple(build_keys[j] for j in order)
        self.probe_keys = probe_keys
        self.build_keys = build_keys
        self.output_schema = join_ops.join_output_schema(
            probe.output_schema, build.output_schema, spec
        )
        self.dictionaries = dict(probe.dictionaries)
        self.col_stats = dict(probe.col_stats)
        if spec.join_type not in ("semi", "anti"):
            off = len(probe.output_schema)
            for i, d in build.dictionaries.items():
                self.dictionaries[off + i] = d
            for i, s in build.col_stats.items():
                self.col_stats[off + i] = s
        # host-side string-key bridges
        self.probe_hash_tables = {}
        self.build_hash_tables = {}
        self.build_code_remaps = {}
        for pos, (pk, bk) in enumerate(zip(probe_keys, build_keys)):
            pt = probe.output_schema.types[pk]
            if pt.family is Family.STRING:
                pd = probe.dictionaries[pk]
                bd = build.dictionaries[bk]
                if (getattr(pd, "_runtime", False)
                        or getattr(bd, "_runtime", False)):
                    # its hashes/values fill at the child's finalize —
                    # captured here they are empty and every probe misses
                    raise ValueError(
                        "joining on a string_agg result is not supported "
                        "(its dictionary fills at runtime)"
                    )
                self.probe_hash_tables[pk] = pd.hashes
                self.build_hash_tables[bk] = bd.hashes
                self.build_code_remaps[pos] = np.array(
                    [pd.code_of(str(v)) for v in bd.values], dtype=np.int32
                )
        # exact packed keys when every key column is bounded (catalog stats /
        # dictionary sizes): probes become control-flow-free — no hash, no
        # collision loop, no per-column verification gathers
        self.exact_layout = join_ops.plan_exact_key(
            probe.output_schema, probe_keys,
            build.output_schema, build_keys,
            probe.col_stats, build.col_stats,
            {pk: len(probe.dictionaries[pk]) for pk in probe_keys
             if pk in probe.dictionaries},
            have_remaps=True,
        )
        self._built = False
        self._analytic = None
        # Adaptive compact emission. A selective probe (e.g. TPC-H Q18's
        # lineitem against 14 surviving orders) emits probe-aligned tiles
        # that are almost entirely dead; every downstream kernel then pays
        # O(tile x ncols) for a handful of rows. Sticky modes:
        #   learn       first run: probe output materializes with a live
        #               count per tile (device futures, fetched ONCE at
        #               query end in post_run_update); also where a join
        #               that has compacted goes while its literals are not
        #               selective, to compact again when one is
        #   compact     output compacts in-kernel to _emit_cap; counts keep
        #               recording so an overflow (count > cap: results
        #               truncated) is detected at query end and the runtime
        #               re-runs with a corrected cap. Handed tiles a compact
        #               join below already cut to no more than _emit_cap,
        #               there is nothing to compact: for that run the probe
        #               composes into the consumer as a transparent one
        #               does (_composes), and the mode and cap stay
        #   transparent dense probes: fully fused into the consumer (no
        #               materialization, no counts)
        from ..utils import settings as _settings

        # general duplicate-key inner/left probes fuse too, as speculative
        # streaming emitters: the probe runs at a learned static out-capacity
        # inside the (chain o probe) kernel, per-tile totals record as device
        # futures, and post_run_update validates them once per query — an
        # overflow (truncated rows) grows the capacity and re-runs. Replaces
        # the per-tile int(total) host-sync retry loop as the streaming path.
        self._gen_fusable = (
            not self._fusable
            and spec.join_type in ("inner", "left")
            and _settings.get("sql.distsql.fusion.general_probe")
        )
        self._emit_mode = (
            "learn" if self._fusable
            else ("general" if self._gen_fusable else "transparent")
        )
        self._emit_cap = None
        self._emit_cap_seen = 0  # the largest compact cap learned so far
        self._emit_kerns: dict = {}  # (chain fn, probe fn, cap) -> jit
        self._emit_counts: list = []
        self._emit_tilecap = 0

    def _plan_analytic(self):
        """Dense analytic build detection: the build side is a position-
        preserving chain (Scan + Filter/Project only — masks, never row
        movement) over a table whose first build-key column is an affine
        function of the row index (catalog Table.dense_key_info). Probing
        such a build is pure arithmetic + one liveness gather — no hash
        table, no sorted index, no build-spool sync (ops/join.py rationale).
        """
        if not self._fusable:
            return None
        got = self._dense_key(self.build_keys[0])
        if got is None:
            return None
        table, (lo, fanout) = got
        if (self.spec.build_unique and fanout > 1
                and len(self.build_keys) < 2):
            return None  # fanout rows share the first key: not unique by it
        # the analytic build materializes the WHOLE table (plus projection-
        # derived columns) on device with no spill path — honor the workmem
        # byte budget the Grace-join spool enforces, falling back to the
        # metered hash path when the table is too big to pin
        from ..utils import settings

        row_bytes = sum(
            ((t.width or 8) if t.family is Family.BYTES
             else t.dtype.itemsize) + 1
            for t in self.build.output_schema.types
        ) + 1  # +1s: valid bitmaps and the row mask (bool each)
        if table.num_rows * row_bytes > settings.get(
            "sql.distsql.workmem_bytes"
        ):
            return None
        return join_ops.DenseAnalytic(
            key_lo=lo, fanout=fanout, build_rows=table.num_rows
        )

    def _dense_key(self, key: int):
        """(table, (lo, fanout)) when build column ``key`` is, through a
        position-preserving chain (Scan + Filter/Project only), a column of
        the scanned table that is an affine function of the row index
        (catalog Table.dense_key_info); else None."""
        op = self.build
        while not isinstance(op, ScanOp):
            if isinstance(op, ProjectOp):
                e = op.exprs[key]
                if not isinstance(e, ex.ColRef):
                    return None
                key = e.idx
                op = op.child
            elif isinstance(op, FilterOp):
                op = op.child
            else:
                return None
        table = op.table
        dense_fn = getattr(table, "dense_key_info", None)
        if not callable(dense_fn):
            return None
        got = dense_fn().get(table.schema.names[op.col_idxs[key]])
        return None if got is None else (table, got)

    def init(self):
        self.build.init()
        super().init()
        self._built = False
        self._grace = None
        if getattr(self, "_build_alloc", None) is not None:
            # cached-plan re-run: the prior build batch is garbage now
            self._build_alloc.close()
            self._build_alloc = None
        self._analytic = self._plan_analytic()
        if hasattr(self, "_build_fn"):
            return
        bschema = self.build.output_schema
        bkeys = self.build_keys
        bht = self.build_hash_tables or None
        layout = self.exact_layout
        eremaps = self.build_code_remaps or None
        # two programs, chosen once from the plan: a producer that proves
        # its tiles live-prefix has them placed at a running offset, any
        # other compacted through a `live_index` a tile
        self._places_build = self.build.emits_live_prefix
        into_one = concat_prefix if self._places_build else concat

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="hashjoin_build")
        def build_fn(tiles, cap):
            big = into_one(list(tiles), capacity=cap)
            index = join_ops.build_index(big, bschema, bkeys, bht,
                                         exact_layout=layout,
                                         exact_remaps=eremaps)
            return big, index

        self._build_fn = build_fn

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="hashjoin_lut")
        def lut_fn(tiles, cap):
            big = into_one(list(tiles), capacity=cap)
            return big, join_ops.build_dense_lut(big, bkeys, layout, eremaps)

        self._lut_fn = lut_fn
        self._probe_raw = self._probe_pair = None
        if not self._fusable:
            pschema = self.child.output_schema
            pkeys = self.probe_keys
            pht = self.probe_hash_tables or None
            remaps = self.build_code_remaps or None
            spec = self.spec

            def probe_gen_raw(p, build, index, out_cap):
                return join_ops.hash_join_general(
                    p, pschema, pkeys, build, bschema, bkeys, spec, out_cap,
                    pht, bht, remaps, index=index, exact_layout=layout,
                )

            self._probe_gen_raw = probe_gen_raw
            self._probe_gen_fn = functools.partial(
                dispatch.jit, static_argnames=("out_cap",),
                name="hashjoin_probe_gen",
            )(probe_gen_raw)
            self._out_cap = 0

    def _set_probe(self, kind: str):
        """Install the probe function for the index strategy chosen at build
        time. All strategies share the (probe, build_batch, index) calling
        convention so fusion and the pull path stay uniform. Cached per
        strategy kind: a fresh closure per init() would invalidate every
        downstream jit composition keyed on its identity (re-tracing the
        whole fused segment once per query run)."""
        if getattr(self, "_probe_kind", None) == kind and (
                kind != "analytic" or self._probe_analytic == self._analytic):
            return
        self._probe_kind = kind
        self._probe_analytic = self._analytic if kind == "analytic" else None
        pschema = self.child.output_schema
        bschema = self.build.output_schema
        pkeys, bkeys = self.probe_keys, self.build_keys
        pht = self.probe_hash_tables or None
        bht = self.build_hash_tables or None
        remaps = self.build_code_remaps or None
        layout = self.exact_layout
        spec = self.spec

        # the probe alone, (found_idx, found): what an emission needs to
        # cut the tile before it gathers a build column
        if kind == "analytic":
            info = self._analytic

            def probe_pair(p, build, index):
                return join_ops.dense_analytic_probe(
                    p, pkeys, build, bkeys, info, remaps
                )
        elif kind == "lut":

            def probe_pair(p, build, index):
                return join_ops.dense_lut_probe(p, pkeys, layout, index)
        elif spec.build_unique:

            def probe_pair(p, build, index):
                return join_ops.probe_unique(
                    p, pschema, pkeys, build, bschema, bkeys,
                    pht, bht, remaps, index=index, exact_layout=layout,
                )
        else:
            probe_pair = None

        if probe_pair is not None:

            def probe_raw(p, build, index):
                return join_ops.emit_unique(
                    p, build, spec, *probe_pair(p, build, index))
        else:  # sorted-index existence probe over duplicate build keys

            def probe_raw(p, build, index):
                out, _ = join_ops.hash_join_general(
                    p, pschema, pkeys, build, bschema, bkeys, spec,
                    out_capacity=1,
                    probe_hash_tables=pht, build_hash_tables=bht,
                    build_code_remaps=remaps, index=index,
                    exact_layout=layout,
                )
                return out

        self._probe_raw = probe_raw
        # semi/anti carry no build column: nothing to materialise late
        self._probe_pair = (probe_pair if spec.join_type in ("inner", "left")
                            else None)
        self._probe_fn = dispatch.jit(probe_raw, name="hashjoin_probe")

    def _ensure_built(self):
        from ..utils import settings
        from .memory import Allocator, batch_bytes

        if self._built:
            return
        if self._analytic is not None:
            # position-preserving concat (NO compaction): row i of the build
            # batch is row i of the table, so key arithmetic addresses it.
            # No live-count host sync, no workmem spill (the build is the
            # resident table plus projection-derived columns).
            tiles = list(_consume_op(self.build, "build_spool"))
            if tiles:
                if len(tiles) == 1:
                    self._build_batch = tiles[0]
                else:
                    self._build_batch = jax.tree_util.tree_map(
                        lambda *xs: jnp.concatenate(xs), *tiles
                    )
                self._index = ()
                self._set_probe("analytic")
                self._built = True
                return
            tiles = []
        else:
            alloc = self._build_alloc = Allocator("hash join build",
                                                  stats=self.stats)
            tiles = []
            for b in _consume_op(self.build, "build_spool"):
                nb = batch_bytes(b)
                over = alloc.would_exceed(nb)
                # account the tile even past the budget: it is resident,
                # and the spilling build's max-mem must show it
                alloc.reserve(nb, force=over)
                if over:
                    # build side exceeds workmem: swap in the Grace hash join
                    # (both sides hash-partition so each partition's build
                    # fits the budget — disk_spiller.go's swap), attributed
                    # to the owning query's monitor
                    from .external import ChainOp, GraceHashJoinOp
                    from .memory import note_spill

                    note_spill("join")
                    self.stats.spilled = True
                    alloc.close()
                    self._build_alloc = None
                    chain = ChainOp(tiles + [b], self.build.output_schema,
                                    self.build.dictionaries, self.build)
                    self._grace = GraceHashJoinOp(
                        self.child, chain, self.probe_keys, self.build_keys,
                        self.spec,
                    )
                    self._grace.init()
                    self._built = True
                    return
                tiles.append(b)
        if not tiles:
            self._build_batch = empty_batch(self.build.output_schema, 1024)
            self._index = join_ops.build_index(
                self._build_batch, self.build.output_schema, self.build_keys,
                self.build_hash_tables or None,
            )
            if self._fusable:
                self._set_probe("sorted")
        else:
            cap = _spool_cap(tiles)
            self._note_build(cap, len(tiles))
            use_lut = (
                self._fusable
                and self.exact_layout is not None
                and self.exact_layout.total_bits
                <= settings.get("sql.distsql.dense_lut_bits")
            )
            if use_lut:
                self._build_batch, self._index = self._lut_fn(
                    tuple(tiles), cap=cap
                )
                self._set_probe("lut")
            else:
                self._build_batch, self._index = self._build_fn(
                    tuple(tiles), cap=cap
                )
                if self._fusable:
                    self._set_probe("sorted")
            if (cap <= _LOWEST_RUNG and self._emit_mode == "learn"
                    and not self._emit_cap_seen
                    and _LOWEST_RUNG < settings.get("sql.distsql.tile_size")):
                # a build side that fits the ladder's lowest rung (its live
                # rows were just counted for the spool) seldom keeps more
                # than a rung of a probe tile: start compact at that rung,
                # where a learn run would emit, and compile its consumers
                # for, full tiles. The emit still counts the whole tile, so
                # a tile that keeps more overflows and re-runs on the cap
                # post_run_update learns from the count, as any compact
                # join whose literal widened
                self._emit_mode = "compact"
                self._emit_cap = _LOWEST_RUNG
        self._built = True

    def children(self):
        return [self.child, self.build]

    def _composes(self) -> bool:
        """Whether this join's probe rides in its consumer's kernel for the
        run at hand, read from what the chain below is set to do in it.
        Modes and caps change only between runs (post_run_update), so this
        is known before the first tile.

        A transparent join composes. So does a compact-mode join whose
        tiles will already come at a capacity no larger than its own cap,
        because a compact join below emits them and only position-
        preserving links (_passes_tiles: they mask, never move rows) or
        composed probes lie between. Compacting them again shrinks nothing
        and costs a kernel and a copy of every carried column a tile (q9:
        four joins re-compacting 131,072 rows into 131,072). A unique-build
        probe emits at most one row a probe row: nothing can overflow here,
        and the compact join below keeps counting.

        Either way only while the composed jit stays within
        sql.distsql.max_fused_joins probes: the compile-size safety valve,
        so one fused segment never accretes unbounded XLA program size.
        The count stops where composition actually splits: at a fusion-pass
        segment boundary (_chain_split barrier source) and at joins that
        drive their own kernel (learn, general, and compact emission that
        compacts); joins below those never enter this jit."""
        from ..utils import settings

        def composes(j, fed):
            return j._emit_mode == "transparent" or (
                j._emit_mode == "compact" and fed is not None
                and fed <= j._emit_cap and below < valve)

        valve = settings.get("sql.distsql.max_fused_joins")
        chain = []
        op = self.child
        while op is not None:
            chain.append(op)
            op = getattr(op, "child", None)
        fed = None  # tile capacity a compact join below hands up
        below = 0  # probes already in the jit this join's would enter
        for op in reversed(chain):
            if getattr(op, "_chain_split", False):
                below = 0
            elif isinstance(op, HashJoinOp):
                if composes(op, fed):
                    below += 1
                    if not op._fusable:  # a duplicate-key probe moves rows
                        fed = None
                else:
                    below = 0
                    fed = (op._emit_cap if op._emit_mode == "compact"
                           else None)
            elif isinstance(op, MergeJoinOp):
                below += 1
                fed = None
            elif not op._passes_tiles:
                fed = None
        return below < valve and composes(self, fed)

    def stream_parts(self):
        if not (self._fusable or self._gen_fusable):
            return None
        if getattr(self, "_grace", None) is not None:
            return None  # spilled: the Grace join drives the probe itself
        if not self._initialized:
            self.init()
        # what this join is when its probe does not compose. Transparent:
        # a barrier (it runs as its own per-operator jit). Learn / compact /
        # general: a tile SOURCE — it drives the child chain through its
        # own (chain o probe [o compact]) kernel, records a live count per
        # tile (device future, fetched once per query in post_run_update)
        # and hands downstream consumers small compacted tiles to compose
        # their kernels on. Costs one extra async dispatch per tile; saves
        # O(tile x ncols) per downstream operator when the probe is
        # selective.
        alone = (None if self._emit_mode == "transparent"
                 else (self, _identity_fn, ()))
        if not self._composes():
            return alone
        parts = self.child.stream_parts()
        if parts is None:
            return alone
        self._ensure_built()
        if getattr(self, "_grace", None) is not None:
            return alone  # the build spilled while spooling
        src, cfn, cargs = parts
        raw = self._probe_raw
        nc = len(cargs)
        # one function object a (chain below, probe): the consumer's
        # composed kernel is cached on its identity
        chain = _per_chain(
            self, "_chain_fns", (cfn, raw),
            lambda: lambda t, *a: raw(cfn(t, *a[:nc]), a[nc], a[nc + 1]))
        counted = getattr(self, "_counted_src", None)
        if counted is None or counted.src is not src:
            counted = self._counted_src = _CountedProbeTiles(src, self)
        return counted, chain, cargs + (self._build_batch, self._index)

    def _note_probe_tile(self, t, src=None, composed=False) -> None:
        """One probe tile into the pull span's ``join_unique_tiles`` (served
        by a unique-build strategy: analytic, LUT, sorted-unique) or
        ``join_general_tiles`` (by hash_join_general; under an exact packed
        key, where that emits by run expansion and runs no loop, also into
        ``join_expanded_tiles``), and its capacity
        into ``join_probe_tile_rows`` (rows the probe pays for, live or
        dead: known on the host, no sync; a semi or anti join's also into
        ``semijoin_probe_tile_rows``), beside the dispatch tags;
        tracing.totals() sums them over a window. ``composed``: the probe
        ran inside the consumer's kernel; for a compact-mode join that is
        a tile it did not emit and compact itself, counted into
        ``join_passthrough_tiles`` as well; a tile it did emit, and cut
        to its cap before it gathered a build column (`_emits_late`),
        counts into ``join_late_emit_tiles``. Every tile also adds the
        join's output width to ``join_output_columns``: the columns its
        emitted (or composed) tile carries, read above or not
        (plan/prune.py cuts them to those read). A LEFT join's tile on a
        unique-build route (its unmatched rows NULL-extended in place: a
        NOT EXISTS read as an anti-join by IS NULL above it) counts into
        ``join_null_extended_tiles`` too. ``t`` is a Batch, or a
        resident scan's (table batch, offset) token whose tile size
        ``src`` knows."""
        sp = tracing.current()
        if sp is None:
            return
        if composed and self._emit_mode == "compact":
            sp.inc_tag("join_passthrough_tiles", 1)
        unique = self._probe_raw is not None and (
            self.spec.build_unique or self._probe_kind != "sorted")
        sp.inc_tag("join_unique_tiles" if unique else "join_general_tiles", 1)
        if not unique and self.exact_layout is not None:
            sp.inc_tag("join_expanded_tiles", 1)
        sp.inc_tag("join_output_columns", len(self.output_schema))
        rows = _tile_rows(t, src)
        sp.inc_tag("join_probe_tile_rows", rows)
        if self.spec.join_type in ("semi", "anti"):
            sp.inc_tag("semijoin_probe_tile_rows", rows)
        if not composed and self._emits_late(rows):
            sp.inc_tag("join_late_emit_tiles", 1)
        if unique and self.spec.join_type == "left":
            sp.inc_tag("join_null_extended_tiles", 1)

    def _note_build(self, cap: int, tiles: int) -> None:
        """The static capacity of a build side this join makes or re-makes
        (``hashjoin_lut`` / ``hashjoin_build``; an analytic build launches
        neither) into the pull span's ``join_build_rows``: which rung each
        build of a statement ran at, and whether a build is redone a
        statement. ``cap`` comes of the spool's one live count: no sync of
        its own. ``join_build_placed_tiles``: of the ``tiles`` that launch
        is handed, those it places at a running offset (`concat_prefix`:
        all of them where the build side proves its tiles live-prefix, 0
        on the gather route)."""
        sp = tracing.current()
        if sp is not None:
            sp.inc_tag("join_build_rows", cap)
            sp.inc_tag("join_build_placed_tiles",
                       tiles if self._places_build else 0)

    def _emits_late(self, tile_rows: int) -> bool:
        """Whether this join's own emit of a ``tile_rows`` probe tile cuts
        the tile to the learned cap BEFORE it materialises the build side
        (ops/join.py `emit_unique_compact`): compact mode, a unique-build
        strategy of an inner or left join (`_probe_pair`), and a cap that
        shrinks the tile. All host-known before the launch (the capacity
        is static in the trace); `_emit_kernel` decides by the same three.
        Everything else (learn, general, semi/anti, a cap no smaller than
        the tile) emits probe-aligned as it always did."""
        return (self._emit_mode == "compact"
                and self._probe_pair is not None
                and self._emit_cap < tile_rows)

    def _emit_kernel(self, cfn, nc):
        """The jit for source-mode emission, one program a tile, cached on
        (chain fn, probe fn, emission cap). Learn mode (no cap): chain o
        probe o probe-aligned emit o count. Compact mode: chain o probe,
        then the compaction's index from the probe's own output mask, and
        only then the build columns, gathered once at the cap (`_emits_late`;
        a cap that does not shrink the tile, or semi/anti, emits aligned
        and compacts after). General duplicate-key probes emit
        speculatively at the learned static capacity. The kernel's second
        output is always the TRUE total of the whole tile, so a truncating
        overflow is detectable at query end without a per-tile host sync."""
        from ..coldata.batch import compact as compact_batch

        cap = self._emit_cap
        if self._emit_mode == "general":
            graw = self._probe_gen_raw
            key = (cfn, graw, cap)
            if key in self._emit_kerns:
                return self._emit_kerns[key]

            def kern(t, *a):
                p = cfn(t, *a[:nc]) if cfn is not None else t
                return graw(p, a[nc], a[nc + 1], cap)

        else:
            raw = self._probe_raw  # installed with its _probe_pair
            key = (cfn, raw, cap)
            if key in self._emit_kerns:
                return self._emit_kerns[key]
            # learn mode has no cap to cut to
            pair = self._probe_pair if cap is not None else None
            spec = self.spec

            def kern(t, *a):
                p = cfn(t, *a[:nc]) if cfn is not None else t
                if pair is not None and cap < p.capacity:
                    return join_ops.emit_unique_compact(
                        p, a[nc], spec, *pair(p, a[nc], a[nc + 1]), cap)
                out = raw(p, a[nc], a[nc + 1])
                cnt = jnp.sum(out.mask, dtype=jnp.int64)
                if cap is not None:
                    out = compact_batch(out, capacity=cap)
                return out, cnt

        if len(self._emit_kerns) >= 8:  # the last few modes and caps
            self._emit_kerns.pop(next(iter(self._emit_kerns)))
        self._emit_kerns[key] = dispatch.jit(kern, name="hashjoin_emit")
        return self._emit_kerns[key]

    def stream_tiles(self):
        """Source-mode drive loop (learn/compact emission)."""
        return dispatch.sectioned(self, self._stream_tiles())

    def _stream_tiles(self):
        self._ensure_built()
        if getattr(self, "_grace", None) is not None:
            # build spilled mid-spool: serve grace output as plain tiles
            while True:
                b = self._grace._next()
                if b is None:
                    return
                yield b
            return
        parts = self.child.stream_parts()
        if parts is not None:
            src, cfn, cargs = parts
            args = cargs + (self._build_batch, self._index)
            if self._emit_mode == "general" and self._emit_cap is None:
                # initial speculation: FK-ish fanout <= 1 per probe row at
                # full scan tiles (the _next estimate — source tiles are raw
                # tuples here, so the setting stands in for their capacity);
                # post_run_update corrects in either direction
                from ..utils import settings

                self._emit_cap = max(4096, _canonical_cap(
                    settings.get("sql.distsql.tile_size")))
            kern = self._emit_kernel(cfn, len(cargs))
            for t in src.stream_tiles():
                self._note_probe_tile(t, src)
                out, cnt = kern(t, *args)
                self._emit_counts.append(cnt)
                self._note_tilecap(out, t, src)
                self._note_emit_tile(out)
                yield out
            return
        kern = None
        while True:
            b = self.child.next_batch()
            if b is None:
                return
            if kern is None:
                if self._emit_mode == "general" and self._emit_cap is None:
                    self._emit_cap = max(4096, _canonical_cap(b.capacity))
                kern = self._emit_kernel(None, 0)
            self._note_probe_tile(b)
            out, cnt = kern(b, self._build_batch, self._index)
            self._emit_counts.append(cnt)
            self._note_tilecap(out, b)
            self._note_emit_tile(out)
            yield out

    def _note_emit_tile(self, out: Batch) -> None:
        """The static capacity of a tile hash_join_general emitted (a join
        whose build key may repeat: general mode, or the per-tile retry of
        _next) into the pull span's ``join_emit_tile_rows``: the rows every
        consumer kernel of the tile pays for, whatever the join matched.
        Says which learned cap ran (host-known, no sync)."""
        sp = tracing.current()
        if sp is not None and not self._fusable:
            sp.inc_tag("join_emit_tile_rows", out.capacity)

    def _note_tilecap(self, out: Batch, t, src=None) -> None:
        """The widest probe tile seen, for post_run_update to judge a cap
        by: a learn run emits probe-aligned, so its output's capacity; a
        join that started compact on a small build never ran one, so its
        input's."""
        if self._emit_cap is None:
            self._emit_tilecap = max(self._emit_tilecap, out.capacity)
        elif not self._emit_tilecap:
            self._emit_tilecap = _tile_rows(t, src)

    def post_run_update(self, truncated: bool = False) -> bool:
        if truncated and self._emit_mode in ("learn", "compact"):
            # a join below overflowed: these counts are of tiles it cut
            # short (a join that passed its tiles through has none). Count
            # again at full tiles in the re-run, beside it, instead of
            # finding the overflow one join an attempt
            self._emit_counts = []
            if self._emit_mode == "compact":
                self._emit_mode, self._emit_cap = "learn", None
            return False
        if not self._emit_counts:
            return False
        # crlint: allow-host-sync(post_run_update: ONE stacked sync per query)
        counts = np.asarray(jax.block_until_ready(
            jnp.stack(self._emit_counts)
        ))
        self._emit_counts = []
        mx = int(counts.max()) if counts.size else 0
        if self._emit_mode == "general":
            # speculative duplicate-key probe: a total past the emission
            # capacity means that tile's rows were truncated — grow (with
            # headroom: every retry recompiles) and re-run the query
            if mx > self._emit_cap:
                from ..utils import log

                self._emit_cap = _canonical_cap(2 * mx)
                log.warning(log.SQL_EXEC,
                            "general join emission cap overflowed; re-running",
                            max_rows=mx)
                return True
            if mx * 8 <= self._emit_cap and self._emit_cap > 4096:
                # learned fanout far below speculation: shrink (keeping 2x
                # headroom) so steady-state tiles stop carrying dead rows
                self._emit_cap = max(4096, _canonical_cap(2 * mx))
            return False
        overflow = (
            self._emit_mode == "compact" and self._emit_cap is not None
            and mx > self._emit_cap
        )
        tile = self._emit_tilecap
        # a learned cap only grows: the plan serves every literal of its
        # shape, and a run that kept few rows (a pattern that matches
        # nothing) must not shrink the cap under the next statement (an
        # overflow there: a re-run and new programs)
        cap = max(_LOWEST_RUNG, _emission_cap(2 * mx), self._emit_cap_seen)
        if tile and mx * 4 <= tile and cap < tile:
            # compacting only pays when the learned cap actually SHRINKS the
            # tile — at small tile sizes the cap floor equals the tile and
            # "compact" degenerates to one extra kernel per tile for nothing
            # (every join in a chain then self-drives: q9's five-join run
            # used to pay 5 kernels/tile instead of composing into 2)
            self._emit_cap = self._emit_cap_seen = cap
            self._emit_mode = "compact"
        elif self._emit_cap_seen:
            # selective for some literal of this plan, not for this one
            # ('%a%' after '%green%'): keep counting at full tiles, so the
            # next selective literal compacts again. Transparent joins
            # record nothing and would never come back.
            self._emit_mode = "learn"
            self._emit_cap = None
        else:
            self._emit_mode = "transparent"
            self._emit_cap = None
        if overflow:
            from ..utils import log

            log.warning(log.SQL_EXEC,
                        "join emission cap overflowed; re-running",
                        max_rows=mx)
        return overflow

    def _next(self):
        self._ensure_built()
        if getattr(self, "_grace", None) is not None:
            return self._grace._next()
        p = self.child.next_batch()
        if p is None:
            return None
        self._note_probe_tile(p)
        if self._probe_raw is not None:
            if self._emit_mode != "transparent":
                out, cnt = self._emit_kernel(None, 0)(
                    p, self._build_batch, self._index
                )
                self._emit_counts.append(cnt)
                self._note_tilecap(out, p)
                return out
            return self._probe_fn(p, self._build_batch, self._index)
        if self._out_cap <= 0:
            # initial capacity: assume FK-ish fanout <= 1 per probe row
            # (planner estimate), double on overflow — the retry recompiles,
            # so the estimate errs large
            self._out_cap = max(4096, _canonical_cap(p.capacity))
        while True:
            out, total = self._probe_gen_fn(
                p, self._build_batch, self._index, out_cap=self._out_cap
            )
            if int(total) <= self._out_cap:
                self._note_emit_tile(out)
                return out
            self._out_cap = _canonical_cap(int(total))

    def close(self):
        super().close()
        self.build.close()
        if getattr(self, "_build_alloc", None) is not None:
            self._build_alloc.close()
            self._build_alloc = None


def _tile_rows(t, src=None) -> int:
    """Capacity in rows of a probe tile: a Batch, or a resident scan's
    (table batch, offset) token whose tile size ``src`` knows."""
    rows = getattr(t, "capacity", None)
    if rows is None:
        while isinstance(src, _CountedProbeTiles):
            src = src.src
        rows = src._res_tile
    return int(rows)


class _CountedProbeTiles:
    """The tile source a transparent or passing-through join hands its
    consumer: the probe is fused into the consumer's kernel, so the join
    sees a tile only here."""

    def __init__(self, src, join: HashJoinOp):
        self.src = src
        self.join = join

    def stream_tiles(self):
        return dispatch.sectioned(self.join, self._stream_tiles())

    def _stream_tiles(self):
        for t in self.src.stream_tiles():
            self.join._note_probe_tile(t, self.src, composed=True)
            yield t


def _consume_op(op: Operator, tag: str):
    """Pull every tile from `op`, fused with its streaming chain when
    possible (build-side spools ride one jit instead of one per operator).
    The composed kernel is `op`'s, as a tile next_batch hands out is."""
    parts = (None if (op._collect or not _fusion_enabled())
             else op.stream_parts())
    if parts is None:
        while True:
            b = op.next_batch()
            if b is None:
                return
            yield b
        return
    yield from dispatch.sectioned(op, _drive_chain(op, tag, parts))


def _drive_chain(op: Operator, tag: str, parts):
    src, cfn, args = parts
    fn = _per_chain(
        op, f"_fused_src_{tag}", cfn,
        lambda: dispatch.jit(cfn, name=f"pipe_{op.KERNEL}_{tag}"))
    for t in src.stream_tiles():
        yield fn(t, *args)


class WindowOp(OneInputOperator):
    """Buffering window-function operator (colexecwindow analog): spool all
    tiles, one sorted segmented-scan pass appends the window columns."""

    def __init__(self, child: Operator, partition_cols: tuple[int, ...],
                 order_keys, specs):
        from ..ops import window as win_ops

        super().__init__(child)
        self.partition_cols = partition_cols
        self.order_keys = tuple(order_keys)
        self.specs = tuple(specs)
        self.output_schema = win_ops.window_output_schema(
            child.output_schema, self.specs
        )
        self.dictionaries = dict(child.dictionaries)
        # string-valued window outputs (lag/lead/min/max/first/last over a
        # STRING column) carry the source column's dictionary
        base_len = len(child.output_schema)
        for i, sp in enumerate(self.specs):
            if (sp.col is not None and sp.col in child.dictionaries
                    and sp.func in ("lag", "lead", "min", "max",
                                    "first_value", "last_value")):
                self.dictionaries[base_len + i] = child.dictionaries[sp.col]
        self._emitted = False

    def init(self):
        super().init()
        self._emitted = False
        if hasattr(self, "_fn"):
            return
        from ..ops import window as win_ops

        schema = self.child.output_schema
        # rank tables for every STRING column the kernel sorts or reduces:
        # order keys, partition keys, and min/max inputs
        need = {k.col for k in self.order_keys}
        need.update(self.partition_cols)
        need.update(
            sp.col for sp in self.specs
            if sp.col is not None and sp.func in ("min", "max")
        )
        for c in need:
            if getattr(self.child.dictionaries.get(c), "_runtime", False):
                raise ValueError(
                    "window functions over a string_agg result are not "
                    "supported (its dictionary fills at runtime)"
                )
        rank_tables = {
            c: self.child.dictionaries[c].ranks
            for c in need
            if c in self.child.dictionaries
        }
        pcols = self.partition_cols
        okeys = self.order_keys
        specs = self.specs

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="window_spool")
        def fn(batches, cap):
            big = concat(list(batches), capacity=cap)
            return win_ops.compute_windows(
                big, schema, pcols, okeys, specs, rank_tables
            )

        self._fn = fn

    def _next(self):
        if self._emitted:
            return None
        tiles = list(_consume(self, "spool", _identity_fn))
        self._emitted = True
        if not tiles:
            return None
        return self._fn(tuple(tiles), cap=_spool_cap(tiles))


class OrderedSyncOp(Operator):
    """Merge-ordered fan-in — the OrderedSynchronizer analog (colexec/
    ordered_synchronizer.eg.go): K inputs whose streams are each sorted
    on `keys` merge into one sorted stream, INCREMENTALLY: per round,
    one tile is pulled from each input that needs one, the buffered rows
    merge (concat + packed-key sort, the TPU merge idiom), and rows at or
    below the BARRIER — the smallest of the inputs' maximum buffered
    keys — are safe to emit (no later row can sort before them). Rows
    past the barrier carry to the next round in a fixed-capacity tile
    (bounded: each input contributes at most one tile beyond the
    barrier).

    Streams whenever the key list packs into uint64 words (ops/keys.py
    bit-packing; true for int/date/string/bool keys — barrier compares
    compose lexicographically across words). Float keys ride native f64
    operands and fall back to a full spool + one sort — same results, no
    streaming."""

    def __init__(self, children_ops: tuple[Operator, ...], keys):
        super().__init__()
        assert children_ops, "ordered fan-in needs at least one input"
        self._children = list(children_ops)
        self.keys = tuple(keys)
        self.output_schema = children_ops[0].output_schema
        self.dictionaries = dict(children_ops[0].dictionaries)
        self.col_stats = {}
        self._rank_tables = {
            k.col: children_ops[0].dictionaries[k.col].ranks
            for k in self.keys
            if k.col in children_ops[0].dictionaries
        }

    def children(self):
        return list(self._children)

    def _packed_words(self, b: Batch):
        """Packed sort-key words per row ([w0, w1, ...], lexicographic),
        or None when any operand is not a uint64 word (float keys ride
        native f64 — fallback path)."""
        ops = sort_ops.pack_sort_operands(
            b, self.output_schema, self.keys, self._rank_tables,
            include_mask=False,
        )
        if any(o.dtype != jnp.uint64 for o in ops):
            return None
        return ops

    @staticmethod
    def _lex_max(words, live):
        """Lexicographic max of multi-word keys over live rows (no host
        sync): fix each word greedily, narrowing the candidate set."""
        sel = live
        out = []
        for w in words:
            m = jnp.max(jnp.where(sel, w, jnp.uint64(0)))
            out.append(m)
            sel = sel & (w == m)
        return out

    @staticmethod
    def _lex_le(words, barrier):
        """rowwise (w0, w1, ...) <= (b0, b1, ...)."""
        lt = jnp.zeros(words[0].shape, jnp.bool_)
        eq = jnp.ones(words[0].shape, jnp.bool_)
        for w, b in zip(words, barrier):
            lt = lt | (eq & (w < b))
            eq = eq & (w == b)
        return lt | eq

    def init(self):
        for c in self._children:
            c.init()
        self._bufs: list[Batch | None] = [None] * len(self._children)
        self._done = [False] * len(self._children)
        self._carry: Batch | None = None
        self._flushed = False
        probe = empty_batch(self.output_schema, 16)
        self._streaming = self._packed_words(probe) is not None
        self._spooled = None
        self._initialized = True

    # -- fallback: full spool + one sort (correct, not streaming) ----------

    def _fallback_next(self):
        if self._spooled is None:
            tiles = []
            for c in self._children:
                while True:
                    b = c.next_batch()
                    if b is None:
                        break
                    tiles.append(b)
            if not tiles:
                self._spooled = ()
                return None
            big = concat(tiles, capacity=_spool_cap(tiles))
            self._spooled = (sort_ops.sort_batch(
                big, self.output_schema, self.keys, self._rank_tables),)
        if self._spooled:
            out, self._spooled = self._spooled[0], ()
            return out
        return None

    # -- streaming rounds --------------------------------------------------

    def _round(self):
        """(emit_batch | None). Pull-missing, merge, split at barrier."""
        for i, c in enumerate(self._children):
            if not self._done[i] and self._bufs[i] is None:
                b = c.next_batch()
                if b is None:
                    self._done[i] = True
                else:
                    self._bufs[i] = b
        tiles = [b for b in self._bufs if b is not None]
        live_inputs = [
            i for i in range(len(self._children))
            if not self._done[i] or self._bufs[i] is not None
        ]
        parts = ([self._carry] if self._carry is not None else []) + tiles
        if not parts:
            return None
        cap = _spool_cap(parts)
        big = concat(parts, capacity=cap)
        merged = sort_ops.sort_batch(
            big, self.output_schema, self.keys, self._rank_tables)
        if all(self._done) :
            # final flush: everything is safe
            self._carry = None
            self._bufs = [None] * len(self._children)
            self._flushed = True
            return merged
        words = self._packed_words(merged)
        # barrier: lexicographic MIN over NON-EXHAUSTED inputs of their
        # buffered max key (no later row of any input can sort below it)
        bars = []
        for i in range(len(self._children)):
            if self._done[i] or self._bufs[i] is None:
                continue
            bw = self._packed_words(self._bufs[i])
            bars.append(self._lex_max(bw, self._bufs[i].mask))
        barrier = bars[0]
        for b in bars[1:]:
            # lex min of two multi-word values via the compare helper
            b_le = self._lex_le([jnp.asarray(x)[None] for x in b],
                                [jnp.asarray(x)[None] for x in barrier])[0]
            barrier = [jnp.where(b_le, x, y) for x, y in zip(b, barrier)]
        safe = self._lex_le(words, barrier)
        emit_mask = merged.mask & safe
        hold_mask = merged.mask & ~safe
        out = merged.with_mask(emit_mask)
        # carry holds the tail in ORDER (compact preserves row order);
        # bounded by sum of per-input tile caps, so a static capacity of
        # the current spool cap always fits
        from ..coldata.batch import compact as compact_batch

        self._carry = compact_batch(merged.with_mask(hold_mask),
                                    capacity=cap)
        self._bufs = [None] * len(self._children)
        return out

    def _next(self):
        if not self._streaming:
            return self._fallback_next()
        while not self._flushed:
            out = self._round()
            if out is None:
                return None
            return out
        return None

    def close(self):
        for c in self._children:
            c.close()


class ParallelUnorderedSyncOp(Operator):
    """Unordered fan-in with one PULLER THREAD per input — the
    ParallelUnorderedSynchronizer analog (colexec/parallel_unordered_
    synchronizer.go:66): batches surface in arrival order through a
    bounded queue, so inputs overlap their waits. Essential for remote
    FlowInboxes (serial draining would serialize the hosts' compute and
    network time); for local inputs it adds pipeline overlap at the cost
    of thread handoff."""

    _QUEUE_DEPTH = 4  # per-flow backpressure (bounded buffering)
    _DONE = object()

    def __init__(self, children_ops: tuple[Operator, ...]):
        super().__init__()
        assert children_ops, "fan-in needs at least one input"
        self._children = list(children_ops)
        self.output_schema = children_ops[0].output_schema
        for c in children_ops[1:]:
            assert len(c.output_schema) == len(self.output_schema), \
                "fan-in inputs must have equal arity"
        self.dictionaries = dict(children_ops[0].dictionaries)
        self.col_stats = {}

    def children(self):
        return list(self._children)

    def init(self):
        import queue
        import threading

        # a re-init (run_operator's capacity-retry loop) must not leave
        # the previous run's pullers racing the new ones on the children
        self._shutdown_pullers()
        for c in self._children:
            c.init()
        self._q = queue.Queue(
            maxsize=self._QUEUE_DEPTH * len(self._children))
        self._stop = threading.Event()
        self._live = len(self._children)
        self._threads = []
        for c in self._children:
            t = threading.Thread(target=self._pull, args=(c,),
                                 name="unordered-sync", daemon=True)
            t.start()
            self._threads.append(t)
        self._initialized = True

    def _pull(self, child: Operator) -> None:
        try:
            while not self._stop.is_set():
                b = child.next_batch()
                if b is None:
                    break
                self._q.put(b)
        except BaseException as e:  # surface in the consumer, not a log  # crlint: allow-broad-except(producer thread forwards the exception to the consumer via the queue)
            self._q.put(e)
            return
        self._q.put(self._DONE)

    def _next(self):
        while self._live > 0:
            item = self._q.get()
            if item is self._DONE:
                self._live -= 1
                continue
            if isinstance(item, BaseException):
                self._stop.set()
                raise item
            return item
        return None

    def _shutdown_pullers(self) -> None:
        """Stop + join puller threads, draining the queue while joining so
        a producer blocked in put() always gets space to observe stop."""
        if not getattr(self, "_threads", None):
            return
        import queue

        self._stop.set()
        for t in self._threads:
            while t.is_alive():
                try:
                    while True:
                        self._q.get_nowait()
                except queue.Empty:
                    pass  # drained — producers have space to observe stop
                t.join(timeout=0.05)
        self._threads = []

    def close(self):
        # children first: closing a remote FlowInbox closes its socket,
        # which is the ONLY thing that unblocks a puller stuck in a
        # timeout-less recv (the drain-while-join below only unblocks
        # pullers stuck in q.put)
        self._stop.set()
        for c in self._children:
            c.close()
        self._shutdown_pullers()


class UnionOp(Operator):
    """UNION ALL: pull each input to exhaustion in order (the plan-level
    unordered fan-in; inputs share one output schema)."""

    def __init__(self, children_ops: tuple[Operator, ...]):
        super().__init__()
        assert children_ops, "UNION ALL needs at least one input"
        self._children = list(children_ops)
        self.output_schema = children_ops[0].output_schema
        for c in children_ops[1:]:
            assert len(c.output_schema) == len(self.output_schema), \
                "UNION ALL inputs must have equal arity"
        self.dictionaries = dict(children_ops[0].dictionaries)
        self._cur = 0

    def children(self):
        return list(self._children)

    def init(self):
        for c in self._children:
            c.init()
        self._cur = 0
        self._initialized = True

    def _next(self):
        while self._cur < len(self._children):
            b = self._children[self._cur].next_batch()
            if b is not None:
                return b
            self._cur += 1
        return None

    def close(self):
        for c in self._children:
            c.close()
        super().close()


class MergeJoinOp(OneInputOperator):
    """Merge join: spool+sort the build side by exact (possibly composite)
    key order, stream probe tiles through vectorized lexicographic binary
    search (mergejoiner.go analog; no hash, no collision loop)."""

    def __init__(self, probe: Operator, build: Operator, probe_key,
                 build_key, spec):
        from ..ops import join as join_ops
        from ..ops.merge_join import _norm_keys

        super().__init__(probe)
        self.build = build
        self.probe_key = _norm_keys(probe_key)
        self.build_key = _norm_keys(build_key)
        self.spec = spec
        self.output_schema = join_ops.join_output_schema(
            probe.output_schema, build.output_schema, spec
        )
        self.dictionaries = dict(probe.dictionaries)
        self.col_stats = dict(probe.col_stats)
        if spec.join_type not in ("semi", "anti"):
            off = len(probe.output_schema)
            for i, d in build.dictionaries.items():
                self.dictionaries[off + i] = d
            for i, s in build.col_stats.items():
                self.col_stats[off + i] = s
        # STRING keys need a shared rank space per key position: remap
        # build codes into the probe dictionary's rank table (shared helper
        # with the SPMD lowering so the two paths can't diverge)
        from ..ops.merge_join import rank_tables_for

        self.probe_rank, self.build_rank = rank_tables_for(
            probe.output_schema, self.probe_key, probe.dictionaries,
            self.build_key, build.dictionaries,
        )
        self._built = False

    def children(self):
        return [self.child, self.build]

    def init(self):
        self.build.init()
        super().init()
        self._built = False
        if hasattr(self, "_probe_fn"):
            return
        from ..ops import merge_join as mj_ops

        bschema = self.build.output_schema
        bkey = self.build_key
        brank = self.build_rank

        @functools.partial(dispatch.jit, static_argnames=("cap",),
                           name="mergejoin_build")
        def build_fn(tiles, cap):
            big = concat(list(tiles), capacity=cap)
            return big, mj_ops.build_merge_index(big, bschema, bkey, brank)

        self._build_fn = build_fn
        pschema = self.child.output_schema
        pkey = self.probe_key
        prank = self.probe_rank
        spec = self.spec

        @functools.partial(dispatch.jit, static_argnames=("out_cap",),
                           name="mergejoin_probe")
        def probe_fn(p, build, index, out_cap):
            return mj_ops.merge_join(
                p, pschema, pkey, build, bschema, bkey, spec, out_cap,
                prank, brank, build_index=index,
            )

        self._probe_fn = probe_fn
        self._out_cap = 4096

    def _ensure_built(self):
        if self._built:
            return
        tiles = list(_consume_op(self.build, "build_spool"))
        if not tiles:
            from ..ops import merge_join as mj_ops

            self._build_batch = empty_batch(self.build.output_schema, 1024)
            self._index = mj_ops.build_merge_index(
                self._build_batch, self.build.output_schema, self.build_key,
                self.build_rank,
            )
        else:
            self._build_batch, self._index = self._build_fn(
                tuple(tiles), cap=_spool_cap(tiles)
            )
        self._built = True

    def _next(self):
        self._ensure_built()
        p = self.child.next_batch()
        if p is None:
            return None
        while True:
            out, total = self._probe_fn(
                p, self._build_batch, self._index, out_cap=self._out_cap
            )
            if int(total) <= self._out_cap:
                return out
            self._out_cap = _canonical_cap(int(total))

    def close(self):
        super().close()
        self.build.close()


# one-hot membership beats scatter only while the [rows, G] matrix stays a
# cheap fused VPU pass; past this, scatter's O(rows + G) wins
_ONEHOT_MAX_G = 64


class SmallGroupAggregateOp(OneInputOperator):
    """Dense-code aggregation for planner-bounded group key spaces — the
    hashAggregator specialization where the packed key IS the (collision-
    free) hash-table slot. Two kernels by cardinality:

    - tiny G (<= _ONEHOT_MAX_G, e.g. TPC-H Q1's returnflag x linestatus):
      one-hot membership matrix, a single fused VPU pass;
    - large-but-bounded G (e.g. GROUP BY l_orderkey with catalog bounds):
      segment scatters — O(rows) scatter + O(G) states, NO sort and NO
      live-count host sync (the sort path's per-spool capacity sync stalls
      the pull loop for a device round trip).

    Keys are dictionary codes (lo=0) or integer-family columns bounded by
    catalog/ANALYZE stats (key_lows offsets). Rows outside the planned
    bounds (stale stats) scatter to a detectable overflow slot; the
    operator re-runs the spool through the general sort path in that case
    rather than mis-grouping, checking the overflow count ONCE per spool.

    States are positionally aligned [G] arrays, so cross-tile (and
    cross-device) merging is elementwise."""

    KERNEL = "groupagg"

    def __init__(self, child: Operator, group_cols: tuple[int, ...],
                 aggs: tuple[agg_ops.AggSpec, ...], key_sizes: tuple[int, ...],
                 key_lows: tuple[int, ...] | None = None):
        super().__init__(child)
        self.group_cols = group_cols
        self.aggs = aggs
        self.key_sizes = key_sizes
        self.key_lows = key_lows or (0,) * len(group_cols)
        base = child.output_schema
        self.base_schema = base
        self.partial_specs, _, self.final_map = partial_layout(
            base, group_cols, aggs
        )
        self.G, self.strides = agg_ops.dense_layout(key_sizes)
        self.output_schema = agg_ops.agg_output_schema(base, group_cols, aggs)
        self.dictionaries = {
            group_cols.index(gi): d
            for gi, d in child.dictionaries.items()
            if gi in group_cols
        }
        self.col_stats = {
            group_cols.index(gi): s
            for gi, s in child.col_stats.items()
            if gi in group_cols
        }
        # group keys keep exact bounds even without upstream stats: the
        # output column g is in [lo, lo+size)
        for pos, (size, lo) in enumerate(zip(self.key_sizes, self.key_lows)):
            self.col_stats.setdefault(pos, (lo, lo + size - 1))
        self._emitted = False

    def init(self):
        super().init()
        self._emitted = False
        if hasattr(self, "_tile_raw"):
            return
        base = self.base_schema
        gcols = self.group_cols
        strides = self.strides
        G = self.G
        sizes = self.key_sizes
        lows = self.key_lows
        pspecs = self.partial_specs

        # the one-hot kernel covers the plain reductions only; statistical
        # states (sum_f/sum_sq) always take the scatter kernel. Platform
        # split (segscan.use_scans rationale inverted): on CPU scatter is a
        # cheap serial loop and one-hot is O(rows x G) real work, so scatter
        # wins at EVERY G; on TPU the [rows, G] membership matrix rides the
        # VPU in one fused pass while scatter serializes, so tiny G keeps
        # one-hot
        from ..ops import segscan

        use_onehot = (
            segscan.use_scans()
            and G <= _ONEHOT_MAX_G
            and all(
                s.func in ("sum", "count", "count_rows", "min", "max",
                           "any_not_null") for s in pspecs
            )
        )

        def tile_fn(b: Batch):
            code, oob = agg_ops.dense_group_codes(b, gcols, strides, sizes,
                                                  lows)
            if use_onehot:
                states, rows = agg_ops.dense_onehot_states(
                    b, base, code, G, pspecs
                )
            else:
                states, rows = agg_ops.dense_scatter_states(
                    b, base, code, G, pspecs
                )
            return states, rows, jnp.sum(oob & b.mask, dtype=jnp.int64)

        def merge_fn(acc, new):
            astates, arows, aoob = acc
            nstates, nrows, noob = new
            return (agg_ops.merge_dense_states(pspecs, astates, nstates),
                    arows + nrows, aoob + noob)

        def finalize_fn(acc):
            states, rows, _ = acc
            return agg_ops.dense_finalize(
                base, gcols, strides, sizes, G, self.final_map, states, rows,
                key_lows=lows,
            )

        self._tile_raw = tile_fn
        self._tile_fn = dispatch.jit(tile_fn, name="groupagg_tile")
        self._merge_raw = merge_fn
        self._merge_fn = dispatch.jit(merge_fn, donate_argnums=0,
                                      name="groupagg_merge")
        self._finalize_fn = dispatch.jit(finalize_fn,
                                         name="groupagg_finalize")

    def _next(self):
        if self._emitted:
            return None
        acc = _fold(self, "dense", self._tile_raw, self._tile_fn,
                    self._merge_raw, self._merge_fn)
        self._emitted = True
        if acc is None:
            return None
        if int(acc[2]) > 0:
            # stale-stats overflow: re-run the whole spool through the
            # general sort-groupby path (correctness over speed; ONE check
            # per spool, after the streaming pass)
            from ..utils import log

            log.warning(log.SQL_EXEC,
                        "dense agg overflow; sort-path fallback",
                        oob_rows=int(acc[2]))
            fb = AggregateOp(self.child, self.group_cols, self.aggs,
                             input_schema=self.base_schema)
            fb.init()
            return fb._next()
        return self._finalize_fn(acc)
