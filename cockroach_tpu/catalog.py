"""Table catalog — host-side table storage feeding device scans.

The reference reads tables from the KV layer through cFetcher
(pkg/sql/colfetcher/cfetcher.go:230); here a Table holds canonical-typed host
columns (strings already dictionary-encoded) plus per-column Dictionaries, and
materializes a device-resident padded Batch once (the "table is in HBM" model
— the TPU analog of a warmed block cache). The storage layer (cockroach_tpu/
storage) layers MVCC versions and SST-style runs beneath this.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from .coldata.batch import Batch, Dictionary, from_host
from .coldata.types import Family, Schema



# column families stored as host integers (what dense_key_info walks)
_INT_KEY_FAMILIES = (Family.INT, Family.DECIMAL, Family.DATE,
                     Family.TIMESTAMP, Family.INTERVAL)

TILE_ALIGN = 1024  # pad device tables to a multiple of this (8x128 lanes)

# canonical tile-shape menu (L0 of the cache hierarchy — see README):
# sub-tile tables pad UP to the next rung instead of to their own 1024-
# aligned cardinality, so every kernel over a small table compiles at one
# of ~5 shapes shared process-wide rather than one shape per table size.
# Tables larger than a rung keep tile-multiple padding: their downstream
# kernels already see tile-shaped slices, and padding further would add
# tiles (= dispatches) for zero compile benefit.
SHAPE_BUCKETS = (1 << 10, 1 << 13, 1 << 16, 1 << 19, 1 << 21)


def _stable_bounds(lo: int, hi: int) -> tuple[int, int]:
    """(lo, hi) widened to powers of two: [0, 2**k - 1], or [-2**j, 2**k - 1]
    below zero. A DECIMAL column holds measured amounts whose extremes move
    with every load of the same schema at the same scale (o_totalprice's
    largest order), and a bound that reaches a sort key's packing is a
    constant of the program: the exact pair keys the compile cache by the
    data. The widened pair costs a key at most one bit and is the same
    from load to load."""
    return (0 if lo >= 0 else -(1 << (-lo).bit_length()),
            (1 << max(hi, 0).bit_length()) - 1)


def mesh_shard_shape(n: int, D: int) -> tuple[int, int]:
    """(rows, capacity) of one device's share of an ``n``-row table spread
    over ``D`` devices: ceil(n / D) rows rounded up to 1,024, so that every
    device holds a quarter within D x 1,024 rows (the last one is short by
    the rounding), in a tile whose capacity is rounded up again, to 65,536
    rows above that size: TPC-H's `lineitem` moves with the seed by
    thousands of rows (1 to 7 lines an order), and a capacity that followed
    it would key the mesh program's compile cache by the data."""
    rows = max(TILE_ALIGN, -(-n // (D * TILE_ALIGN)) * TILE_ALIGN)
    g = TILE_ALIGN if rows < (1 << 16) else (1 << 16)
    return rows, -(-rows // g) * g


def _bucket_cap(n: int) -> int:
    for b in SHAPE_BUCKETS:
        if n <= b:
            return b
    top = SHAPE_BUCKETS[-1]
    return ((n + top - 1) // top) * top


def _pad_cap(n: int, tile: int | None = None) -> int:
    """Padded device capacity: a multiple of the scan tile (so bounded-tile
    resident scans slice evenly — no full-table kernel shapes), min one tile.
    With shape bucketing (default), sub-tile tables round up the pow2 rung
    ladder; with it off, they align to 1024 lanes only (the pre-bucketing
    behavior the bit-identity sweep compares against)."""
    from .utils import settings

    if settings.get("sql.distsql.shape_buckets.enabled"):
        cap = _bucket_cap(n)
        if tile is None or tile <= 0 or cap <= tile:
            return cap
        # above one tile: tile-multiple padding (never MORE tiles than the
        # unbucketed shape — the dispatch budget must hold with padding on)
        return max(tile, ((n + tile - 1) // tile) * tile)
    align = TILE_ALIGN
    if tile is not None and n > tile:
        align = tile
    return max(align, ((n + align - 1) // align) * align)


@dataclass
class Table:
    name: str
    schema: Schema
    columns: dict[str, np.ndarray]
    valids: dict[str, np.ndarray] = field(default_factory=dict)
    dictionaries: dict[str, Dictionary] = field(default_factory=dict)
    _device: dict | None = None
    _stats: dict | None = None
    # the table's placement on a multi-device node: (mesh, per-column
    # row-sharded arrays), filled by mesh_batch as statements read columns
    _mesh_device: tuple | None = None
    # physical clustering: host rows are stored grouped (equal values
    # adjacent) by this column prefix — e.g. TPC-H lineitem by l_orderkey,
    # KV tables by primary key. Enables the sort-free ordered aggregation
    # (colexec orderedAggregator role, ordered_aggregator.go)
    ordering: tuple[str, ...] = ()

    @property
    def num_rows(self) -> int:
        return len(next(iter(self.columns.values())))

    def dict_by_index(self) -> dict[int, Dictionary]:
        return {
            self.schema.index(name): d for name, d in self.dictionaries.items()
        }

    def set_stats(self, st) -> None:
        """Install ANALYZE-collected statistics (sql/stats.TableStats).
        Planner consumers (join order, broadcast threshold, exact-key bit
        widths) read the SNAPSHOT — deliberately stale-able, like the
        reference's optimizer stats."""
        self.table_stats = st
        # exact-key/sort-key consumers read col_stats(): refresh the (lo,
        # hi) view from the analyzed snapshot
        self._stats = {
            n: (c.lo, c.hi)
            for n, c in st.cols.items()
            if c.lo is not None and c.hi is not None
        } if st is not None else None

    def estimated_rows(self) -> int:
        """Planner cardinality: the ANALYZE snapshot when present, else the
        physical count."""
        st = getattr(self, "table_stats", None)
        return st.row_count if st is not None else self.num_rows

    def col_stats(self) -> dict[str, tuple]:
        """Per-column (lo, hi) bounds over valid rows for integer-represented
        columns (the table-statistics analog of pkg/sql/stats, reduced to
        what the kernel layer consumes: sort-key bit widths). Computed once
        on the host, cached; ANALYZE (set_stats) replaces the snapshot."""
        if getattr(self, "_stats", None) is None:
            stats: dict[str, tuple] = {}
            for name, t in zip(self.schema.names, self.schema.types):
                if t.family in (Family.FLOAT, Family.BYTES, Family.BOOL,
                                Family.JSON):
                    continue
                a = np.asarray(self.columns[name])
                if name in self.valids:
                    a = a[np.asarray(self.valids[name])]
                if len(a) == 0:
                    continue
                lo, hi = int(a.min()), int(a.max())
                if t.family is Family.DECIMAL:
                    lo, hi = _stable_bounds(lo, hi)
                stats[name] = (lo, hi)
            self._stats = stats
        return self._stats

    def dense_key_info(self) -> dict[str, tuple[int, int]]:
        """{column: (lo, fanout)} for integer columns whose value IS an
        affine function of the row index: col == repeat(arange(lo, lo+n/f), f).

        fanout 1 covers surrogate primary keys (TPC-H o_orderkey = 1..N and
        friends — the reference reads the same structure out of its index
        key prefix, pkg/sql/colfetcher/cfetcher.go:230); fanout f covers
        clustered child tables (partsupp: exactly 4 contiguous rows per
        part). Joins against such a column need no hash table and no sorted
        index: the matching row index is arithmetic (ops/join.py
        DenseAnalytic). Host-verified once, cached."""
        cached = getattr(self, "_dense_keys", None)
        if cached is not None:
            return cached
        info: dict[str, tuple[int, int]] = {}
        n = self.num_rows
        for name, t in zip(self.schema.names, self.schema.types):
            if t.family not in _INT_KEY_FAMILIES:
                continue
            if name in self.valids or n == 0:
                continue  # NULLs break the bijection
            a = np.asarray(self.columns[name])
            if a.ndim != 1 or a.dtype.kind not in ("i", "u"):
                continue
            lo = int(a[0])
            hi = int(a[-1])
            distinct = hi - lo + 1
            if distinct <= 0 or n % distinct != 0:
                continue
            fanout = n // distinct
            if np.array_equal(
                a, np.repeat(np.arange(lo, lo + distinct, dtype=a.dtype),
                             fanout)
            ):
                info[name] = (lo, fanout)
        self._dense_keys = info
        return info

    def unique_key(self, cols: tuple[str, ...]) -> bool:
        """Is the tuple of columns a key of this table: NULL-free, and no
        two rows equal on all of it? A PROOF over the host rows, never an
        estimate: the binder plans a join's build side as unique on it
        (sql/binder.py _build_unique), and a wrong True drops matches.

        dense_key_info answers where a column is a surrogate key (fanout
        1); ANALYZE statistics only ever say no early (fewer distinct
        values than rows); otherwise one np.unique over the key, packed
        into one word where its ranges fit. Host-verified once per column
        set, cached; the cache dies with _dense_keys wherever the table's
        columns are swapped."""
        cols = tuple(sorted(set(cols)))
        cache = getattr(self, "_unique_keys", None)
        if cache is None:
            cache = self._unique_keys = {}
        got = cache.get(cols)
        if got is None:
            got = cache[cols] = self._verify_unique(cols)
        return got

    def _verify_unique(self, cols: tuple[str, ...]) -> bool:
        n = self.num_rows
        arrs = []
        for name in cols:
            a = np.asarray(self.columns[name])
            if (self.schema.type_of(name).family not in _INT_KEY_FAMILIES
                    or a.ndim != 1 or a.dtype.kind not in ("i", "u")
                    or a.dtype == np.uint64):
                return False  # no exact int64 representation
            if name in self.valids and not np.asarray(
                    self.valids[name]).all():
                return False
            arrs.append(a)
        if not arrs or n <= 1:
            return bool(arrs)
        dense = self.dense_key_info()
        if any(dense.get(name, (0, 0))[1] == 1 for name in cols):
            return True
        st = getattr(self, "table_stats", None)
        if st is not None and st.row_count == n:
            room = 1
            for name in cols:
                cs = st.cols.get(name)
                room *= cs.ndv if cs is not None else n
            if room < n:
                return False  # pigeonhole: some key repeats
        # mixed-radix pack (x - lo) over each column's range into one int64
        # while the product of the ranges fits; rows otherwise
        key, span = np.zeros(n, np.int64), 1
        for a in arrs:
            lo, hi = int(a.min()), int(a.max())
            span *= hi - lo + 1
            if span >= 1 << 63:
                return len(np.unique(np.stack(arrs, axis=1), axis=0)) == n
            key = key * (hi - lo + 1) + (a.astype(np.int64) - lo)
        return len(np.unique(key)) == n

    def device_batch(self, names: tuple[str, ...] | None = None) -> Batch:
        """Device-resident batch of the requested columns. Cached per column,
        so a query never uploads columns it does not scan.

        The host source dicts are snapshotted into the cache when it is
        created: a concurrent re-host that swaps ``columns``/``valids``
        wholesale (matview materialize) leaves an in-flight reader
        uploading from the generation its cache was built over — one
        consistent snapshot, never a torn mix of old and new columns."""
        from .utils import settings

        names = names or self.schema.names
        dev = self._device
        if dev is None:
            dev = self._device = {}
        host = dev.setdefault("__host__", self.columns)
        valids = dev.setdefault("__valids__", self.valids)
        n = len(next(iter(host.values()))) if host else 0
        # pin the padded capacity when the cache is created: tile_size is a
        # live setting, and per-column uploads after a change must match the
        # capacity of already-cached columns
        cap = dev.get("__cap__")
        if cap is None:
            cap = _pad_cap(n, settings.get("sql.distsql.tile_size"))
            dev["__cap__"] = cap
        if "__mask__" not in dev:
            m = np.zeros((cap,), dtype=np.bool_)
            m[:n] = True
            dev["__mask__"] = jnp.asarray(m)
        cols = []
        for cname in names:
            if cname not in dev:
                t = self.schema.type_of(cname)
                one = Schema((cname,), (t,))
                v = {cname: valids[cname]} if cname in valids else None
                b = from_host(
                    one, {cname: np.asarray(host[cname])},
                    valids=v, capacity=cap,
                )
                dev[cname] = b.cols[0]
            cols.append(dev[cname])
        return Batch(cols=tuple(cols), mask=dev["__mask__"])

    def mesh_batch(self, mesh, names: tuple[str, ...] | None = None
                   ) -> Batch:
        """The table as a multi-device node holds it: row-sharded over the
        mesh axis in contiguous primary-key (storage) order, device i the
        rows [i x share, (i + 1) x share) as a live prefix of its tile
        (`mesh_shard_shape`). A column goes from the host to its D devices
        ONCE, when a statement first reads it (device_batch's rule, PR 38),
        and stays with the table, not with a plan. Host sources are
        snapshotted as device_batch snapshots them."""
        import jax

        from .parallel.mesh import AXIS, row_sharding

        names = names or self.schema.names
        held = self._mesh_device
        if held is None or held[0] != mesh:
            held = self._mesh_device = (mesh, {})
        dev = held[1]
        host = dev.setdefault("__host__", self.columns)
        valids = dev.setdefault("__valids__", self.valids)
        n = len(next(iter(host.values()))) if host else 0
        D = mesh.shape[AXIS]
        share, local_cap = mesh_shard_shape(n, D)
        sharding = row_sharding(mesh)

        def put(a, dtype, width=()):
            buf = np.zeros((D, local_cap) + width, dtype=dtype)
            for i in range(D):
                part = a[i * share:(i + 1) * share] if np.ndim(a) else a
                buf[i, :max(0, min(share, n - i * share))] = part
            return jax.device_put(
                buf.reshape((D * local_cap,) + width), sharding)

        if "__mask__" not in dev:
            dev["__mask__"] = put(True, np.bool_)
        cols = []
        for cname in names:
            if cname not in dev:
                t = self.schema.type_of(cname)
                a = np.asarray(host[cname])
                data = (put(a, np.uint8, (t.width,))
                        if t.family is Family.BYTES
                        else put(a.astype(t.dtype), t.dtype))
                from .coldata.batch import Column

                dev[cname] = Column(
                    data=data,
                    valid=(put(np.asarray(valids[cname]), np.bool_)
                           if cname in valids else dev["__mask__"]))
            cols.append(dev[cname])
        return Batch(cols=tuple(cols), mask=dev["__mask__"])

    def mesh_shard_rows(self) -> dict[int, int] | None:
        """{device id: live rows resident there} of the placement above,
        None where no statement has placed the table on a mesh yet."""
        if self._mesh_device is None or "__mask__" not in self._mesh_device[1]:
            return None
        return {sh.device.id: int(np.asarray(sh.data).sum())
                for sh in self._mesh_device[1]["__mask__"].addressable_shards}

    @staticmethod
    def from_strings(
        name: str,
        schema: Schema,
        raw: dict[str, np.ndarray],
        valids: dict[str, np.ndarray] | None = None,
        ordering: tuple[str, ...] = (),
    ) -> "Table":
        """Build a table from raw host columns, dictionary-encoding STRING
        columns (object/str arrays -> int32 codes + Dictionary)."""
        cols: dict[str, np.ndarray] = {}
        dicts: dict[str, Dictionary] = {}
        for cname, t in zip(schema.names, schema.types):
            a = raw[cname]
            if t.family is Family.STRING and a.dtype.kind in ("O", "U", "S"):
                values, codes = np.unique(a.astype(str), return_inverse=True)
                dicts[cname] = Dictionary(values.astype(object))
                cols[cname] = codes.astype(np.int32)
            else:
                cols[cname] = a
        return Table(
            name=name,
            schema=schema,
            columns=cols,
            valids=valids or {},
            dictionaries=dicts,
            ordering=ordering,
        )


class Catalog:
    """Table namespace plus a monotonically increasing schema version.

    Every DDL that can invalidate a compiled plan — CREATE/DROP TABLE,
    CREATE/DROP INDEX, ALTER — bumps ``version``; the prepared-plan cache
    (sql/plancache.py) keys entries on it, so a stale plan (e.g. one built
    against a since-dropped index) can never serve another statement."""

    def __init__(self):
        self.tables: dict[str, Table] = {}
        self.version = 0
        # the devices this catalog's node spans (server/node.py
        # Node(devices=n)); None: one device, every plan runs there
        self.mesh = None

    def bump_version(self) -> int:
        self.version += 1
        return self.version

    def add(self, table: Table) -> Table:
        self.tables[table.name] = table
        self.bump_version()
        return table

    def get(self, name: str) -> Table:
        t = self.tables.get(name)
        if t is None and name.startswith("crdb_internal."):
            # virtual introspection tables materialize on read from the
            # process registries (sql/crdb_internal.py); lazy import — the
            # sql layer imports this module
            from .sql import crdb_internal as _ci

            return _ci.build(self, name)
        if t is None:
            return self.tables[name]  # KeyError with the usual shape
        return t
