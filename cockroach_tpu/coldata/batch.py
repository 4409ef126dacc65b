"""Columnar batch format — the coldata.Batch analog, TPU-first.

Reference semantics (pkg/col/coldata/batch.go:24, vec.go:43, nulls.go:35):
a Batch is a vector of typed columns + a selection vector + a length, with a
default capacity of 1024 and max 4096. The TPU redesign keeps the same logical
model but makes every shape static:

- capacity is a *static* tile size (default 4096 == coldata.MaxBatchSize,
  pkg/col/coldata/batch.go:102); jit specializes per capacity.
- the selection vector becomes a boolean liveness ``mask`` over the tile;
  logical length is ``mask.sum()`` (a traced scalar, never a Python int).
- each column carries an Arrow-convention ``valid`` bitmap (True = non-NULL),
  like Vec.Nulls but inverted to match Arrow (pkg/col/colserde ships Arrow on
  the wire already — arrowbatchconverter.go:126).

A Batch is a registered pytree whose leaves are device arrays, so it flows
through jit / shard_map / collectives directly. All schema information
(types, dictionaries) is static plan-side metadata and never enters the pytree.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .types import Family, Schema, zeros_like_type

DEFAULT_CAPACITY = 4096  # coldata.MaxBatchSize (pkg/col/coldata/batch.go:102)


def pack_be_words(data: jax.Array) -> jax.Array:
    """[N, W] uint8 -> [N, ceil(W/8)] big-endian uint64 word lanes.

    Tuple order over the word lanes equals bytewise lexicographic order of
    the rows; widths not a multiple of 8 are zero-padded on the right
    (order-preserving for the zero-padded fixed-width representation).
    The single canonical byte->word packing — storage key encoding and
    BYTES sort keys both ride this."""
    n, w = data.shape
    if w % 8:
        data = jnp.pad(data, ((0, 0), (0, 8 - w % 8)))
        w = data.shape[1]
    groups = data.reshape(n, w // 8, 8).astype(jnp.uint64)
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint64) * jnp.uint64(8)
    return jnp.sum(groups << shifts, axis=-1, dtype=jnp.uint64)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Column:
    """One typed column over a static-capacity tile.

    data  : [cap] canonical-dtype array ([cap, W] uint8 for BYTES)
    valid : [cap] bool, True = non-NULL (Arrow convention)
    """

    data: jax.Array
    valid: jax.Array

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Batch:
    """cols: one Column per schema field; mask: [cap] bool row liveness."""

    cols: tuple[Column, ...]
    mask: jax.Array

    @property
    def capacity(self) -> int:
        return self.mask.shape[0]

    def length(self) -> jax.Array:
        """Logical row count — a traced int32 scalar."""
        return jnp.sum(self.mask, dtype=jnp.int32)

    def col(self, i: int) -> Column:
        return self.cols[i]

    def with_cols(self, cols: tuple[Column, ...]) -> "Batch":
        return Batch(cols=cols, mask=self.mask)

    def with_mask(self, mask: jax.Array) -> "Batch":
        return Batch(cols=self.cols, mask=mask)

    def select(self, idxs: tuple[int, ...]) -> "Batch":
        return Batch(cols=tuple(self.cols[i] for i in idxs), mask=self.mask)


class Dictionary:
    """Host-side string dictionary for a STRING column (codes on device).

    Cross-table string operations are pre-bridged on the host and become
    gathers on device:
      - ``hashes``: code -> 64-bit hash of the underlying bytes, so string
        group-by/join keys hash identically across tables with different
        dictionaries.
      - ``ranks``: code -> rank in sorted byte order, so ORDER BY / range
        predicates on strings become integer comparisons.
    """

    def __init__(self, values: np.ndarray):
        self.values = np.asarray(values, dtype=object)
        order = np.argsort(self.values.astype(str))
        ranks = np.empty(len(self.values), dtype=np.int32)
        ranks[order] = np.arange(len(self.values), dtype=np.int32)
        self.ranks = ranks
        self.hashes = _fnv64_batch(self.values)

    def __len__(self) -> int:
        return len(self.values)

    def reset(self, values: np.ndarray) -> None:
        """Rebuild this dictionary IN PLACE. Operators whose string output
        values exist only at runtime (string_agg) pre-create an empty
        Dictionary at plan-build time — so parent operators hold the
        reference — and fill it here when the values materialize."""
        self.__init__(values)

    def code_of(self, value: str) -> int:
        """Code for a literal value, or -1 if absent (predicate is then false)."""
        hits = np.nonzero(self.values.astype(str) == value)[0]
        return int(hits[0]) if len(hits) else -1

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(codes.shape, dtype=object)
        in_range = (codes >= 0) & (codes < len(self.values))
        out[in_range] = self.values[codes[in_range]]
        out[~in_range] = None
        return out


def _fnv64_batch(values: np.ndarray) -> np.ndarray:
    """FNV-1a 64-bit over utf-8 bytes for an array of strings, vectorized:
    one masked pass per byte position over the whole dictionary.
    Deterministic across processes (unlike Python's hash())."""
    encoded = [str(v).encode("utf-8") for v in values]
    n = len(encoded)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    lens = np.array([len(b) for b in encoded], dtype=np.int64)
    maxlen = max(1, int(lens.max()))
    # Sort by length descending so byte-position i only touches a prefix:
    # total work is O(sum of lengths), immune to one long outlier string.
    order = np.argsort(-lens, kind="stable")
    flat = np.frombuffer(b"".join(encoded[j] for j in order), dtype=np.uint8)
    sorted_lens = lens[order]
    starts = np.concatenate([[0], np.cumsum(sorted_lens[:-1])])
    # rows with len > i form the prefix [0, counts[i])
    asc = sorted_lens[::-1]
    counts = n - np.searchsorted(asc, np.arange(maxlen), side="right")
    h = np.full(n, 0xCBF29CE484222325, dtype=np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for i in range(maxlen):
            c = int(counts[i])
            if c == 0:
                break
            h[:c] = (h[:c] ^ flat[starts[:c] + i]) * prime
    out = np.empty_like(h)
    out[order] = h
    return out


def empty_batch(schema: Schema, capacity: int = DEFAULT_CAPACITY) -> Batch:
    cols = tuple(
        Column(
            data=zeros_like_type(t, capacity),
            valid=jnp.zeros((capacity,), dtype=jnp.bool_),
        )
        for t in schema.types
    )
    return Batch(cols=cols, mask=jnp.zeros((capacity,), dtype=jnp.bool_))


def from_host(
    schema: Schema,
    arrays: dict[str, np.ndarray],
    valids: dict[str, np.ndarray] | None = None,
    capacity: int | None = None,
) -> Batch:
    """Build a Batch from host numpy columns, padding to capacity.

    STRING columns must already be dictionary codes (int32); encoding raw
    string arrays happens at table-load time (see bench/tpch.py).
    """
    valids = valids or {}
    n = len(next(iter(arrays.values())))
    cap = capacity if capacity is not None else max(DEFAULT_CAPACITY, n)
    cols = []
    for name, t in zip(schema.names, schema.types):
        a = np.asarray(arrays[name])
        assert len(a) == n, f"column {name} length {len(a)} != {n}"
        if t.family is Family.BYTES:
            buf = np.zeros((cap, t.width), dtype=np.uint8)
            buf[:n] = a
            data = jnp.asarray(buf)
        else:
            buf = np.zeros((cap,), dtype=t.dtype)
            buf[:n] = a.astype(t.dtype)
            data = jnp.asarray(buf)
        v = np.zeros((cap,), dtype=np.bool_)
        v[:n] = valids.get(name, np.ones(n, dtype=np.bool_))
        cols.append(Column(data=data, valid=jnp.asarray(v)))
    mask = np.zeros((cap,), dtype=np.bool_)
    mask[:n] = True
    return Batch(cols=tuple(cols), mask=jnp.asarray(mask))


def to_host(
    batch: Batch, schema: Schema, dictionaries: dict[int, Dictionary] | None = None
) -> dict[str, np.ndarray]:
    """Materialize live rows to host numpy (the Materializer analog,
    pkg/sql/colexec/materializer.go:30). Decodes STRING via dictionaries
    (column index -> Dictionary) and CHAR(n) bytes to str; NULLs become None
    in object arrays."""
    dictionaries = dictionaries or {}
    mask = np.asarray(batch.mask)
    out: dict[str, np.ndarray] = {}
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        data = np.asarray(batch.cols[i].data)[mask]
        valid = np.asarray(batch.cols[i].valid)[mask]
        if t.family is Family.STRING and i in dictionaries:
            vals = dictionaries[i].decode(data)
            vals[~valid] = None
            out[name] = vals
        elif t.family is Family.BYTES and t.text:
            # CHAR(n): the zero padding carries the length (no NUL in text)
            raw = np.ascontiguousarray(data).view(
                f"S{data.shape[1]}").reshape(len(data))
            vals = np.empty(len(raw), dtype=object)
            vals[:] = [b.decode("utf-8", "replace") for b in raw]
            vals[~valid] = None
            out[name] = vals
        elif t.family is Family.DECIMAL:
            res = data.astype(np.float64) / (10.0**t.scale)
            obj = res.astype(object)
            obj[~valid] = None
            out[name] = obj if not valid.all() else res
        else:
            if valid.all():
                out[name] = data
            else:
                obj = data.astype(object)
                obj[~valid] = None
                out[name] = obj
    return out


def live_index(mask: jax.Array, capacity: int):
    """(idx, n): the positions of ``mask``'s set rows, in order, as the
    first ``n`` of min(len(mask), capacity) int32 slots (the rest hold
    len(mask), which `take_rows` fills), and the TRUE number set, which may
    exceed the slots. The one index every compaction gathers through.

    A select, not a histogram: every dead row takes the fill value and ONE
    single-operand int32 sort brings the live positions to the front, in
    order (they are distinct, so the sort needs no second key and no
    stability: asking for a stable one makes XLA:TPU add an iota operand
    and compile seven times as long). The library's sized `nonzero` gives the
    same array by a scatter-add of one update a row of the mask, which the chip
    runs serially: 50-77 ms a 1,048,576-row tile whatever the cap."""
    cap_in = mask.shape[0]
    pos = jnp.where(mask, jnp.arange(cap_in, dtype=jnp.int32),
                    jnp.int32(cap_in))
    idx = jax.lax.sort(pos, is_stable=False)[:min(cap_in, capacity)]
    return idx, jnp.sum(mask, dtype=jnp.int32)


def pad_rows(x: jax.Array, capacity: int) -> jax.Array:
    """``x`` with zero rows appended up to ``capacity`` leading rows."""
    pad = capacity - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])


def take_rows(col: Column, idx: jax.Array, capacity: int) -> Column:
    """``col``'s rows at ``idx`` (a `live_index`), then zero / invalid rows
    up to ``capacity``; a slot past the live count is zero and invalid."""
    data = jnp.take(col.data, idx, axis=0, mode="fill", fill_value=0)
    valid = jnp.take(col.valid, idx, mode="fill", fill_value=False)
    return Column(data=pad_rows(data, capacity),
                  valid=pad_rows(valid, capacity))


@functools.partial(jax.jit, static_argnames=("capacity",))  # crlint: allow-raw-jit(shared helper: call sites count via dispatch.note)
def compact(batch: Batch, capacity: int | None = None) -> Batch:
    """Pack live rows to the front of a (possibly smaller) tile.

    The reference compacts via selection vectors; here each column GATHERS
    its live rows through one shared index (`live_index`, moved by
    `take_rows`) — O(cap_in) once for the index plus O(cap_out) per column,
    so compacting a sparse 1M-row tile to 1k costs index-scan + a few tiny
    gathers, not a full-width scatter per column (the prior design, measured
    as the dominant cost of selective spool merges). Every column of
    ``batch`` is already materialised at cap_in when this runs: a join's
    emission that knows its output mask before it has gathered the build
    side compacts FIRST, through the same two helpers
    (ops/join.py `emit_unique_compact`)."""
    cap_out = capacity or batch.capacity
    idx, n = live_index(batch.mask, cap_out)
    new_mask = jnp.arange(cap_out, dtype=jnp.int32) < n
    return Batch(cols=tuple(take_rows(c, idx, cap_out) for c in batch.cols),
                 mask=new_mask)


def concat(batches: list[Batch], capacity: int) -> Batch:
    """Concatenate batches' LIVE rows into one compacted tile of `capacity`
    (must fit; caller checks). Each source batch gathers its live rows once
    (per-batch `live_index`) and scatters them at its running offset —
    never materializing the full-capacity concatenation the previous design
    paid for (O(sum cap_in) per column).

    This is the route for tiles whose live rows may lie anywhere: every
    spool but one compacts through it (the aggregate's merge, the sort,
    window, merge-join and Grace spools, the fan-in syncs, a join build fed
    by a filter, a scan or a join). A join build whose producer proves its
    tiles live-prefix (`Operator.emits_live_prefix`) places them through
    `concat_prefix`: same result, no index."""
    if len(batches) == 1:
        return compact(batches[0], capacity)
    ncols = len(batches[0].cols)
    idxs, lives = zip(*(live_index(b.mask, capacity) for b in batches))
    offs = []
    acc = jnp.int32(0)
    for lv in lives:
        offs.append(acc)
        acc = acc + lv
    total = acc

    cols = []
    for i in range(ncols):
        first = batches[0].cols[i].data
        if first.ndim == 2:
            data = jnp.zeros((capacity, first.shape[1]), first.dtype)
        else:
            data = jnp.zeros((capacity,), first.dtype)
        valid = jnp.zeros((capacity,), jnp.bool_)
        for b, idx, off, lv in zip(batches, idxs, offs, lives):
            rows = jnp.take(b.cols[i].data, idx, axis=0, mode="fill",
                            fill_value=0)
            vrows = jnp.take(b.cols[i].valid, idx, mode="fill",
                             fill_value=False)
            pos = jnp.arange(idx.shape[0], dtype=jnp.int32)
            dest = jnp.where(pos < lv, off + pos, capacity)
            data = data.at[dest].set(rows, mode="drop")
            valid = valid.at[dest].set(vrows, mode="drop")
        cols.append(Column(data=data, valid=valid))
    mask = jnp.arange(capacity, dtype=jnp.int32) < total
    return Batch(cols=tuple(cols), mask=mask)


def concat_prefix(batches: list[Batch], capacity: int) -> Batch:
    """`concat` for tiles whose live rows are a dense PREFIX
    (``mask[i] == (i < n)``, the producer's guarantee: the caller holds an
    `Operator.emits_live_prefix` proof, nothing here checks it): the same
    compacted tile of ``capacity``, by placement, not by index. Tile k's
    rows belong at ``[off_k, off_k + n_k)``, ``n_k = sum(mask_k)`` and
    ``off_k`` the running sum (both in-kernel: no host sync), so each
    column is written whole at ``off_k``, in tile order, and tile k + 1
    overwrites tile k's dead tail: a block copy a tile where `concat` runs an
    index, a gather and a scatter.

    XLA clamps a `dynamic_update_slice` whose end passes the buffer's (the
    tile would shift DOWN over live rows), so the buffer has the widest
    tile's rows of slack and is cut to ``capacity`` at the end. Rows past
    the total then hold some tile's dead tail: they are zeroed and their
    valid bits cleared, as `concat` leaves them."""
    slack = max(b.capacity for b in batches)
    total = jnp.int32(0)
    offs = []
    for b in batches:
        offs.append(total)
        total = total + jnp.sum(b.mask, dtype=jnp.int32)
    mask = jnp.arange(capacity, dtype=jnp.int32) < total

    def place(parts):
        if len(parts) == 1:  # a slice or a pad: nothing to offset
            return pad_rows(parts[0], capacity)[:capacity]
        buf = jnp.zeros((capacity + slack,) + parts[0].shape[1:],
                        parts[0].dtype)
        for x, off in zip(parts, offs):
            buf = jax.lax.dynamic_update_slice_in_dim(buf, x, off, axis=0)
        return buf[:capacity]

    cols = []
    for i in range(len(batches[0].cols)):
        data = place([b.cols[i].data for b in batches])
        valid = place([b.cols[i].valid for b in batches])
        live = mask.reshape(mask.shape + (1,) * (data.ndim - 1))
        cols.append(Column(data=jnp.where(live, data,
                                          jnp.zeros((), data.dtype)),
                           valid=valid & mask))
    return Batch(cols=tuple(cols), mask=mask)
