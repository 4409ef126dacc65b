"""Sort / top-k kernels — the colexec Sorter analog.

Reference: pkg/sql/colexec/sort.go:26 (NewSorter) spools all input then runs a
pdqsort per type (pdqsort.eg.go); sorttopk.go keeps a heap of K. On TPU both
become XLA's native sort over order-preserving uint64 key transforms:

- every key column maps to a uint64 whose unsigned order equals SQL order
  (ints: sign-flip bitcast; floats: IEEE total-order trick; strings: host-
  prepared dictionary rank gather — coldata.Dictionary.ranks);
- DESC inverts bits; NULL ordering is a leading bool key (CockroachDB sorts
  NULLs first ascending — tree.Datum ordering);
- dead rows sort last via a leading ~mask key, so output is also compacted.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, Column
from ..coldata.types import Family, Schema, SQLType


@dataclass(frozen=True)
class SortKey:
    col: int
    desc: bool = False
    # CockroachDB semantics: NULLs order first ascending, last descending.
    nulls_first: bool | None = None

    def effective_nulls_first(self) -> bool:
        return (not self.desc) if self.nulls_first is None else self.nulls_first


def order_keys(
    data: jax.Array,
    valid: jax.Array,
    k: "SortKey",
    t: SQLType,
    rank_table: np.ndarray | None = None,
) -> list[jax.Array]:
    """Sort-key operands whose ascending order equals SQL order for this key.

    TPU note: XLA:TPU's X64 rewriting cannot bitcast f64<->u64 (observed on
    an attached v5e, PR 22), so floats sort as
    native float keys (with an explicit NaN flag — CockroachDB orders NaN
    before all other values) instead of the classic IEEE bit-trick. Integer
    families use sign-flipped uint64; DESC inverts bits / negates.
    """
    nf = k.effective_nulls_first()
    null_key = valid if nf else ~valid  # False sorts first
    if t.family is Family.STRING:
        assert rank_table is not None, "STRING sort needs a dictionary rank table"
        table = jnp.asarray(rank_table)
        codes = jnp.clip(data, 0, table.shape[0] - 1)
        u = table[codes].astype(jnp.int32)
        return [null_key, -u if k.desc else u]
    if t.family is Family.FLOAT:
        d = data.astype(jnp.float64)
        isnan = jnp.isnan(d)
        nan_key = isnan if k.desc else ~isnan  # NaN smallest in SQL order
        d = jnp.where(isnan, 0.0, d)
        return [null_key, nan_key, -d if k.desc else d]
    if t.family is Family.BOOL:
        key = data
        return [null_key, key != k.desc]
    if t.family is Family.BYTES:
        # lexicographic byte order == unsigned order of big-endian-packed
        # uint64 words (zero padding ranks shorter strings first, matching
        # the engine's zero-padded fixed-width representation)
        from ..coldata.batch import pack_be_words

        words = pack_be_words(data)
        return [null_key] + [
            ~words[:, i] if k.desc else words[:, i]
            for i in range(words.shape[1])
        ]
    u = data.astype(jnp.int64).astype(jnp.uint64) ^ np.uint64(0x8000000000000000)
    if k.desc:
        u = ~u
    return [null_key, u]


def pack_sort_operands(
    batch: Batch,
    schema: Schema,
    keys: tuple[SortKey, ...],
    rank_tables: dict[int, np.ndarray] | None = None,
    col_stats: dict[int, tuple] | None = None,
    include_mask: bool = True,
) -> list[jax.Array]:
    """Bit-packed sort operands for the key list (see ops/keys.py): dead rows
    last (leading ~mask bit), then per-key [null flag, value] segments packed
    into as few uint64 words as possible; float keys ride as native f64."""
    from . import keys as key_ops

    rank_tables = rank_tables or {}
    col_stats = col_stats or {}
    segs: list = []
    if include_mask:
        segs.append(key_ops.BitSeg(1, (~batch.mask).astype(jnp.uint64)))
    for k in keys:
        c = batch.cols[k.col]
        t = schema.types[k.col]
        segs.extend(key_ops.key_segments(
            c.data, c.valid, t, k.desc, k.effective_nulls_first(),
            rank_table=rank_tables.get(k.col),
            stats=col_stats.get(k.col),
        ))
    return key_ops.pack_operands(segs)


def sort_perm(  # crlint: allow-mem-accounting(traced kernel: permutation lanes shaped like the charged input tile)
    batch: Batch,
    schema: Schema,
    keys: tuple[SortKey, ...],
    rank_tables: dict[int, np.ndarray] | None = None,
    col_stats: dict[int, tuple] | None = None,
) -> jax.Array:
    """Stable permutation ordering live rows by keys, dead rows last.

    Stability comes from the row index participating as the FINAL sort key
    (equal-key rows order by original position) — measurably cheaper to
    compile on TPU than is_stable=True with the index as payload."""
    cap = batch.capacity
    operands = pack_sort_operands(batch, schema, keys, rank_tables, col_stats)
    perm = jnp.arange(cap, dtype=jnp.int32)
    res = jax.lax.sort(operands + [perm], num_keys=len(operands) + 1)
    return res[-1]


def apply_perm(batch: Batch, perm: jax.Array) -> Batch:
    cols = tuple(
        Column(data=c.data[perm], valid=c.valid[perm]) for c in batch.cols
    )
    return Batch(cols=cols, mask=batch.mask[perm])


def sort_batch(
    batch: Batch,
    schema: Schema,
    keys: tuple[SortKey, ...],
    rank_tables: dict[int, np.ndarray] | None = None,
    col_stats: dict[int, tuple] | None = None,
) -> Batch:
    return apply_perm(
        batch, sort_perm(batch, schema, keys, rank_tables, col_stats)
    )


def topk_batch(  # crlint: allow-mem-accounting(traced kernel: k-selection transients shaped like the charged input tile)
    batch: Batch,
    schema: Schema,
    keys: tuple[SortKey, ...],
    k: int,
    capacity: int,
    rank_tables: dict[int, np.ndarray] | None = None,
    col_stats: dict[int, tuple] | None = None,
) -> Batch:
    """Stable k-selection: the first ``k`` live rows of the stable sort
    order, re-materialized at static ``capacity`` (>= k). Equal keys at
    the k boundary resolve by original row position — exactly the rows a
    full sort + LIMIT k keeps — so folding per-tile selections through
    concat (earlier tiles first) stays bit-identical with the full-sort
    oracle. Output is sorted and compacted (dead rows masked off)."""
    perm = sort_perm(batch, schema, keys, rank_tables, col_stats)
    idx = jnp.arange(capacity, dtype=jnp.int32)
    take = perm[jnp.minimum(idx, batch.capacity - 1)]
    out = apply_perm(batch, take)
    keep = out.mask & (idx < batch.capacity) & (idx < k)
    return out.with_mask(keep)


def limit_mask(batch: Batch, limit: int, offset: int = 0) -> Batch:
    """LIMIT/OFFSET over live rows in tile order (apply after sort_batch,
    whose output is compacted). Reference: colexec limit/offset ops."""
    pos = jnp.cumsum(batch.mask.astype(jnp.int32)) - 1  # rank among live rows
    keep = batch.mask & (pos >= offset) & (pos < offset + limit)
    return batch.with_mask(keep)
