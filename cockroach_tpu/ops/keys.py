"""Canonical sort-key encoding — bit-packed u64 operands for lax.sort.

The round-2 engine handed lax.sort one operand per null band / NaN band /
value column (a k-key sort cost 2k+1 operands). For a v5e each lax.sort
instantiation above a few thousand rows costs tens of seconds to minutes of
XLA compile time, growing with operand count and rows (PR 22, compiled for a
described v5e: u64 key + i32 perm 0.9 s at 4,096 rows, 37 s at 65,536, 55 s
at 1M; the 6-operand MVCC sort 5 s at 4,096 rows, 75 s at 16,384) — so
sort instantiations and their operand count are the compile budget.

This module packs an ordered key list into the *minimum* number of sort
operands: every key contributes a bit-segment stream
``[null_flag(1), value(bits)]`` and the stream is packed MSB-first into
uint64 words. Comparing the word tuple lexicographically equals comparing the
concatenated bit string, so ANY split of segments across word boundaries
preserves order — values may straddle words freely. Typical TPC-H sorts and
group-bys land in ONE packed word (+ the permutation operand), so the whole
engine reuses a single compiled sort kernel per capacity.

Value encodings (order-preserving within the segment's bit width):
- INT/DECIMAL/DATE/TIMESTAMP/INTERVAL: ``x - lo`` when catalog stats give a
  [lo, hi] range (bits = ceil(log2(hi-lo+1))), else sign-flip at type width.
- STRING: dictionary rank gather (ORDER BY) or raw code (GROUP BY equality),
  bits from the dictionary size.
- BOOL: 1 bit.
- BYTES: big-endian packed 64-bit word lanes (coldata.pack_be_words).
- FLOAT: passes through as a NATIVE float64 sort operand — XLA:TPU's X64
  rewriting does not implement f64<->u32 bitcast-convert (observed on an
  attached v5e, PR 22: UNIMPLEMENTED at compile), so floats ride lax.sort's
  comparator directly, with their NaN band packed as a bit-segment.

DESC inverts value bits within the segment (floats: negation); NULL ordering
follows CockroachDB (NULLs first ascending — tree.Datum ordering).

Reference analog: pkg/sql/colexec/sort.go builds per-type comparators via
execgen; here the "comparator" is the packed key itself.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.types import Family, SQLType

_U64_ONE = np.uint64(1)


@dataclass(frozen=True)
class BitSeg:
    """`bits` wide unsigned values (< 2**bits) in a uint64 lane."""

    bits: int
    arr: jax.Array  # uint64


@dataclass(frozen=True)
class FloatSeg:
    """A native float64 sort operand (comparator-ordered by lax.sort)."""

    arr: jax.Array  # float64


def bits_for_count(n: int) -> int:
    """Bits to distinguish n values (>=1)."""
    return max(1, int(n - 1).bit_length()) if n > 1 else 1


def _u64(x) -> jax.Array:
    return x.astype(jnp.uint64)


def _int_segment(data, valid, t: SQLType, stats, desc: bool) -> BitSeg:
    """Order-preserving unsigned encoding of an integer-represented column."""
    d = data.astype(jnp.int64)
    if stats is not None:
        lo, hi = int(stats[0]), int(stats[1])
        bits = bits_for_count(hi - lo + 1)
        v = _u64(jnp.clip(d, lo, hi) - lo)
    else:
        w = 64
        if t.family is Family.INT:
            w = t.width
        elif t.family is Family.DATE:
            w = 32
        elif t.family is Family.STRING:
            w = 32
        bits = w
        # sign-flip maps the signed range onto [0, 2^w)
        v = _u64(d + (1 << (w - 1))) if w < 64 else (
            _u64(d) ^ (_U64_ONE << np.uint64(63))
        )
    v = jnp.where(valid, v, jnp.uint64(0))
    if desc and bits < 64:
        v = (jnp.uint64((1 << bits) - 1) - v)
    elif desc:
        v = ~v
    return BitSeg(bits, v)


def key_segments(
    data,
    valid,
    t: SQLType,
    desc: bool,
    nulls_first: bool,
    rank_table: np.ndarray | None = None,
    stats: tuple | None = None,
    order_semantics: bool = True,
) -> list:
    """Bit/float segments for one key column, null flag included.

    order_semantics=False (GROUP BY) only needs equality: STRING columns use
    raw dictionary codes instead of requiring a rank table.
    """
    segs: list = []
    # null flag: rows whose flag bit is 0 sort first
    nf = _u64(valid) if nulls_first else _u64(~valid)
    segs.append(BitSeg(1, nf))

    fam = t.family
    if fam is Family.FLOAT:
        d = data.astype(jnp.float64)
        # mask by valid: NULL rows carry garbage data, and a garbage NaN
        # would otherwise split the NULL group's packed key bits
        isnan = valid & jnp.isnan(d)
        # CRDB orders NaN before all other values ascending
        nan_flag = _u64(isnan) if desc else _u64(~isnan)
        segs.append(BitSeg(1, nan_flag))
        d = jnp.where(valid & ~isnan, d, 0.0)
        segs.append(FloatSeg(-d if desc else d))
        return segs
    if fam is Family.BYTES:
        from ..coldata.batch import pack_be_words

        words = pack_be_words(data)
        for i in range(words.shape[1]):
            w = jnp.where(valid, words[:, i], jnp.uint64(0))
            segs.append(BitSeg(64, ~w if desc else w))
        return segs
    if fam is Family.BOOL:
        v = _u64(data) & _U64_ONE
        v = jnp.where(valid, v, jnp.uint64(0))
        segs.append(BitSeg(1, (_U64_ONE - v) if desc else v))
        return segs
    if fam is Family.STRING:
        if order_semantics:
            assert rank_table is not None, \
                "STRING ORDER BY needs a dictionary rank table"
            table = jnp.asarray(rank_table)
            codes = jnp.clip(data, 0, table.shape[0] - 1)
            ranked = table[codes].astype(jnp.int64)
            bits = bits_for_count(int(rank_table.shape[0]) + 1)
            v = jnp.where(valid, _u64(ranked), jnp.uint64(0))
            if desc:
                v = jnp.uint64((1 << bits) - 1) - v
            segs.append(BitSeg(bits, v))
            return segs
        # equality only: raw codes; width from stats or dictionary size
        segs.append(_int_segment(data, valid, t, stats, desc))
        return segs
    # integer-represented families
    segs.append(_int_segment(data, valid, t, stats, desc))
    return segs


def pack_operands(segs: list) -> list[jax.Array]:
    """Pack a segment stream into sort operands: uint64 words (bit segments,
    MSB-first) interleaved with native float64 operands. Lexicographic order
    over the returned operand tuple equals order over the segment stream."""
    ops: list[jax.Array] = []
    cur = None
    pos = 0  # bits used in cur, from the MSB
    for s in segs:
        if isinstance(s, FloatSeg):
            if cur is not None:
                ops.append(cur)
                cur, pos = None, 0
            ops.append(s.arr)
            continue
        b = s.bits
        v = s.arr
        if b < 64:
            v = v & jnp.uint64((1 << b) - 1)
        while b > 0:
            if cur is None:
                cur = jnp.zeros_like(v)
                pos = 0
            avail = 64 - pos
            take = min(b, avail)
            chunk = v >> np.uint64(b - take)
            if take < 64:
                chunk = chunk & jnp.uint64((1 << take) - 1)
            cur = cur | (chunk << np.uint64(avail - take))
            pos += take
            b -= take
            if pos == 64:
                ops.append(cur)
                cur, pos = None, 0
    if cur is not None:
        ops.append(cur)
    return ops
