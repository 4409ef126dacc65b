"""Pallas MVCC scan-filter — the pebbleMVCCScanner hot loop as a TPU kernel.

Reference: pkg/storage/pebble_mvcc_scanner.go:381 advances one KV at a
time; the jnp version (mvcc.mvcc_scan_filter) is ~8 separate fused passes
over the block (boundary compare, visibility algebra, segmented min scan,
broadcast-back, conflict algebra). This kernel runs the WHOLE filter in
one VMEM-resident pass over the batched-scan window layout:

- rows    = scan windows ([B, CW]: multi_scan_sources packs one scan per
  row, CW a multiple of 128 lanes — no key run crosses a row);
- u64 key words and i64 ts/txn arrive PRE-SPLIT as i32 hi/lo planes
  (Mosaic's native lane type; equality and ordering compose from 32-bit
  compares);
- the per-key "first visible position" is a segmented min-scan along the
  lane axis (log2(CW) shifted selects) followed by a reverse segmented
  fill — all register/VMEM traffic, no HBM round trips between passes.

The jnp filter stays the portable fallback and the correctness oracle
(tests/test_pallas_scan.py runs both, interpret mode on CPU); the real-
chip win is measured by the bench's YCSB phase on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import mvcc as mvcc_mod

_SUBLANES = 8  # window rows per grid step (i32 sublane tile)


def _split_u64(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """u64/i64 [..]-array -> (hi, lo) i32 planes (bit pattern halves)."""
    u = a.astype(jnp.uint64)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32).astype(jnp.int32)
    lo = u.astype(jnp.uint32).astype(jnp.int32)
    return hi, lo


# Every predicate in the kernel is an i32 0/1 plane, combined with & | ^:
# Mosaic for v5e refuses the i1 vector relayouts that `jnp.where` chains and
# scalar-vs-vector boolean broadcasts produce ("Invalid relayout ... for
# vector<8x128xi1>"), so a comparison is widened to i32 the moment it is
# made and no i1 value outlives the expression that made it.


def _b(cond: jax.Array) -> jax.Array:
    return cond.astype(jnp.int32)


def _u32_le(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unsigned a <= b on i32 bit patterns (flip sign bit, signed compare)."""
    bias = jnp.int32(-0x80000000)
    return _b((a ^ bias) <= (b ^ bias))


def _i64_le(ahi, alo, bhi, blo) -> jax.Array:
    """(ahi:alo) <= (bhi:blo) for signed 64-bit split into i32 planes."""
    return _b(ahi < bhi) | (_b(ahi == bhi) & _u32_le(alo, blo))


def _shift_right(x: jax.Array, k: int, fill: int, lane: jax.Array):
    """Shift lanes right by k (element i reads i-k); fill on the left. A
    lane rotate (XLU) plus an iota select — no concatenate of a pad."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.where(lane < k, jnp.int32(fill), pltpu.roll(x, jnp.int32(k), 1))


def _shift_left(x: jax.Array, k: int, fill: int, lane: jax.Array):
    from jax.experimental.pallas import tpu as pltpu

    CW = x.shape[-1]
    return jnp.where(lane >= CW - k, jnp.int32(fill),
                     pltpu.roll(x, jnp.int32(CW - k), 1))


def _scan_filter_kernel(reader, kh0, kl0, kh1, kl1, tshi, tslo, txhi, txlo,
                        tomb, mask, sel_ref, conf_ref):
    """One grid step: [_SUBLANES, CW] windows through the full filter.
    `reader` is the SMEM scalar row (read_ts hi, lo, reader_txn hi, lo)."""
    CW = kh0.shape[-1]
    khi0, klo0 = kh0[:], kl0[:]
    khi1, klo1 = kh1[:], kl1[:]
    ts_hi, ts_lo = tshi[:], tslo[:]
    tx_hi, tx_lo = txhi[:], txlo[:]
    live = _b(mask[:] != 0)
    is_tomb = _b(tomb[:] != 0)
    read_hi, read_lo = reader[0], reader[1]
    rdr_hi, rdr_lo = reader[2], reader[3]
    lane = jax.lax.broadcasted_iota(jnp.int32, khi0.shape, 1)

    # key-run boundaries: adjacent-equality on both 64-bit key words
    same = jnp.ones(khi0.shape, jnp.int32)
    for w in (khi0, klo0, khi1, klo1):
        same = same & _b(w == _shift_right(w, 1, 0, lane))
    prev_dead = _shift_right(live, 1, 0, lane) ^ 1
    boundary = live & (_b(lane == 0) | (same ^ 1) | prev_dead)

    committed = _b(tx_hi == 0) & _b(tx_lo == 0)
    own = _b(tx_hi == rdr_hi) & _b(tx_lo == rdr_lo) & (committed ^ 1)
    ts_le = _i64_le(ts_hi, ts_lo, read_hi, read_lo)
    visible = live & ((committed & ts_le) | own)

    big = 0x7FFFFFFF
    cand = jnp.where(visible != 0, lane, jnp.int32(big))

    # segmented min-scan along lanes: prefix-min restarting at boundaries
    flags = boundary
    vals = cand
    k = 1
    while k < CW:
        sh_f = _shift_right(flags, k, 1, lane)
        sh_v = _shift_right(vals, k, big, lane)
        vals = jnp.where(flags != 0, vals, jnp.minimum(vals, sh_v))
        flags = flags | sh_f
        k *= 2
    # vals now holds, at each lane, the min over its segment PREFIX; the
    # segment TOTAL sits at the segment's last lane. Reverse fill: propagate
    # each segment's end value back over the segment.
    nxt_boundary = _shift_left(boundary, 1, 1, lane)
    nxt_dead = _shift_left(live, 1, 0, lane) ^ 1
    is_end = live & (nxt_boundary | nxt_dead)
    rflags = is_end
    rvals = jnp.where(is_end != 0, vals, jnp.int32(big))
    k = 1
    while k < CW:
        sh_f = _shift_left(rflags, k, 0, lane)
        sh_v = _shift_left(rvals, k, big, lane)
        rvals = jnp.where(rflags != 0, rvals, jnp.minimum(rvals, sh_v))
        rflags = rflags | sh_f
        k *= 2
    first = rvals  # first visible lane of this lane's key run

    newest = visible & _b(lane == first)
    sel_ref[:] = newest & (is_tomb ^ 1)
    conf_ref[:] = (live & (committed ^ 1) & (own ^ 1) & ts_le
                   & _b(lane <= first))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def pallas_scan_filter(block, read_ts, reader_txn, window: int,
                       interpret: bool = False):
    """Drop-in for mvcc.mvcc_scan_filter over the window-packed layout:
    block capacity must be B*window with window % 128 == 0 and key width
    16 bytes (two u64 words). Returns (selected, conflict) flat bools.
    ``interpret`` is for tests only: no production caller passes it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N = block.capacity
    B = N // window
    words = mvcc_mod.key_words(block.key)
    assert words.shape[1] == 2, "pallas filter covers 16-byte keys"

    def plane(x):
        return x.reshape(B, window)

    kh0, kl0 = _split_u64(plane(words[:, 0]))
    kh1, kl1 = _split_u64(plane(words[:, 1]))
    tshi, tslo = _split_u64(plane(block.ts))
    txhi, txlo = _split_u64(plane(block.txn))
    # i32 planes in and out: an i8 plane's native tile is 32 sublanes, not
    # the 8 a grid step covers
    tomb = plane(block.tomb).astype(jnp.int32)
    mask = plane(block.mask).astype(jnp.int32)
    reader = jnp.stack(_split_u64(read_ts.reshape(()))
                       + _split_u64(reader_txn.reshape(())))

    rows = max(1, min(_SUBLANES, B))
    grid = ((B + rows - 1) // rows,)
    # index maps return i32 explicitly: under x64 a Python 0 traces as i64,
    # which Mosaic cannot legalize
    spec = pl.BlockSpec((rows, window), lambda i: (i, jnp.int32(0)))
    sspec = pl.BlockSpec((4,), lambda i: (jnp.int32(0),),
                         memory_space=pltpu.SMEM)
    sel, conf = pl.pallas_call(
        _scan_filter_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, window), jnp.int32),
            jax.ShapeDtypeStruct((B, window), jnp.int32),
        ),
        grid=grid,
        in_specs=[sspec] + [spec] * 10,
        out_specs=(spec, spec),
        interpret=interpret,
    )(reader, kh0, kl0, kh1, kl1, tshi, tslo, txhi, txlo, tomb, mask)
    return sel.reshape(-1) != 0, conf.reshape(-1) != 0
