"""MVCC kernels — the pebbleMVCCScanner hot loop, TPU-first.

Reference semantics (pkg/storage/pebble_mvcc_scanner.go:381): iterate entries
sorted by (key asc, ts desc); per key pick the newest version with ts <=
read_ts; skip deletion tombstones; an intent (provisional value of an
uncommitted txn) at ts <= read_ts from another txn is a WriteIntentError,
while the reader's own intent is visible regardless of its timestamp.

The reference walks this one KV at a time per range scan. Here the whole
sorted block is processed in one vectorized pass:

- key-run boundaries come from comparing adjacent key word lanes;
- "newest visible per key" is a segmented argmin over row position (rows are
  already ts-desc within a key), via ``jax.ops.segment_min``;
- intents, tombstones and bounds are boolean algebra over the block.

Compaction (pebble's merging iterator + GC, the "LSM compaction k-way merge"
north-star kernel) is the same machinery: sort the concatenation of runs by
(key, ts desc) with XLA's lane-parallel sort, then a segmented pass drops
versions shadowed below the GC threshold.
"""

from __future__ import annotations

import collections
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import segscan
from .keys import key_words, words_cmp_eq, words_in_range

_BIG = np.int32(2**31 - 1)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class KVBlock:
    """Columnar MVCC entries over a static-capacity tile.

    key   : [cap, KW] uint8 zero-padded key bytes
    ts    : [cap] int64 version timestamp (HLC collapsed to one int64)
    seq   : [cap] int64 write sequence; breaks ties among same-(key, ts)
            writes, newest-sequence-wins (the reference's intent sequence
            numbers, enginepb.TxnSeq)
    txn   : [cap] int64 intent owner txn id; 0 = committed
    tomb  : [cap] bool deletion tombstone
    value : [cap, VW] uint8 fixed-width value payload
    vlen  : [cap] int32 logical value length
    mask  : [cap] bool row liveness
    """

    key: jax.Array
    ts: jax.Array
    seq: jax.Array
    txn: jax.Array
    tomb: jax.Array
    value: jax.Array
    vlen: jax.Array
    mask: jax.Array

    @property
    def capacity(self) -> int:
        return self.mask.shape[0]


def empty_block(cap: int, key_width: int, val_width: int) -> KVBlock:
    return KVBlock(
        key=jnp.zeros((cap, key_width), jnp.uint8),
        ts=jnp.zeros((cap,), jnp.int64),
        seq=jnp.zeros((cap,), jnp.int64),
        txn=jnp.zeros((cap,), jnp.int64),
        tomb=jnp.zeros((cap,), jnp.bool_),
        value=jnp.zeros((cap, val_width), jnp.uint8),
        vlen=jnp.zeros((cap,), jnp.int32),
        mask=jnp.zeros((cap,), jnp.bool_),
    )


def block_from_host(
    keys: np.ndarray,
    ts: np.ndarray,
    txn: np.ndarray,
    tomb: np.ndarray,
    value: np.ndarray,
    vlen: np.ndarray,
    cap: int | None = None,
    seq: np.ndarray | None = None,
) -> KVBlock:
    """Pad on the HOST, then one upload per field. (The previous device
    `.at[:n].set` scatters re-specialized per live count n — every
    memtable rebuild after an insert paid ~50ms x 8 fields of XLA compile
    on the scan path.)"""
    n = len(ts)
    cap = cap or max(1, n)
    if seq is None:
        seq = np.zeros(n, dtype=np.int64)

    def pad(a: np.ndarray, dtype) -> jnp.ndarray:
        a = np.asarray(a, dtype=dtype)
        out = np.zeros((cap,) + a.shape[1:], dtype=dtype)
        out[:n] = a
        return jnp.asarray(out)

    mask = np.zeros(cap, np.bool_)
    mask[:n] = True
    return KVBlock(
        key=pad(keys, np.uint8),
        ts=pad(ts, np.int64),
        seq=pad(seq, np.int64),
        txn=pad(txn, np.int64),
        tomb=pad(tomb, np.bool_),
        value=pad(value, np.uint8),
        vlen=pad(vlen, np.int32),
        mask=jnp.asarray(mask),
    )


# ---------------------------------------------------------------------------
# Sorting / merging


def _mvcc_sort_operands(block: KVBlock) -> list[jax.Array]:
    """THE canonical MVCC sort key as lax.sort operands: dead rows last,
    key bytes ascending, ts DESC, seq DESC (sign bit flipped then inverted
    for the descending u64 encodings). sort_block and the window merge
    must agree exactly — the filter's newest-visible logic assumes it."""
    words = key_words(block.key)
    operands = [~block.mask]
    operands += [words[:, i] for i in range(words.shape[1])]
    operands.append(~(block.ts.astype(jnp.uint64) ^ np.uint64(1 << 63)))
    operands.append(~(block.seq.astype(jnp.uint64) ^ np.uint64(1 << 63)))
    return operands


@jax.jit  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def sort_block(block: KVBlock) -> KVBlock:
    """Sort by (key asc, ts desc), dead rows last — the SST/memtable order
    (pkg/storage/mvcc_key.go EncodeMVCCKey ordering)."""
    cap = block.capacity
    operands = _mvcc_sort_operands(block)
    perm = jnp.arange(cap, dtype=jnp.int32)
    res = jax.lax.sort(operands + [perm], num_keys=len(operands), is_stable=True)
    p = res[-1]
    return jax.tree_util.tree_map(lambda x: x[p], block)


@functools.partial(jax.jit, static_argnames=("cap",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def merge_blocks(blocks: tuple[KVBlock, ...], cap: int) -> KVBlock:
    """K-way merge of sorted runs into one sorted tile of `cap` rows.

    The reference merges with a loser-tree of iterators (pebble
    mergingIter); on TPU the idiomatic merge of K sorted runs is a single
    lane-parallel sort of the concatenation — XLA lowers it onto the VPU,
    and the pre-sortedness costs nothing.
    """
    big = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *blocks
    )
    total = big.capacity
    if total < cap:
        pad = empty_block(cap - total, big.key.shape[1], big.value.shape[1])
        big = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), big, pad
        )
    return sort_block(big)


# -- the same order, planned on the host ------------------------------------
# `lax.sort` over the canonical operands is 3 + key_width / 8 operands wide:
# for the node store's 64-byte keys the chip's compiler takes 19 s at 4,096
# rows and 650 s at 16,384 (a described v5e, PR 41), once a shape, under the
# store's mutex. The engine's WRITE path (compaction, a run rewritten by an
# intent resolution, a cleared span, an imported snapshot) therefore plans
# the order on the host (one `np.lexsort` over the same operands, read back
# from the device: 100 B a row) and moves the rows with one device gather,
# which compiles in a second at any size. Reads keep `merge_blocks` for
# their candidate tiles of a few hundred rows.


def host_order(block: KVBlock) -> tuple[np.ndarray, int]:
    """(permutation into canonical MVCC order, live rows): what
    `_mvcc_sort_operands` + a stable `lax.sort` give, computed by
    `np.lexsort` over host copies of mask, key, ts and seq."""
    mask = np.asarray(block.mask)
    words = np.ascontiguousarray(np.asarray(block.key)).view(">u8")
    flip = np.uint64(1 << 63)
    ts = ~(np.asarray(block.ts).astype(np.uint64) ^ flip)
    seq = ~(np.asarray(block.seq).astype(np.uint64) ^ flip)
    # lexsort's LAST key is the primary one
    keys = [seq, ts] + [words[:, i] for i in range(words.shape[1] - 1, -1,
                                                   -1)] + [~mask]
    return np.lexsort(keys), int(mask.sum())


@functools.partial(jax.jit, static_argnames=("cap",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _take_rows(block: KVBlock, perm: jax.Array, n_live: jax.Array,
               cap: int) -> KVBlock:
    """Rows `perm[:cap]` of `block`; the first `n_live` are live, the rest
    dead and zeroed (a dead row holds zero key bytes, as everywhere)."""
    live = jnp.arange(cap, dtype=jnp.int32) < n_live

    def take(x):
        y = x[perm]
        m = live.reshape((cap,) + (1,) * (y.ndim - 1))
        return jnp.where(m, y, jnp.zeros((), y.dtype))

    out = jax.tree_util.tree_map(take, block)
    return KVBlock(key=out.key, ts=out.ts, seq=out.seq, txn=out.txn,
                   tomb=out.tomb, value=out.value, vlen=out.vlen, mask=live)


def sort_block_host(block: KVBlock, cap=None) -> KVBlock:
    """`sort_block` with its order planned on the host: canonical MVCC
    order, dead rows last, at capacity `cap`: an int, a function of the
    live count (the engine's `_pad`: the block shrinks to its rows), or
    None for the block's own. A `cap` under the live count is refused."""
    perm, n_live = host_order(block)
    cap = (block.capacity if cap is None
           else cap(n_live) if callable(cap) else cap)
    if n_live > cap:
        raise ValueError(f"{n_live} live rows do not fit capacity {cap}")
    if cap > len(perm):
        perm = np.concatenate([perm, np.zeros(cap - len(perm), perm.dtype)])
    return _take_rows(block, jnp.asarray(perm[:cap], jnp.int32),
                      jnp.int32(n_live), cap)


def merge_blocks_host(blocks: tuple[KVBlock, ...], cap: int) -> KVBlock:
    """`merge_blocks` with its order planned on the host."""
    big = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *blocks)
    return sort_block_host(big, cap)


# ---------------------------------------------------------------------------
# The scan-filter kernel


def _key_boundaries(block: KVBlock, window: int | None = None) -> jax.Array:
    """True on the first row of each key run (block sorted by key). With
    `window`, every multiple-of-window position also starts a segment —
    the multi-scan kernel packs independent scan windows side by side and
    must not let a key run bleed across a window edge."""
    words = key_words(block.key)
    same = words_cmp_eq(words[1:], words[:-1]) & block.mask[1:] & block.mask[:-1]
    boundary = jnp.concatenate([jnp.ones((1,), jnp.bool_), ~same])
    if window:
        pos = jnp.arange(block.capacity, dtype=jnp.int32)
        boundary = boundary | (pos % window == 0)
    return boundary


def _seg_bcast(op, vals, boundary, live):
    """Per-segment total of `vals` under `op`, broadcast to every row of the
    segment. Backend-adaptive (ops/segscan.py): segmented scans on TPU
    (scatter serializes on the VPU, ~100ms per 1M-row op), segment_* on CPU
    (where scatter is a cheap serial loop and 20 scan passes are not)."""
    segop = jax.ops.segment_min if op is jnp.minimum else jax.ops.segment_max
    return segscan.seg_bcast(op, segop, vals, boundary, live)


@functools.partial(jax.jit, static_argnames=("window",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def mvcc_scan_filter(
    block: KVBlock,
    read_ts: jax.Array,
    reader_txn: jax.Array,
    start_words: jax.Array | None = None,
    end_words: jax.Array | None = None,
    window: int | None = None,
):
    """Newest-visible-version selection over a sorted block.

    Returns (selected, conflict):
      selected : [cap] bool — rows that the scan returns (newest version per
                 key with ts <= read_ts, own intents always visible, deletion
                 tombstones dropped, bounds applied)
      conflict : [cap] bool — intents of *other* txns at ts <= read_ts that
                 shadow the read (WriteIntentError rows; pebble_mvcc_scanner
                 accumulates these the same way)

    `window` (static) segments the block into independent scan windows
    (scan_batch packs one scan per window).
    """
    cap = block.capacity
    words = key_words(block.key)
    in_range = block.mask & words_in_range(words, start_words, end_words)
    boundary = _key_boundaries(block, window)

    own = block.txn == reader_txn
    committed = block.txn == 0
    # visibility: committed at or before read_ts, or the reader's own intent
    # (CRDB: a txn always reads its own provisional values)
    visible = in_range & ((committed & (block.ts <= read_ts)) | (own & (block.txn != 0)))

    pos = jnp.arange(cap, dtype=jnp.int32)
    cand_pos = jnp.where(visible, pos, _BIG)
    first = _seg_bcast(jnp.minimum, cand_pos, boundary, block.mask)
    newest = visible & (pos == first)

    # an *other-txn* intent visible to this read shadows any selected version
    # at-or-below it — that's a conflict, not a silent skip
    conflict = (
        in_range
        & (block.txn != 0)
        & ~own
        & (block.ts <= read_ts)
    )
    # conflicts only matter if they are the newest candidate or newer than it:
    # since rows are ts-desc, an intent above `first` within the segment
    # conflicts; one below `first` is shadowed and irrelevant.
    conflict = conflict & (pos <= first)

    selected = newest & ~block.tomb
    return selected, conflict


@functools.partial(jax.jit, static_argnames=("bottom",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def mvcc_gc_filter(block: KVBlock, gc_ts: jax.Array, bottom: bool):
    """Compaction GC (pebble compaction + MVCC GC semantics, pkg/storage
    mvcc.go GC): keep rows that are

    - intents (never GC'd by compaction),
    - versions with ts > gc_ts (still readable by someone), or
    - the newest version at-or-below gc_ts per key — unless `bottom` and it
      is a tombstone with nothing below it (tombstone elision at the last
      level).
    """
    cap = block.capacity
    boundary = _key_boundaries(block)
    pos = jnp.arange(cap, dtype=jnp.int32)

    old = block.mask & (block.txn == 0) & (block.ts <= gc_ts)
    cand_pos = jnp.where(old, pos, _BIG)
    first_old = _seg_bcast(jnp.minimum, cand_pos, boundary, block.mask)
    newest_old = old & (pos == first_old)

    keep = block.mask & (
        (block.txn != 0) | (block.ts > gc_ts) | newest_old
    )
    if bottom:
        # elide a kept tombstone when it is the oldest surviving row of its
        # key (nothing below it to shadow)
        keep_pos = jnp.where(keep, pos, -1)
        last_keep = _seg_bcast(jnp.maximum, keep_pos, boundary, block.mask)
        elide = keep & block.tomb & newest_old & (pos == last_keep)
        keep = keep & ~elide
    return keep


# ---------------------------------------------------------------------------
# Batched multi-scan (the kv Streamer analog)


def _lex_lt(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic a < b over trailing word lanes ([..., W] uint64)."""
    lt = jnp.zeros(a.shape[:-1], jnp.bool_)
    gt = jnp.zeros(a.shape[:-1], jnp.bool_)
    for w in range(a.shape[-1]):
        aw, bw = a[..., w], b[..., w]
        undecided = ~lt & ~gt
        lt = lt | (undecided & (aw < bw))
        gt = gt | (undecided & (aw > bw))
    return lt


def seek_positions(
    view_words: jax.Array, query_words: jax.Array, n_live: jax.Array
) -> jax.Array:
    """First LIVE row position with key >= query, per query — the iterator
    SeekGE over the sorted view, as an unrolled branchless binary search
    (the same shape as ops/join.bsearch, lifted to multi-word keys).

    Dead rows sort past the live prefix but hold zero key bytes (they'd
    compare below every real key), so the search is clamped to n_live."""
    n = view_words.shape[0]
    bits = max(1, int(n).bit_length())
    pos = jnp.zeros(query_words.shape[:-1], jnp.int32)
    for sb in range(bits - 1, -1, -1):
        cand = pos + (1 << sb)
        rows = view_words[jnp.clip(cand - 1, 0, n - 1)]
        ok = (cand <= n_live) & _lex_lt(rows, query_words)
        pos = jnp.where(ok, cand, pos)
    return pos


@functools.partial(jax.jit, static_argnames=("window",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _gather_stage(view: KVBlock, lo, n_live, window: int):
    n = view.capacity
    c = jnp.arange(window, dtype=jnp.int32)
    idx = lo[:, None] + c[None, :]  # [B, window]
    valid = idx < n_live
    idxc = jnp.clip(idx, 0, n - 1).reshape(-1)
    return KVBlock(
        key=view.key[idxc],
        ts=view.ts[idxc],
        seq=view.seq[idxc],
        txn=view.txn[idxc],
        tomb=view.tomb[idxc],
        value=view.value[idxc],
        vlen=view.vlen[idxc],
        mask=view.mask[idxc] & valid.reshape(-1),
    )


@functools.partial(jax.jit, static_argnames=("window",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _window_merge_stage(wins: tuple[KVBlock, ...], cuts, truncs, window: int):
    """Merge S per-source windows per scan: concatenate along the window
    axis, then ONE small sort keyed (scan id, key asc, ts desc, seq desc,
    dead-last) — the lazy merging-iterator step, paying O(B*S*window)
    per batch instead of re-sorting the whole store.

    cuts: [S, B, W] per-source truncation cut keys; truncs: [S, B] bool.
    Returns (flat merged KVBlock of capacity B*(S*window), complete flags,
    truncated-per-scan)."""
    S = len(wins)
    B = truncs.shape[1]
    CW = S * window

    def cat(field):
        parts = [getattr(w, field).reshape((B, window) +
                                           getattr(w, field).shape[1:])
                 for w in wins]
        merged = jnp.concatenate(parts, axis=1)
        return merged.reshape((B * CW,) + merged.shape[2:])

    blk = KVBlock(**{f: cat(f) for f in (
        "key", "ts", "seq", "txn", "tomb", "value", "vlen", "mask")})
    wid = jnp.repeat(jnp.arange(B, dtype=jnp.int32), CW)
    # scan id leads; within a window the CANONICAL MVCC order applies
    operands = [wid] + _mvcc_sort_operands(blk)
    perm = jnp.arange(B * CW, dtype=jnp.int32)
    res = jax.lax.sort(operands + [perm], num_keys=len(operands),
                       is_stable=True)
    p = res[-1]
    blk = jax.tree_util.tree_map(lambda x: x[p], blk)

    # completeness: a scan is truncated if ANY source cut it; rows at or
    # past the smallest cut key among truncated sources are withheld
    truncated = truncs.any(axis=0)  # [B]
    _MAXW = jnp.full(cuts.shape[1:], ~jnp.uint64(0))
    cut = _MAXW
    for s in range(S):
        s_cut = jnp.where(truncs[s][:, None], cuts[s], _MAXW)
        take = _lex_lt(s_cut, cut)
        cut = jnp.where(take[:, None], s_cut, cut)
    wwords = key_words(blk.key).reshape(B, CW, -1)
    below = _lex_lt(wwords, cut[:, None, :])
    complete = (~truncated[:, None]) | below
    return blk, complete.reshape(-1), truncated


@functools.partial(jax.jit, static_argnames=("window",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _seek_cut_stage(src: KVBlock, starts_words, window: int):
    """Seek + cut-key extraction for ONE source. Deliberately jitted
    SEPARATELY from the window gather: fusing the unrolled binary search
    with the window gathers sends XLA:CPU's fusion planner into
    minutes-long compiles (the same pathology the multi_scan split fixed);
    apart they compile in ~1s each, and no host sync separates them."""
    vwords = key_words(src.key)
    n_live = jnp.sum(src.mask, dtype=jnp.int32)
    lo = seek_positions(vwords, starts_words, n_live)
    cut_idx = jnp.clip(lo + window - 1, 0, src.capacity - 1)
    return lo, n_live, vwords[cut_idx], (lo + window) < n_live


def _source_stage(src: KVBlock, starts_words, window: int):
    lo, n_live, cut, trunc = _seek_cut_stage(src, starts_words, window)
    return _gather_stage(src, lo, n_live, window), cut, trunc


def pallas_wanted(mode: str) -> bool:
    """Does this value of `storage.pallas_filter` / `storage.pallas_merge`
    select the kernel here? auto: TPU only — the kernels' tiling/shift
    shapes target Mosaic and have never been exercised through the Triton
    (GPU) lowering. 'on' selects it on any backend, compiled for that
    backend: where the compiler refuses, its error surfaces."""
    return mode == "on" or (mode == "auto"
                            and jax.default_backend() == "tpu")


# which implementation served each storage-plane kernel call, and how often
# ("scan_filter.pallas", "scan_filter.jnp", "merge.pallas", "merge.jnp");
# chip_smoke.py prints the deltas around its kv phase
KERNEL_CALLS: collections.Counter = collections.Counter()

# tests only: run the Pallas filter in interpret mode (the engine's
# _pallas_merge_interpret is the merge kernel's equal). The program never
# infers interpret mode from the backend's name.
PALLAS_FILTER_INTERPRET = False


def _filter_stage_flat(win: KVBlock, read_ts, reader_txn, window: int):
    """Window filter, Pallas-fused when eligible (storage.pallas_filter):
    the kernel runs the whole pebbleMVCCScanner decision in one
    VMEM-resident pass instead of ~8 separate fused HBM passes."""
    from ..utils import settings

    if (pallas_wanted(settings.get("storage.pallas_filter"))
            and win.key.shape[1] == 16 and window % 128 == 0
            and win.capacity % window == 0):
        from .pallas_scan import pallas_scan_filter

        KERNEL_CALLS["scan_filter.pallas"] += 1
        return pallas_scan_filter(
            win, jnp.asarray(read_ts, jnp.int64),
            jnp.asarray(reader_txn, jnp.int64), window=window,
            interpret=PALLAS_FILTER_INTERPRET,
        )
    KERNEL_CALLS["scan_filter.jnp"] += 1
    return _filter_stage_jnp(win, read_ts, reader_txn, window)


@functools.partial(jax.jit, static_argnames=("window",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _filter_stage_jnp(win: KVBlock, read_ts, reader_txn, window: int):
    return mvcc_scan_filter(win, read_ts, reader_txn, window=window)


@functools.partial(jax.jit, static_argnames=("B", "max_keys"))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _emit_stage(blk: KVBlock, flags, B: int, max_keys: int):
    """Compact each window's selected rows to its first max_keys slots ON
    DEVICE, so the host receives B*max_keys rows instead of the full
    windows. One stable sort by
    (window, ~selected, position) puts every window's hits at the front
    of its slice."""
    N = blk.capacity
    CW = N // B
    wid = jnp.repeat(jnp.arange(B, dtype=jnp.int32), CW)
    pos = jnp.arange(N, dtype=jnp.int32)
    _, order = jax.lax.sort(
        [(wid.astype(jnp.int64) << 32)
         | ((~flags).astype(jnp.int64) << 31) | pos.astype(jnp.int64),
         pos], num_keys=1,
    )
    take = (jnp.arange(B, dtype=jnp.int32)[:, None] * CW
            + jnp.arange(max_keys, dtype=jnp.int32)[None, :]).reshape(-1)
    idx = order[take]
    counts = jnp.sum(flags.reshape(B, CW), axis=1, dtype=jnp.int32)
    return (blk.key[idx].reshape(B, max_keys, -1),
            blk.value[idx].reshape(B, max_keys, -1),
            blk.vlen[idx].reshape(B, max_keys),
            counts)


def multi_scan_sources(
    sources: tuple[KVBlock, ...],
    starts_words: jax.Array,  # [B, W]
    read_ts: jax.Array,
    reader_txn: jax.Array,
    window: int,
):
    """B scans against S SORTED sources (memtable block + runs) with NO
    up-front store-wide merge: per-source seeks + window gathers, one
    window-local merge sort, one filter pass. The per-batch cost scales
    with B*S*window, never with the store — the pebble mergingIter
    discipline, vectorized."""
    wins, cuts, truncs = [], [], []
    for src in sources:
        win, cut, trunc = _source_stage(src, starts_words, window)
        wins.append(win)
        cuts.append(cut)
        truncs.append(trunc)
    blk, complete, truncated = _window_merge_stage(
        tuple(wins), jnp.stack(cuts), jnp.stack(truncs), window
    )
    sel, conflict = _filter_stage_flat(blk, read_ts, reader_txn,
                                       len(sources) * window)
    return blk, sel, conflict, complete, truncated


# ---------------------------------------------------------------------------
# Intent resolution


@functools.partial(jax.jit, static_argnames=("commit",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def resolve_intents(
    block: KVBlock, txn_id: jax.Array, commit_ts: jax.Array, commit: bool
) -> KVBlock:
    """Commit (rewrite to committed at commit_ts) or abort (drop) all intents
    of one txn — intent resolution (reference: pkg/storage/mvcc.go
    MVCCResolveWriteIntent), applied blockwise."""
    is_intent = block.mask & (block.txn == txn_id) & (block.txn != 0)
    if commit:
        return KVBlock(
            key=block.key,
            ts=jnp.where(is_intent, commit_ts, block.ts),
            seq=block.seq,
            txn=jnp.where(is_intent, 0, block.txn),
            tomb=block.tomb,
            value=block.value,
            vlen=block.vlen,
            mask=block.mask,
        )
    return KVBlock(
        key=block.key,
        ts=block.ts,
        seq=block.seq,
        txn=block.txn,
        tomb=block.tomb,
        value=block.value,
        vlen=block.vlen,
        mask=block.mask & ~is_intent,
    )
