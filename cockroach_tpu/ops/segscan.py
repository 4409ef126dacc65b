"""Segmented scans — scatter-free per-group reductions over sorted tiles.

The reference's per-group aggregation walks hash-table buckets row by row
(pkg/sql/colexec/colexecagg/hash_*_agg.eg.go); the first TPU design used
``jax.ops.segment_sum`` over sorted segment ids, which XLA lowers to a
scatter-add that the chip runs an update at a time: 71.3 ms for an int64
lane of a 1,048,576-row tile on v5e and 9.2 ms for an int32 one (PR 47,
step 0; PR 46 read 68-74 ms for the scatter-add inside a sized `nonzero`).
This module replaces every hot-path segment reduction with a *segmented
scan* of log2(n) shifted elementwise passes (`seg_scan_multi`: 0.27 ms for
a flag and two int64 lanes of such a tile, PR 47), which is also how the
MVCC visibility pass gets its per-key minima and maxima.

Layout contract: rows are sorted so each segment is contiguous; ``boundary``
is True on the first row of every segment. Scans are inclusive. Per-segment
totals live at the segment's END row; `totals_everywhere` broadcasts them
back over the whole segment, `rows_to_front` hands the end rows out as a
prefix in row order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def use_scans() -> bool:
    """Strategy pick at trace time: segmented scans on accelerators (the
    chip runs a scatter an update at a time: 71 ms an int64 segment op over
    1,048,576 rows, PR 47's step 0), jax.ops.segment_* on CPU (XLA:CPU
    scatters are a cheap serial loop while log-depth scans cost ~20 full
    passes over the tile)."""
    return jax.default_backend() != "cpu"


def seg_bcast(op, segop, vals, boundary, live):
    """Per-segment total of `vals`, broadcast to every row of its segment.
    op: elementwise combiner (jnp.minimum/maximum/add) for the scan path;
    segop: the matching jax.ops.segment_* for the CPU scatter path."""
    if not use_scans():
        seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        tot = segop(vals, seg, num_segments=vals.shape[0])
        return tot[seg]
    s = seg_scan(op, vals, boundary)
    return totals_everywhere(s, boundary, live)


def seg_scan(op, vals, boundary, reverse: bool = False):
    """Inclusive segmented scan of `vals` with associative `op`.

    boundary[i]=True starts a new segment at i (in scan direction: when
    reverse=True, boundaries must mark segment starts in the REVERSED order,
    i.e. segment ENDS of the forward order).
    """
    return seg_scan_multi([op], [vals], boundary, reverse)[0]


def seg_scan_multi(ops, vals_list, boundary, reverse: bool = False):
    """Inclusive segmented scans of several value arrays that share one
    segment structure, each under its own associative `op`.

    Hillis-Steele passes: log2(n) rounds in which every lane meets itself
    shifted by 1, 2, 4, ... rows in scan direction (one contiguous slice
    and a pad a lane; the flag lane is shared). On the chip (PR 47, step 0)
    a flag and two int64 lanes of 1,048,576 rows take 0.27 ms this way and
    1.8 s to compile, at 65,536 rows 0.016 ms; `jax.lax.associative_scan`,
    whose odd / even recursion slices and interleaves with a stride at each
    of its 20 levels, took 6.07 ms and 82 s, and 0.46 ms at 65,536.

    A FLOAT lane keeps that recursion: a float sum sees the order of its
    additions, and its bits stay what they have always been. Every other
    lane's op (integer add, min, max, and / or, a copy) cannot see it.

    The first row in scan direction starts a segment whatever `boundary`
    says (an inclusive scan has nothing before it), so what a shift brings
    in from outside the tile is never combined: the flag fills with True."""
    vals_list = list(vals_list)
    floats = [i for i, v in enumerate(vals_list)
              if jnp.issubdtype(v.dtype, jnp.floating)]
    out = list(vals_list)
    if floats:
        res = _scan_strided([ops[i] for i in floats],
                            [vals_list[i] for i in floats], boundary, reverse)
        for i, r in zip(floats, res):
            out[i] = r
    exact = [i for i in range(len(vals_list)) if i not in floats]
    if not exact:
        return out
    n = boundary.shape[0]
    edge = n - 1 if reverse else 0
    flag = boundary | (jnp.arange(n, dtype=jnp.int32) == edge)

    def shifted(x, k, fill):
        pad = jnp.full((k,), fill, x.dtype)
        return (jnp.concatenate([x[k:], pad]) if reverse
                else jnp.concatenate([pad, x[:-k]]))

    vals = [vals_list[i] for i in exact]
    k = 1
    while k < n:
        vals = [jnp.where(flag, v, ops[i](shifted(v, k, 0), v))
                for i, v in zip(exact, vals)]
        flag = flag | shifted(flag, k, True)
        k *= 2
    for i, v in zip(exact, vals):
        out[i] = v
    return out


def _scan_strided(ops, vals_list, boundary, reverse):
    """`seg_scan_multi` by `jax.lax.associative_scan` (the flag lane and
    the fusion pass shared): the order of association float sums keep."""

    def combine(a, b):
        f1 = a[0]
        f2 = b[0]
        outs = tuple(
            jnp.where(f2, v2, op(v1, v2))
            for op, v1, v2 in zip(ops, a[1:], b[1:])
        )
        return (f1 | f2,) + outs

    res = jax.lax.associative_scan(
        combine, (boundary,) + tuple(vals_list), reverse=reverse)
    return list(res[1:])


def seg_ends(boundary, live):
    """True on the LAST live row of each segment. Dead rows must be sorted
    after live rows (the engine's canonical groupby sort order)."""
    nxt_boundary = jnp.concatenate(
        [boundary[1:], jnp.ones((1,), jnp.bool_)]
    )
    nxt_live = jnp.concatenate([live[1:], jnp.zeros((1,), jnp.bool_)])
    return live & (nxt_boundary | ~nxt_live)


def totals_everywhere(scanned, boundary, live):
    """Broadcast each segment's inclusive-scan END value over the whole
    segment (per-row segment totals, the window-frame ROWS UNBOUNDED case).

    Scatter-free: a reverse copy-scan seeded at segment ends."""
    ends = seg_ends(boundary, live)
    seeded = jnp.where(ends, scanned, jnp.zeros_like(scanned))

    # reverse scan: the seed (segment end, scan-direction start) must win —
    # seg_scan's combine keeps op(v1, v2) for non-boundary rows, so the op
    # propagates the accumulated (end-row) value v1 over the current row
    def keep_acc(v1, v2):
        return v1

    return seg_scan(keep_acc, seeded, ends, reverse=True)


def pack_bits(flags):
    """Boolean arrays as bits of shared uint32 words, 32 to a word, in
    order: a `pred` array costs the chip a gather or a select three times
    a 32-bit one's (PR 26; step 0 of PR 47: 10.1 ms against 9.0 for ONE
    column of 1,048,576 rows), so what moves rows moves every flag of a row
    in one word."""
    words = []
    for at in range(0, len(flags), 32):
        w = jnp.zeros(flags[at].shape, jnp.uint32)
        for j, f in enumerate(flags[at:at + 32]):
            w = w | (f.astype(jnp.uint32) << j)
        words.append(w)
    return words


def unpack_bits(words, count: int):
    """`pack_bits` undone: the first `count` flags."""
    return [((words[j // 32] >> (j % 32)) & 1) == 1 for j in range(count)]


def rows_to_front(wanted, arrays):
    """Every array's rows at `wanted`, in row order, as its first
    sum(wanted) rows; what follows them is garbage (callers mask by their
    own count). A monotone compaction by log2(n) conditional shifts towards
    row 0: a wanted row moves up by the number of unwanted rows before it,
    one bit of that distance a round, lowest bit first. No two wanted rows
    ever meet (their distances differ by less than the rows between them),
    so a round is one select a lane between a row and the row 2**bit below
    it: no sort, no gather, no scatter of a tile's worth of rows (what a
    1,048,576-row tile of q18 read on the chip, PR 47, step 0: 0.74 ms the
    whole kernel this way, 2.54 with the columns riding the index sort as
    payload operands, which also compiled for 36-74 s against 7; a 32-bit
    gather 9.0 ms a column, an int64 one 16.6, a `pred` one 10.1). Boolean
    arrays travel as bits of shared uint32 words."""
    n = wanted.shape[0]
    bits = [i for i, a in enumerate(arrays) if a.dtype == jnp.bool_]
    lanes = [a for a in arrays if a.dtype != jnp.bool_]
    lanes += pack_bits([arrays[i] for i in bits])

    (rank,) = seg_scan_multi([jnp.add], [wanted.astype(jnp.int32)],
                             jnp.zeros((n,), jnp.bool_))
    dist = jnp.arange(n, dtype=jnp.int32) - (rank - 1)
    here = wanted  # a wanted row sits here now

    def up(x, k):
        return jnp.concatenate([x[k:], jnp.zeros((k,) + x.shape[1:],
                                                 x.dtype)])

    k, bit = 1, 0
    while k < n:
        leaves = here & (((dist >> bit) & 1) == 1)
        arrives = up(leaves, k)
        here = arrives | (here & ~leaves)
        dist = jnp.where(arrives, up(dist, k), dist)
        lanes = [jnp.where(arrives.reshape((n,) + (1,) * (a.ndim - 1)),
                           up(a, k), a) for a in lanes]
        k *= 2
        bit += 1

    out = iter(lanes)
    front = [None if a.dtype == jnp.bool_ else next(out) for a in arrays]
    for i, flag in zip(bits, unpack_bits(list(out), len(bits))):
        front[i] = flag
    return front
