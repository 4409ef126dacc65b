"""Join kernels — the colexecjoin analog.

Reference: pkg/sql/colexec/colexecjoin/hashjoiner.go:165 builds a vectorized
chained hash table (colexechash.HashTable.FullBuild, hashtable.go:473) then
probes per batch. Pointer-chasing hash chains don't map to TPU, so the build
becomes *sort by 64-bit key hash* and the probe becomes *vectorized binary
search* (log2(n) gathers of the whole probe tile) + a short collision-advance
loop. Two probe paths:

- ``hash_join_unique``: build keys are unique (FK->PK joins — most TPC-H
  joins). Output is probe-aligned, fully static shapes: inner / left-outer /
  semi / anti.
- ``hash_join_general``: duplicate build keys; per-probe match counts and
  an emission into a caller-sized output tile (capacity bucketing: the host
  re-invokes with the next power-of-two capacity on overflow — reported via
  the returned total). This mirrors how the reference's probe emits
  variable-size output batches per input batch. Two emissions, chosen by
  the key the caller planned: under an EXACT packed key a probe row's
  matches are the run [lo, hi) of the sorted build index, so the count is
  hi - lo and the output is a run-length expansion (``expand_runs``: no
  loop, one scatter); under a 64-bit HASH a run may hold a collision, so a
  loop of max-run iterations verifies the key columns, counts, and
  scatters the matches out.

SQL semantics: NULL join keys never match (NULL != NULL); anti-join keeps
NULL-key probe rows (NOT EXISTS semantics, matching CRDB's anti join).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, Column, live_index, pad_rows, take_rows
from ..coldata.types import Family, Schema
from .hashing import hash_columns

_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


@dataclass(frozen=True)
class JoinSpec:
    join_type: str = "inner"  # inner | left | semi | anti
    build_unique: bool = True


# ---------------------------------------------------------------------------
# Exact packed join keys
#
# When every join-key column has known bounds (catalog stats for ints/dates/
# decimals; dictionary size for strings), the multi-column key bit-packs
# EXACTLY into one uint64. Key equality then IS packed-word equality: the
# probe needs no hash, no collision-advance while_loop and no per-column
# key-verification gathers — on TPU that turns the probe into straight-line
# gathers, an order of magnitude cheaper to XLA-compile than control flow.
# The hash path below remains the fallback for unbounded keys.


@dataclass(frozen=True)
class ExactKeyLayout:
    """Per key position: (kind, lo, bits). kind 'int' encodes (x - lo);
    kind 'str' uses probe dictionary codes (build codes remapped host-side,
    absent values -> the never-matching code 2**bits - 1)."""

    segs: tuple[tuple[str, int, int], ...]
    total_bits: int


def plan_exact_key(
    probe_schema: Schema,
    probe_keys: tuple[int, ...],
    build_schema: Schema,
    build_keys: tuple[int, ...],
    probe_stats: dict | None,
    build_stats: dict | None,
    probe_dict_sizes: dict | None,
    have_remaps: bool,
) -> ExactKeyLayout | None:
    """Try to plan an exact packed key; None when any column is unbounded."""
    from .keys import bits_for_count

    probe_stats = probe_stats or {}
    build_stats = build_stats or {}
    probe_dict_sizes = probe_dict_sizes or {}
    segs = []
    total = 0
    for pk, bk in zip(probe_keys, build_keys):
        t = probe_schema.types[pk]
        if t.family is Family.STRING:
            if not have_remaps or pk not in probe_dict_sizes:
                return None
            n = probe_dict_sizes[pk]
            bits = bits_for_count(n + 2)  # probe codes + absent sentinel
            segs.append(("str", 0, bits))
        elif t.family in (Family.FLOAT, Family.BYTES, Family.JSON):
            return None
        elif t.family is Family.BOOL:
            segs.append(("int", 0, 1))
            bits = 1
        else:
            ps = probe_stats.get(pk)
            bs = build_stats.get(bk)
            if ps is None or bs is None:
                return None
            lo = min(int(ps[0]), int(bs[0]))
            hi = max(int(ps[1]), int(bs[1]))
            bits = bits_for_count(hi - lo + 1)
            segs.append(("int", lo, bits))
        total += segs[-1][2]
    if total > 63:
        return None
    return ExactKeyLayout(tuple(segs), total)


def exact_keys(
    batch: Batch,
    keys: tuple[int, ...],
    layout: ExactKeyLayout,
    code_remaps: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(packed u64 key, active) — NULL-key and dead rows get the sentinel
    (which no packed key can equal: total_bits <= 63)."""
    k = jnp.zeros((batch.capacity,), jnp.uint64)
    active = batch.mask
    for pos, (ki, (kind, lo, bits)) in enumerate(zip(keys, layout.segs)):
        c = batch.cols[ki]
        active = active & c.valid
        if kind == "str":
            v = c.data.astype(jnp.int64)
            if code_remaps is not None and pos in code_remaps:
                remap = jnp.asarray(code_remaps[pos]).astype(jnp.int64)
                v = remap[jnp.clip(v, 0, remap.shape[0] - 1)]
            # absent-in-probe-dict (-1) -> the never-matching top code
            v = jnp.where(v < 0, jnp.int64((1 << bits) - 1), v)
        else:
            v = c.data.astype(jnp.int64) - lo
        k = (k << np.uint64(bits)) | (
            v.astype(jnp.uint64) & jnp.uint64((1 << bits) - 1)
        )
    return jnp.where(active, k, _SENTINEL), active


# ---------------------------------------------------------------------------
# Dense direct addressing
#
# The reference's hash table (colexechash) exists because Go can chase
# pointers; the first TPU design replaced it with sort + unrolled binary
# search (log2(n) dependent gathers per probe — ~20 x 7.5ms per 1M-row tile
# on v5e, the measured join bottleneck). When the build key's VALUE RANGE is
# dense, addressing is direct instead:
#
# - 'analytic': the build side is a position-preserving chain over a resident
#   table whose key column IS (an offset of) the row index — true for every
#   TPC-H PK (o_orderkey = 1..N, p_partkey = 1..N, ...) and for clustered
#   child tables (partsupp: 4 rows per part, contiguous). Probe cost: ONE
#   gather of the build liveness mask (+ fanout-1 verification gathers).
#   Build cost: ZERO — no sort, no spool sync, no hash table at all.
# - 'lut': the packed exact key (plan_exact_key) fits in few bits; a dense
#   int32 position table is scatter-built ONCE from the (compacted, usually
#   small) build spool. Probe cost: one gather. Build cost: one scatter of
#   build-side size.
#
# Both paths are exact (no hash, no collision handling): key equality is
# index equality by construction.


@dataclass(frozen=True)
class DenseAnalytic:
    """Probe row index = (first_key - key_lo) * fanout + j, j in [0, fanout).
    verify: remaining key positions needing equality checks (all but the
    first when fanout > 1 or multi-column keys)."""

    key_lo: int
    fanout: int
    build_rows: int  # fanout * number-of-distinct-first-keys (live prefix)


def dense_analytic_probe(
    probe: Batch,
    probe_keys: tuple[int, ...],
    build: Batch,
    build_keys: tuple[int, ...],
    info: DenseAnalytic,
    build_code_remaps=None,
):
    """(found_idx, found) for unique-build joins via direct addressing."""
    k0 = probe.cols[probe_keys[0]]
    base = (k0.data.astype(jnp.int64) - info.key_lo) * info.fanout
    active = probe.mask & k0.valid
    in_range = active & (base >= 0) & (base < info.build_rows)
    base_c = jnp.clip(base, 0, build.capacity - 1).astype(jnp.int32)
    rest_p = probe_keys[1:]
    rest_b = build_keys[1:]
    rest_remaps = None
    if build_code_remaps:
        rest_remaps = {
            pos - 1: r for pos, r in build_code_remaps.items() if pos >= 1
        }
    found = jnp.zeros((probe.capacity,), jnp.bool_)
    found_idx = jnp.zeros((probe.capacity,), jnp.int32)
    for j in range(info.fanout):
        idx = jnp.minimum(base_c + j, build.capacity - 1)
        ok = in_range & build.mask[idx]
        if rest_p:
            ok = ok & _keys_equal(
                probe, rest_p, build, rest_b, idx, rest_remaps
            )
        found_idx = jnp.where(ok & ~found, idx, found_idx)
        found = found | ok
    return found_idx, found


def build_dense_lut(
    build: Batch,
    build_keys: tuple[int, ...],
    layout: ExactKeyLayout,
    exact_remaps=None,
) -> jax.Array:
    """[2**total_bits] int32 build positions (-1 absent). Dead/NULL rows
    carry the u64 sentinel key and drop out of the scatter."""
    bk, _ = exact_keys(build, build_keys, layout, exact_remaps)
    lut = jnp.full((1 << layout.total_bits,), -1, jnp.int32)
    pos = jnp.arange(build.capacity, dtype=jnp.int32)
    return lut.at[bk].set(pos, mode="drop")


def dense_lut_probe(
    probe: Batch,
    probe_keys: tuple[int, ...],
    layout: ExactKeyLayout,
    lut: jax.Array,
):
    """(found_idx, found): one gather; packed-key equality IS key equality."""
    ph, p_active = exact_keys(probe, probe_keys, layout)
    size = lut.shape[0]
    phc = jnp.clip(ph, jnp.uint64(0), jnp.uint64(size - 1)).astype(jnp.int32)
    idx = lut[phc]
    found = p_active & (ph < size) & (idx >= 0)
    return jnp.maximum(idx, 0), found


def emit_unique(probe: Batch, build: Batch, spec: JoinSpec,
                found_idx, found) -> Batch:
    """Probe-ALIGNED emission shared by every unique-build probe strategy
    (dense analytic / dense LUT / sorted bsearch): every build column is
    gathered for every probe row, matched or not. Right where the output
    stays at the probe tile's capacity (a composed or transparent probe, a
    learn run, semi/anti, which carry no build column); a join that cuts
    its output to a learned cap emits through `emit_unique_compact`."""
    if spec.join_type == "semi":
        return probe.with_mask(probe.mask & found)
    if spec.join_type == "anti":
        return probe.with_mask(probe.mask & ~found)
    bcols = tuple(
        Column(data=c.data[found_idx], valid=c.valid[found_idx] & found)
        for c in build.cols
    )
    return Batch(cols=probe.cols + bcols,
                 mask=_joined_mask(probe, spec, found))


def _joined_mask(probe: Batch, spec: JoinSpec, found) -> jax.Array:
    if spec.join_type == "inner":
        return probe.mask & found
    if spec.join_type == "left":
        return probe.mask
    raise ValueError(f"unsupported join type {spec.join_type}")


def emit_unique_compact(probe: Batch, build: Batch, spec: JoinSpec,
                        found_idx, found, capacity: int):
    """(compact(emit_unique(...), capacity), true output rows) of an inner
    or left join, materialising late: the output mask is known from the
    probe alone, so the compaction's index (coldata `live_index`) is taken
    FIRST, the probe columns move through it as `compact` moves them, and
    each build column is gathered ONCE, at ``capacity`` rows, through
    found_idx[index] — not at every probe row and then again at the cut
    (q3's first join: nine columns and their valid bitmaps at 1,048,576
    rows for the 2.6% that survive). Same columns, row order, mask and
    valid bits as the eager order; a slot past the live count is invalid
    (its build data is build row 0's, where `compact` leaves zeros). The
    count is of the whole tile, so a cap below it shows as an overflow."""
    idx, n = live_index(_joined_mask(probe, spec, found), capacity)
    pcols = tuple(take_rows(c, idx, capacity) for c in probe.cols)
    bi = jnp.take(found_idx, idx, mode="fill", fill_value=0)
    f = jnp.take(found, idx, mode="fill", fill_value=False)
    bcols = tuple(
        Column(data=pad_rows(c.data[bi], capacity),
               valid=pad_rows(c.valid[bi] & f, capacity))
        for c in build.cols
    )
    mask = jnp.arange(capacity, dtype=jnp.int32) < n
    return Batch(cols=pcols + bcols, mask=mask), n.astype(jnp.int64)


def bsearch(sorted_u64: jax.Array, queries: jax.Array,
            side: str = "left") -> jax.Array:
    """Branchless UNROLLED binary search (log2(n) static gather+select
    steps). Replaces jnp.searchsorted, whose lax.scan lowering is far more
    expensive for XLA:TPU to compile inside fused query kernels."""
    n = sorted_u64.shape[0]
    # n.bit_length() (not n-1): the insertion point ranges over [0, n]
    # INCLUSIVE, and a power-of-two n needs the extra step to reach n when
    # the query is >= the last element (otherwise the final matching build
    # row of a fully-live power-of-two batch is silently dropped)
    bits = max(1, int(n).bit_length())
    pos = jnp.zeros(queries.shape, jnp.int32)
    for sb in range(bits - 1, -1, -1):
        cand = pos + (1 << sb)
        v = sorted_u64[jnp.clip(cand - 1, 0, n - 1)]
        if side == "left":
            ok = (cand <= n) & (v < queries)
        else:
            ok = (cand <= n) & (v <= queries)
        pos = jnp.where(ok, cand, pos)
    return pos


def _key_hashes(batch: Batch, keys: tuple[int, ...], schema: Schema, hash_tables):
    cols = [batch.cols[i] for i in keys]
    types = [schema.types[i] for i in keys]
    h = hash_columns(cols, types, hash_tables)
    all_valid = batch.mask
    for c in cols:
        all_valid = all_valid & c.valid
    # rows that can never match: dead, or any NULL key
    return jnp.where(all_valid, h, _SENTINEL), all_valid


def _keys_equal(probe: Batch, pkeys, build: Batch, bkeys, bidx, build_remaps=None):
    """Exact key equality probe[i] == build[bidx[i]] per row.

    build_remaps: {key position -> np.ndarray} host-prepared remap of build
    dictionary codes into the probe column's dictionary code space (-1 when
    the value is absent there), so STRING equality is exact across tables
    with different dictionaries."""
    build_remaps = build_remaps or {}
    eq = jnp.ones((probe.capacity,), jnp.bool_)
    for pos, (pk, bk) in enumerate(zip(pkeys, bkeys)):
        pc = probe.cols[pk]
        bc = build.cols[bk]
        bdata = bc.data[bidx]
        if pos in build_remaps:
            remap = jnp.asarray(build_remaps[pos])
            bdata = remap[jnp.clip(bdata, 0, remap.shape[0] - 1)]
        if bdata.ndim == 2:  # BYTES keys: every byte, at the wider width
            w = max(pc.data.shape[1], bdata.shape[1])
            same = jnp.all(
                jnp.pad(pc.data, ((0, 0), (0, w - pc.data.shape[1])))
                == jnp.pad(bdata, ((0, 0), (0, w - bdata.shape[1]))), axis=1)
        else:
            same = pc.data == bdata
        eq = eq & same & pc.valid & bc.valid[bidx]
    return eq


def build_index(
    build: Batch, schema: Schema, keys: tuple[int, ...], hash_tables=None,
    exact_layout: ExactKeyLayout | None = None, exact_remaps=None,
):
    """Sort build rows by key (exact packed key when the layout allows, else
    64-bit hash) -> (sorted_keys, orig_index). NULL-key and dead rows get
    the max sentinel and sort to the end."""
    if exact_layout is not None:
        if (exact_remaps is None
                and any(k == "str" for k, _, _ in exact_layout.segs)):
            raise ValueError(
                "exact STRING join keys need build-code remaps (pass "
                "exact_remaps or a precomputed index)"
            )
        bh, _ = exact_keys(build, keys, exact_layout, exact_remaps)
    else:
        bh, _ = _key_hashes(build, keys, schema, hash_tables)
    perm = jnp.arange(build.capacity, dtype=jnp.int32)
    sh, order = jax.lax.sort([bh, perm], num_keys=1)
    return sh, order


def _probe_positions(sh, ph):
    return bsearch(sh, ph, side="left")


def probe_unique(
    probe: Batch,
    probe_schema: Schema,
    probe_keys: tuple[int, ...],
    build: Batch,
    build_schema: Schema,
    build_keys: tuple[int, ...],
    probe_hash_tables=None,
    build_hash_tables=None,
    build_code_remaps=None,
    index=None,
    exact_layout: ExactKeyLayout | None = None,
    exact_remaps=None,
):
    """(found_idx, found) of a sorted-index probe over unique build keys:
    the probe half of `hash_join_unique`, in the shape the dense strategies
    return, for an emission of the caller's choosing.
    `index` is an optional precomputed build_index() result so the build-side
    sort runs once per build batch, not once per probe tile.

    With an exact_layout the probe is control-flow-free: one unrolled binary
    search + one equality compare (packed-key equality IS key equality).
    The hash path verifies columns and advances past 64-bit collisions."""
    cap = probe.capacity
    bcap = build.capacity
    sh, order = index if index is not None else build_index(
        build, build_schema, build_keys, build_hash_tables,
        exact_layout=exact_layout, exact_remaps=exact_remaps,
    )
    if exact_layout is not None:
        ph, p_active = exact_keys(probe, probe_keys, exact_layout)
        pos = _probe_positions(sh, ph)
        posc = jnp.clip(pos, 0, bcap - 1)
        found_idx = order[posc]
        found = (pos < bcap) & (sh[posc] == ph) & p_active
        found = found & build.mask[found_idx]
    else:
        ph, p_active = _key_hashes(
            probe, probe_keys, probe_schema, probe_hash_tables
        )
        pos = _probe_positions(sh, jnp.where(p_active, ph, _SENTINEL))

        def cond(state):
            _, _, active, _ = state
            return jnp.any(active)

        def body(state):
            pos, found_idx, active, found = state
            inb = pos < bcap
            posc = jnp.clip(pos, 0, bcap - 1)
            bidx = order[posc]
            hash_eq = inb & (sh[posc] == ph) & active
            key_eq = _keys_equal(
                probe, probe_keys, build, build_keys, bidx, build_code_remaps
            )
            hit = hash_eq & key_eq
            found_idx = jnp.where(hit, bidx, found_idx)
            found = found | hit
            # advance only on hash collision with key mismatch
            advance = hash_eq & ~key_eq
            return pos + advance, found_idx, advance, found

        init = (
            pos,
            jnp.zeros((cap,), jnp.int32),
            p_active,
            jnp.zeros((cap,), jnp.bool_),
        )
        _, found_idx, _, found = jax.lax.while_loop(cond, body, init)
        # guard against sentinel-hash self-matches
        found = found & p_active & build.mask[found_idx]

    return found_idx, found


def hash_join_unique(
    probe: Batch,
    probe_schema: Schema,
    probe_keys: tuple[int, ...],
    build: Batch,
    build_schema: Schema,
    build_keys: tuple[int, ...],
    spec: JoinSpec,
    probe_hash_tables=None,
    build_hash_tables=None,
    build_code_remaps=None,
    index=None,
    exact_layout: ExactKeyLayout | None = None,
    exact_remaps=None,
) -> Batch:
    """Join with unique build keys (`probe_unique`, then `emit_unique`).
    Output tile is probe-capacity: probe columns followed by build columns
    (semi/anti: probe columns only)."""
    found_idx, found = probe_unique(
        probe, probe_schema, probe_keys, build, build_schema, build_keys,
        probe_hash_tables, build_hash_tables, build_code_remaps,
        index=index, exact_layout=exact_layout, exact_remaps=exact_remaps,
    )
    return emit_unique(probe, build, spec, found_idx, found)


def hash_join_general(
    probe: Batch,
    probe_schema: Schema,
    probe_keys: tuple[int, ...],
    build: Batch,
    build_schema: Schema,
    build_keys: tuple[int, ...],
    spec: JoinSpec,
    out_capacity: int,
    probe_hash_tables=None,
    build_hash_tables=None,
    build_code_remaps=None,
    index=None,
    exact_layout: ExactKeyLayout | None = None,
    exact_remaps=None,
):
    """General join (duplicate build keys). Returns (out_batch, total_rows);
    if total_rows > out_capacity the caller must retry with a larger tile
    (capacity bucketing keeps shapes static per bucket).

    With an exact_layout the sorted index holds every live, key-equal build
    row of an active probe row in its run [lo, hi), and nothing else (dead
    and NULL-key build rows carry the sentinel): the count is hi - lo and
    the emission is `expand_runs`, straight-line. Without one the index is
    sorted by a 64-bit hash and a run may hold a collision: a loop of
    max-run iterations compares the key columns to count the matches, and
    a second one scatters them out. The two lay the same rows out in the
    same slots."""
    sh, order = index if index is not None else build_index(
        build, build_schema, build_keys, build_hash_tables,
        exact_layout=exact_layout, exact_remaps=exact_remaps,
    )
    if exact_layout is not None:
        ph, p_active = exact_keys(probe, probe_keys, exact_layout)
        lo = bsearch(sh, ph, side="left")
        hi = bsearch(sh, ph, side="right")
        cnt = jnp.where(p_active, hi - lo, 0)
        if spec.join_type == "semi":
            return probe.with_mask(probe.mask & (cnt > 0)), jnp.sum(cnt > 0)
        if spec.join_type == "anti":
            return probe.with_mask(probe.mask & (cnt == 0)), jnp.sum(cnt == 0)
        return expand_runs(probe, build, spec, lo, cnt, order, out_capacity)

    cap = probe.capacity
    bcap = build.capacity
    ph, p_active = _key_hashes(
        probe, probe_keys, probe_schema, probe_hash_tables
    )
    phs = jnp.where(p_active, ph, _SENTINEL)
    lo = bsearch(sh, phs, side="left")
    hi = bsearch(sh, phs, side="right")
    run = jnp.where(p_active, hi - lo, 0)
    max_run = jnp.max(run)

    def key_eq_at(k):
        posc = jnp.clip(lo + k, 0, bcap - 1)
        bidx = order[posc]
        valid_k = (k < run) & p_active & build.mask[bidx]
        return bidx, valid_k & _keys_equal(
            probe, probe_keys, build, build_keys, bidx, build_code_remaps
        )

    # phase 1: count real key matches per probe row
    def count_body(state):
        k, cnt = state
        _, eq = key_eq_at(k)
        return k + 1, cnt + eq.astype(jnp.int32)

    _, cnt = jax.lax.while_loop(
        lambda s: s[0] < max_run,
        count_body,
        (jnp.int32(0), jnp.zeros((cap,), jnp.int32)),
    )

    left = spec.join_type == "left"
    if spec.join_type == "semi":
        return probe.with_mask(probe.mask & (cnt > 0)), jnp.sum(cnt > 0)
    if spec.join_type == "anti":
        return probe.with_mask(probe.mask & (cnt == 0)), jnp.sum(cnt == 0)

    out_rows = jnp.where(left & probe.mask, jnp.maximum(cnt, 1), cnt)
    base = jnp.cumsum(out_rows) - out_rows  # exclusive prefix
    total = jnp.sum(out_rows)

    OC = out_capacity
    out_pidx = jnp.zeros((OC,), jnp.int32)
    out_bidx = jnp.zeros((OC,), jnp.int32)
    out_found = jnp.zeros((OC,), jnp.bool_)
    out_live = jnp.zeros((OC,), jnp.bool_)

    if left:
        # unmatched probe rows emit one null-extended row at their base slot
        unmatched = probe.mask & (cnt == 0)
        dest0 = jnp.where(unmatched, base.astype(jnp.int32), OC)
        out_pidx = out_pidx.at[dest0].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        out_live = out_live.at[dest0].set(True, mode="drop")

    # phase 2: emit the m-th key match of probe i at slot base[i] + m
    def emit_body(state):
        k, m, op, ob, of, ol = state
        bidx, eq = key_eq_at(k)
        dest = jnp.where(eq, (base + m).astype(jnp.int32), OC)
        op = op.at[dest].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        ob = ob.at[dest].set(bidx, mode="drop")
        of = of.at[dest].set(True, mode="drop")
        ol = ol.at[dest].set(True, mode="drop")
        return k + 1, m + eq.astype(jnp.int32), op, ob, of, ol

    _, _, out_pidx, out_bidx, out_found, out_live = jax.lax.while_loop(
        lambda s: s[0] < max_run,
        emit_body,
        (jnp.int32(0), jnp.zeros((cap,), jnp.int32), out_pidx, out_bidx, out_found, out_live),
    )

    pcols = tuple(
        Column(data=c.data[out_pidx], valid=c.valid[out_pidx] & out_live)
        for c in probe.cols
    )
    bcols = tuple(
        Column(data=c.data[out_bidx], valid=c.valid[out_bidx] & out_found)
        for c in build.cols
    )
    return Batch(cols=pcols + bcols, mask=out_live), total


_OWNER_ROW = 1024


def _slot_owner(out_rows, base, out_capacity: int):
    """Which probe row owns each output slot, when row i owns the
    ``out_rows[i]`` slots from ``base[i]`` (its exclusive prefix) on: every
    row that owns a slot writes its index at its first one (ONE scatter;
    the destinations are distinct and rise with the row, those at or past
    the capacity drop), and a running maximum carries it over the rest of
    the run. Slots past the last run read the last owner: the caller masks
    them by the total.

    The running maximum goes in two levels, inside rows of 1,024 slots and
    then over the rows' last slots (row indices are >= 0, so 0 is its
    identity): one `lax.cummax` over 2,097,152 slots costs the chip's
    compiler 24 s against half a second, and q13's launch a tenth more
    (PERF.md section 6, PR 36)."""
    rows = jnp.arange(out_rows.shape[0], dtype=jnp.int32)
    padded = -(-out_capacity // _OWNER_ROW) * _OWNER_ROW
    first = jnp.where(out_rows > 0, base, padded)
    heads = jnp.zeros((padded,), jnp.int32).at[first].set(rows, mode="drop")
    inner = jax.lax.cummax(heads.reshape(-1, _OWNER_ROW), axis=1)
    before = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jax.lax.cummax(inner[:-1, -1])])
    return jnp.maximum(inner, before[:, None]).reshape(-1)[:out_capacity]


def expand_runs(probe: Batch, build: Batch, spec: JoinSpec, lo, cnt, order,
                out_capacity: int):
    """Emit an inner or left join whose probe row i matches the ``cnt[i]``
    build rows ``order[lo[i]] .. order[lo[i] + cnt[i] - 1]`` (a contiguous
    run of a sorted build index; cnt is 0 for a row that must not match):
    (out_batch, total_rows). The m-th match of row i lands in slot
    base[i] + m, base the exclusive prefix of the rows each probe row
    emits; a live LEFT row with no match emits one slot with its build
    side NULL. A run-length expansion: the owner of each slot
    (`_slot_owner`), then gathers; no loop, whatever the longest run. The
    first ``out_capacity`` rows are kept and the returned total is the
    true one, so a caller sees an overflow."""
    left = spec.join_type == "left"
    out_rows = jnp.where(left & probe.mask, jnp.maximum(cnt, 1), cnt)
    # exclusive prefix
    base = (jnp.cumsum(out_rows) - out_rows).astype(jnp.int32)
    total = jnp.sum(out_rows)

    slot = jnp.arange(out_capacity, dtype=jnp.int32)
    owner = _slot_owner(out_rows, base, out_capacity)
    live = slot < total
    # slot j of owner i is its match number j - base[i]: found while that
    # is under cnt[i], at sorted position lo[i] + j - base[i]
    found = live & (slot < (base + cnt)[owner])
    pos = slot + (lo - base)[owner]
    out_pidx = jnp.where(live, owner, 0)
    out_bidx = jnp.where(
        found, order[jnp.clip(pos, 0, build.capacity - 1)], 0)

    pcols = tuple(
        Column(data=c.data[out_pidx], valid=c.valid[out_pidx] & live)
        for c in probe.cols
    )
    bcols = tuple(
        Column(data=c.data[out_bidx], valid=c.valid[out_bidx] & found)
        for c in build.cols
    )
    return Batch(cols=pcols + bcols, mask=live), total


def join_output_schema(
    probe_schema: Schema, build_schema: Schema, spec: JoinSpec
) -> Schema:
    if spec.join_type in ("semi", "anti"):
        return probe_schema
    return probe_schema.concat(build_schema)
