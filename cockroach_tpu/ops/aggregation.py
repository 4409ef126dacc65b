"""Grouped aggregation kernels — the hashAggregator / orderedAggregator analog.

Reference: pkg/sql/colexec/hash_aggregator.go:62 builds a vectorized hash table
(colexechash.HashTable, hashtable.go:215) and accumulates per-bucket; ordered
aggregation detects group boundaries in sorted input. The TPU redesign uses two
strategies, both static-shape:

1. ``sort_groupby`` — the general path. Sort the tile by the group key columns
   (XLA sort), detect segment boundaries, reduce with segmented scans
   (ops/segscan.py; jax.ops.segment_* lowers to scatter, which serializes on
   the TPU vector unit). Replaces pointer-chasing hash tables, which TPUs
   cannot do, with sorts and scans, which they do well. Over keys already
   adjacent (``presorted``) nothing is sorted by key and no row moves before
   the reduction: ``_ordered_groupby``.

2. ``smallgroup_partial_states`` — the MXU/VPU path for planner-known small group
   cardinality G (e.g. TPC-H Q1's returnflag x linestatus = 6): a one-hot
   [tile, G] membership matrix and masked reductions; exact in int64, no sort.

NULL semantics: NULLs form their own group (SQL GROUP BY); aggregates skip
NULL inputs; SUM/MIN/MAX over an empty (all-NULL) group is NULL; COUNT is 0.

Partial aggregation across devices/batches: every aggregate here has a
well-defined merge (sum+sum, count+count, min of mins...), used by the
distributed final-stage aggregator (reference analog: local+final aggregation
stages in distsql_physical_planner.go).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, Column, pad_rows
from ..coldata.types import FLOAT64, INT64, Family, Schema, SQLType
from . import segscan


@dataclass(frozen=True)
class AggSpec:
    # sum | count | count_rows | min | max | avg | any_not_null
    # | bool_and | bool_or | string_agg
    # | var | stddev | var_pop | stddev_pop | sum_sq (internal state)
    func: str
    col: int | None = None  # input column index (None for count_rows)
    name: str = ""
    sep: str = ","  # string_agg separator (ignored by every other func)


# statistical aggregates decompose into (sum, sum of squares, count) states
STAT_FUNCS = ("var", "stddev", "var_pop", "stddev_pop")


def agg_output_type(spec: AggSpec, schema: Schema) -> SQLType:
    from ..coldata.types import BOOL

    if spec.func in ("count", "count_rows"):
        return INT64
    if spec.func in ("bool_and", "bool_or"):
        return BOOL
    if spec.func == "string_agg":
        from ..coldata.types import STRING

        return STRING
    if spec.func in ("avg",) + STAT_FUNCS or spec.func == "sum_sq":
        return FLOAT64
    t = schema.types[spec.col]
    if spec.func == "sum":
        # CRDB promotes sum(int) to DECIMAL; we keep int64 and document the
        # divergence (overflow policy: TPC-H fits; see SURVEY.md §7 hard parts).
        # Float sums accumulate and return in float64.
        if t.family is Family.FLOAT:
            return FLOAT64
        return t
    return t  # min/max/any_not_null keep input type


def _minmax_sentinel(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(np.inf if is_min else -np.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.array(is_min, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if is_min else info.min, dtype)


def _segment_agg(spec: AggSpec, col: Column | None, live, seg, cap,
                 t: SQLType | None):
    """Per-segment reduction -> (data[cap], valid[cap]) given segment ids —
    the CPU path (XLA:CPU scatters are a cheap serial loop; see
    segscan.use_scans for the strategy split)."""
    if spec.func == "count_rows":
        data = jax.ops.segment_sum(live.astype(jnp.int64), seg, num_segments=cap)
        return data, jnp.ones((cap,), jnp.bool_)
    contributes = live & col.valid
    if spec.func == "count":
        data = jax.ops.segment_sum(contributes.astype(jnp.int64), seg, num_segments=cap)
        return data, jnp.ones((cap,), jnp.bool_)
    cnt = jax.ops.segment_sum(contributes.astype(jnp.int32), seg, num_segments=cap)
    nonempty = cnt > 0
    if spec.func in ("sum_f", "sum_sq"):
        d = col.data.astype(jnp.float64)
        if t is not None and t.family is Family.DECIMAL:
            d = d / (10.0 ** t.scale)
        if spec.func == "sum_sq":
            d = d * d
        vals = jnp.where(contributes, d, 0.0)
        return jax.ops.segment_sum(vals, seg, num_segments=cap), nonempty
    if spec.func in ("sum", "avg"):
        if t.family is Family.FLOAT or spec.func == "avg":
            vals = jnp.where(contributes, col.data.astype(jnp.float64), 0.0)
            s = jax.ops.segment_sum(vals, seg, num_segments=cap)
            if spec.func == "avg":
                denom = jnp.where(nonempty, cnt, 1).astype(jnp.float64)
                avg = s / denom
                if t.family is Family.DECIMAL:
                    avg = avg / (10.0**t.scale)
                return avg, nonempty
            return s, nonempty
        vals = jnp.where(contributes, col.data.astype(jnp.int64), 0)
        return jax.ops.segment_sum(vals, seg, num_segments=cap), nonempty
    if spec.func in ("min", "max"):
        is_min = spec.func == "min"
        sent = _minmax_sentinel(col.data.dtype, is_min)
        vals = jnp.where(contributes, col.data, sent)
        fn = jax.ops.segment_min if is_min else jax.ops.segment_max
        return fn(vals, seg, num_segments=cap), nonempty
    if spec.func == "any_not_null":
        sent = _minmax_sentinel(col.data.dtype, False)
        vals = jnp.where(contributes, col.data, sent)
        return jax.ops.segment_max(vals, seg, num_segments=cap), nonempty
    if spec.func in ("bool_and", "bool_or"):
        # AND = min over {0,1}, OR = max; non-contributing rows carry the
        # identity. int32 lanes: XLA segment reductions over pred are
        # unreliable on some backends
        is_and = spec.func == "bool_and"
        vals = jnp.where(contributes, col.data.astype(jnp.bool_),
                         jnp.bool_(is_and)).astype(jnp.int32)
        fn = jax.ops.segment_min if is_and else jax.ops.segment_max
        return (fn(vals, seg, num_segments=cap).astype(jnp.bool_), nonempty)
    raise ValueError(f"unknown aggregate {spec.func}")


def _scan_agg_entries(spec: AggSpec, col: Column | None, live,
                      t: SQLType | None):
    """Plan one aggregate as segmented-scan work: returns (entries, finish)
    where entries is a list of (op, row_vals) to scan and finish(*scanned)
    maps the scans' running totals, elementwise, to (data, valid): what it
    gives at a segment's last row is the segment's.

    The scans replace jax.ops.segment_* (scatter-lowered on TPU: 71 ms an
    int64 op over a 1,048,576-row tile, PR 47) with log-depth shifted
    passes (segscan.py)."""
    add = jnp.add

    if spec.func == "count_rows":
        return ([(add, live.astype(jnp.int64))],
                lambda c: (c, jnp.ones_like(c, dtype=jnp.bool_)))
    contributes = live & col.valid
    if spec.func == "count":
        return ([(add, contributes.astype(jnp.int64))],
                lambda c: (c, jnp.ones_like(c, dtype=jnp.bool_)))
    cnt_entry = (add, contributes.astype(jnp.int64))
    if spec.func in ("sum_f", "sum_sq"):
        d = col.data.astype(jnp.float64)
        if t is not None and t.family is Family.DECIMAL:
            d = d / (10.0 ** t.scale)
        if spec.func == "sum_sq":
            d = d * d
        vals = jnp.where(contributes, d, 0.0)
        return ([cnt_entry, (add, vals)],
                lambda c, s: (s, c > 0))
    if spec.func in ("sum", "avg"):
        if t.family is Family.FLOAT or spec.func == "avg":
            vals = jnp.where(contributes, col.data.astype(jnp.float64), 0.0)

            def finish_f(c, s):
                if spec.func != "avg":
                    return s, c > 0
                avg = s / jnp.where(c > 0, c, 1).astype(jnp.float64)
                if t.family is Family.DECIMAL:
                    avg = avg / (10.0 ** t.scale)
                return avg, c > 0

            return [cnt_entry, (add, vals)], finish_f
        vals = jnp.where(contributes, col.data.astype(jnp.int64), 0)
        return [cnt_entry, (add, vals)], lambda c, s: (s, c > 0)
    if spec.func in ("min", "max"):
        is_min = spec.func == "min"
        sent = _minmax_sentinel(col.data.dtype, is_min)
        vals = jnp.where(contributes, col.data, sent)
        op = jnp.minimum if is_min else jnp.maximum
        return [cnt_entry, (op, vals)], lambda c, s: (s, c > 0)
    if spec.func == "any_not_null":
        sent = _minmax_sentinel(col.data.dtype, False)
        vals = jnp.where(contributes, col.data, sent)
        return [cnt_entry, (jnp.maximum, vals)], lambda c, s: (s, c > 0)
    if spec.func in ("bool_and", "bool_or"):
        is_and = spec.func == "bool_and"
        vals = jnp.where(contributes, col.data.astype(jnp.bool_),
                         jnp.bool_(is_and))
        op = jnp.logical_and if is_and else jnp.logical_or
        return [cnt_entry, (op, vals)], lambda c, s: (s, c > 0)
    raise ValueError(f"unknown aggregate {spec.func}")


def _packed_group_keys(batch: Batch, schema: Schema,
                       group_cols: tuple[int, ...], col_stats: dict,
                       dead_bit: bool = True) -> list:
    """Every row's (dead?, group keys) bit-packed into uint64 words: live
    rows sort first, then by group keys, and word equality IS group-key
    equality (nulls are their own group; NULL rows' garbage data is zeroed
    inside key_segments so the NULL group is contiguous even with later
    key columns in play). ``dead_bit=False`` leaves the liveness bit out:
    the words of the keys alone, for a caller that compares a dead row's
    key with its neighbours' (`_ordered_groupby`)."""
    from . import keys as key_ops

    segs: list = ([key_ops.BitSeg(1, (~batch.mask).astype(jnp.uint64))]
                  if dead_bit else [])
    for gi in group_cols:
        c = batch.cols[gi]
        segs.extend(key_ops.key_segments(
            c.data, c.valid, schema.types[gi], desc=False, nulls_first=False,
            stats=col_stats.get(gi), order_semantics=False,
        ))
    return key_ops.pack_operands(segs)


def sort_groupby(
    batch: Batch,
    schema: Schema,
    group_cols: tuple[int, ...],
    aggs: tuple[AggSpec, ...],
    out_capacity: int | None = None,
    col_stats: dict[int, tuple] | None = None,
    presorted: bool = False,
    compact: bool = True,
) -> tuple[Batch, jax.Array]:
    """General grouped aggregation over one tile. Output tile: one live row per
    group (group key columns first, then aggregates), padded to capacity.

    Returns (batch, num_groups). If num_groups > out_capacity the output is
    truncated and the caller must retry with a larger tile (same capacity-
    bucketing contract as hash_join_general).

    The group keys bit-pack into as few uint64 sort operands as possible
    (ops/keys.py; catalog stats shrink integer keys) — on TPU lax.sort
    compile time scales with operand count, so a 3-column TPC-H group-by
    sorts on ONE packed word instead of seven operands.

    presorted=True asserts equal group keys are already ADJACENT in the
    input (clustered storage, Table.ordering) and skips the key sort —
    the colexec orderedAggregator specialization (ordered sort-free
    grouping). On an accelerator the tile is then grouped where its rows
    lie, dead rows included (`_ordered_groupby`, which says what it needs
    of a dead row's key; an unsorted tile is one after its key sort), and
    ``compact`` is not read. On the CPU compact=True still runs a stable
    sort that pushes dead rows last (needed when filters interleave dead
    rows); compact=False additionally asserts live rows form a prefix
    (pure scan tiles), making the whole grouping sort-free."""
    cap = batch.capacity
    cap_out = out_capacity or cap
    live = batch.mask
    col_stats = col_stats or {}

    if segscan.use_scans():
        if not presorted:
            batch = _sorted_by_keys(batch, schema, group_cols, aggs,
                                    col_stats)
        return _ordered_groupby(batch, schema, group_cols, aggs, cap_out,
                                col_stats)

    # the CPU from here on (XLA:CPU scatters are a cheap serial loop; 20
    # log-depth scan passes are not — segscan.use_scans)
    operands = _packed_group_keys(batch, schema, group_cols, col_stats)
    perm = jnp.arange(cap, dtype=jnp.int32)
    if not presorted:
        sorted_res = jax.lax.sort(
            operands + [perm], num_keys=len(operands) + 1
        )
        perm = sorted_res[-1]
        key_words = sorted_res[:-1]
    elif compact:
        # clustered keys: only push dead rows last (stable, so group
        # adjacency survives) — one u8 operand instead of the packed keys
        _, perm = jax.lax.sort(
            [(~live).astype(jnp.uint8), perm], num_keys=2
        )
        key_words = [w[perm] for w in operands]
    else:
        key_words = operands  # identity permutation, zero sorts

    live_s = live[perm] if (not presorted or compact) else live
    keys_s = [
        (batch.cols[gi].data[perm], batch.cols[gi].valid[perm])
        for gi in group_cols
    ] if (not presorted or compact) else [
        (batch.cols[gi].data, batch.cols[gi].valid) for gi in group_cols
    ]

    # Group boundaries: compare adjacent rows on the SORTED packed words
    # (word equality == full group-key equality, NULL==NULL included).
    idx = jnp.arange(cap)
    changed = jnp.zeros((cap,), jnp.bool_)
    for w in key_words:
        changed = changed | (w != jnp.roll(w, 1, axis=0))
    prev_live = jnp.roll(live_s, 1)
    boundary = live_s & ((idx == 0) | changed | ~prev_live)
    num_groups = jnp.sum(boundary, dtype=jnp.int32)

    out_cols: list[Column] = []
    out_mask = jnp.arange(cap_out, dtype=jnp.int32) < num_groups

    # scatter the boundary row's key into its segment slot and reduce with
    # jax.ops.segment_*
    seg = jnp.maximum(jnp.cumsum(boundary.astype(jnp.int32)) - 1, 0)
    dest = jnp.where(boundary, seg, cap_out)
    for kd, kv in keys_s:
        data = jnp.zeros(
            (cap_out,) + kd.shape[1:], kd.dtype
        ).at[dest].set(kd, mode="drop")
        valid = jnp.zeros((cap_out,), jnp.bool_).at[dest].set(
            kv, mode="drop"
        )
        out_cols.append(Column(data=data, valid=valid))
    for spec in aggs:
        col = None
        t = None
        if spec.col is not None:
            t = schema.types[spec.col]
            col = Column(
                data=batch.cols[spec.col].data[perm],
                valid=batch.cols[spec.col].valid[perm],
            )
        data, valid = _segment_agg(spec, col, live_s, seg, cap_out, t)
        out_cols.append(Column(data=data, valid=valid & out_mask))
    return Batch(cols=tuple(out_cols), mask=out_mask), num_groups


def _sorted_by_keys(batch: Batch, schema: Schema,
                    group_cols: tuple[int, ...], aggs: tuple[AggSpec, ...],
                    col_stats: dict) -> Batch:
    """``batch`` in the order of its packed group keys, dead rows last: a
    presorted tile. Only the mask and the columns the grouping reads follow
    the sort's permutation (the others stay as they were), and the mask
    and every valid bitmap follow in shared words (`segscan.pack_bits`):
    one 32-bit gather where each was a `pred` one."""
    operands = _packed_group_keys(batch, schema, group_cols, col_stats)
    perm = jax.lax.sort(
        operands + [jnp.arange(batch.capacity, dtype=jnp.int32)],
        num_keys=len(operands) + 1)[-1]
    used = sorted(set(group_cols) | {
        spec.col for spec in aggs if spec.col is not None})
    words = [w[perm] for w in segscan.pack_bits(
        [batch.mask] + [batch.cols[i].valid for i in used])]
    mask, *valids = segscan.unpack_bits(words, 1 + len(used))
    moved = dict(zip(used, valids))
    return Batch(cols=tuple(
        Column(data=c.data[perm], valid=moved[i]) if i in moved else c
        for i, c in enumerate(batch.cols)), mask=mask)


def _ordered_groupby(batch: Batch, schema: Schema,
                     group_cols: tuple[int, ...], aggs: tuple[AggSpec, ...],
                     cap_out: int, col_stats: dict):
    """`sort_groupby` on an accelerator, over a tile whose equal keys are
    adjacent (stored so, or just sorted so): it is grouped where its rows
    lie. No row moves before the reduction (nothing pushes dead rows last)
    and the groups leave as a live prefix, in arrival order, by
    `segscan.rows_to_front`: no sort, gather or scatter of a tile's size.
    The parent's kernel gathered each key word, state word and valid bitmap
    through a stable sort's permutation, 120-208 ms a 1,048,576-row tile of
    q18 and q21 (9-28 ms a gather in its trace); this one reads 1.05 ms
    (PR 47, q18 traced).

    What it relies on, under a Filter as without one: a dead row between
    two live rows of one group carries that group's key. `plan/builder.py`
    `_clustered_input` proves it for every chain it calls ordered (Scan ->
    Filter -> Project(ColRef): a Filter clears mask bits and nothing
    rewrites a stored key; `AggregateOp` refuses any other chain), and the
    key sort puts dead rows last. A dead row elsewhere may hold anything (a
    tile's padded tail): it adds each lane's identity, a run of keys with
    no live row ends nowhere, and one that continues a live group only
    moves that group's end.

    Boundaries compare EVERY row's key words with its neighbour's; the lanes
    (`_scan_agg_entries`, dead rows at their identities) and one `or` lane
    of liveness reduce in one `segscan.seg_scan_multi`; a segment's last row
    holds its totals and is wanted when the segment has a live row; the
    finishers run elementwise over the tile, so a count read only as
    `c > 0` leaves as one bit of the shared word of valid bits."""
    cap = batch.capacity
    live = batch.mask
    idx = jnp.arange(cap, dtype=jnp.int32)
    boundary = idx == 0
    for w in _packed_group_keys(batch, schema, group_cols, col_stats,
                                dead_bit=False):
        boundary = boundary | (w != jnp.roll(w, 1, axis=0))

    entries: list = [(jnp.logical_or, live)]
    finishers: list = []
    for spec in aggs:
        col = batch.cols[spec.col] if spec.col is not None else None
        t = schema.types[spec.col] if spec.col is not None else None
        es, finish = _scan_agg_entries(spec, col, live, t)
        finishers.append((len(entries), len(es), finish))
        entries.extend(es)
    scanned = segscan.seg_scan_multi(
        [op for op, _ in entries], [v for _, v in entries], boundary)

    last = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    ends = last & scanned[0]
    num_groups = jnp.sum(ends, dtype=jnp.int32)

    arrays: list = []
    for gi in group_cols:
        arrays += [batch.cols[gi].data, batch.cols[gi].valid]
    for start, n, finish in finishers:
        arrays += list(finish(*scanned[start:start + n]))
    front = segscan.rows_to_front(ends, arrays)

    out_mask = jnp.arange(cap_out, dtype=jnp.int32) < num_groups
    out_cols: list[Column] = []
    for data, valid in zip(front[::2], front[1::2]):
        data, valid = (pad_rows(data[:cap_out], cap_out),
                       pad_rows(valid[:cap_out], cap_out))
        m = out_mask.reshape((cap_out,) + (1,) * (data.ndim - 1))
        out_cols.append(Column(data=jnp.where(m, data, jnp.zeros_like(data)),
                               valid=valid & out_mask))
    return Batch(cols=tuple(out_cols), mask=out_mask), num_groups


def groupby_output_schema(
    schema: Schema, group_cols: tuple[int, ...], aggs: tuple[AggSpec, ...]
) -> Schema:
    names = [schema.names[i] for i in group_cols]
    types = [schema.types[i] for i in group_cols]
    for spec in aggs:
        names.append(spec.name or f"{spec.func}_{spec.col}")
        types.append(agg_output_type(spec, schema))
    return Schema(tuple(names), tuple(types))


_MERGE_FUNC = {
    "sum": "sum",
    "sum_f": "sum",
    "sum_sq": "sum",
    "count": "sum",
    "count_rows": "sum",
    "min": "min",
    "max": "max",
    "any_not_null": "any_not_null",
    "bool_and": "bool_and",
    "bool_or": "bool_or",
}


def partial_layout(
    schema: Schema, group_cols: tuple[int, ...], aggs: tuple[AggSpec, ...]
):
    """The partial-aggregation state layout shared by partial and final
    stages: group keys first, then state columns (avg -> sum + count).

    Returns (partial_specs, state_schema, final_map) where final_map[j] gives,
    for output agg j, ('avg', sum_state_idx, count_state_idx) or
    (func, state_idx) with state indices relative to the first state column."""
    partial_specs: list[AggSpec] = []
    final_map = []
    for spec in aggs:
        if spec.func in STAT_FUNCS:
            si = len(partial_specs)
            partial_specs.append(AggSpec("sum_f", spec.col, f"_s{si}"))
            partial_specs.append(AggSpec("sum_sq", spec.col, f"_q{si}"))
            partial_specs.append(AggSpec("count", spec.col, f"_c{si}"))
            final_map.append((spec.func, si, si + 1, si + 2))
        elif spec.func == "avg":
            si = len(partial_specs)
            t = schema.types[spec.col]
            partial_specs.append(AggSpec("sum", spec.col, f"_s{si}"))
            partial_specs.append(AggSpec("count", spec.col, f"_c{si}"))
            final_map.append(("avg", si, si + 1, t))
        else:
            si = len(partial_specs)
            partial_specs.append(
                AggSpec(spec.func, spec.col, f"_st{si}")
            )
            final_map.append((spec.func, si))
    state_schema = groupby_output_schema(
        schema, group_cols, tuple(partial_specs)
    )
    return tuple(partial_specs), state_schema, final_map




def merge_specs_for(partial_specs: tuple[AggSpec, ...], num_keys: int):
    """Merge aggregation specs over the partial-state layout (group keys at
    0..num_keys-1, states after)."""
    return tuple(
        AggSpec(_MERGE_FUNC[s.func], num_keys + i, s.name)
        for i, s in enumerate(partial_specs)
    )


def finalize_states(state: Batch, final_map, num_keys: int) -> Batch:
    """Turn a merged partial-state batch into final SQL results (avg = sum /
    count, decimal scale restored). Shared by the single-node AggregateOp and
    the distributed final stage."""
    k = num_keys
    cols = list(state.cols[:k])
    for fm in final_map:
        if fm[0] in STAT_FUNCS:
            func, si, qi, ci = fm
            sm = state.cols[k + si].data.astype(jnp.float64)
            sq = state.cols[k + qi].data.astype(jnp.float64)
            n = state.cols[k + ci].data.astype(jnp.float64)
            safe_n = jnp.where(n > 0, n, 1.0)
            mean = sm / safe_n
            if func.endswith("_pop"):
                var = jnp.maximum(sq / safe_n - mean * mean, 0.0)
                valid = state.cols[k + ci].data > 0
            else:
                denom = jnp.where(n > 1, n - 1.0, 1.0)
                var = jnp.maximum((sq - n * mean * mean) / denom, 0.0)
                valid = state.cols[k + ci].data > 1
            d = jnp.sqrt(var) if func.startswith("stddev") else var
            cols.append(Column(data=d, valid=valid & state.mask))
            continue
        if fm[0] == "avg":
            _, si, ci, t = fm
            s = state.cols[k + si]
            c = state.cols[k + ci]
            denom = jnp.where(c.data > 0, c.data, 1).astype(jnp.float64)
            d = s.data.astype(jnp.float64) / denom
            if t.family is Family.DECIMAL:
                d = d / (10.0**t.scale)
            cols.append(Column(data=d, valid=s.valid & (c.data > 0)))
        else:
            cols.append(state.cols[k + fm[1]])
    return Batch(cols=tuple(cols), mask=state.mask)


def smallgroup_partial_states(
    batch: Batch,
    schema: Schema,
    codes,
    num_groups: int,
    specs: tuple[AggSpec, ...],
):
    """Dense-code partial aggregation: rows with group code g (precomputed,
    in [0, num_groups)) reduce into row g of [num_groups] state arrays.

    Unlike sort_groupby there is no sort and the output is POSITIONALLY
    aligned by code, so cross-tile / cross-device merging is elementwise
    (sum/min/max of equal-shaped arrays) — the TPU-ideal layout for
    planner-known small cardinalities (e.g. TPC-H Q1: 3x2 flag groups).

    Returns (state_cols, group_rows): state_cols is a list of (data[G],
    valid[G]) per spec; group_rows[G] counts rows per group."""
    G = num_groups
    live = batch.mask
    codes = jnp.clip(codes.astype(jnp.int32), 0, G - 1)
    onehot = (codes[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]) & live[:, None]
    group_rows = jnp.sum(onehot, axis=0, dtype=jnp.int64)
    out = []
    for spec in specs:
        if spec.func == "count_rows":
            out.append((group_rows, jnp.ones((G,), jnp.bool_)))
            continue
        col = batch.cols[spec.col]
        t = schema.types[spec.col]
        member = onehot & col.valid[:, None]
        cnt = jnp.sum(member, axis=0, dtype=jnp.int64)
        nonempty = cnt > 0
        if spec.func == "count":
            out.append((cnt, jnp.ones((G,), jnp.bool_)))
        elif spec.func == "sum":
            if t.family is Family.FLOAT:
                v = jnp.where(member, col.data.astype(jnp.float64)[:, None], 0.0)
            else:
                v = jnp.where(member, col.data.astype(jnp.int64)[:, None], 0)
            out.append((jnp.sum(v, axis=0), nonempty))
        elif spec.func in ("min", "max"):
            is_min = spec.func == "min"
            sent = _minmax_sentinel(col.data.dtype, is_min)
            v = jnp.where(member, col.data[:, None], sent)
            out.append((jnp.min(v, axis=0) if is_min else jnp.max(v, axis=0),
                        nonempty))
        elif spec.func == "any_not_null":
            sent = _minmax_sentinel(col.data.dtype, False)
            v = jnp.where(member, col.data[:, None], sent)
            out.append((jnp.max(v, axis=0), nonempty))
        else:
            raise ValueError(f"unsupported dense-state aggregate {spec.func}")
    return out, group_rows


def combine_state(func: str, a, b):
    """Two states of one group met: the ONE place the per-function combine
    lives (dense states, scalar states, the ordered aggregate's carried
    group). A state that saw no value must hold the function's identity
    (state_identity), as every partial here leaves it."""
    if func in ("sum", "count", "count_rows"):
        return a + b
    if func == "min":
        return jnp.minimum(a, b)
    if func in ("max", "any_not_null"):
        return jnp.maximum(a, b)
    if func == "bool_and":
        return a & b
    if func == "bool_or":
        return a | b
    raise ValueError(func)


def state_identity(func: str, dtype):
    """What combine_state leaves unchanged."""
    if func in ("sum", "count", "count_rows"):
        return jnp.zeros((), dtype)
    if func in ("bool_and", "bool_or"):
        return jnp.array(func == "bool_and", dtype)
    return _minmax_sentinel(dtype, func == "min")


def merge_dense_states(specs: tuple[AggSpec, ...], acc, new):
    """Elementwise merge of positionally-aligned dense states."""
    return [(combine_state(spec.func, ad, nd), av | nv)
            for spec, (ad, av), (nd, nv) in zip(specs, acc, new)]


def stitch_ordered_partial(part: Batch, num_groups, carry: Batch,
                           schema: Schema, group_cols: tuple[int, ...],
                           specs: tuple[AggSpec, ...], col_stats: dict):
    """Meet one tile's presorted partial states with the group the tiles
    before it left open — the orderedAggregator's carry across batches.

    Input clustered on the group keys cuts at most one group at each tile
    edge, so of ``part`` (sort_groupby(presorted=True): ``num_groups`` live
    rows in arrival order, state layout ``schema``, merge specs ``specs``)
    every group is final but the first, which may continue ``carry`` (one
    row, live or not), and the last, which the next tile may continue.

    Returns (closed, carry'): ``closed`` is ``part`` with the carried group
    combined into row 0 where the keys are equal (packed-word equality as
    sort_groupby compares, NULL = NULL), its last group masked out, and in
    that vacated row the carried group where it did NOT continue (it
    ended with the tile before); ``carry'`` is the last group after the
    combine, or ``carry`` itself when the tile has no live row. All
    elementwise at the tile's capacity: no sort, scatter or gather."""
    k = len(group_cols)
    head = jax.tree_util.tree_map(lambda x: x[:1], part)
    same = carry.mask[0] & head.mask[0]
    for a, b in zip(_packed_group_keys(carry, schema, group_cols, col_stats),
                    _packed_group_keys(head, schema, group_cols, col_stats)):
        same = same & (a[0] == b[0])
    has = num_groups > 0
    last = num_groups - 1
    at = jnp.maximum(last, 0)
    idx = jnp.arange(part.capacity, dtype=jnp.int32)
    stitch = (idx == 0) & same
    # the carried group ended before this tile: it takes the vacated row
    put = (idx == last) & carry.mask[0] & ~same
    mask = (idx < last) | put

    def rows(m, like):  # BYTES keys are [capacity, W]
        return m.reshape(m.shape + (1,) * (like.ndim - 1))

    closed, open_row = [], []
    for i, (col, c) in enumerate(zip(part.cols, carry.cols)):
        data, valid = col.data, col.valid
        if i >= k:
            func = specs[i - k].func
            zero = state_identity(func, data.dtype)
            met = combine_state(func, jnp.where(c.valid, c.data, zero),
                                jnp.where(valid[:1], data[:1], zero))
            data = jnp.where(rows(stitch, data), met, data)
            valid = jnp.where(stitch, c.valid | valid[:1], valid)
        open_row.append(Column(
            data=jnp.where(has, jax.lax.dynamic_slice_in_dim(data, at, 1),
                           c.data),
            valid=jnp.where(has, jax.lax.dynamic_slice_in_dim(valid, at, 1),
                            c.valid)))
        closed.append(Column(
            data=jnp.where(rows(put, data), c.data, data),
            valid=jnp.where(put, c.valid, valid) & mask))
    return (Batch(cols=tuple(closed), mask=mask),
            Batch(cols=tuple(open_row), mask=carry.mask | has))


# ---------------------------------------------------------------------------
# mesh reduction of positionally-aligned states (sharded -> replicated)


def psum_dense_states(specs: tuple[AggSpec, ...], states, axis_name: str):
    """Reduce dense states across a mesh axis with XLA collectives — the
    all_to_all-free path for positionally-aligned layouts: sums/counts ride
    psum, min/max ride pmin/pmax, valid flags OR via psum>0. Must run inside
    shard_map over `axis_name`."""
    out = []
    for spec, (d, v) in zip(specs, states):
        if spec.func in ("sum", "count", "count_rows"):
            rd = jax.lax.psum(d, axis_name)
        elif spec.func == "min":
            rd = jax.lax.pmin(d, axis_name)
        elif spec.func in ("max", "any_not_null"):
            rd = jax.lax.pmax(d, axis_name)
        elif spec.func == "avg":
            rd = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axis_name), d
            )
        elif spec.func in ("bool_and", "bool_or"):
            # AND = min over {0,1} lanes, OR = max (pred collectives are
            # unreliable on some backends: ride int32)
            fn = jax.lax.pmin if spec.func == "bool_and" else jax.lax.pmax
            rd = fn(d.astype(jnp.int32), axis_name).astype(jnp.bool_)
        else:
            raise ValueError(spec.func)
        rv = jax.lax.psum(v.astype(jnp.int32), axis_name) > 0
        out.append((rd, rv))
    return out


def dense_layout(key_sizes: tuple[int, ...]) -> tuple[int, tuple[int, ...]]:
    """(G, strides) for the dense group-code space: one extra code per key
    column for NULL (every NULL combination is its own group, matching SQL
    GROUP BY semantics)."""
    eff = tuple(s + 1 for s in key_sizes)
    G = 1
    for s in eff:
        G *= s
    strides = []
    acc = 1
    for s in reversed(eff):
        strides.append(acc)
        acc *= s
    return G, tuple(reversed(strides))


def dense_group_codes(batch: Batch, group_cols, strides, key_sizes,
                      key_lows=None):
    """Per-row dense group code from bounded key columns (NULL maps to the
    extra per-column code). key_lows[i] offsets integer-family keys whose
    catalog stats put them in [lo, lo+size) — dictionary codes use lo=0."""
    code = jnp.zeros((batch.capacity,), jnp.int32)
    oob = jnp.zeros((batch.capacity,), jnp.bool_)
    lows = key_lows or (0,) * len(group_cols)
    for gi, st, size, lo in zip(group_cols, strides, key_sizes, lows):
        c = batch.cols[gi]
        v = c.data.astype(jnp.int32) - jnp.int32(lo)
        # rows outside the planned bounds (stale stats) are flagged, not
        # clipped into a neighboring group — callers route them to a
        # detectable overflow slot and fall back to the sort path
        oob = oob | (c.valid & ((v < 0) | (v >= size)))
        ci = jnp.where(c.valid, jnp.clip(v, 0, size - 1), size)
        code = code + ci * st
    return code, oob


def dense_scatter_states(
    batch: Batch,
    schema: Schema,
    codes,
    G: int,
    specs: tuple[AggSpec, ...],
):
    """Scatter-based dense-code partial aggregation: rows with group code g
    reduce into slot g of [G] state arrays via segment_* ops — O(rows)
    scatters plus O(G) state traffic, NO sort and NO one-hot (the
    smallgroup one-hot matmul is O(rows x G), viable only for tiny G).
    The missing middle this covers: bounded-but-large key spaces like
    TPC-H's GROUP BY l_orderkey (reference hash agg: hash_aggregator.go:62;
    here the dense code IS the hash table slot, collision-free).

    Returns (state_cols, group_rows) positionally aligned by code —
    cross-tile/device merge stays elementwise (merge_dense_states)."""
    live = batch.mask
    seg = jnp.where(live, codes.astype(jnp.int32), G)  # dead rows drop
    group_rows = jax.ops.segment_sum(
        live.astype(jnp.int64), seg, num_segments=G
    )
    out = []
    for spec in specs:
        col = None
        t = None
        if spec.col is not None:
            t = schema.types[spec.col]
            col = batch.cols[spec.col]
        data, valid = _segment_agg(spec, col, live, seg, G, t)
        out.append((data, valid))
    return out, group_rows


def dense_onehot_states(
    batch: Batch,
    schema: Schema,
    codes,
    G: int,
    specs: tuple[AggSpec, ...],
):
    """One-hot dense partial states (alias of smallgroup_partial_states) —
    O(rows x G), the right shape only for tiny G where the [rows, G]
    membership matrix rides the VPU in one fused pass."""
    return smallgroup_partial_states(batch, schema, codes, G, specs)


def dense_finalize(base: Schema, group_cols, strides, key_sizes, G,
                   final_map, states, rows, key_lows=None) -> Batch:
    """Decode dense group codes back into key columns and finalize the
    aggregate states — shared by SmallGroupAggregateOp and the SPMD path.
    key_lows restores integer-stat key offsets (see dense_group_codes)."""
    gid = jnp.arange(G, dtype=jnp.int32)
    lows = key_lows or (0,) * len(group_cols)
    cols = []
    for gi, st, size, lo in zip(group_cols, strides, key_sizes, lows):
        code_i = (gid // st) % (size + 1)
        t = base.types[gi]
        valid = code_i < size  # code==size means NULL key
        cols.append(Column(
            data=jnp.where(valid, code_i + jnp.int32(lo), 0).astype(t.dtype),
            valid=valid,
        ))
    mask = rows > 0
    for (d, v) in states:
        cols.append(Column(data=d, valid=v & mask))
    state_batch = Batch(cols=tuple(cols), mask=mask)
    return finalize_states(state_batch, final_map, len(group_cols))


# ---------------------------------------------------------------------------
# scalar (no GROUP BY) aggregation states — shared by ScalarAggregateOp and
# the SPMD planner's psum-merged scalar stage


def scalar_tile_states(batch: Batch, aggs: tuple[AggSpec, ...], base: Schema):
    """Per-tile scalar states: one (value, valid) pair per agg (avg carries
    (sum, count))."""
    out = []
    for spec in aggs:
        if spec.func == "count_rows":
            out.append((jnp.sum(batch.mask, dtype=jnp.int64), jnp.bool_(True)))
            continue
        c = batch.cols[spec.col]
        t = base.types[spec.col]
        m = batch.mask & c.valid
        cnt = jnp.sum(m, dtype=jnp.int64)
        if spec.func == "count":
            out.append((cnt, jnp.bool_(True)))
        elif spec.func in ("sum", "avg"):
            if t.family is Family.FLOAT or spec.func == "avg":
                s = jnp.sum(jnp.where(m, c.data.astype(jnp.float64), 0.0))
            else:
                s = jnp.sum(jnp.where(m, c.data.astype(jnp.int64), 0))
            if spec.func == "avg":
                out.append(((s, cnt), cnt > 0))
            else:
                out.append((s, cnt > 0))
        elif spec.func in ("min", "max"):
            is_min = spec.func == "min"
            sent = _minmax_sentinel(c.data.dtype, is_min)
            vals = jnp.where(m, c.data, sent)
            red = jnp.min(vals) if is_min else jnp.max(vals)
            out.append((red, cnt > 0))
        elif spec.func in STAT_FUNCS:
            d = c.data.astype(jnp.float64)
            if t.family is Family.DECIMAL:
                d = d / (10.0 ** t.scale)
            s_ = jnp.sum(jnp.where(m, d, 0.0))
            q_ = jnp.sum(jnp.where(m, d * d, 0.0))
            ok = cnt > 0 if spec.func.endswith("_pop") else cnt > 1
            out.append(((s_, q_, cnt), ok))
        elif spec.func in ("bool_and", "bool_or"):
            is_and = spec.func == "bool_and"
            vals = jnp.where(m, c.data.astype(jnp.bool_), jnp.bool_(is_and))
            red = jnp.all(vals) if is_and else jnp.any(vals)
            out.append((red, cnt > 0))
        else:
            raise ValueError(spec.func)
    return out


def scalar_merge_states(aggs: tuple[AggSpec, ...], acc, new):
    out = []
    for spec, (a, av), (n, nv) in zip(aggs, acc, new):
        if spec.func in ("count", "count_rows"):
            out.append((a + n, jnp.bool_(True)))
        elif spec.func == "avg":
            out.append(((a[0] + n[0], a[1] + n[1]), av | nv))
        elif spec.func in STAT_FUNCS:
            cnt = a[2] + n[2]
            ok = cnt > 0 if spec.func.endswith("_pop") else cnt > 1
            out.append(((a[0] + n[0], a[1] + n[1], cnt), ok))
        else:
            out.append((combine_state(spec.func, a, n), av | nv))
    return out


def scalar_result_batch(aggs: tuple[AggSpec, ...], base: Schema,
                        out_schema: Schema, acc) -> Batch:
    """States -> one-row result Batch (acc=None means empty input: counts
    are 0, everything else NULL — SQL scalar aggregate semantics)."""
    acc = list(acc) if acc is not None else None
    cols = []
    for spec, t in zip(aggs, out_schema.types):
        if acc is None:
            if spec.func in ("count", "count_rows"):
                d, v = jnp.zeros((1,), jnp.int64), jnp.ones((1,), jnp.bool_)
            else:
                d = jnp.zeros((1,), t.dtype)
                v = jnp.zeros((1,), jnp.bool_)
        else:
            (val, valid) = acc.pop(0)  # states consumed in agg order
            if spec.func in STAT_FUNCS:
                sm, sq, c = val
                n = c.astype(jnp.float64)
                safe_n = jnp.where(n > 0, n, 1.0)
                mean = sm / safe_n
                if spec.func.endswith("_pop"):
                    var = jnp.maximum(sq / safe_n - mean * mean, 0.0)
                else:
                    denom = jnp.where(n > 1, n - 1.0, 1.0)
                    var = jnp.maximum((sq - n * mean * mean) / denom, 0.0)
                d = (jnp.sqrt(var) if spec.func.startswith("stddev")
                     else var)[None]
                cols.append(Column(data=d, valid=jnp.asarray(valid)[None]))
                continue
            if spec.func == "avg":
                s, c = val
                base_t = base.types[spec.col]
                d = s.astype(jnp.float64) / jnp.where(
                    c > 0, c, 1
                ).astype(jnp.float64)
                if base_t.family is Family.DECIMAL:
                    d = d / (10.0**base_t.scale)
                d = d[None]
            else:
                d = val.astype(t.dtype)[None]
            v = jnp.asarray(valid)[None]
        cols.append(Column(data=d, valid=v))
    return Batch(cols=tuple(cols), mask=jnp.ones((1,), jnp.bool_))


def agg_output_schema(
    base: Schema, group_cols: tuple[int, ...], aggs: tuple[AggSpec, ...],
    mode: str = "complete",
) -> Schema:
    """Output schema of an aggregation stage — the ONE place the group-key
    + per-agg naming/typing rule lives (avg -> FLOAT64, else
    agg_output_type), shared by the flow operators, the distribution
    rewrite, and the SPMD lowering."""
    _, state_schema, final_map = partial_layout(base, group_cols, aggs)
    if mode == "partial":
        return state_schema
    k = len(group_cols)
    if mode == "final":
        names = list(state_schema.names[:k])
        types = list(state_schema.types[:k])
    else:
        names = [base.names[i] for i in group_cols]
        types = [base.types[i] for i in group_cols]
    for spec, fm in zip(aggs, final_map):
        names.append(spec.name or spec.func)
        types.append(FLOAT64 if fm[0] in ("avg",) + STAT_FUNCS
                     else agg_output_type(spec, base))
    return Schema(tuple(names), tuple(types))
