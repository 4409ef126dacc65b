"""Merge join — the colexecjoin mergejoiner analog.

Reference: pkg/sql/colexec/colexecjoin/mergejoiner.go streams two inputs
sorted on the join key, advancing two cursors (per-join-type generated
variants, composite ordered keys included). On TPU the cursor walk becomes
vectorized binary search over order-preserving uint64 key lanes
(sort_ops.order_keys): with EXACT keys (not hashes) there are no
collisions, so each probe row's match run is just [searchsorted left,
searchsorted right) in the build tile — no advance loop at all. Duplicate
keys emit through the exact-key hash join's run-length expansion
(join.expand_runs): the active matches of a run are contiguous.

Composite keys compare lexicographically: the build side sorts on all key
lanes at once (multi-operand lax.sort), and the probe's binary search
composes per-lane compares into one tuple compare per step (log2(n) steps
x ncols gathers — the generated mergejoiner's multi-column cursor compare,
vectorized).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch
from ..coldata.types import Schema
from .join import JoinSpec, expand_runs

_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _u64_key(batch: Batch, key: int, schema: Schema, rank_table=None):
    """Order-preserving uint64 of one key column; NULL/dead -> sentinel
    (never matches, matching SQL NULL != NULL)."""
    from ..coldata.types import Family

    c = batch.cols[key]
    t = schema.types[key]
    if t.family is Family.STRING:
        assert rank_table is not None, "STRING merge join needs a rank table"
        table = jnp.asarray(rank_table)
        codes = jnp.clip(c.data, 0, table.shape[0] - 1)
        payload = table[codes].astype(jnp.int64).astype(jnp.uint64) ^ np.uint64(
            1 << 63
        )
    elif t.family is Family.FLOAT:
        # IEEE total-order trick composed from 32-bit lanes. Canonical
        # -0.0 == 0.0 and NaN == NaN (Postgres float equality semantics).
        # Guarded by the backend's round-trip check (an attached v5e
        # refuses to compile the bitcast: utils/backend.float_bitcast_ok).
        from ..utils.backend import require_float_bitcast

        require_float_bitcast("float merge-join key")
        f = c.data.astype(jnp.float64)
        f = jnp.where(f == 0.0, 0.0, f)
        f = jnp.where(jnp.isnan(f), jnp.float64(jnp.nan), f)
        parts = jax.lax.bitcast_convert_type(f, jnp.uint32)  # [..., 2]
        u = (parts[..., 1].astype(jnp.uint64) << np.uint64(32)) | parts[
            ..., 0
        ].astype(jnp.uint64)
        neg = (u >> np.uint64(63)) != 0
        payload = jnp.where(neg, ~u, u | np.uint64(1 << 63))
    elif t.family is Family.BOOL:
        payload = c.data.astype(jnp.uint64)
    else:
        payload = c.data.astype(jnp.int64).astype(jnp.uint64) ^ np.uint64(
            1 << 63
        )
    active = batch.mask & c.valid
    return jnp.where(active, payload, _SENTINEL), active


def _norm_keys(key) -> tuple[int, ...]:
    return (key,) if isinstance(key, int) else tuple(key)


def rank_tables_for(probe_schema: Schema, probe_key, probe_dicts,
                    build_key, build_dicts):
    """Per-key-position STRING rank tables: the probe dictionary's rank
    space, with build codes remapped into it (absent build values rank past
    the probe's range so they compare unequal to everything). One shared
    helper so the flow (MergeJoinOp) and SPMD (_lower_mergejoin) paths can
    never diverge. Returns (probe_ranks, build_ranks) tuples aligned with
    the normalized key positions (None for non-STRING keys)."""
    from ..coldata.types import Family

    pkeys = _norm_keys(probe_key)
    bkeys = _norm_keys(build_key)
    probe_ranks: list = []
    build_ranks: list = []
    for pk, bk in zip(pkeys, bkeys):
        if probe_schema.types[pk].family is not Family.STRING:
            probe_ranks.append(None)
            build_ranks.append(None)
            continue
        pd = probe_dicts[pk]
        bd = build_dicts[bk]
        probe_ranks.append(pd.ranks)
        ranks = []
        for i, v in enumerate(bd.values):
            code = pd.code_of(str(v))
            ranks.append(pd.ranks[code] if code >= 0
                         else len(pd.values) + i)
        # crlint: allow-host-sync(ranks is a host python list, not a device array)
        build_ranks.append(np.array(ranks, dtype=np.int32))  # crlint: allow-mem-accounting(dictionary-sized rank table, metadata not query data)
    return tuple(probe_ranks), tuple(build_ranks)


def _norm_ranks(rank_tables, nkeys: int) -> tuple:
    """Accept the legacy single-table form (one table for a single key) or
    a tuple/dict keyed by key position."""
    if rank_tables is None:
        return (None,) * nkeys
    if isinstance(rank_tables, dict):
        return tuple(rank_tables.get(i) for i in range(nkeys))
    if isinstance(rank_tables, (list, tuple)):
        assert len(rank_tables) == nkeys
        return tuple(rank_tables)
    assert nkeys == 1
    return (rank_tables,)


def _u64_keys(batch: Batch, keys: tuple[int, ...], schema: Schema,
              rank_tables) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """(per-column order lanes, combined active). A row is active only when
    EVERY key column is non-NULL (SQL: one NULL key kills the match)."""
    ranks = _norm_ranks(rank_tables, len(keys))
    lanes = []
    active = batch.mask
    for k, rt in zip(keys, ranks):
        lane, a = _u64_key(batch, k, schema, rt)
        lanes.append(lane)
        active = active & a
    return tuple(lanes), active


def lex_bsearch(sorted_lanes: tuple[jax.Array, ...],
                query_lanes: tuple[jax.Array, ...],
                side: str = "left") -> jax.Array:
    """Branchless unrolled binary search over LEXICOGRAPHIC tuples.
    Same step structure as join.bsearch (log2(n) static gather+select
    rounds), with the scalar compare replaced by a composed tuple compare
    — ncols gathers per step instead of one."""
    n = sorted_lanes[0].shape[0]
    bits = max(1, int(n).bit_length())
    pos = jnp.zeros(query_lanes[0].shape, jnp.int32)
    for sb in range(bits - 1, -1, -1):
        cand = pos + (1 << sb)
        at = jnp.clip(cand - 1, 0, n - 1)
        lt = jnp.zeros(pos.shape, jnp.bool_)
        eq = jnp.ones(pos.shape, jnp.bool_)
        for sl, ql in zip(sorted_lanes, query_lanes):
            v = sl[at]
            lt = lt | (eq & (v < ql))
            eq = eq & (v == ql)
        ok = lt if side == "left" else (lt | eq)
        pos = jnp.where((cand <= n) & ok, cand, pos)
    return pos


def merge_join(  # crlint: allow-mem-accounting(traced kernel: buffers are XLA transients sized by out_capacity, which the dispatching operator reserves)
    probe: Batch,
    probe_schema: Schema,
    probe_key,
    build: Batch,
    build_schema: Schema,
    build_key,
    spec: JoinSpec,
    out_capacity: int,
    probe_rank_table=None,
    build_rank_table=None,
    build_index=None,
):
    """Returns (out_batch, total_rows); retry with a bigger tile if
    total_rows > out_capacity (same capacity-bucketing contract as
    hash_join_general). `build_index` caches the build-side sorted keys.
    probe_key/build_key: one column index or a tuple of them (composite
    ordered keys, compared lexicographically)."""
    pkeys = _norm_keys(probe_key)
    bkeys = _norm_keys(build_key)
    if build_index is None:
        build_index = build_merge_index(
            build, build_schema, bkeys, build_rank_table
        )
    sks, order, prefix = build_index
    pks, p_active = _u64_keys(probe, pkeys, probe_schema, probe_rank_table)

    lo = lex_bsearch(sks, pks, side="left")
    hi = lex_bsearch(sks, pks, side="right")
    # count only ACTIVE build rows in the run (dead/NULL rows share the key
    # lanes of inactive rows and sort to the run's tail)
    cnt = jnp.where(p_active, prefix[hi] - prefix[lo], 0)
    if spec.join_type == "semi":
        return probe.with_mask(probe.mask & (cnt > 0)), jnp.sum(cnt > 0)
    if spec.join_type == "anti":
        return probe.with_mask(probe.mask & (cnt == 0)), jnp.sum(cnt == 0)
    return expand_runs(probe, build, spec, lo, cnt, order, out_capacity)


def build_merge_index(build: Batch, schema: Schema, key, rank_table=None):  # crlint: allow-mem-accounting(traced kernel: index lanes are shaped like the build batch the operator already charged)
    """Sort build rows by exact (composite) key order -> (sorted_key_lanes,
    orig_index, active_prefix). Inactive (dead/NULL-key) rows sort AFTER
    actives within an equal-key run, and active_prefix[i] counts active rows
    before sorted position i — so a probe run [lo, hi) has its active
    matches contiguous at [lo, lo + prefix[hi] - prefix[lo])."""
    keys = _norm_keys(key)
    lanes, active = _u64_keys(build, keys, schema, rank_table)
    perm = jnp.arange(build.capacity, dtype=jnp.int32)
    out = jax.lax.sort([*lanes, ~active, perm], num_keys=len(lanes) + 1)
    sks, order = tuple(out[:len(lanes)]), out[-1]
    sorted_active = active[order]
    prefix = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(sorted_active.astype(jnp.int32)),
    ])
    return sks, order, prefix
