"""Hash repartition over the mesh — the HashRouter + Outbox/Inbox shuffle.

Reference: colflow/routers.go:420 (HashRouter) hash-partitions each producer's
batches into one stream per consumer; colrpc/outbox.go:44 / inbox.go:48 carry
those streams over gRPC FlowStream with Arrow-serialized batches. On TPU the
entire mechanism becomes ONE collective: inside shard_map each device buckets
its rows by key hash, scatters them into per-destination send buffers, and a
single ``lax.all_to_all`` over the ICI mesh axis delivers every bucket to its
owner. No serialization, no streams, no flow registry — the interconnect is
the router.

Static-shape contract: send buffers are [D, send_cap]; rows that overflow
their destination bucket are counted and reported so the host can retry with
a larger factor (same capacity-bucketing pattern as the join/groupby kernels).
With a balanced 64-bit hash, overflow at send_cap = 2x fair share is
vanishingly rare at real tile sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..coldata.batch import Batch, Column
from ..coldata.types import Schema
from ..flow import dispatch
from ..ops.hashing import hash_columns
from .mesh import AXIS


def _local_shuffle(batch: Batch, keys, types, hash_tables, D, send_cap,  # crlint: allow-mem-accounting(shard_map kernel: send/recv buffers are [D, send_cap] statics from make_shuffle capacities the planner budgets)
                   out_cap, hot=None):
    """Per-device half of the shuffle (runs inside shard_map)."""
    cap = batch.capacity
    cols = [batch.cols[i] for i in keys]
    h = hash_columns(cols, types, hash_tables)
    bucket = (h % np.uint64(D)).astype(jnp.int32)
    keep = None
    if hot is not None:
        # heavy-hitter keys keep their rows LOCAL instead of funneling the
        # key's entire row mass through one destination device — the skew
        # escape hatch of the hash router. Kept rows never enter the send
        # buffers (zero interconnect cost, no send-cap pressure); they
        # merge into the output tile after the all_to_all. The caller must
        # pair this with a REPLICATED build table for the hot keys (every
        # device holds their build rows), which keeps local joins exact.
        pos = jnp.clip(jnp.searchsorted(hot, h), 0, hot.shape[0] - 1)
        keep = batch.mask & (hot[pos] == h)
        bucket = jnp.where(keep, D, bucket)
    bucket = jnp.where(batch.mask, bucket, D)  # dead rows sort last

    # slot within destination bucket, via sort (stable rank-in-bucket)
    iota = jnp.arange(cap, dtype=jnp.int32)
    sb, si = jax.lax.sort([bucket, iota], num_keys=1, is_stable=True)
    first = jnp.searchsorted(sb, sb, side="left").astype(jnp.int32)
    pos_sorted = iota - first
    slot = jnp.zeros((cap,), jnp.int32).at[si].set(pos_sorted)

    send_live = batch.mask if keep is None else (batch.mask & ~keep)
    live = send_live & (slot < send_cap)
    overflow = jnp.sum(send_live & (slot >= send_cap), dtype=jnp.int32)
    dest = jnp.where(live, bucket * send_cap + slot, D * send_cap)

    def scatter_col(c: Column) -> Column:
        if c.data.ndim == 2:
            data = jnp.zeros((D * send_cap, c.data.shape[1]), c.data.dtype)
        else:
            data = jnp.zeros((D * send_cap,), c.data.dtype)
        data = data.at[dest].set(c.data, mode="drop")
        valid = jnp.zeros((D * send_cap,), jnp.bool_).at[dest].set(
            c.valid, mode="drop"
        )
        return Column(data=data, valid=valid)

    send_mask = jnp.zeros((D * send_cap,), jnp.bool_).at[dest].set(
        batch.mask, mode="drop"
    )
    send = Batch(
        cols=tuple(scatter_col(c) for c in batch.cols), mask=send_mask
    )
    # [D*send_cap] -> [D, send_cap] -> all_to_all -> received from each peer
    send = jax.tree_util.tree_map(
        lambda x: x.reshape((D, send_cap) + x.shape[1:]), send
    )
    recv = jax.tree_util.tree_map(
        lambda x: jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0),
        send,
    )
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((D * send_cap,) + x.shape[2:]), recv
    )
    # compact received rows (plus locally-kept hot rows) into the output
    if keep is None:
        m = flat.mask
        srcs = flat.cols
    else:
        m = jnp.concatenate([flat.mask, keep])
        srcs = tuple(
            Column(data=jnp.concatenate([fc.data, bc.data]),
                   valid=jnp.concatenate([fc.valid, bc.valid]))
            for fc, bc in zip(flat.cols, batch.cols)
        )
    rdest = jnp.cumsum(m.astype(jnp.int32)) - 1
    rdest = jnp.where(m, rdest, out_cap)
    received = jnp.sum(m, dtype=jnp.int32)

    def compact_col(c: Column) -> Column:
        if c.data.ndim == 2:
            data = jnp.zeros((out_cap, c.data.shape[1]), c.data.dtype)
        else:
            data = jnp.zeros((out_cap,), c.data.dtype)
        data = data.at[rdest].set(c.data, mode="drop")
        valid = jnp.zeros((out_cap,), jnp.bool_).at[rdest].set(c.valid, mode="drop")
        return Column(data=data, valid=valid)

    out_mask = jnp.arange(out_cap, dtype=jnp.int32) < jnp.minimum(received, out_cap)
    out = Batch(cols=tuple(compact_col(c) for c in srcs), mask=out_mask)
    dropped = jnp.maximum(received - out_cap, 0)
    return out, (overflow + dropped)[None]  # [1] per device -> [D] global


def make_shuffle(
    mesh,
    schema: Schema,
    keys: tuple[int, ...],
    local_capacity: int,
    hash_tables: dict[int, np.ndarray] | None = None,
    send_factor: float = 2.0,
    out_capacity: int | None = None,
    hot_hashes: np.ndarray | None = None,
):
    """Build a jitted shuffle: (row-sharded Batch) -> (row-sharded Batch
    repartitioned by key hash, plus per-device overflow counts).

    After the shuffle, every row whose keys hash equal lives on the same
    device — the precondition for local final aggregation / joins, exactly
    what the reference's hash router guarantees per consumer flow.

    ``hot_hashes`` (sorted or not; 64-bit key hashes) marks heavy-hitter
    keys whose rows stay on their producing device instead of shuffling to
    ``hash % D`` — the planner supplies them from build-side sampling
    (GraceHashJoinOp's reservoir) and replicates those keys' build rows so
    device-local joins stay exact. Every other row routes normally."""
    D = mesh.shape[AXIS]
    types = [schema.types[i] for i in keys]
    send_cap = max(128, int(local_capacity / D * send_factor) // 128 * 128)
    out_cap = out_capacity or local_capacity
    hot = None
    if hot_hashes is not None and len(hot_hashes) > 0:
        hot = jnp.asarray(np.sort(np.asarray(hot_hashes, dtype=np.uint64)))

    fn = functools.partial(
        _local_shuffle,
        keys=keys,
        types=types,
        hash_tables=hash_tables,
        D=D,
        send_cap=send_cap,
        out_cap=out_cap,
        hot=hot,
    )
    sharded = shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(AXIS),),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    )
    # dispatch.jit, not jax.jit: an SPMD shuffle is one XLA dispatch like
    # any flow kernel — it must count into sql_kernel_dispatches
    return dispatch.jit(sharded, name="shuffle_spmd")
