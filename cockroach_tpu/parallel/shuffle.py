"""Hash repartition over the mesh — the HashRouter + Outbox/Inbox shuffle.

Reference: colflow/routers.go:420 (HashRouter) hash-partitions each producer's
batches into one stream per consumer; colrpc/outbox.go:44 / inbox.go:48 carry
those streams over gRPC FlowStream with Arrow-serialized batches. On TPU the
entire mechanism becomes ONE collective: inside shard_map each device buckets
its rows by key hash, scatters them into per-destination send buffers, and a
single ``lax.all_to_all`` over the ICI mesh axis delivers every bucket to its
owner. No serialization, no streams, no flow registry — the interconnect is
the router.

Static-shape contract: send buffers are [D, send_cap]; every device reports
the rows it had for each destination, so a bucket past ``send_cap`` is seen
by the host, which re-sizes and re-runs (parallel/planner.py learns its caps
from the same counts, as a join learns its emission cap).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..coldata.batch import Batch, Column, live_index, pad_rows, take_rows
from ..coldata.types import Schema
from ..flow import dispatch
from ..ops import segscan
from ..ops.hashing import hash_columns
from .mesh import AXIS


def route(batch: Batch, keys, types, hash_tables, D: int):
    """Every row's destination device: the 64-bit key hash every one-chip
    join computes (`ops/hashing.hash_columns`), modulo D; D for a dead row.
    The same function on every chip, so equal keys meet on one."""
    h = hash_columns([batch.cols[i] for i in keys], types, hash_tables)
    return h, jnp.where(batch.mask, (h % np.uint64(D)).astype(jnp.int32), D)


def exchange(batch: Batch, bucket, D: int, send_cap: int):  # crlint: allow-mem-accounting(shard_map kernel: send/recv buffers are [D, send_cap] statics the planner budgets)
    """Per-device half of the all-to-all (runs inside shard_map): rows whose
    ``bucket`` is d go to device d. -> (received, counts).

    A row's rank in its destination bucket is its place among the bucket's
    rows in tile order, and for the D <= 8 devices a host has the send
    buffer of each destination is the tile with that bucket's rows brought
    to the front (`segscan.rows_to_front`: log2(rows) rounds of selects),
    cut at ``send_cap``: no sort of the tile, no gather and no scatter (the
    first design ranked by a stable two-operand `lax.sort` and scattered
    every column twice; PR 46 read 68-74 ms a 1,048,576-update scatter on
    this chip). One `lax.all_to_all` a lane delivers the [D, send_cap]
    buffers. ``received`` is [D * send_cap]: source s's rows are a live
    prefix of segment s, in s's tile order, so a learned ``send_cap`` leaves
    little padding and nothing compacts it. ``counts`` [D] int32 are the
    rows this device had for each destination, BEFORE the cut: a count past
    ``send_cap`` is an overflow the host sees (with the learned caps it
    reads the same vector for), re-sizes and re-runs; it never truncates
    silently."""
    arrays = []
    for c in batch.cols:
        arrays += [c.data, c.valid]
    counts, sends = [], []
    for d in range(D):
        wanted = bucket == d
        counts.append(jnp.sum(wanted, dtype=jnp.int32))
        front = segscan.rows_to_front(wanted, arrays)
        sends.append([pad_rows(a[:send_cap], send_cap) for a in front])
    counts = jnp.stack(counts)
    live = (jnp.arange(send_cap, dtype=jnp.int32)[None, :]
            < jnp.minimum(counts, send_cap)[:, None])  # [D, send_cap]
    recv = []
    for lane in zip(*sends):
        x = jnp.stack(lane)  # [D, send_cap, ...]
        x = jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0)
        recv.append(x.reshape((D * send_cap,) + x.shape[2:]))
    # what lies past a bucket's count is garbage of the compaction: the
    # mask that travels is the prefix mask, not the garbage's
    rmask = jax.lax.all_to_all(live, AXIS, split_axis=0, concat_axis=0
                               ).reshape((D * send_cap,))
    cols = tuple(Column(data=d, valid=v & rmask)
                 for d, v in zip(recv[0::2], recv[1::2]))
    return Batch(cols=cols, mask=rmask), counts


def _local_shuffle(batch: Batch, keys, types, hash_tables, D, send_cap,  # crlint: allow-mem-accounting(shard_map kernel: send/recv buffers are [D, send_cap] statics from make_shuffle capacities the planner budgets)
                   out_cap, hot=None):
    """`exchange` with its output compacted to ``out_cap`` rows (the
    standalone shuffles below; the planner's stages keep the received
    layout) -> (out, [1] rows lost to a full bucket or a full output)."""
    h, bucket = route(batch, keys, types, hash_tables, D)
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
    recv, counts = exchange(batch, bucket, D, send_cap)
    overflow = jnp.sum(jnp.maximum(counts - send_cap, 0), dtype=jnp.int32)
    if keep is not None:
        recv = Batch(
            cols=tuple(
                Column(data=jnp.concatenate([fc.data, bc.data]),
                       valid=jnp.concatenate([fc.valid, bc.valid]))
                for fc, bc in zip(recv.cols, batch.cols)),
            mask=jnp.concatenate([recv.mask, keep]))
    idx, received = live_index(recv.mask, out_cap)
    out_mask = jnp.arange(out_cap, dtype=jnp.int32) < received
    out = Batch(cols=tuple(take_rows(c, idx, out_cap) for c in recv.cols),
                mask=out_mask)
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
