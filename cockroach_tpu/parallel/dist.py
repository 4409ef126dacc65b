"""Distributed query pipelines — the DistSQL physical planner analog.

Reference: pkg/sql/distsql_physical_planner.go plans partitioned TableReaders
per node, local (partial) aggregation, a hash-router shuffle, and a final
aggregation stage (aggregation planning around OutputRouterSpec); joins
shuffle both sides on the join key so each consumer joins co-located
partitions. Here each of those multi-node flow graphs compiles into ONE SPMD
program over the mesh:

    partial sort_groupby (local)  ->  all_to_all shuffle by key hash
        ->  merge sort_groupby (local)  ->  finalize

The whole pipeline is a single jit: XLA sees the collective and overlaps it
with local compute — there is no flow registry, no outbox goroutines, no
Arrow serialization (SURVEY §2.3 TPU-native equivalent row).
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..coldata.batch import Batch
from ..coldata.types import Schema
from ..flow import dispatch
from ..ops import aggregation as agg_ops
from ..ops import join as join_ops
from .mesh import AXIS
from .shuffle import _local_shuffle


def shard_batch(batch: Batch, mesh) -> Batch:
    """Place a host-built global batch row-sharded across the mesh
    (partitioned-scan placement; capacity must divide the mesh size)."""
    sh = NamedSharding(mesh, P(AXIS))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sh), batch
    )


def make_distributed_groupby(
    mesh,
    schema: Schema,
    group_cols: tuple[int, ...],
    aggs: tuple[agg_ops.AggSpec, ...],
    local_capacity: int,
    hash_tables: dict[int, np.ndarray] | None = None,
    send_factor: float = 2.0,
):
    """Build (jitted_fn, output_schema). jitted_fn: row-sharded Batch ->
    (row-sharded final Batch, [D] shuffle overflow counts). Every group lands
    on exactly one device (hash placement), so results are globally correct
    without a gather."""
    D = mesh.shape[AXIS]
    partial_specs, state_schema, final_map = agg_ops.partial_layout(
        schema, group_cols, aggs
    )
    k = len(group_cols)
    merge_specs = agg_ops.merge_specs_for(partial_specs, k)
    state_keys = tuple(range(k))
    key_types = [state_schema.types[i] for i in state_keys]
    # final schema: keys + finalized aggs
    names = list(state_schema.names[:k])
    types = list(state_schema.types[:k])
    for spec, fm in zip(aggs, final_map):
        names.append(spec.name or spec.func)
        if fm[0] == "avg":
            from ..coldata.types import FLOAT64

            types.append(FLOAT64)
        else:
            types.append(agg_ops.agg_output_type(spec, schema))
    final_schema = Schema(tuple(names), tuple(types))

    lcap = local_capacity
    send_cap = max(128, int(lcap / D * send_factor) // 128 * 128)

    def local_pipeline(b: Batch):
        part, _ = agg_ops.sort_groupby(b, schema, group_cols, partial_specs)
        shuffled, overflow = _local_shuffle(
            part, state_keys, key_types, hash_tables, D, send_cap, lcap
        )
        merged, _ = agg_ops.sort_groupby(
            shuffled, state_schema, state_keys, merge_specs
        )
        return agg_ops.finalize_states(merged, final_map, k), overflow

    fn = shard_map(
        local_pipeline,
        mesh=mesh,
        in_specs=(P(AXIS),),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    )
    return dispatch.jit(fn, name="dist_groupby"), final_schema


def make_distributed_join(
    mesh,
    probe_schema: Schema,
    probe_keys: tuple[int, ...],
    build_schema: Schema,
    build_keys: tuple[int, ...],
    spec: join_ops.JoinSpec,
    probe_capacity: int,
    build_capacity: int,
    probe_hash_tables=None,
    build_hash_tables=None,
    build_code_remaps=None,
    send_factor: float = 2.0,
):
    """Shuffle-join: repartition both sides by key hash over ICI, then join
    co-located partitions locally (the reference's both-sides-hash-routed
    hash join). Returns (jitted_fn, output_schema); fn maps row-sharded
    (probe, build) -> (row-sharded joined Batch, [D] overflow counts)."""
    D = mesh.shape[AXIS]
    p_types = [probe_schema.types[i] for i in probe_keys]
    b_types = [build_schema.types[i] for i in build_keys]
    p_send = max(128, int(probe_capacity / D * send_factor) // 128 * 128)
    b_send = max(128, int(build_capacity / D * send_factor) // 128 * 128)

    def local_pipeline(p: Batch, b: Batch):
        ps, pov = _local_shuffle(
            p, probe_keys, p_types, probe_hash_tables, D, p_send, probe_capacity
        )
        bs, bov = _local_shuffle(
            b, build_keys, b_types, build_hash_tables, D, b_send, build_capacity
        )
        out = join_ops.hash_join_unique(
            ps, probe_schema, probe_keys, bs, build_schema, build_keys, spec,
            probe_hash_tables, build_hash_tables, build_code_remaps,
        )
        return out, pov + bov

    fn = shard_map(
        local_pipeline,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    )
    return dispatch.jit(fn, name="dist_join"), join_ops.join_output_schema(
        probe_schema, build_schema, spec)
