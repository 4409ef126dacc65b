"""SPMD plan lowering — distributed plans compile to ONE mesh program.

Reference: the DistSQL flow machinery (vectorizedFlowCreator building an
operator DAG per node, colrpc Outbox/Inbox streams between them —
pkg/sql/colflow/vectorized_flow.go:219, distsql_running.go:710). The TPU
redesign collapses the entire distributed flow graph into a single jitted
shard_map: every per-node local pipeline is ordinary traced compute, every
router/stream edge is a collective (Exchange -> lax.all_to_all via
parallel/shuffle.py; Broadcast/Gather -> lax.all_gather; dense/scalar
aggregation states -> psum/pmin/pmax). XLA schedules the collectives and
overlaps them with local compute; there is no flow registry and no
serialization.

Capacity contract: every stage has a static output capacity. A stage that
can overflow (an exchange's send buckets, a unique join's compact emission,
a general join's output) writes what it counted into ONE int32 vector the
program returns beside its result, read back once a statement. The host
learns each stage's cap from those counts as a join learns its emission cap
(flow/operators.py): the first run sizes an exchange at 1.25 x the fair
share of its input and emits joins probe-aligned; its counts then fit every
cap to what the data holds (`_fit_cap`: an eighth of room, three
significant bits), and from there caps only grow. A count past its cap
re-sizes and re-runs the statement (`MeshOp.post_run_update`, counted as
`mesh_overflow_reruns`); nothing is ever truncated silently.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..catalog import Catalog
from ..coldata.batch import Batch, Column, Dictionary, to_host
from ..coldata.types import FLOAT64, Family, Schema
from ..flow import dispatch
from ..flow.operator import SourceOperator
from ..ops import aggregation as agg_ops
from ..ops import expr as ex
from ..ops import join as join_ops
from ..ops import sort as sort_ops
from ..plan import spec as S
from ..plan.distribute import distribute
from ..utils import metric, settings, tracing
from .mesh import AXIS
from .shuffle import exchange, route


def _pow2(n: int) -> int:
    p = 1024
    while p < n:
        p *= 2
    return p


def _fit_cap(n: int) -> int:
    """A learned cap for a stage that counted ``n`` rows at its fullest:
    an eighth of room (a run's other DATE moves a count by a percent or
    two), rounded up to three significant bits so that seeds and
    parameters land on few program shapes, 128 rows at the least."""
    n = max(128, n + n // 8 + 1)
    g = max(128, (1 << (n - 1).bit_length()) // 8)
    return -(-n // g) * g


def _row_bytes(schema: Schema) -> int:
    """Bytes of one row on the wire: every column's data and its valid
    byte (benchmarks/mesh_bytes.py reads the same widths)."""
    return sum((t.width if t.family is Family.BYTES
                else int(np.dtype(t.dtype).itemsize)) + 1
               for t in schema.types)


@dataclass
class _Stage:
    """One stage of the program that counts rows against a cap: what the
    host reads back for it and learns from."""

    kind: str  # exchange | emit | general
    cap: int | None  # None: an emission not yet learned (probe-aligned)
    width: int = 0  # exchange: counts a device; others: 1
    row_bytes: int = 0
    keys: tuple = ()
    node: int = 0  # id() of the plan's Exchange node (EXPLAIN ANALYZE)


@dataclass
class _LNode:
    """One lowered plan node: `emit(env)` returns the node's per-device
    Batch when traced inside the shard_map."""

    emit: Callable
    schema: Schema
    dicts: dict[int, Dictionary]
    replicated: bool
    cap: int  # per-device output capacity (static)
    stats: dict = field(default_factory=dict)  # column -> (lo, hi)


class _Lowering:
    def __init__(self, catalog: Catalog, D: int, caps: dict[int, int]):
        self.catalog = catalog
        self.D = D
        self.caps = caps  # stage index -> learned cap
        self.stages: list[_Stage] = []  # in lowering order
        self.scan_specs: list[tuple[str, tuple[str, ...], int]] = []
        self.counted: list = []  # (stage index, int32 [width]) this trace
        self.emit_cache: dict = {}  # per-trace shared-subtree results

    # -- helpers ------------------------------------------------------------

    def _stage(self, kind: str, default: int | None, **kw) -> int:
        sid = len(self.stages)
        self.stages.append(_Stage(kind, self.caps.get(sid, default), **kw))
        return sid

    def _all_gather(self, ln: _LNode) -> _LNode:
        """Replicate a sharded batch on every device (Gather/Broadcast)."""
        if ln.replicated:
            return ln
        inner = ln.emit

        def emit(env):
            b = inner(env)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, AXIS, axis=0, tiled=True), b
            )

        return _LNode(emit, ln.schema, ln.dicts, True, ln.cap * self.D,
                      ln.stats)

    def _exchange(self, ln: _LNode, keys: tuple[int, ...],
                  node: int = 0) -> _LNode:
        types = [ln.schema.types[i] for i in keys]
        hash_tables = {
            pos: ln.dicts[i].hashes
            for pos, i in enumerate(keys) if i in ln.dicts
        } or None
        # key positions are passed positionally to hash_columns via the
        # extracted column list, so hash tables index by position
        D = self.D
        # before anything is learned: 1.25 x the fair share of the input
        # tile, which a uniform hash cannot pass whatever the filters keep
        fair = -(-ln.cap * 5 // (4 * D * 128)) * 128
        sid = self._stage("exchange", min(ln.cap, max(128, fair)), width=D,
                          row_bytes=_row_bytes(ln.schema), keys=tuple(keys),
                          node=node)
        send_cap = self.stages[sid].cap
        inner = ln.emit

        def emit(env):
            b = inner(env)
            _h, bucket = route(b, keys, types, hash_tables, D)
            out, counts = exchange(b, bucket, D, send_cap)
            self.counted.append((sid, counts))
            return out

        return _LNode(emit, ln.schema, ln.dicts, False, D * send_cap,
                      ln.stats)

    # -- node dispatch ------------------------------------------------------

    def lower(self, plan: S.PlanNode) -> _LNode:
        # memoize by plan-node identity: DAG-shaped plans (a shared subtree
        # feeding two consumers, e.g. q15's max-revenue branch) lower — and
        # therefore trace and COMPUTE — once inside the single SPMD program
        memo = getattr(self, "_memo", None)
        if memo is None:
            memo = self._memo = {}
        ln = memo.get(id(plan))
        if ln is not None:
            return ln
        m = getattr(self, f"_lower_{type(plan).__name__.lower()}", None)
        if m is None:
            raise TypeError(f"cannot lower {type(plan).__name__}")
        ln = m(plan)
        # cache emit RESULTS per trace as well: two consumers of a shared
        # subtree reuse the same traced value instead of emitting the whole
        # subgraph twice (emit_cache is cleared by local_fn per trace)
        orig_emit = ln.emit
        lowering = self

        def cached_emit(env, _key=id(plan)):
            r = lowering.emit_cache.get(_key)
            if r is None:
                r = orig_emit(env)
                lowering.emit_cache[_key] = r
            return r

        ln = _LNode(cached_emit, ln.schema, ln.dicts, ln.replicated, ln.cap,
                    ln.stats)
        memo[id(plan)] = ln
        return ln

    def _lower_tablescan(self, plan: S.TableScan) -> _LNode:
        table = self.catalog.get(plan.table)
        names = plan.columns or table.schema.names
        idxs = tuple(table.schema.index(n) for n in names)
        schema = table.schema.select(idxs)
        full = table.dict_by_index()
        dicts = {i: full[ci] for i, ci in enumerate(idxs) if ci in full}
        # size from the SNAPSHOT's live count where the table distinguishes
        # it: num_rows is the newest-visible count at now(), but a KV table
        # pinned to an older read_ts (or reading as a txn) can hold more
        # live rows — sizing from num_rows would drop the tail at compact
        snap_fn = getattr(table, "snapshot_live_rows", None)
        rows = snap_fn() if callable(snap_fn) else table.num_rows
        from ..catalog import mesh_shard_shape

        _share, local_cap = mesh_shard_shape(rows, self.D)
        slot = len(self.scan_specs)
        self.scan_specs.append((plan.table, tuple(names), local_cap))
        stats_fn = getattr(table, "col_stats", None)
        by_name = stats_fn() if callable(stats_fn) else {}
        stats = {i: by_name[n] for i, n in enumerate(names) if n in by_name}
        return _LNode(lambda env: env[slot], schema, dicts, False, local_cap,
                      stats)

    def _lower_filter(self, plan: S.Filter) -> _LNode:
        ln = self.lower(plan.input)
        schema, pred, inner = ln.schema, plan.predicate, ln.emit

        def emit(env):
            b = inner(env)
            return b.with_mask(ex.filter_mask(b, schema, pred))

        return _LNode(emit, schema, ln.dicts, ln.replicated, ln.cap,
                      ln.stats)

    def _lower_project(self, plan: S.Project) -> _LNode:
        ln = self.lower(plan.input)
        schema = ln.schema
        types = tuple(ex.expr_type(e, schema) for e in plan.exprs)
        out_schema = Schema(tuple(plan.names), types)
        dicts = {
            i: ln.dicts[e.idx]
            for i, e in enumerate(plan.exprs)
            if isinstance(e, ex.ColRef) and e.idx in ln.dicts
        }
        for i, d in plan.dict_overrides:
            dicts[i] = d
        inner = ln.emit

        def emit(env):
            b = inner(env)
            cols = []
            for e in plan.exprs:
                d, v = ex.eval_expr(e, b.cols, schema)
                cols.append(Column(data=d, valid=v))
            return Batch(cols=tuple(cols), mask=b.mask)

        stats = {}
        for i, e in enumerate(plan.exprs):
            b = ex.expr_bounds(e, schema, ln.stats)
            if b is not None:
                stats[i] = b
        return _LNode(emit, out_schema, dicts, ln.replicated, ln.cap, stats)

    def _lower_exchange(self, plan: S.Exchange) -> _LNode:
        return self._exchange(self.lower(plan.input), plan.keys, id(plan))

    def _lower_broadcast(self, plan: S.Broadcast) -> _LNode:
        return self._all_gather(self.lower(plan.input))

    def _lower_gather(self, plan: S.Gather) -> _LNode:
        return self._all_gather(self.lower(plan.input))

    # -- aggregation --------------------------------------------------------

    def _agg_final_schema(self, base, group_cols, aggs, state_schema, mode):
        return agg_ops.agg_output_schema(base, group_cols, aggs, mode)

    def _lower_aggregate(self, plan: S.Aggregate) -> _LNode:
        ln = self.lower(plan.input)
        if plan.key_sizes is not None:
            return self._lower_dense_agg(plan, ln)
        if plan.mode == "partial":
            base = ln.schema
            pspecs, state_schema, _ = agg_ops.partial_layout(
                base, plan.group_cols, plan.aggs
            )
            gcols, cap, inner = plan.group_cols, ln.cap, ln.emit
            # a contiguous device shard of a clustered table keeps equal
            # keys adjacent: the per-shard grouping can skip its key sort
            # (orderedAggregator role; plan/builder._clustered_input)
            from ..plan.builder import _clustered_input

            ordered, prefix_live = _clustered_input(
                plan.input, plan.group_cols, self.catalog
            )

            in_stats = ln.stats

            def emit(env):
                b = inner(env)
                part, _ = agg_ops.sort_groupby(
                    b, base, gcols, pspecs, out_capacity=cap,
                    col_stats=in_stats,
                    presorted=ordered, compact=not prefix_live,
                )  # num_groups <= live rows <= cap: no overflow possible
                return part

            dicts = {
                plan.group_cols.index(gi): d
                for gi, d in ln.dicts.items() if gi in plan.group_cols
            }
            return _LNode(emit, state_schema, dicts, ln.replicated, cap,
                          _group_stats(ln.stats, plan.group_cols))

        if plan.mode == "final":
            base = plan.base_schema
            pspecs, state_schema, final_map = agg_ops.partial_layout(
                base, plan.group_cols, plan.aggs
            )
            k = len(plan.group_cols)
            merge_specs = agg_ops.merge_specs_for(pspecs, k)
            out_schema = self._agg_final_schema(
                base, plan.group_cols, plan.aggs, state_schema, "final"
            )
            cap, inner = ln.cap, ln.emit
            key_stats = {i: b for i, b in ln.stats.items() if i < k}

            def emit(env):
                b = inner(env)
                merged, _ = agg_ops.sort_groupby(
                    b, state_schema, tuple(range(k)), merge_specs,
                    out_capacity=cap, col_stats=key_stats,
                )
                return agg_ops.finalize_states(merged, final_map, k)

            dicts = {i: d for i, d in ln.dicts.items() if i < k}
            return _LNode(emit, out_schema, dicts, ln.replicated, cap,
                          key_stats)

        # complete (replicated input): partial + finalize in one pass
        base = ln.schema
        pspecs, state_schema, final_map = agg_ops.partial_layout(
            base, plan.group_cols, plan.aggs
        )
        k = len(plan.group_cols)
        out_schema = self._agg_final_schema(
            base, plan.group_cols, plan.aggs, state_schema, "complete"
        )
        gcols, cap, inner = plan.group_cols, ln.cap, ln.emit
        in_stats = ln.stats

        def emit(env):
            b = inner(env)
            part, _ = agg_ops.sort_groupby(
                b, base, gcols, pspecs, out_capacity=cap, col_stats=in_stats
            )
            return agg_ops.finalize_states(part, final_map, k)

        dicts = {
            plan.group_cols.index(gi): d
            for gi, d in ln.dicts.items() if gi in plan.group_cols
        }
        return _LNode(emit, out_schema, dicts, ln.replicated, cap,
                      _group_stats(ln.stats, plan.group_cols))

    def _lower_dense_agg(self, plan: S.Aggregate, ln: _LNode) -> _LNode:
        """Dense-code aggregation: [G] states merge across the mesh with
        psum/pmin/pmax — Q1's path has zero all-to-all traffic."""
        base = ln.schema
        pspecs, _, final_map = agg_ops.partial_layout(
            base, plan.group_cols, plan.aggs
        )
        G, strides = agg_ops.dense_layout(plan.key_sizes)
        gcols, sizes, inner = plan.group_cols, plan.key_sizes, ln.emit
        replicated = ln.replicated
        out_schema = self._agg_final_schema(
            base, gcols, plan.aggs, None, "complete"
        )

        def emit(env):
            b = inner(env)
            code, _ = agg_ops.dense_group_codes(b, gcols, strides, sizes)
            from ..ops import segscan

            states, rows = (
                agg_ops.dense_onehot_states(b, base, code, G, pspecs)
                if G <= 64 and segscan.use_scans()
                else agg_ops.dense_scatter_states(b, base, code, G, pspecs)
            )
            if not replicated:
                states = agg_ops.psum_dense_states(pspecs, states, AXIS)
                rows = jax.lax.psum(rows, AXIS)
            return agg_ops.dense_finalize(
                base, gcols, strides, sizes, G, final_map, states, rows
            )

        dicts = {
            gcols.index(gi): d for gi, d in ln.dicts.items() if gi in gcols
        }
        return _LNode(emit, out_schema, dicts, True, G)

    def _lower_scalaraggregate(self, plan: S.ScalarAggregate) -> _LNode:
        ln = self.lower(plan.input)
        base = ln.schema
        names, types = [], []
        for spec in plan.aggs:
            names.append(spec.name or spec.func)
            types.append(FLOAT64 if spec.func == "avg"
                         else agg_ops.agg_output_type(spec, base))
        out_schema = Schema(tuple(names), tuple(types))
        aggs, inner, replicated = plan.aggs, ln.emit, ln.replicated

        def emit(env):
            b = inner(env)
            st = agg_ops.scalar_tile_states(b, aggs, base)
            if not replicated:
                st = agg_ops.psum_dense_states(aggs, st, AXIS)
            return agg_ops.scalar_result_batch(aggs, base, out_schema, st)

        return _LNode(emit, out_schema, {}, True, 1)

    def _lower_distinct(self, plan: S.Distinct) -> _LNode:
        ln = self.lower(plan.input)
        cols = plan.cols or tuple(range(len(ln.schema)))
        out_schema = ln.schema.select(cols)
        dicts = {
            cols.index(i): d for i, d in ln.dicts.items() if i in cols
        }
        pspecs, state_schema, _ = agg_ops.partial_layout(ln.schema, cols, ())
        cap, inner = ln.cap, ln.emit

        def emit(env):
            b = inner(env)
            out, _ = agg_ops.sort_groupby(
                b, ln.schema, cols, pspecs, out_capacity=cap
            )
            return out

        return _LNode(emit, out_schema, dicts, ln.replicated, cap)

    # -- joins --------------------------------------------------------------

    def _join_bridges(self, pl: _LNode, bl: _LNode, probe_keys, build_keys):
        """Host-side string-key bridges (HashJoinOp's dictionary glue)."""
        pht, bht, remaps = {}, {}, {}
        for pos, (pk, bk) in enumerate(zip(probe_keys, build_keys)):
            if pl.schema.types[pk].family is Family.STRING:
                pd, bd = pl.dicts[pk], bl.dicts[bk]
                pht[pk] = pd.hashes
                bht[bk] = bd.hashes
                remaps[pos] = np.array(
                    [pd.code_of(str(v)) for v in bd.values], dtype=np.int32
                )
        return pht or None, bht or None, remaps or None

    def _join_dicts(self, pl: _LNode, bl: _LNode, spec) -> dict:
        dicts = dict(pl.dicts)
        if spec.join_type not in ("semi", "anti"):
            off = len(pl.schema)
            for i, d in bl.dicts.items():
                dicts[off + i] = d
        return dicts

    def _lower_hashjoin(self, plan: S.HashJoin) -> _LNode:
        pl = self.lower(plan.probe)
        bl = self.lower(plan.build)
        pht, bht, remaps = self._join_bridges(
            pl, bl, plan.probe_keys, plan.build_keys
        )
        out_schema = join_ops.join_output_schema(pl.schema, bl.schema,
                                                 plan.spec)
        dicts = self._join_dicts(pl, bl, plan.spec)
        pemit, bemit = pl.emit, bl.emit
        pschema, bschema = pl.schema, bl.schema
        pkeys, bkeys, spec = plan.probe_keys, plan.build_keys, plan.spec
        replicated = pl.replicated and bl.replicated
        stats = dict(pl.stats)
        if spec.join_type not in ("semi", "anti"):
            for i, st in bl.stats.items():
                stats[len(pschema) + i] = st
        # every bounded key (catalog statistics, dictionary sizes) packs
        # EXACTLY into one word, as the served flow's HashJoinOp plans it:
        # the probe is then one gather of a dense table (few bits) or an
        # unrolled binary search, with no hash, no collision loop
        layout = join_ops.plan_exact_key(
            pschema, pkeys, bschema, bkeys, pl.stats, bl.stats,
            {pk: len(pl.dicts[pk]) for pk in pkeys if pk in pl.dicts},
            have_remaps=True,
        )

        if spec.build_unique:
            lut = (layout is not None and layout.total_bits
                   <= settings.get("sql.distsql.dense_lut_bits"))
            # an inner or left join cuts its output to a learned cap and
            # gathers its build columns late, at that cap (`emit_unique_
            # compact`); until a run has counted the rows it emits
            # probe-aligned and cannot overflow
            sid = (self._stage("emit", None, width=1)
                   if spec.join_type in ("inner", "left") else None)
            cap = None if sid is None else self.stages[sid].cap

            def emit(env):
                p, b = pemit(env), bemit(env)
                if lut:
                    found_idx, found = join_ops.dense_lut_probe(
                        p, pkeys, layout,
                        join_ops.build_dense_lut(b, bkeys, layout, remaps))
                else:
                    found_idx, found = join_ops.probe_unique(
                        p, pschema, pkeys, b, bschema, bkeys, pht, bht,
                        remaps, exact_layout=layout, exact_remaps=remaps)
                if cap is None:
                    out = join_ops.emit_unique(p, b, spec, found_idx, found)
                    n = jnp.sum(out.mask, dtype=jnp.int32)
                else:
                    out, n = join_ops.emit_unique_compact(
                        p, b, spec, found_idx, found, cap)
                if sid is not None:
                    self.counted.append((sid, n.astype(jnp.int32)[None]))
                return out

            return _LNode(emit, out_schema, dicts, replicated,
                          pl.cap if cap is None else min(cap, pl.cap), stats)

        sid = self._stage("general", _pow2(pl.cap * 2), width=1)
        out_cap = self.stages[sid].cap

        def emit(env):
            p, b = pemit(env), bemit(env)
            out, total = join_ops.hash_join_general(
                p, pschema, pkeys, b, bschema, bkeys, spec, out_cap,
                pht, bht, remaps,
            )
            self.counted.append((sid, total.astype(jnp.int32)[None]))
            return out

        return _LNode(emit, out_schema, dicts, replicated, out_cap, stats)

    def _lower_mergejoin(self, plan: S.MergeJoin) -> _LNode:
        from ..ops import merge_join as mj_ops

        pl = self.lower(plan.probe)
        bl = self.lower(plan.build)
        out_schema = join_ops.join_output_schema(pl.schema, bl.schema,
                                                 plan.spec)
        dicts = self._join_dicts(pl, bl, plan.spec)
        # STRING keys share the probe dictionary's rank space, per key
        # position (shared helper with MergeJoinOp; composite keys included)
        probe_rank, build_rank = mj_ops.rank_tables_for(
            pl.schema, plan.probe_key, pl.dicts, plan.build_key, bl.dicts,
        )
        sid = self._stage("general", _pow2(pl.cap * 2), width=1)
        out_cap = self.stages[sid].cap
        pemit, bemit = pl.emit, bl.emit
        pschema, bschema = pl.schema, bl.schema
        pk, bk, spec = plan.probe_key, plan.build_key, plan.spec

        def emit(env):
            p, b = pemit(env), bemit(env)
            out, total = mj_ops.merge_join(
                p, pschema, pk, b, bschema, bk, spec, out_cap,
                probe_rank, build_rank,
            )
            self.counted.append((sid, total.astype(jnp.int32)[None]))
            return out

        return _LNode(emit, out_schema, dicts,
                      pl.replicated and bl.replicated, out_cap)

    # -- order / limit / window --------------------------------------------

    def _lower_sort(self, plan: S.Sort) -> _LNode:
        ln = self.lower(plan.input)
        rank_tables = {
            k.col: ln.dicts[k.col].ranks
            for k in plan.keys if k.col in ln.dicts
        }
        schema, keys, inner, stats = ln.schema, plan.keys, ln.emit, ln.stats

        def emit(env):
            return sort_ops.sort_batch(inner(env), schema, keys, rank_tables,
                                       stats)

        return _LNode(emit, schema, ln.dicts, ln.replicated, ln.cap, stats)

    def _lower_topk(self, plan: S.TopK) -> _LNode:
        """ORDER BY ... LIMIT k as `ops/sort.topk_batch`'s k-selection: a
        shard keeps its first k rows of the order at pow2(k) slots, so the
        Gather above moves D x pow2(k) rows and the merge selects again
        (sorttopk.go + OrderedSynchronizer; plan/distribute.py stages it)."""
        ln = self.lower(plan.input)
        rank_tables = {
            key.col: ln.dicts[key.col].ranks
            for key in plan.keys if key.col in ln.dicts
        }
        schema, keys, k, inner, stats = (ln.schema, plan.keys, plan.k,
                                         ln.emit, ln.stats)
        out_cap = min(ln.cap, _pow2(k))

        def emit(env):
            return sort_ops.topk_batch(inner(env), schema, keys, k, out_cap,
                                       rank_tables, stats)

        return _LNode(emit, schema, ln.dicts, ln.replicated, out_cap, stats)

    def _lower_limit(self, plan: S.Limit) -> _LNode:
        from ..coldata.batch import compact

        ln = self.lower(plan.input)
        limit, offset, inner = plan.limit, plan.offset, ln.emit
        # shrink the tile to the limit: a top-k feeding a Gather then moves
        # D*pow2(k) rows over ICI, not the whole per-device result
        out_cap = min(ln.cap, _pow2(limit + offset))

        def emit(env):
            b = sort_ops.limit_mask(inner(env), limit, offset)
            if out_cap < b.capacity:
                b = compact(b, capacity=out_cap)  # order-preserving
            return b

        return _LNode(emit, ln.schema, ln.dicts, ln.replicated, out_cap,
                      ln.stats)

    def _lower_union(self, plan: S.Union) -> _LNode:
        from ..coldata.batch import concat

        lns = [self.lower(p) for p in plan.inputs]
        assert all(ln.replicated == lns[0].replicated for ln in lns), \
            "distribute() must make Union children uniformly placed"
        cap = _pow2(sum(ln.cap for ln in lns))
        emits = [ln.emit for ln in lns]

        def emit(env):
            return concat([e(env) for e in emits], capacity=cap)

        return _LNode(emit, lns[0].schema, dict(lns[0].dicts),
                      lns[0].replicated, cap)

    def _lower_window(self, plan: S.Window) -> _LNode:
        from ..ops import window as win_ops

        ln = self.lower(plan.input)
        out_schema = win_ops.window_output_schema(ln.schema, plan.specs)
        dicts = dict(ln.dicts)
        base_len = len(ln.schema)
        for i, sp in enumerate(plan.specs):
            if (sp.col is not None and sp.col in ln.dicts
                    and sp.func in ("lag", "lead", "min", "max",
                                    "first_value", "last_value")):
                dicts[base_len + i] = ln.dicts[sp.col]
        need = {k.col for k in plan.order_keys}
        need.update(plan.partition_cols)
        need.update(sp.col for sp in plan.specs
                    if sp.col is not None and sp.func in ("min", "max"))
        rank_tables = {
            c: ln.dicts[c].ranks for c in need if c in ln.dicts
        }
        schema, inner = ln.schema, ln.emit
        pcols, okeys, specs = plan.partition_cols, plan.order_keys, plan.specs

        def emit(env):
            return win_ops.compute_windows(
                inner(env), schema, pcols, okeys, specs, rank_tables
            )

        return _LNode(emit, out_schema, dicts, ln.replicated, ln.cap)


def _group_stats(stats: dict, group_cols) -> dict:
    return {pos: stats[gi] for pos, gi in enumerate(group_cols)
            if gi in stats}


def _needs_local(plan) -> bool:
    """True when the plan contains a construct the SPMD lowering cannot
    express (today: string_agg's host-side concatenation)."""
    stack = [plan]
    while stack:
        n = stack.pop()
        aggs = getattr(n, "aggs", None)
        if aggs and any(getattr(s, "func", "") == "string_agg"
                        for s in aggs):
            return True
        for f in getattr(n, "__dataclass_fields__", {}):
            v = getattr(n, f)
            if isinstance(v, S.PlanNode):
                stack.append(v)
            elif isinstance(v, tuple):
                stack.extend(x for x in v if isinstance(x, S.PlanNode))
    return False


class DistributedQuery:
    """One distributed query: plan rewrite + SPMD lowering + learned caps.

    The reference analog of DistSQLPlanner.PlanAndRunAll + the flow runtime
    (distsql_running.go:1751,:710), collapsed into build-jit-run. ``params``
    is the plan-cache entry's ParamStore: its slots ride into the program
    as replicated device arguments, so another literal binds the same
    compiled program."""

    def __init__(self, plan: S.PlanNode, catalog: Catalog, mesh,
                 broadcast_rows: int | None = None,
                 already_distributed: bool = False, params=None):
        self.catalog = catalog
        self.mesh = mesh
        self.D = mesh.shape[AXIS]
        self.params = params
        # a plan the SPMD lowering cannot express never gets here:
        # sql/distsql.py `decide` keeps it local (the reference's
        # checkSupportForPlanNode discipline,
        # distsql_physical_planner.go:541)
        if _needs_local(plan):
            raise TypeError("plan not distributable (string_agg)")
        self.dplan = plan if already_distributed else distribute(
            plan, catalog, broadcast_rows
        )
        self.caps: dict[int, int] = {}  # stage index -> learned cap
        self.learned = False  # a run's counts have fitted every cap
        self.reruns = 0  # statements sent round again by an overflow
        self._build()

    def _build(self):
        low = _Lowering(self.catalog, self.D, self.caps)
        root = low.lower(self.dplan)
        self.root = root
        self.stages = low.stages
        self.scan_specs = low.scan_specs
        # where each stage's counts lie in the vector the program returns
        self._slices, at = [], 0
        for st in low.stages:
            self._slices.append((at, at + st.width))
            at += st.width

        def local_fn(params, *scan_batches):
            low.counted = []
            low.emit_cache = {}
            with ex.param_scope(params):
                out = root.emit(list(scan_batches))
            low.emit_cache = {}
            by_stage = dict(low.counted)
            counts = (jnp.concatenate([by_stage[i].astype(jnp.int32)
                                       for i in range(len(low.stages))])
                      if low.stages else jnp.zeros((0,), jnp.int32))
            return out, counts[None]

        in_specs = (P(),) + tuple(P(AXIS) for _ in low.scan_specs)
        out_specs = (P() if root.replicated else P(AXIS), P(AXIS))
        # dispatch.jit so the whole-pipeline SPMD program counts into
        # sql_kernel_dispatches (one dispatch a run) and compiles into
        # dispatch.compiles() like any flow kernel
        self._fn = dispatch.jit(shard_map(
            local_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        ), name="dist_query")

    def _scan_inputs(self) -> list[Batch]:
        """Every scanned table's row-sharded columns. A host table keeps
        them itself (`Table.mesh_batch`: uploaded once, when first read, by
        whichever plan reads them); a KV-engine-backed table is snapshotted
        anew every launch (`Rel.run_distributed` reaches here; `distsql=auto`
        keeps such a plan local) through its direct columnar scan and the
        snapshot row-sharded (the
        range/leaseholder placement model would instead read per-device
        spans; one-snapshot-then-shard keeps the same SPMD program shape
        meanwhile)."""
        from .dist import shard_batch

        out = []
        for spec in self.scan_specs:
            tname, names, local_cap = spec
            t = self.catalog.get(tname)
            if hasattr(t, "mesh_batch"):
                gb = t.mesh_batch(self.mesh, names)
                if gb.capacity != local_cap * self.D:
                    raise RuntimeError(
                        f"{tname} holds {gb.capacity} padded rows but the "
                        f"plan sized {local_cap * self.D}; re-plan after "
                        "the table changed")
                out.append(gb)
                continue
            from ..coldata.batch import compact

            gb = t.device_batch(tuple(names))  # this launch's snapshot
            # backstop for the snapshot/now() divergence (sizing uses
            # snapshot_live_rows): compacting more live rows than planned
            # would silently DROP the tail — fail loudly instead (one
            # live-count sync a launch)
            live = int(np.asarray(jnp.sum(gb.mask, dtype=jnp.int32)))
            if live > local_cap * self.D:
                raise RuntimeError(
                    f"snapshot of {tname} holds {live} live rows "
                    f"but the plan sized {local_cap * self.D}; "
                    "re-plan after the snapshot moved"
                )
            out.append(shard_batch(compact(gb, capacity=local_cap * self.D),
                                   self.mesh))
        return out

    def launch(self):
        """One run of the program -> (global output batch, [D, n] counts,
        both still on the device)."""
        args = tuple(self.params.args()) if self.params is not None else ()
        return self._fn(args, *self._scan_inputs())

    def absorb(self, counts: np.ndarray) -> tuple[bool, dict, list]:
        """Read one run's counts ([D, n], on the host): -> (an overflow cut
        this run's output short, the exchanges' totals, and the same a
        stage for EXPLAIN ANALYZE). Caps are fitted to the first clean
        run's counts and only grow afterwards; any change re-lowers the
        program, which then compiles at its next launch."""
        fullest = [int(counts[:, a:b].max()) if counts.size else 0
                   for a, b in self._slices]
        over = [st.cap is not None and m > st.cap
                for st, m in zip(self.stages, fullest)]
        overflow, changed = any(over), False
        for sid, (st, m) in enumerate(zip(self.stages, fullest)):
            if over[sid] or not (overflow or self.learned):
                fit = _fit_cap(m)
                if st.kind == "general":
                    fit = _pow2(fit)
                changed = changed or fit != st.cap
                self.caps[sid] = fit
        by_stage = []
        for st, (a, b) in zip(self.stages, self._slices):
            if st.kind != "exchange":
                continue
            c = np.minimum(counts[:, a:b], st.cap).astype(np.int64)
            off = int(c.sum() - np.trace(c))
            by_stage.append({
                "node": st.node, "keys": st.keys, "send_cap": st.cap,
                "rows": int(c.sum()),
                "offchip_rows": off, "offchip_bytes": off * st.row_bytes,
                "send_slots": self.D * self.D * st.cap})
        seen = {"exchange_stages": len(by_stage)}
        for k in ("rows", "offchip_rows", "offchip_bytes", "send_slots"):
            seen["exchange_" + k] = sum(s[k] for s in by_stage)
        if not overflow:
            self.learned = True
        if changed:
            self._build()
        return overflow, seen, by_stage

    def run_batch(self, max_retries: int = 6) -> tuple[Batch, Schema, dict]:
        """Execute with the overflow re-run loop; returns the global output
        batch (+ schema and dictionaries for host decode)."""
        for _ in range(max_retries):
            out, counts = self.launch()
            overflow = self.absorb(np.asarray(counts))[0]
            if not overflow:
                return out, self.root.schema, self.root.dicts
            self.reruns += 1
        raise RuntimeError(
            f"distributed query still overflows after {max_retries} runs"
        )

    def run(self) -> dict[str, np.ndarray]:
        from ..utils.errors import query_boundary

        @query_boundary("distributed flow")
        def _go():
            out, schema, dicts = self.run_batch()
            return to_host(out, schema, dicts)

        return _go()

    def explain(self) -> str:
        from ..plan.explain import explain_plan

        return explain_plan(self.dplan)


class MeshOp(SourceOperator):
    """A plan-cache entry's mesh executor as the root of an operator tree:
    one tile, the whole program's output, so that `runtime.run_operator`
    pulls, reads back, annotates and re-runs a mesh statement as it does any
    other. The counts the program returns are read once a statement, at the
    end of the stream and so inside the `flow/pull` span: the wait for the
    program lands in the span's ``readback_ms``, what the exchanges
    delivered in its tags, and an overflow re-runs the statement through
    `post_run_update` like a join's emission cap."""

    KERNEL = "mesh"

    def __init__(self, query: DistributedQuery):
        super().__init__()
        self.query = query
        self.output_schema = query.root.schema
        self.dictionaries = dict(query.root.dicts)
        self.col_stats = dict(query.root.stats)
        self.what = f"{query.D} devices"
        self.exchange_stages: list[dict] = []  # the last run's, a stage
        self._counts = None
        self._drained = False
        self._overflow = False
        self._rerun = False

    def init(self) -> None:
        super().init()
        self._counts = None
        self._drained = False
        self._overflow = False

    def _next(self):
        sp = tracing.current()
        if self._counts is None:
            if self._drained:
                return None
            if self._rerun and sp is not None:
                sp.add_tag("mesh_overflow_reruns", 1)
            self._rerun = False
            out, self._counts = self.query.launch()
            metric.PLAN_CACHE_MESH_RUNS.inc()
            return out
        # the end of the stream: the statement's one wait for the program
        t0 = time.perf_counter()
        with tracing.annotation("flow.readback"):
            # crlint: allow-host-sync(the mesh program's counts: ONE readback a statement)
            counts = np.asarray(self._counts)
        self._counts, self._drained = None, True
        self._overflow, seen, self.exchange_stages = self.query.absorb(counts)
        if sp is not None:
            sp.inc_tag("readback_ms",
                       round((time.perf_counter() - t0) * 1e3, 3))
            if not self._overflow:
                for tag, v in seen.items():
                    sp.inc_tag(tag, v)
        return None

    def post_run_update(self, truncated: bool = False) -> bool:
        overflow, self._overflow = self._overflow, False
        if overflow:
            self.query.reruns += 1
            self._rerun = True
        return overflow
