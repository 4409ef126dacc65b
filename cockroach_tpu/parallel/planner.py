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

Capacity contract: every stage has a static output capacity derived from its
inputs (scaled by a host-controlled `factor`). Stages that can overflow —
Exchange send buckets and general (duplicate-key) join outputs — report
overflow counts; `DistributedQuery.run()` retries with a doubled factor
until clean (the host-side retry loop the shuffle contract promises,
parallel/shuffle.py:12-16).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..catalog import Catalog
from ..coldata.batch import Batch, Column, Dictionary, from_host, to_host
from ..coldata.types import FLOAT64, Family, Schema
from ..flow import dispatch
from ..ops import aggregation as agg_ops
from ..ops import expr as ex
from ..ops import join as join_ops
from ..ops import sort as sort_ops
from ..plan import spec as S
from ..plan.distribute import distribute
from .mesh import AXIS
from .shuffle import _local_shuffle


def _pow2(n: int) -> int:
    p = 1024
    while p < n:
        p *= 2
    return p


@dataclass
class _LNode:
    """One lowered plan node: `emit(env)` returns the node's per-device
    Batch when traced inside the shard_map."""

    emit: Callable
    schema: Schema
    dicts: dict[int, Dictionary]
    replicated: bool
    cap: int  # per-device output capacity (static)


class _Lowering:
    def __init__(self, catalog: Catalog, D: int, factor: int):
        self.catalog = catalog
        self.D = D
        self.factor = factor
        self.scan_specs: list[tuple[str, tuple[str, ...], int]] = []
        self.overflows: list[jax.Array] = []  # collected during tracing
        self.emit_cache: dict = {}  # per-trace shared-subtree results

    # -- helpers ------------------------------------------------------------

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

        return _LNode(emit, ln.schema, ln.dicts, True, ln.cap * self.D)

    def _exchange(self, ln: _LNode, keys: tuple[int, ...]) -> _LNode:
        types = [ln.schema.types[i] for i in keys]
        hash_tables = {
            pos: ln.dicts[i].hashes
            for pos, i in enumerate(keys) if i in ln.dicts
        } or None
        # key positions are passed positionally to hash_columns via the
        # extracted column list, so hash tables index by position
        out_cap = _pow2(ln.cap * 2 * self.factor)
        send_cap = max(
            128, (ln.cap * 2 * self.factor // self.D) // 128 * 128
        )
        D = self.D
        inner = ln.emit

        def emit(env):
            b = inner(env)
            out, ovf = _local_shuffle(
                b, keys, types, hash_tables, D, send_cap, out_cap
            )
            self.overflows.append(ovf[0])
            return out

        return _LNode(emit, ln.schema, ln.dicts, False, out_cap)

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

        ln = _LNode(cached_emit, ln.schema, ln.dicts, ln.replicated, ln.cap)
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
        local_cap = max(
            1024, -(-rows // (self.D * 1024)) * 1024
        )
        slot = len(self.scan_specs)
        self.scan_specs.append((plan.table, tuple(names), local_cap))
        return _LNode(lambda env: env[slot], schema, dicts, False, local_cap)

    def _lower_filter(self, plan: S.Filter) -> _LNode:
        ln = self.lower(plan.input)
        schema, pred, inner = ln.schema, plan.predicate, ln.emit

        def emit(env):
            b = inner(env)
            return b.with_mask(ex.filter_mask(b, schema, pred))

        return _LNode(emit, schema, ln.dicts, ln.replicated, ln.cap)

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

        return _LNode(emit, out_schema, dicts, ln.replicated, ln.cap)

    def _lower_exchange(self, plan: S.Exchange) -> _LNode:
        return self._exchange(self.lower(plan.input), plan.keys)

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

            def emit(env):
                b = inner(env)
                part, _ = agg_ops.sort_groupby(
                    b, base, gcols, pspecs, out_capacity=cap,
                    presorted=ordered, compact=not prefix_live,
                )  # num_groups <= live rows <= cap: no overflow possible
                return part

            dicts = {
                plan.group_cols.index(gi): d
                for gi, d in ln.dicts.items() if gi in plan.group_cols
            }
            return _LNode(emit, state_schema, dicts, ln.replicated, cap)

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

            def emit(env):
                b = inner(env)
                merged, _ = agg_ops.sort_groupby(
                    b, state_schema, tuple(range(k)), merge_specs,
                    out_capacity=cap,
                )
                return agg_ops.finalize_states(merged, final_map, k)

            dicts = {i: d for i, d in ln.dicts.items() if i < k}
            return _LNode(emit, out_schema, dicts, ln.replicated, cap)

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

        def emit(env):
            b = inner(env)
            part, _ = agg_ops.sort_groupby(
                b, base, gcols, pspecs, out_capacity=cap
            )
            return agg_ops.finalize_states(part, final_map, k)

        dicts = {
            plan.group_cols.index(gi): d
            for gi, d in ln.dicts.items() if gi in plan.group_cols
        }
        return _LNode(emit, out_schema, dicts, ln.replicated, cap)

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

        if spec.build_unique:
            def emit(env):
                p, b = pemit(env), bemit(env)
                return join_ops.hash_join_unique(
                    p, pschema, pkeys, b, bschema, bkeys, spec,
                    pht, bht, remaps,
                )

            return _LNode(emit, out_schema, dicts, replicated, pl.cap)

        out_cap = _pow2(pl.cap * 2 * self.factor)

        def emit(env):
            p, b = pemit(env), bemit(env)
            out, total = join_ops.hash_join_general(
                p, pschema, pkeys, b, bschema, bkeys, spec, out_cap,
                pht, bht, remaps,
            )
            self.overflows.append(
                jnp.maximum(total - out_cap, 0).astype(jnp.int32)
            )
            return out

        return _LNode(emit, out_schema, dicts, replicated, out_cap)

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
        out_cap = _pow2(pl.cap * 2 * self.factor)
        pemit, bemit = pl.emit, bl.emit
        pschema, bschema = pl.schema, bl.schema
        pk, bk, spec = plan.probe_key, plan.build_key, plan.spec

        def emit(env):
            p, b = pemit(env), bemit(env)
            out, total = mj_ops.merge_join(
                p, pschema, pk, b, bschema, bk, spec, out_cap,
                probe_rank, build_rank,
            )
            self.overflows.append(
                jnp.maximum(total - out_cap, 0).astype(jnp.int32)
            )
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
        schema, keys, inner = ln.schema, plan.keys, ln.emit

        def emit(env):
            return sort_ops.sort_batch(inner(env), schema, keys, rank_tables)

        return _LNode(emit, schema, ln.dicts, ln.replicated, ln.cap)

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

        return _LNode(emit, ln.schema, ln.dicts, ln.replicated, out_cap)

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
    """One distributed query: plan rewrite + SPMD lowering + retry loop.

    The reference analog of DistSQLPlanner.PlanAndRunAll + the flow runtime
    (distsql_running.go:1751,:710), collapsed into build-jit-run."""

    def __init__(self, plan: S.PlanNode, catalog: Catalog, mesh,
                 broadcast_rows: int | None = None,
                 already_distributed: bool = False):
        self.catalog = catalog
        self.mesh = mesh
        self.D = mesh.shape[AXIS]
        # unsupported-for-distribution constructs fall back to local
        # operator execution — the reference's checkSupportForPlanNode
        # discipline (distsql_physical_planner.go:541): distribute what we
        # can, never fail a query for being non-distributable
        self._local_fallback = _needs_local(plan)
        if self._local_fallback:
            self.plan = plan
            self.dplan = plan  # explain() shows the (local) plan
            return
        self.dplan = plan if already_distributed else distribute(
            plan, catalog, broadcast_rows
        )
        self._build(factor=1)

    def _build(self, factor: int):
        self.factor = factor
        low = _Lowering(self.catalog, self.D, factor)
        root = low.lower(self.dplan)
        self.root = root
        nscans = len(low.scan_specs)

        def local_fn(*scan_batches):
            low.overflows = []
            low.emit_cache = {}
            out = root.emit(list(scan_batches))
            low.emit_cache = {}
            if low.overflows:
                ovf = sum(jnp.asarray(o, jnp.int32) for o in low.overflows)
            else:
                ovf = jnp.int32(0)
            return out, ovf[None]

        in_specs = tuple(P(AXIS) for _ in range(nscans))
        out_specs = (P() if root.replicated else P(AXIS), P(AXIS))
        # dispatch.jit so the whole-pipeline SPMD program counts into
        # sql_kernel_dispatches (one dispatch per run_batch attempt)
        self._fn = dispatch.jit(shard_map(
            local_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        ), name="dist_query")
        # global sharded scan inputs (partitioned-scan placement), cached:
        # scan shapes don't depend on `factor`, so overflow retries reuse
        # the already-uploaded shards instead of re-sharding every table
        from .dist import shard_batch

        if not hasattr(self, "_scan_cache"):
            self._scan_cache = {}
        self._scan_batches = []
        for spec in low.scan_specs:
            if spec not in self._scan_cache:
                tname, names, local_cap = spec
                t = self.catalog.get(tname)
                if hasattr(t, "columns"):
                    sub = t.schema.select(
                        tuple(t.schema.index(n) for n in names))
                    arrays = {n: np.asarray(t.columns[n]) for n in names}
                    valids = {n: t.valids[n]
                              for n in names if n in t.valids}
                    gb = from_host(sub, arrays, valids=valids,
                                   capacity=local_cap * self.D)
                else:
                    # KV-engine-backed table: snapshot the newest-visible
                    # rows through the direct columnar scan, then row-shard
                    # the snapshot like any other input (the
                    # range/leaseholder placement model would instead read
                    # per-device spans; one-snapshot-then-shard keeps the
                    # same SPMD program shape meanwhile)
                    from ..coldata.batch import compact

                    gb = t.device_batch(tuple(names))
                    # backstop for the snapshot/now() divergence (sizing
                    # uses snapshot_live_rows): compacting more live rows
                    # than planned would silently DROP the tail — fail
                    # loudly instead (one live-count sync at scan setup)
                    live = int(np.asarray(
                        jnp.sum(gb.mask, dtype=jnp.int32)))
                    if live > local_cap * self.D:
                        raise RuntimeError(
                            f"snapshot of {tname} holds {live} live rows "
                            f"but the plan sized {local_cap * self.D}; "
                            "re-plan after the snapshot moved"
                        )
                    gb = compact(gb, capacity=local_cap * self.D)
                self._scan_cache[spec] = shard_batch(gb, self.mesh)
            self._scan_batches.append(self._scan_cache[spec])

    def run_batch(self, max_retries: int = 4) -> tuple[Batch, Schema, dict]:
        """Execute with the overflow-retry loop; returns the global output
        batch (+ schema and dictionaries for host decode)."""
        for _ in range(max_retries):
            out, ovf = self._fn(*self._scan_batches)
            if int(np.asarray(ovf).sum()) == 0:
                return out, self.root.schema, self.root.dicts
            # a shuffle bucket or join output overflowed its static
            # capacity: double every stage capacity and re-lower
            self._build(factor=self.factor * 2)
        raise RuntimeError(
            f"distributed query still overflows at factor {self.factor}"
        )

    def run(self) -> dict[str, np.ndarray]:
        from ..utils.errors import query_boundary

        if self._local_fallback:
            from ..flow.runtime import run_operator
            from ..plan import builder as plan_builder

            return run_operator(plan_builder.build(self.plan, self.catalog))

        @query_boundary("distributed flow")
        def _go():
            out, schema, dicts = self.run_batch()
            return to_host(out, schema, dicts)

        return _go()

    def explain(self) -> str:
        from ..plan.explain import explain_plan

        if self._local_fallback:
            # checkSupportForPlanNode said no: the plan runs locally
            return ("distribution: local (plan not distributable)\n"
                    + explain_plan(self.dplan))
        return explain_plan(self.dplan)
