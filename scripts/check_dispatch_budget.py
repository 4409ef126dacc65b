"""Kernel-dispatch budget regression guard (tier-1, same spirit as
check_settings_registered.py).

Every case takes the path a user's statement takes: one ``Session`` over a
generated TPC-H catalog executes the served SQL text (bench/tpch_sql.py,
what the benchmark's cells send) until it has settled, and the case reads
the ``flow/dispatch.total()`` delta around the next ``execute`` — the
counter the benchmark's ``flow.dispatches_per_stmt`` divides.

- **steady total**: warm (post-adaptive-learning) dispatches of one
  statement must stay at or under its recorded budget. A fusion regression
  (a chain member silently falling back to its own per-operator jit)
  roughly doubles q1's; a join that stops fusing into the per-tile step
  adds one a tile.
- **per tile**: halving the tile size doubles q1's input tile count; the
  dispatch increase per extra tile must stay at or under BUDGET_PER_TILE
  (the fused pipeline pays exactly ONE pre-aggregation dispatch per tile).

Budgets are recorded constants, not ratios, so a regression shows up as a
hard failure with the measured numbers in the message. Runnable directly:

    python -m scripts.check_dispatch_budget
"""

from __future__ import annotations

import os
import sys

_SF = 0.001
_TILE = 1024
_SF_COMPACT = 0.01
_TILE_COMPACT = 1 << 20  # the setting's default
# learn, settle: the second execution re-specializes the kernels whose
# capacities the first one learned, the third runs the settled plan (the
# warm-up the cells do before their window opens)
_SETTLE = 3

# one budget a served text, warm at sf 0.001 / 1,024-row tiles (6 lineitem
# tiles; every join stays transparent there: the compaction cap's floor
# equals the tile). Each budget IS the CPU's reading, taken in PR 30
# (q18: PR 32) through Session.execute: the counts do not vary, so none
# has slack.
BUDGETS = {
    # 6 fused slice+filter+project+group+merge dispatches (fold seed + 5
    # fold steps) + finalize + 2 sort (the ORDER BY's spool with the
    # select list's projection fused in, then the sort). The unfused
    # engine reads 31.
    "q1": 9,
    # 3 pipe_filter_build_spool, 6 groupagg fold (seed + 5 steps, both
    # join probes inside), finalize, topk_fold_seed + limit_tile
    "q3": 12,
    # 6 build spools under the binder's reducing-first order (lineitem x
    # part(filtered) x supplier x nation x partsupp x orders), 6
    # pipe_hashjoin (all probes in one fused step a tile), 6 groupagg
    # fold, finalize, 2 sort; an unfused chain pays one dispatch +
    # readback per join per tile and blows well past this
    "q9": 21,
    # the IN (SELECT ... HAVING) grouping's 6 fold + finalize and its
    # keys' pipe_project_build_spool; the semi-join on `orders` BELOW both
    # joins (PR 32): 2 pipe_hashjoin_build_spool (orders' two tiles probed
    # inside lineitem's build spool) + 2 hashjoin_lut (the subquery's keys,
    # the kept orders); customer's pipe_scan_build_spool; the outer
    # aggregate's 6 hashagg_partial_fused (both inner probes inside) +
    # merge + finalize; ORDER BY ... LIMIT as a folded device top-k
    # (topk_fold_seed + limit_tile). 22 with the semi-join on top of the
    # joined rows (PR 30): one orders tile more is one spool more
    "q18": 23,
    # the one text whose join runs hash_join_general (PR 34): orders' 2
    # pipe_filter_build_spool (the NOT LIKE table bound), hashjoin_build
    # (the sorted index of a build key that repeats), customer's one tile
    # through hashjoin_emit in general mode, the dense aggregate's fold
    # seed + finalize, the outer aggregate's partial + finalize, 2 sort
    "q13": 10,
    # the one text with a correlated EXISTS and NOT EXISTS (PR 39), both
    # decorrelated as a GROUP BY l_orderkey with min and max of l_suppkey
    # joined back: each aggregate's 6 fold + finalize over a lineitem
    # pass of its own (14), 2 hashjoin_lut built from their outputs,
    # orders' 2 pipe_project_build_spool under the status filter,
    # supplier's pipe_scan_build_spool and nation's
    # pipe_project_build_spool, 6 pipe_hashjoin (the third pass: all five
    # probes, the LEFT one included, in one fused step a tile), the outer
    # dense aggregate's 6 fold + finalize, topk_fold_seed + limit_tile
    "q21": 35,
}
# q9 where the joins DO compact (sf 0.01, one lineitem tile at the default
# tile size): the part join cuts the tile to its cap and emits; the four
# joins above are handed tiles at their own cap and compose into the
# aggregate's fold (HashJoinOp._composes). Read 10 (5 build spools, 1
# emit, 1 fold seed, finalize, 2 sort); each upper join that goes back to
# emitting and compacting for itself adds one a tile (the parent of PR 29
# read 14), so the budget has no slack.
BUDGET_Q9_COMPACT = 10
# q18 on the route the chip takes at SF1, where 1.5M order keys pass the
# dense aggregate's state budget (`sql.distsql.dense_agg_states` at its
# floor here): the subquery's ordered GROUP BY streams (PR 33). Read 29:
# q18's 23 with the subquery's 6 fold + finalize replaced by 6
# hashagg_stream_fused (one a lineitem tile: partial, carried group,
# finalize) + hashagg_stream_tail, and pipe_project_build_spool once a
# streamed tile (7) where it ran once over the merged tile. The parent of
# PR 33 read 24 (6 partials + merge + finalize, one build spool); a
# hashagg_merge or a finalize of its own coming back adds to 29.
BUDGET_Q18_STREAMED = 29
# q21 on the route the chip takes at SF1 (PR 39): both decorrelated
# aggregates are ordered and stream, the second under the late filter
# (prefix_live=False). Read 35, as the dense route: each aggregate's 6 fold
# + finalize become 6 hashagg_stream_fused + hashagg_stream_tail, and the
# joins built from their streamed tiles are still one hashjoin_lut each. A
# hashagg_merge or a spool coming back adds to 35.
BUDGET_Q21_STREAMED = 35
# case -> (served text, budget, what a miss means)
STREAMED = {
    "q18_streamed": ("q18", BUDGET_Q18_STREAMED,
                     "the ordered aggregate spools and merges again, or "
                     "finalizes in a launch of its own"),
    "q21_streamed": ("q21", BUDGET_Q21_STREAMED,
                     "one of the two decorrelated aggregates spools and "
                     "merges, or a join built from one is built twice"),
}
# ONE fused pre-aggregation kernel per extra input tile (acceptance
# criterion of the fusion work; read exactly 1.0) — the accumulator merge
# rides inside the fold step kernel. The unfused engine pays 5.
BUDGET_PER_TILE = 1.0
# a distributed plan (partial agg -> all_to_all shuffle -> merge agg ->
# finalize over the 8-way mesh) is ONE SPMD program = ONE dispatch; the
# lower bound of 1 proves parallel/* kernels route through dispatch.jit
# and count at all (they used to call jax.jit directly and were invisible
# to this accounting).
BUDGET_SPMD = 2

CASES = (*BUDGETS, *STREAMED, "q1_per_tile", "q9_compact", "spmd")


def catalog(sf: float = _SF):
    """The generated catalog every case of one scale factor shares."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from cockroach_tpu.bench.tpch import gen_tpch

    return gen_tpch(sf=sf, seed=3)


def _steady_dispatches(cat, tile: int, qname: str) -> int:
    """Dispatches of one settled execution of TPCH_SQL[qname] through a
    Session (parser -> binder -> plan cache -> admission -> flow)."""
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.sql import Session
    from cockroach_tpu.utils import settings

    settings.set("sql.distsql.tile_size", tile)
    sess = Session(cat)
    try:
        for _ in range(_SETTLE):
            sess.execute(TPCH_SQL[qname])
        d0 = dispatch.total()
        sess.execute(TPCH_SQL[qname])
        return dispatch.total() - d0
    finally:
        sess.close()
        settings.reset("sql.distsql.tile_size")


def _spmd_dispatches() -> int:
    """Warm dispatches for one distributed groupby over an 8-way mesh."""
    import jax
    import numpy as np

    if len(jax.devices()) < 8:  # standalone run: conftest hasn't forced
        from cockroach_tpu.utils.backend import force_cpu_backend

        force_cpu_backend(8)
    from cockroach_tpu import coldata as cd
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.ops import aggregation as agg
    from cockroach_tpu.parallel import dist, mesh as mesh_mod

    mesh = mesh_mod.make_mesh(8)
    schema = cd.Schema.of(g=cd.INT64, v=cd.INT64)
    rng = np.random.default_rng(11)
    n = 2000
    b = cd.from_host(
        schema,
        {"g": rng.integers(0, 32, n), "v": rng.integers(0, 100, n)},
        capacity=512 * 8,
    )
    b = dist.shard_batch(b, mesh)
    fn, _ = dist.make_distributed_groupby(
        mesh, schema, (0,),
        (agg.AggSpec("sum", 1, "s"), agg.AggSpec("count_rows", None, "n")),
        local_capacity=512,
    )
    fn(b)  # warm: compile
    d0 = dispatch.total()
    fn(b)
    return dispatch.total() - d0


def case(name: str, cat=None) -> list[str]:
    """One entry of CASES; returns its violations (empty = clean). ``cat``
    is the sf-0.001 catalog when the caller already holds one."""
    if name == "spmd":
        spmd = _spmd_dispatches()
        if spmd < 1:
            return ["distributed groupby registered 0 kernel dispatches — "
                    "the SPMD plan no longer routes through "
                    "flow/dispatch.jit and is invisible to dispatch "
                    "accounting"]
        if spmd > BUDGET_SPMD:
            return [f"distributed groupby dispatches {spmd} exceed the "
                    f"budget {BUDGET_SPMD} — the partial-agg/shuffle/merge "
                    "pipeline is no longer one SPMD program"]
        return []
    if name == "q9_compact":
        got = _steady_dispatches(catalog(_SF_COMPACT), _TILE_COMPACT, "q9")
        if got > BUDGET_Q9_COMPACT:
            return [f"q9 (compacting joins) steady-state kernel dispatches "
                    f"{got} exceed the recorded budget {BUDGET_Q9_COMPACT} "
                    "— a join handed tiles already cut to its own cap "
                    "drives an emit of its own again instead of composing "
                    "into its consumer"]
        return []
    cat = cat if cat is not None else catalog()
    if name in STREAMED:
        from cockroach_tpu.utils import settings

        text, budget, miss = STREAMED[name]
        settings.set("sql.distsql.dense_agg_states", 64)
        try:
            got = _steady_dispatches(cat, _TILE, text)
        finally:
            settings.reset("sql.distsql.dense_agg_states")
        if got > budget:
            return [f"{text} (ordered GROUP BY streaming) steady-state "
                    f"kernel dispatches {got} exceed the recorded budget "
                    f"{budget} — {miss}"]
        return []
    if name == "q1_per_tile":
        tiles = -(-cat.get("lineitem").num_rows // _TILE)
        steady = _steady_dispatches(cat, _TILE, "q1")
        halved = _steady_dispatches(cat, _TILE // 2, "q1")
        per_tile = (halved - steady) / tiles
        if per_tile > BUDGET_PER_TILE:
            return [f"marginal dispatches per extra input tile "
                    f"{per_tile:.2f} ({steady} -> {halved} when tiles "
                    f"double from {tiles}) exceed the budget "
                    f"{BUDGET_PER_TILE} — the per-tile chain is no longer "
                    "one fused kernel"]
        return []
    got = _steady_dispatches(cat, _TILE, name)
    if got > BUDGETS[name]:
        return [f"{name} steady-state kernel dispatches {got} exceed the "
                f"recorded budget {BUDGETS[name]} — a pipeline member or a "
                "join probe stopped fusing into the per-tile step, or a "
                "new per-tile dispatch crept into the pull loop"]
    return []


def check() -> list[str]:
    """Every case; returns the human-readable violations (empty = clean)."""
    cat = catalog()
    return [p for name in CASES for p in case(name, cat)]


def main() -> int:
    problems = check()
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if not problems:
        print("dispatch budget clean: "
              + ", ".join(f"{q} within {b}" for q, b in BUDGETS.items())
              + "".join(f", {t} streaming within {b}"
                        for t, b, _miss in STREAMED.values())
              + f", q9 compacting within {BUDGET_Q9_COMPACT}, "
              f"{BUDGET_PER_TILE} a tile, distributed plan within "
              f"{BUDGET_SPMD}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
