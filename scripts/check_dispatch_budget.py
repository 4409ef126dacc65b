"""Kernel-dispatch budget regression guard (tier-1, same spirit as
check_settings_registered.py).

Runs ONE representative fused query — TPC-H q1, a scan -> filter ->
project -> group-by chain — at two tile sizes and checks two budgets
against flow/dispatch.py's per-call accounting:

- **steady total**: warm (post-adaptive-learning) dispatches for the whole
  query must stay at or under BUDGET_STEADY. A fusion regression (a chain
  member silently falling back to its own per-operator jit) roughly
  doubles this.
- **per tile**: halving the tile size doubles the input tile count; the
  dispatch increase per extra tile must stay at or under BUDGET_PER_TILE
  (the fused pipeline pays exactly ONE pre-aggregation dispatch per tile).

Budgets are recorded constants, not ratios, so a regression shows up as a
hard failure with the measured numbers in the message. Runnable directly:

    python -m scripts.check_dispatch_budget
"""

from __future__ import annotations

import os
import sys

# measured 8 with the fusion pass on (6 input tiles): 6 fused
# slice+filter+project+group+merge dispatches + finalize + sort. The
# unfused engine measures 31.
BUDGET_STEADY = 10
# the join-plane queries, same harness: q9's part|supplier|orders chain
# probes its build tables inside the fused per-tile step kernel (measured
# 20 warm at sf 0.001 / 6 lineitem tiles; an unfused chain pays one
# dispatch + readback per join per tile and blows well past this), and
# q18's ORDER BY ... LIMIT runs as a folded device top-k instead of a
# full sort spool (measured 23).
BUDGET_STEADY_Q9 = 24
BUDGET_STEADY_Q18 = 27
# q9 as the served SQL text (bench/tpch_sql.py, what the cell tpch_sf1.q9
# sends), under the reducing-first default join order (lineitem x
# part(filtered) x supplier x nation x partsupp x orders). Re-read in
# PR 29: 21 warm at sf 0.001 / 6 lineitem tiles (the trees above read 20
# and 23 as before) — at 1,024-row tiles every join stays transparent
# (the compaction cap floor equals the tile), so neither the order nor
# the pass-through of compact joins moves this count. One more than the
# Rel-built q9 above (its order is written by hand, the binder never sees
# it): its part join is inner with a build spool, not a semi probe.
BUDGET_STEADY_Q9_SQL = 25
# the same text where the joins DO compact (sf 0.01, one lineitem tile at
# the default tile size): the part join cuts the tile to its cap and
# emits; the four joins above are handed tiles at their own cap and
# compose into the aggregate's fold (HashJoinOp._composes). Measured
# 10 (5 build spools, 1 emit, 1 fold seed, finalize, 2 sort); each upper
# join that goes back to emitting and compacting for itself adds one a
# tile (the parent of PR 29 read 14), so the budget has no slack.
BUDGET_STEADY_Q9_SQL_COMPACT = 10
# ONE fused pre-aggregation kernel per extra input tile (acceptance
# criterion of the fusion work; measured exactly 1.0) — the accumulator
# merge rides inside the fold step kernel. The unfused engine pays 5.
BUDGET_PER_TILE = 1.25
# a distributed plan (partial agg -> all_to_all shuffle -> merge agg ->
# finalize over the 8-way mesh) is ONE SPMD program = ONE dispatch; the
# lower bound of 1 proves parallel/* kernels route through dispatch.jit
# and count at all (they used to call jax.jit directly and were invisible
# to this accounting).
BUDGET_SPMD = 2

_SF = 0.001
_TILE = 1024
_SF_COMPACT = 0.01
_TILE_COMPACT = 1 << 20  # the setting's default


def _steady_dispatches(cat, tile: int, qname: str = "q1",
                       text: str | None = None) -> int:
    from cockroach_tpu.bench import queries as Q
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.plan import builder as plan_builder
    from cockroach_tpu.utils import settings

    settings.set("sql.distsql.tile_size", tile)
    if text is None:
        rel = Q.QUERIES[qname](cat)
    else:
        from cockroach_tpu.sql import sql

        rel = sql(cat, text)
    root = plan_builder.build(rel.optimized_plan(), cat)
    run_operator(root)  # warm: compile + adaptive capacity learning
    d0 = dispatch.total()
    run_operator(root)
    return dispatch.total() - d0


def _spmd_dispatches() -> int:
    """Warm dispatches for one distributed groupby over an 8-way mesh."""
    import numpy as np

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


def check() -> list[str]:
    """Returns a list of human-readable violations (empty = clean)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from cockroach_tpu.bench.tpch import gen_tpch
    from cockroach_tpu.utils import settings

    import jax

    if len(jax.devices()) < 8:  # standalone run: conftest hasn't forced
        from cockroach_tpu.utils.backend import force_cpu_backend

        force_cpu_backend(8)  # the SPMD case needs the full virtual mesh
    problems = []
    try:
        settings.set("sql.distsql.fusion.enabled", True)
        cat = gen_tpch(sf=_SF, seed=3)
        tiles = -(-cat.get("lineitem").num_rows // _TILE)
        steady = _steady_dispatches(cat, _TILE)
        if steady > BUDGET_STEADY:
            problems.append(
                f"q1 steady-state kernel dispatches {steady} exceed the "
                f"recorded budget {BUDGET_STEADY} ({tiles} input tiles) — "
                "a pipeline member stopped fusing or a new per-tile "
                "dispatch crept into the pull loop")
        halved = _steady_dispatches(cat, _TILE // 2)
        per_tile = (halved - steady) / tiles
        if per_tile > BUDGET_PER_TILE:
            problems.append(
                f"marginal dispatches per extra input tile {per_tile:.2f} "
                f"({steady} -> {halved} when tiles double from {tiles}) "
                f"exceed the budget {BUDGET_PER_TILE} — the per-tile "
                "chain is no longer one fused kernel")
        for qname, budget in (("q9", BUDGET_STEADY_Q9),
                              ("q18", BUDGET_STEADY_Q18)):
            got = _steady_dispatches(cat, _TILE, qname)
            if got > budget:
                problems.append(
                    f"{qname} steady-state kernel dispatches {got} exceed "
                    f"the recorded budget {budget} — the multiway fused "
                    "probe (q9) or device top-k fold (q18) stopped "
                    "covering the join plane's per-tile work")
        from cockroach_tpu.bench.tpch_sql import TPCH_SQL

        got = _steady_dispatches(cat, _TILE, text=TPCH_SQL["q9"])
        if got > BUDGET_STEADY_Q9_SQL:
            problems.append(
                f"q9 (SQL text) steady-state kernel dispatches {got} "
                f"exceed the recorded budget {BUDGET_STEADY_Q9_SQL} — a "
                "join of the served six-table plan stopped fusing into "
                "the per-tile step")
        got = _steady_dispatches(gen_tpch(sf=_SF_COMPACT, seed=3),
                                 _TILE_COMPACT, text=TPCH_SQL["q9"])
        if got > BUDGET_STEADY_Q9_SQL_COMPACT:
            problems.append(
                f"q9 (SQL text, compacting joins) steady-state kernel "
                f"dispatches {got} exceed the recorded budget "
                f"{BUDGET_STEADY_Q9_SQL_COMPACT} — a join handed tiles "
                "already cut to its own cap drives an emit of its own "
                "again instead of composing into its consumer")
        spmd = _spmd_dispatches()
        if spmd < 1:
            problems.append(
                "distributed groupby registered 0 kernel dispatches — the "
                "SPMD plan no longer routes through flow/dispatch.jit and "
                "is invisible to dispatch accounting")
        elif spmd > BUDGET_SPMD:
            problems.append(
                f"distributed groupby dispatches {spmd} exceed the budget "
                f"{BUDGET_SPMD} — the partial-agg/shuffle/merge pipeline "
                "is no longer one SPMD program")
    finally:
        settings.reset("sql.distsql.tile_size")
        settings.reset("sql.distsql.fusion.enabled")
    return problems


def main() -> int:
    problems = check()
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if not problems:
        print("dispatch budget clean: fused pipeline within "
              f"{BUDGET_STEADY} steady / {BUDGET_PER_TILE}-per-tile, "
              f"q9 within {BUDGET_STEADY_Q9} (SQL text "
              f"{BUDGET_STEADY_Q9_SQL}, compacting "
              f"{BUDGET_STEADY_Q9_SQL_COMPACT}), q18 within "
              f"{BUDGET_STEADY_Q18}, distributed plan within "
              f"{BUDGET_SPMD}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
