"""Zero-recompile serving-path guard (tier-1, sibling of
check_dispatch_budget.py).

Drives representative TPC-H queries through the prepared-plan cache
(sql/plancache.py) against flow/dispatch.py's compile accounting and
checks three properties:

- **cold budget**: the FIRST execution of each query compiles at most a
  recorded number of distinct kernels. Canonical tile shapes
  (catalog.SHAPE_BUCKETS) and the keyed kernel cache keep this small; a
  regression here is a shape or key leak (e.g. a per-table capacity
  sneaking back into kernel shapes).
- **bounded adaptation**: the SECOND execution (plan-cache hit, same
  literals) may re-specialize a handful of kernels once — join emission
  caps learn from run 1 (operators.post_run_update) — but within a small
  recorded budget. The background warmup thread runs each statement
  twice for exactly this reason.
- **zero-recompile serving**: the THIRD execution — same statement
  shape, DIFFERENT literals — must trigger 0 new XLA traces (the
  plan-cache hit rebinds literals as jit arguments, and learned
  capacities snap to the canonical shape ladder) and report a plan-cache
  hit. Its wall time is printed (the <100ms warm-serving target on real
  accelerators); only the compile count is asserted — CI machine speed
  varies.

Tier-1 runs the representative subset; ``--all`` sweeps every TPC-H
query. Runnable directly:

    python -m scripts.check_recompiles [--all]
"""

from __future__ import annotations

import os
import sys
import time

_SF = 0.001

# cold-compile budgets per query (distinct kernel specializations on a
# fresh process, fusion on, tile 1024, measured then padded ~50%): the
# fused pipeline + spool/consumer kernels + finalize/sort. Queries run in
# this order, so later queries already share earlier kernels (the
# process-global kernel cache) — budgets encode that sharing too.
BUDGETS = {
    "q1": 8,    # measured 4
    "q3": 18,   # measured 12
    "q6": 4,    # measured 2
    "q9": 21,   # measured 14
    "q18": 24,  # measured 16
}
# every query not listed above (the --all sweep) gets this generic cap
BUDGET_DEFAULT = 45
# run-2 adaptation: post_run_update switches join emission to compact
# mode at a learned cap, re-specializing once (measured ≤5 on the tier-1
# subset, ≤11 across the full sweep — q7's join tree)
BUDGET_ADAPT = 16

# literal overrides for the serving run: same statement shape, different
# values — the case the zero-recompile path exists for
_REBIND = {
    "q1": {"delta_days": 60},
    "q3": {"date": "1995-03-01"},
    "q6": {"date": "1995-01-01", "discount": 0.05},
    "q9": {"color": "red"},  # a string pattern: its host-prepared lookup
    "q18": {"quantity": 250},  # table rides as a plan argument (PR 28), so a
}                              # new colour compiles nothing


def check(all_queries: bool = False) -> list[str]:
    """Returns a list of human-readable violations (empty = clean)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from cockroach_tpu.bench import queries as Q
    from cockroach_tpu.bench.tpch import gen_tpch
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.sql import plancache
    from cockroach_tpu.utils import settings

    problems: list[str] = []
    names = list(Q.QUERIES) if all_queries else list(BUDGETS)
    try:
        settings.set("sql.distsql.fusion.enabled", True)
        settings.set("sql.distsql.shape_buckets.enabled", True)
        settings.set("sql.distsql.tile_size", 1024)
        settings.set("sql.plan_cache.enabled", True)
        cat = gen_tpch(sf=_SF, seed=3)
        for name in names:
            c0 = dispatch.compiles()
            _, status = plancache.run_cached(Q.QUERIES[name](cat))
            cold = dispatch.compiles() - c0
            budget = BUDGETS.get(name, BUDGET_DEFAULT)
            if cold > budget:
                problems.append(
                    f"{name}: cold run compiled {cold} kernels, budget "
                    f"{budget} — a kernel-cache key or canonical-shape "
                    "regression is minting per-query specializations")
            c1 = dispatch.compiles()
            plancache.run_cached(Q.QUERIES[name](cat))
            adapt = dispatch.compiles() - c1
            if adapt > BUDGET_ADAPT:
                problems.append(
                    f"{name}: adaptation run re-specialized {adapt} "
                    f"kernels, budget {BUDGET_ADAPT} — learned capacities "
                    "are not converging in one run")
            kwargs = _REBIND.get(name, {})
            c2 = dispatch.compiles()
            t0 = time.perf_counter()
            _, status2 = plancache.run_cached(Q.QUERIES[name](cat, **kwargs))
            warm_ms = (time.perf_counter() - t0) * 1e3
            recompiles = dispatch.compiles() - c2
            if status2 != "hit":
                problems.append(
                    f"{name}: serving run reported plan-cache status "
                    f"{status2!r}, expected 'hit' — the statement no "
                    "longer parameterizes to a stable plan key")
            if recompiles:
                problems.append(
                    f"{name}: serving run with rebound literals "
                    f"{kwargs or '(none)'} triggered {recompiles} new XLA "
                    "compiles, expected 0 — the zero-recompile serving "
                    "path is broken")
            print(f"  {name}: cold {cold}/{budget} compiles, adapt "
                  f"{adapt}/{BUDGET_ADAPT}, serve {recompiles} compiles "
                  f"{warm_ms:.1f}ms [{status}->{status2}]")
    finally:
        settings.reset("sql.distsql.fusion.enabled")
        settings.reset("sql.distsql.shape_buckets.enabled")
        settings.reset("sql.distsql.tile_size")
        settings.reset("sql.plan_cache.enabled")
    return problems


def main() -> int:
    all_queries = "--all" in sys.argv[1:]
    problems = check(all_queries=all_queries)
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if not problems:
        n = len(BUDGETS) if not all_queries else "all TPC-H"
        print(f"recompile guard clean ({n} queries): warmed repeats run "
              "with zero new XLA compiles within per-query cold budgets")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
