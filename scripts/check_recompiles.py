"""Zero-recompile serving-path guard (tier-1, sibling of
check_dispatch_budget.py).

Sends the served TPC-H SQL text (bench/tpch_sql.py, what the benchmark's
cells send) through one ``Session`` — parser, binder, plan cache,
admission, flow — against flow/dispatch.py's compile accounting and checks
three properties a query:

- **cold budget**: the FIRST execution compiles at most a recorded number
  of distinct kernels. Canonical tile shapes (catalog.SHAPE_BUCKETS) and
  the keyed kernel cache keep this small; a regression here is a shape or
  key leak (e.g. a per-table capacity sneaking back into kernel shapes).
- **bounded adaptation**: the SECOND execution (the same text) may
  re-specialize a handful of kernels once — join emission caps learn from
  run 1 (operators.post_run_update) — but within a small recorded budget.
- **zero-recompile serving**: the THIRD execution — the text with another
  value of one of the query's substitution parameters (TPC-H clause 2.4),
  changed in the text the way a cell's traffic changes it — must hit the
  plan cache and trigger 0 new XLA traces (the hit rebinds literals and
  host-prepared lookup tables as jit arguments, and learned capacities
  snap to the canonical shape ladder). Its wall time is printed; only the
  compile count is asserted — CI machine speed varies.

Tier-1 runs all 22 texts, one pytest case a query; the CLI runs the
representative subset (BUDGETS) and ``--all`` sweeps the 22. Runnable
directly:

    python -m scripts.check_recompiles [--all]
"""

from __future__ import annotations

import os
import sys
import time

_SF = 0.001
_TILE = 1024

# cold-compile budgets per query: distinct kernel specializations of the
# first execution with no kernel shared from another query (each case
# starts from an empty kernel cache, so the count does not depend on what
# ran before), tile 1024; the CPU's reading in PR 30 beside each
BUDGETS = {
    "q1": 5,
    "q3": 9,
    "q6": 3,
    "q9": 15,
    "q18": 16,  # 15 before PR 32 put the semi-join below the joins
    # the one text whose join runs hash_join_general (PR 34): 2 build
    # spools' worth of orders tiles, hashjoin_build, the general emit, the
    # dense aggregate's fold and finalize, the outer aggregate, the sort
    "q13": 9,
    # the one text with a correlated EXISTS and NOT EXISTS (PR 39): the
    # two decorrelated min/max aggregates' fold and finalize, 2
    # hashjoin_lut built from them, 4 build spools (orders, supplier,
    # nation), the five probes in one pipe_hashjoin, the outer fold,
    # top-k and limit
    "q21": 21,
}
# every query not listed above (the --all sweep) gets this generic cap
BUDGET_DEFAULT = 45
# run-2 adaptation: post_run_update switches join emission to compact
# mode at a learned cap, re-specializing once (read at most 3 across the
# 22 texts: q2, q5, q7, q8, q9, q21; q20's join tree read 4 until PR 32
# put its IN-subquery's semi-join below the join, and reads 2)
BUDGET_ADAPT = 3

# the serving run's text: (old, new) substitutions of one or two of the
# query's substitution parameters, by the clause that defines them
_REBIND = {
    "q1": (("- 90", "- 60"),),                                   # DELTA
    "q2": (("p_size = 15", "p_size = 23"),),                     # SIZE
    "q3": (("1995-03-15", "1995-03-01"),),                       # DATE
    "q4": (("1993-07-01", "1994-01-01"),),                       # DATE
    "q5": (("1994-01-01", "1995-01-01"),),                       # DATE
    "q6": (("1994-01-01", "1995-01-01"),                         # DATE
           ("between 0.05 and 0.07", "between 0.04 and 0.06")),  # DISCOUNT
    "q7": (("FRANCE", "CANADA"),),                               # NATION1
    "q8": (("BRAZIL", "PERU"),),                                 # NATION
    "q9": (("green", "red"),),                                   # COLOR
    "q10": (("1993-10-01", "1994-01-01"),),                      # DATE
    "q11": (("0.0001", "0.0002"),),                              # FRACTION
    "q12": (("1994-01-01", "1995-01-01"),),                      # DATE
    "q13": (("special", "pending"),),                            # WORD1
    "q14": (("1995-09-01", "1996-03-01"),                        # DATE
            ("1995-10-01", "1996-04-01")),
    "q15": (("1996-01-01", "1996-04-01"),),                      # DATE
    "q16": (("(49, 14, 23, 45, 19, 3, 36, 9)",                   # SIZE1-8
             "(48, 15, 22, 44, 18, 4, 35, 8)"),),
    "q17": (("MED BOX", "LG CASE"),),                            # CONTAINER
    "q18": (("> 300", "> 250"),),                                # QUANTITY
    "q19": (("l_quantity >= 1 and l_quantity <= 11",             # QUANTITY1
             "l_quantity >= 2 and l_quantity <= 12"),),
    "q20": (("forest", "azure"),),                               # COLOR
    "q21": (("SAUDI ARABIA", "JAPAN"),),                         # NATION
    "q22": (("'13', '31', '23', '29', '30', '18', '17'",         # I1-7
             "'14', '32', '24', '28', '31', '19', '16'"),),
}

# texts that miss the serving guarantee on this route, with PR 30's
# reading (ROADMAP Queue 1 has each): the case is EXPECTED to fail — a
# strict xfail in tests/test_recompiles.py, "known" in the CLI — and
# fails the gate on the day it passes, so the entry goes with the fix.
# The gate before PR 30 reran these with the literal unchanged.
KNOWN = {
    "q8": "NATION is a string in a CASE arm: plan-cache miss, all 19 "
          "kernels compile again for every nation",
    "q11": "hit, but a new FRACTION compiles 6 kernels once (a learned "
           "capacity moves with the HAVING threshold)",
    "q15": "hit, but a new DATE compiles 5 kernels once (the revenue "
           "view's cardinality moves a learned capacity)",
    "q22": "hit, but new country codes compile 2 kernels once",
}


def rebound(name: str) -> str:
    """TPCH_SQL[name] with the query's parameter changed in the text."""
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL

    text = TPCH_SQL[name]
    for old, new in _REBIND[name]:
        if old not in text:
            raise KeyError(f"{name}: {old!r} is not in the served text")
        text = text.replace(old, new)
    return text


def open_session():
    """A Session over the generated catalog all cases share."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from cockroach_tpu.bench.tpch import gen_tpch
    from cockroach_tpu.sql import Session

    return Session(gen_tpch(sf=_SF, seed=3))


def case(sess, name: str) -> list[str]:
    """Cold, adaptation and serving run of one query through ``sess``;
    returns its violations (empty = clean)."""
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.sql import plancache
    from cockroach_tpu.utils import settings

    problems: list[str] = []
    cache = plancache.cache_for(sess.catalog)
    settings.set("sql.distsql.tile_size", _TILE)
    try:
        dispatch.clear_kernel_cache()
        c0 = dispatch.compiles()
        sess.execute(TPCH_SQL[name])
        cold = dispatch.compiles() - c0
        budget = BUDGETS.get(name, BUDGET_DEFAULT)
        if cold > budget:
            problems.append(
                f"{name}: cold run compiled {cold} kernels, budget "
                f"{budget} — a kernel-cache key or canonical-shape "
                "regression is minting per-query specializations")
        c1 = dispatch.compiles()
        sess.execute(TPCH_SQL[name])
        adapt = dispatch.compiles() - c1
        if adapt > BUDGET_ADAPT:
            problems.append(
                f"{name}: adaptation run re-specialized {adapt} "
                f"kernels, budget {BUDGET_ADAPT} — learned capacities "
                "are not converging in one run")
        text = rebound(name)
        c2, h0 = dispatch.compiles(), cache.hits
        t0 = time.perf_counter()
        sess.execute(text)
        warm_ms = (time.perf_counter() - t0) * 1e3
        recompiles = dispatch.compiles() - c2
        hit = cache.hits - h0
        if hit != 1:
            problems.append(
                f"{name}: serving run with {_REBIND[name]} hit the plan "
                f"cache {hit} times, expected 1 — the statement no "
                "longer parameterizes to a stable plan key")
        if recompiles:
            problems.append(
                f"{name}: serving run with {_REBIND[name]} triggered "
                f"{recompiles} new XLA compiles, expected 0 — the "
                "zero-recompile serving path is broken")
        print(f"  {name}: cold {cold}/{budget} compiles, adapt "
              f"{adapt}/{BUDGET_ADAPT}, serve {recompiles} compiles "
              f"{warm_ms:.1f}ms [{'hit' if hit == 1 else 'miss'}]")
    finally:
        settings.reset("sql.distsql.tile_size")
    return problems


def check(all_queries: bool = False) -> list[str]:
    """Returns a list of human-readable violations (empty = clean)."""
    problems: list[str] = []
    sess = open_session()
    try:
        for name in (_REBIND if all_queries else BUDGETS):
            found = case(sess, name)
            if name not in KNOWN:
                problems += found
            elif found:
                print(f"  {name}: known ({KNOWN[name]})")
            else:
                problems.append(
                    f"{name}: keeps the serving guarantee now — take it "
                    "out of KNOWN")
    finally:
        sess.close()
    return problems


def main() -> int:
    all_queries = "--all" in sys.argv[1:]
    problems = check(all_queries=all_queries)
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if not problems:
        n = len(_REBIND if all_queries else BUDGETS)
        print(f"recompile guard clean ({n} queries): a served text with "
              "another parameter runs with zero new XLA compiles, within "
              "per-query cold budgets")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
