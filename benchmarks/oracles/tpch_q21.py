"""TPC-H Q21 (clause 2.4.21, Suppliers Who Kept Orders Waiting) in pandas,
independent of the engine, with the substitution parameter NATION. Written
from the text's meaning, not from the binder's min/max rewrite: a line is
late when l_receiptdate > l_commitdate; an l1 row counts when it is late,
its order has status 'F', the order has a line of ANOTHER supplier (EXISTS:
at least 2 distinct suppliers an order) and no late line of another supplier
(NOT EXISTS: exactly 1 distinct late supplier, which is l1's own, since l1
is late); then supplier, nation = NATION, count ROWS by s_name (two late
lines of the same lone supplier in one order count 2), numwait descending,
then s_name, the first 100. Every value is an integer or a string: limit 0
on keys and values. `not_exists=False` is the control: the same frame
WITHOUT the NOT EXISTS condition, which counts the suppliers that shared
their lateness too and has to fail by its counts.

The two per-order series (distinct suppliers, distinct late suppliers) and
the kept l1 rows joined to their supplier do not depend on NATION: they are
computed once a process (`_waiting`), so a window's four nations cost one
pass over lineitem, not four.

By hand at SF1: lineitem 6,002,051 rows x 24 B (l_orderkey and l_suppkey
int64, l_commitdate and l_receiptdate int32 days), orders 1,500,000 x 12 B
(int64 key, int32 status code), supplier 10,000 x 20 B (int64 key, int32
name code, int64 nation key), nation 25 x 12 B (int64 key, int32 name
code): 144,049,224 + 18,000,000 + 200,000 + 300 = 162,249,524 B = 0.162 GB,
0.20 ms at 819 GB/s. lineitem is counted ONCE though the text names it three
times: a plan can read it once.
"""

import types

TOUCHES = {"lineitem": ["l_orderkey", "l_suppkey", "l_commitdate",
                        "l_receiptdate"],
           "orders": ["o_orderkey", "o_orderstatus"],
           "supplier": ["s_suppkey", "s_name", "s_nationkey"],
           "nation": ["n_nationkey", "n_name"]}
KEYS = ["s_name"]
VALUES = ["numwait"]
LIMIT = 100

_WAITING: dict = {}  # id(loaded) -> (loaded, {not_exists: frame})


def _waiting(loaded, not_exists: bool):
    """The l1 rows the text keeps before `n_name = NATION`, one row a kept
    line, with the supplier's name and nation key."""
    hit = _WAITING.get(id(loaded))
    if hit is None or hit[0] is not loaded:
        _WAITING.clear()
        hit = _WAITING[id(loaded)] = (loaded, {})
    if not_exists not in hit[1]:
        li = loaded.frame("lineitem", TOUCHES["lineitem"])
        late = li[li.l_receiptdate > li.l_commitdate]
        suppliers = li.groupby("l_orderkey").l_suppkey.nunique()
        o = loaded.frame("orders", TOUCHES["orders"])
        f_orders = o.o_orderkey[o.o_orderstatus.astype(str) == "F"]
        keep = (late.l_orderkey.isin(f_orders)
                & late.l_orderkey.map(suppliers).ge(2))
        if not_exists:
            late_suppliers = late.groupby("l_orderkey").l_suppkey.nunique()
            keep &= late.l_orderkey.map(late_suppliers).eq(1)
        s = loaded.frame("supplier", TOUCHES["supplier"])
        hit[1][not_exists] = late.loc[keep, ["l_suppkey"]].merge(
            s, left_on="l_suppkey", right_on="s_suppkey")
    return hit[1][not_exists]


def answer(loaded, params: dict, not_exists: bool = True):
    import pandas as pd

    n = loaded.frame("nation", TOUCHES["nation"])
    nation = params.get("nation", "SAUDI ARABIA")
    keys = n.n_nationkey[n.n_name.astype(str) == nation]
    w = _waiting(loaded, not_exists)
    names = w.s_name[w.s_nationkey.isin(keys)].astype(str)
    counts = names.value_counts()
    want = (pd.DataFrame({"s_name": counts.index.to_numpy().astype(str),
                          "numwait": counts.to_numpy().astype("int64")})
            .sort_values(["numwait", "s_name"], ascending=[False, True])
            .head(LIMIT).reset_index(drop=True))
    return want


def check(ctx):
    from oracles import tpch

    # the shared comparison's own control is a float32 oracle: nothing to
    # lose in integers and strings, so it is not asked for; the control
    # here is the frame without its NOT EXISTS
    out = tpch.check(types.SimpleNamespace(**dict(vars(ctx), control=False)),
                     "tpch_q21")
    done = [r for r in ctx.records if r["err"] is None]
    # the LIMIT is reached: every nation's answer has its 100 rows, so the
    # comparison held 100 names and counts a statement, never an empty frame
    # (the configuration states the floor: 100 at SF1; a tiny rehearsal has
    # four suppliers a nation and states its own)
    fewest = min((len(r["rows"]) for r in done), default=0)
    out.append({"name": "answer_rows_min", "value": float(fewest),
                "limit": float(ctx.config.get("answer_rows_min", LIMIT)),
                "op": ">="})
    if ctx.control and done:
        params = done[0]["p"]
        want = answer(ctx.loaded, params)
        low = answer(ctx.loaded, params, not_exists=False)
        cols = [[str(v) for v in low[c].tolist()] for c in want.columns]
        bad, rel = tpch._compare(list(want.columns),
                                 [list(r) for r in zip(*cols)], want,
                                 types.SimpleNamespace(VALUES=VALUES))
        # numwait is compared as a value: a wrong count shows as a relative
        # error over the limit 0.0, a wrong name or row count as a key
        out.append({"name": "control.count_mismatch_without_not_exists",
                    "value": float(bad) + rel, "limit": 0.0,
                    "control": True})
    return out
