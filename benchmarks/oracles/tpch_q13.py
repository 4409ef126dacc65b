"""TPC-H Q13 (clause 2.4.13, Customer Distribution) in pandas, independent of
the engine, with the substitution parameters WORD1 and WORD2: `orders` without
the rows whose o_comment matches `.*WORD1.*WORD2.*`, LEFT-merged onto
`customer`, `count(o_orderkey)` a customer (a NULL-extended row counts 0),
then how many customers have each count; custdist descending, then c_count
descending. Every number is an integer: limit 0 on keys and on values, no
tolerance to argue. `how="inner"` is the control: an inner join loses every
customer without an order, so the `c_count = 0` row, and has to fail by keys.

The pattern is matched once a distinct comment (the generator draws from a
pool), not once a row.

By hand at SF1: customer.c_custkey 150,000 x 8 B; orders.o_orderkey and
o_custkey int64 and o_comment an int32 dictionary code, 1,500,000 x 20 B:
1,200,000 + 30,000,000 = 31,200,000 B = 0.0312 GB, 0.038 ms at 819 GB/s.
"""

import re
import types

TOUCHES = {"customer": ["c_custkey"],
           "orders": ["o_orderkey", "o_custkey", "o_comment"]}
KEYS = ["c_count"]
VALUES = ["custdist"]

_FRAMES: dict = {}  # id(loaded) -> (loaded, customer, orders): decoded once


def _frames(loaded):
    hit = _FRAMES.get(id(loaded))
    if hit is not None and hit[0] is loaded:
        return hit[1], hit[2]
    c = loaded.frame("customer", TOUCHES["customer"])
    o = loaded.frame("orders", TOUCHES["orders"])
    _FRAMES.clear()
    _FRAMES[id(loaded)] = (loaded, c, o)
    return c, o


def answer(loaded, params: dict, how: str = "left"):
    import pandas as pd

    c, o = _frames(loaded)
    rx = re.compile(f".*{params.get('word1', 'special')}"
                    f".*{params.get('word2', 'requests')}.*")
    comment = o.o_comment.astype(str)
    hit = {s for s in pd.unique(comment) if rx.match(s)}
    kept = o[~comment.isin(hit)]
    j = c.merge(kept, how=how, left_on="c_custkey", right_on="o_custkey")
    c_count = j.groupby("c_custkey").o_orderkey.count()
    dist = c_count.value_counts()
    want = (pd.DataFrame({"c_count": dist.index.to_numpy().astype("int64"),
                          "custdist": dist.to_numpy().astype("int64")})
            .sort_values(["custdist", "c_count"], ascending=[False, False])
            .reset_index(drop=True))
    return want


def zero_order_customers(names, rows) -> int:
    """The custdist of the `c_count = 0` row of one answer; 0 without it."""
    if not names or "c_count" not in names or "custdist" not in names:
        return 0
    k, v = names.index("c_count"), names.index("custdist")
    return next((int(r[v]) for r in rows
                 if r[k] is not None and int(r[k]) == 0), 0)


def check(ctx):
    from oracles import tpch

    # the shared comparison's own control is a float32 oracle: nothing to
    # lose in integers, so it is not asked for; the control here is the
    # inner join
    out = tpch.check(types.SimpleNamespace(**dict(vars(ctx), control=False)),
                     "tpch_q13")
    done = [r for r in ctx.records if r["err"] is None]
    # an outer join that drops its NULL-extended rows can never pass: the
    # customers without an order, in the engine's own answers
    fewest = min((zero_order_customers(r["names"], r["rows"]) for r in done),
                 default=0)
    out.append({"name": "zero_order_customers_min", "value": float(fewest),
                "limit": 1.0, "op": ">="})
    if ctx.control and done:
        params = done[0]["p"]
        want = answer(ctx.loaded, params)
        low = answer(ctx.loaded, params, how="inner")
        cols = [[str(v) for v in low[c].tolist()] for c in want.columns]
        bad, _rel = tpch._compare(list(want.columns),
                                  [list(r) for r in zip(*cols)], want,
                                  types.SimpleNamespace(VALUES=VALUES))
        out.append({"name": "control.key_mismatches_inner_join",
                    "value": float(bad), "limit": 0.0, "control": True})
    return out
