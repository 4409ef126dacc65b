"""TPC-H Q9 (clause 2.4.9, Product Type Profit Measure) in pandas, float64,
with the substitution parameter COLOR. `precision="float32"` is the
control: the same arithmetic one precision down.

The five unfiltered tables are joined once a process (`_joined`); an answer
filters that frame by the parts whose name holds the colour, so the check of
a window's four colours costs one chain of merges, not four.

By hand at SF1: lineitem 6,002,051 rows x 48 B (three int64 keys, three
scaled-int64 decimals), part 200,000 x 12 B (int64 key, int32 dictionary
code), supplier 10,000 x 16 B, partsupp 800,000 x 24 B, orders 1,500,000 x
12 B, nation 25 x 12 B = 327,858,748 B = 0.328 GB, 0.40 ms at 819 GB/s.
"""

import numpy as np

TOUCHES = {"lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                        "l_extendedprice", "l_discount"],
           "part": ["p_partkey", "p_name"],
           "supplier": ["s_suppkey", "s_nationkey"],
           "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
           "orders": ["o_orderkey", "o_orderdate"],
           "nation": ["n_nationkey", "n_name"]}
KEYS = ["nation", "o_year"]
VALUES = ["sum_profit"]

_JOINED: dict = {}  # id(loaded) -> (loaded, frame): one chain of merges a process


def _joined(loaded):
    hit = _JOINED.get(id(loaded))
    if hit is not None and hit[0] is loaded:
        return hit[1]
    li = loaded.frame("lineitem", TOUCHES["lineitem"])
    ps = loaded.frame("partsupp", TOUCHES["partsupp"])
    s = loaded.frame("supplier", TOUCHES["supplier"])
    n = loaded.frame("nation", TOUCHES["nation"])
    o = loaded.frame("orders", TOUCHES["orders"])
    j = li.merge(ps, left_on=["l_partkey", "l_suppkey"],
                 right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(s, left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(n, left_on="s_nationkey", right_on="n_nationkey")
    j = j.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    days = j.o_orderdate.to_numpy().astype("datetime64[D]")
    j = j[["l_partkey", "l_quantity", "l_extendedprice", "l_discount",
           "ps_supplycost"]].assign(
        nation=j.n_name.astype(str),
        o_year=days.astype("datetime64[Y]").astype(np.int64) + 1970)
    _JOINED.clear()
    _JOINED[id(loaded)] = (loaded, j)
    return j


def answer(loaded, params: dict, precision: str = "float64"):
    p = loaded.frame("part", TOUCHES["part"])
    color = params.get("color", "green")
    keys = p.p_partkey[[color in str(v) for v in p.p_name]]
    j = _joined(loaded)
    j = j[j.l_partkey.isin(keys)]
    ft = np.dtype(precision)
    amount = (j.l_extendedprice.astype(ft) * (1 - j.l_discount.astype(ft))
              - j.ps_supplycost.astype(ft) * j.l_quantity.astype(ft))
    want = (j.assign(sum_profit=amount).groupby(KEYS)
            .agg(sum_profit=("sum_profit", "sum")).reset_index()
            .sort_values(KEYS, ascending=[True, False])
            .reset_index(drop=True))
    return want


def check(ctx):
    from oracles import tpch

    return tpch.check(ctx, "tpch_q9")
