"""TPC-H Q18 (clause 2.4.18, Large Volume Customer) in pandas, float64, with
the substitution parameter QUANTITY. `precision="float32"` is the control:
the same frame one precision down. `sum_qty` is at most 350 and exact in
float32, so the control has to miss by `o_totalprice` (about 3e-8 at
500,000.00).

The sum of l_quantity an order is computed once a process (`_order_qty`):
an answer keeps the orders over QUANTITY, so the check of a window's four
thresholds costs one 6.0M-row group-by, not four.

By hand at SF1: lineitem 6,002,051 rows x 16 B (int64 key, scaled-int64
quantity), orders 1,500,000 x 28 B (two int64 keys, int32 days, scaled-int64
price), customer 150,000 x 12 B (int64 key, int32 dictionary code) =
139,832,816 B = 0.140 GB, 0.17 ms at 819 GB/s. The subquery's second read of
lineitem is not counted: a plan can read it once.
"""

import numpy as np

TOUCHES = {"lineitem": ["l_orderkey", "l_quantity"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_totalprice"],
           "customer": ["c_custkey", "c_name"]}
KEYS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"]
VALUES = ["o_totalprice", "sum_qty"]

_QTY: dict = {}  # id(loaded) -> (loaded, series): one group-by a process


def _order_qty(loaded):
    hit = _QTY.get(id(loaded))
    if hit is not None and hit[0] is loaded:
        return hit[1]
    li = loaded.frame("lineitem", TOUCHES["lineitem"])
    qty = li.groupby("l_orderkey").l_quantity.sum()
    _QTY.clear()
    _QTY[id(loaded)] = (loaded, qty)
    return qty


def answer(loaded, params: dict, precision: str = "float64"):
    qty = _order_qty(loaded)
    keys = qty.index[qty > float(params.get("quantity", 300))]
    o = loaded.frame("orders", TOUCHES["orders"])
    c = loaded.frame("customer", TOUCHES["customer"])
    li = loaded.frame("lineitem", TOUCHES["lineitem"])
    j = (o[o.o_orderkey.isin(keys)]
         .merge(c, left_on="o_custkey", right_on="c_custkey")
         .merge(li[li.l_orderkey.isin(keys)], left_on="o_orderkey",
                right_on="l_orderkey"))
    ft = np.dtype(precision)
    j = j.assign(c_name=j.c_name.astype(str),
                 o_totalprice=j.o_totalprice.astype(ft),
                 l_quantity=j.l_quantity.astype(ft))
    want = (j.groupby(KEYS).agg(sum_qty=("l_quantity", "sum")).reset_index()
            .sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True])
            .head(100).reset_index(drop=True))
    return want


def check(ctx):
    from oracles import tpch

    out = tpch.check(ctx, "tpch_q18")
    # an empty answer compares equal to an empty reference: a run whose
    # thresholds keep no order has checked nothing
    fewest = min((len(r["rows"]) for r in ctx.records if r["err"] is None),
                 default=0)
    out.append({"name": "answer_rows_min", "value": float(fewest),
                "limit": 1.0, "op": ">="})
    return out
