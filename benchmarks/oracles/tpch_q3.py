"""TPC-H Q3 in pandas, float64 (copied from bench.py's `_pandas_baseline`,
with the substitution parameters SEGMENT and DATE). `precision="float32"`
is the control."""

import datetime

import numpy as np

TOUCHES = {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"],
           "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"]}
KEYS = ["l_orderkey", "o_orderdate", "o_shippriority"]
VALUES = ["revenue"]


def answer(loaded, params: dict, precision: str = "float64"):
    date = (datetime.date.fromisoformat(params["date"])
            - datetime.date(1970, 1, 1)).days
    c = loaded.frame("customer", TOUCHES["customer"])
    o = loaded.frame("orders", TOUCHES["orders"])
    li = loaded.frame("lineitem", TOUCHES["lineitem"])
    cb = c[c.c_mktsegment == params.get("segment", "BUILDING")]
    ob = o[o.o_orderdate < date].merge(
        cb, left_on="o_custkey", right_on="c_custkey")
    lb = li[li.l_shipdate > date]
    j = lb.merge(ob, left_on="l_orderkey", right_on="o_orderkey")
    ft = np.dtype(precision)
    j["revenue"] = (j.l_extendedprice.astype(ft)
                    * (1 - j.l_discount.astype(ft)))
    want = (j.groupby(KEYS).agg(revenue=("revenue", "sum")).reset_index()
            .sort_values(["revenue", "o_orderdate"], ascending=[False, True])
            .head(10).reset_index(drop=True))
    return want


def check(ctx):
    from oracles import tpch

    return tpch.check(ctx, "tpch_q3")
