"""TPC-H Q1 in pandas, float64 (copied from bench.py's `_pandas_baseline`,
with the substitution parameter DELTA). `precision="float32"` is the
control: the same arithmetic one precision down."""

import numpy as np

TOUCHES = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax"]}
KEYS = ["l_returnflag", "l_linestatus"]
VALUES = ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
          "avg_qty", "avg_price", "avg_disc", "count_order"]
_CUTOFF = 10561  # 1998-12-01 as days since 1970-01-01


def answer(loaded, params: dict, precision: str = "float64"):
    li = loaded.frame("lineitem", TOUCHES["lineitem"])
    f = li[li.l_shipdate <= _CUTOFF - int(params["delta"])].copy()
    ft = np.dtype(precision)
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        f[c] = f[c].astype(ft)
    f["disc_price"] = f.l_extendedprice * (1 - f.l_discount)
    f["charge"] = f.disc_price * (1 + f.l_tax)
    base = (f.groupby(KEYS).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size")).sort_index().reset_index())
    return base


def check(ctx):
    from oracles import tpch

    return tpch.check(ctx, "tpch_q1")
