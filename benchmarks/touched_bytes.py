"""Bytes a statement has to read from HBM at the least: every column the
query touches, read once, at the tables' own dtypes. The column lists are
the oracle modules' `TOUCHES` (benchmarks/oracles/<oracle>.py), so they
cannot drift from what the reference reads.

By hand for q1 at SF1: l_quantity, l_extendedprice, l_discount, l_tax are
scaled int64 (8 B each), l_returnflag and l_linestatus dictionary codes
(int32, 4 B each), l_shipdate days (int32, 4 B): 44 B a row, times
6,002,051 rows = 264,090,244 B = 0.264 GB, 0.32 ms at 819 GB/s.
"""

import importlib


def touched_bytes(loaded, oracle: str) -> int | None:
    touches = getattr(importlib.import_module(f"oracles.{oracle}"),
                      "TOUCHES", None)
    if not touches or not hasattr(loaded, "column_bytes"):
        return None
    return sum(loaded.column_bytes(t, cols) for t, cols in touches.items())
