"""Bytes an all-to-all stage has to move at the least: its live rows whose
destination is another chip, at the width of a row on the wire, which is
every column's data at the table's own dtype plus one byte of validity (the
engine sends a `valid` byte a column beside the data; the row mask is the
prefix of each bucket and is not counted).

By hand for Q3's first stage at SF1: the filtered lineitem rows carry
l_orderkey, l_extendedprice, l_discount (int64 each) = 3 x (8 + 1) = 27 B a
row; about 3.24M rows pass `l_shipdate > DATE`, 3/4 of them leave their
chip: 2.43M x 27 B = 65.6 MB over four chips, 16.4 MB a chip, 0.082 ms at
200 GB/s (1,600 Gbit/s)."""

VALID_BYTES = 1

# the columns of Q3's five hash-routed stages, in the plan's order
# (EXPLAIN (DISTSQL) of traffic/q3_stream.json, leaves first)
Q3_STAGES = [
    ("lineitem", ["l_orderkey", "l_extendedprice", "l_discount"]),
    ("orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]),
    ("lineitem+orders", ["l_orderkey", "l_extendedprice", "l_discount",
                         "o_orderkey", "o_custkey", "o_orderdate",
                         "o_shippriority"]),
    ("customer", ["c_custkey"]),
    ("partial states", ["l_orderkey", "o_orderdate", "o_shippriority",
                        "l_extendedprice"]),  # the sum's state: int64
]


def row_bytes(loaded, columns: list[str]) -> int:
    """Bytes of one row of ``columns`` on the wire, from the host tables'
    own dtypes."""
    import numpy as np

    by_name = {c: np.asarray(t.columns[c]).dtype.itemsize
               for t in loaded.tables.values() for c in t.columns}
    return sum(by_name[c] + VALID_BYTES for c in columns)


def least_ici_ms(offchip_bytes: float, chips: int, peaks: dict) -> float:
    """The least ms one chip needs to send its share of ``offchip_bytes``
    (a statement's, all chips') at the peak of its interconnect."""
    return 1e3 * (offchip_bytes / chips) / (peaks["ici_bits_per_s"] / 8.0)
