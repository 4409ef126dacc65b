"""Loader `tpch`: TPC-H made from the seed on the host and adopted by an
in-process Node's serving catalog, the way cli.py's --demo-tpch does it
(scans then cache the columns on the device). Returns what run.py needs:
the node, the address clients dial, and the host tables for the oracle."""

from __future__ import annotations

import numpy as np


class Loaded:
    def __init__(self, node, tables, info, pinned):
        self.node, self.tables, self.info, self.pinned = (
            node, tables, info, pinned)
        self.addr = node.pg.addr

    def frame(self, table: str, cols: list[str]):
        """Decode host columns to pandas for the oracle: dictionary codes
        to strings, scaled decimals to float64, dates stay days."""
        import pandas as pd

        t = self.tables[table]
        out = {}
        for c in cols:
            col = np.asarray(t.columns[c])
            typ = t.schema.types[t.schema.names.index(c)]
            if c in t.dictionaries:
                out[c] = np.asarray(t.dictionaries[c].values)[col]
            elif typ.family.name == "DECIMAL":
                out[c] = col / 10.0 ** typ.scale
            else:
                out[c] = col
        return pd.DataFrame(out)

    def column_bytes(self, table: str, cols: list[str]) -> int:
        t = self.tables[table]
        return sum(int(np.asarray(t.columns[c]).dtype.itemsize)
                   * int(t.num_rows) for c in cols)

    def close(self) -> None:
        self.node.stop()


def load(config: dict, seed: int, workdir: str) -> Loaded:
    from cockroach_tpu.bench import tpch
    from cockroach_tpu.server.node import Node

    cat = tpch.gen_tpch(sf=float(config["scale_factor"]), seed=seed)
    node = Node().start(pg_port=0)  # the node as every user runs it
    for name, table in cat.tables.items():
        node._sql_catalog.tables[name] = table
    rows = int(cat.get("lineitem").num_rows)
    # the row count moves with the seed by a few thousand (1 to 7 lines an
    # order); a changed generator or scale shows here
    pinned = [{"name": "lineitem_rows_off_pin",
               "value": float(abs(rows - int(config["lineitem_rows"]))),
               "limit": float(config["lineitem_rows_tolerance"])}]
    return Loaded(node, dict(cat.tables), {"n_rows": rows}, pinned)
