"""Loader `sysbench_oltp`: sysbench's sbtest1 on one in-process Node whose
store is armed as the configuration states (wide keys, a 256-byte value slot,
a synced WAL in the run's work directory), made from the seed and landed
presorted through the AddSSTable path as ONE device-resident run.

First act, before any data: the configuration's table is created over an
empty store and every range statement of its mix is planned. A program whose
EXPLAIN shows no `pk-range` for `WHERE id BETWEEN a AND b` answers it by
merging and decoding the whole table a statement, and one that keeps
CHAR(120) as a dictionary code writes a dictionary entry a row: neither can
serve this deployment, so the run ends here, non-zero, in the time of an
import, not after a 4M-row load. What the probe read is pinned into the
comparison that decides `correct`.

The rows are the oracle's (oracles/sysbench_oltp.py), written again in
vectorized numpy: `digits` turns a hash column into eleven ASCII digits."""

from __future__ import annotations

import os
import time

import numpy as np

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xBF58476D1CE4E5B9)
_M3 = np.uint64(0x94D049BB133111EB)
_GROUP = np.uint64(10 ** 11)
C_GROUPS, PAD_GROUPS, K_POS = 10, 5, 15
ROUTE = "pk-range"


def hashes(ids: np.ndarray, seed: int, p: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (ids.astype(np.uint64)
             + np.uint64(seed & ((1 << 64) - 1)) * _M2
             + np.uint64(p) * _M3) * _M1
        x ^= x >> np.uint64(32)
        x *= _M2
        return x ^ (x >> np.uint64(29))


def groups(ids: np.ndarray, seed: int, first: int, count: int) -> np.ndarray:
    """[N, 12 * count - 1] uint8: `count` groups of eleven digits, '-'
    between them."""
    out = np.full((len(ids), 12 * count - 1), ord("-"), dtype=np.uint8)
    ten = np.uint64(10)
    for g in range(count):
        v = hashes(ids, seed, first + g) % _GROUP
        for d in range(10, -1, -1):
            out[:, 12 * g + d] = (v % ten).astype(np.uint8) + ord("0")
            v //= ten
    return out


def make_rows(seed: int, lo: int, hi: int, table_size: int) -> dict:
    """Columns of rows id lo .. hi - 1: id and k int64, c and pad as
    zero-free ASCII bytes [N, 119] and [N, 59]."""
    ids = np.arange(lo, hi, dtype=np.int64)
    k = 1 + (hashes(ids, seed, K_POS) % np.uint64(table_size)).astype(
        np.int64)
    return {"id": ids, "k": k, "c": groups(ids, seed, 0, C_GROUPS),
            "pad": groups(ids, seed, C_GROUPS, PAD_GROUPS)}


class Loaded:
    def __init__(self, node, session, config, info, pinned):
        self.node, self.session, self.config = node, session, config
        self.info, self.pinned = info, pinned
        self.addr = node.pg.addr

    def column_bytes(self, table: str, kinds) -> int:
        """What touched_bytes.py sums: for this deployment, the bytes a
        mean statement reads at the least (the oracle's function)."""
        from oracles import sysbench_oltp as oracle

        return int(oracle.touched_bytes_per_statement(self.config, kinds))

    def close(self) -> None:
        self.session.close()
        self.node.stop()
        self.node.db.engine.close()


def range_statements(config: dict) -> list[str]:
    import traffic

    mix = traffic.load_mix(config["range_mix"])
    return [t["sql"].format(b=1) for t in mix["templates"]
            if t["name"] != "point"]


def planned_as_scans(session, config: dict) -> int:
    """Range statements of the mix whose plan over `sbtest1` has no
    pk-range node."""
    from cockroach_tpu import sql

    return sum(ROUTE not in sql.explain(session.catalog, text)
               for text in range_statements(config))


def refuse_without_range_route(config: dict) -> int:
    """Plans the mix's range statements over an empty sbtest1 of a store
    of the configuration's widths; ends the run where one is a scan or
    CHAR(120) is a dictionary code. -> the count it read (0)."""
    from cockroach_tpu.sql import Session

    e = config["engine"]
    s = Session(key_width=int(e["key_width"]), val_width=int(e["val_width"]))
    try:
        s.execute(config["schema"])
        fam = s.catalog.tables["sbtest1"].schema.type_of("c").family.name
        if fam != "BYTES":
            raise SystemExit(
                f"loaders/sysbench_oltp.py: configuration "
                f"{config['name']!r} stores CHAR(120) raw in the row; this "
                f"program keeps it as {fam} (a dictionary code, one "
                f"companion row a distinct value)")
        scans = planned_as_scans(s, config)
        if scans:
            raise SystemExit(
                f"loaders/sysbench_oltp.py: configuration "
                f"{config['name']!r} guarantees that a BETWEEN on the "
                f"primary key seeks; this program plans {scans} of its "
                f"range statements with no {ROUTE} node (a decode of the "
                f"whole table a statement)")
        return scans
    finally:
        s.close()


def load(config: dict, seed: int, workdir: str) -> Loaded:
    scans = refuse_without_range_route(config)

    from cockroach_tpu.server.node import Node
    from cockroach_tpu.sql import Session
    from cockroach_tpu.storage.lsm import Engine

    e = config["engine"]
    engine = Engine(key_width=int(e["key_width"]),
                    val_width=int(e["val_width"]),
                    wal_path=os.path.join(workdir, "wal"),
                    wal_fsync=bool(e["wal_fsync"]))
    node = Node(engine=engine).start(pg_port=0)  # with its default loops
    session = Session(catalog=node._sql_catalog, db=node.db, bootstrap=False)
    session.execute(config["schema"])
    n = int(config["table_size"])
    t0 = time.time()
    cols = make_rows(seed, 1, n + 1, n)
    t1 = time.time()
    table = node._sql_catalog.tables["sbtest1"]
    # ascending ids in one chunk: the table lands as it is, as one run
    table.bulk_load(cols, chunk=n, presorted=True)
    info = {"n_rows": n, "runs": len(engine.runs),
            "make_rows_s": round(t1 - t0, 3),
            "bulk_load_s": round(time.time() - t1, 3),
            "run_capacities": sorted(int(r.capacity) for r in engine.runs)}
    pinned = [
        {"name": "wal_fsync_armed",
         "value": float(bool(engine.wal_fsync and engine._wal is not None)),
         "limit": 1.0, "op": ">="},
        {"name": "range_statements_planned_as_scans",
         "value": float(scans + planned_as_scans(session, config)),
         "limit": 0.0},
    ]
    return Loaded(node, session, config, info, pinned)
