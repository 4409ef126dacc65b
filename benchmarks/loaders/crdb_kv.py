"""Loader `crdb_kv`: CockroachDB's kv workload table on one in-process Node
whose store is armed as the configuration states (wide keys, a synced WAL in
the run's work directory), made from the seed and landed through the
AddSSTable path so that the rows live on the device.

First act, before any data: the configuration's write statement goes to the
program's own parser. A program without UPSERT cannot serve this deployment
(its write is the source's UPSERT: a key is written by up to 64 clients, and
by SQL a second INSERT of a key is an error), so the run ends here, non-zero,
in the time of an import, not after a 1M-row load.

`value_index` is the preload: row k holds `alphabet[value_index(seed, k)]`.
The oracle recomputes it on its own, in Python integers."""

from __future__ import annotations

import os
import time

import numpy as np

_MULT = 0x9E3779B97F4A7C15  # 2^64 / golden ratio: a multiplicative hash


def value_index(seed: int, keys: np.ndarray) -> np.ndarray:
    """The top six bits of (k + seed) * _MULT mod 2^64, vectorized."""
    x = (keys.astype(np.uint64) + np.uint64(seed & ((1 << 64) - 1)))
    with np.errstate(over="ignore"):
        x = x * np.uint64(_MULT)
    return (x >> np.uint64(58)).astype(np.int64)


class Loaded:
    def __init__(self, node, session, info, pinned):
        self.node, self.session = node, session
        self.info, self.pinned = info, pinned
        self.addr = node.pg.addr

    def close(self) -> None:
        self.session.close()
        self.node.stop()
        self.node.db.engine.close()


def refuse_without_upsert(config: dict) -> None:
    from cockroach_tpu.sql import parser

    try:
        stmt = parser.parse_statement(config["probe_statement"])
    except SyntaxError as e:
        raise SystemExit(
            f"loaders/crdb_kv.py: configuration {config['name']!r} writes "
            f"with {config['probe_statement']!r}; this program's parser "
            f"refuses it ({e})")
    if not getattr(stmt, "upsert", False):
        raise SystemExit(
            f"loaders/crdb_kv.py: {config['probe_statement']!r} did not "
            f"parse as an UPSERT ({type(stmt).__name__})")


def load(config: dict, seed: int, workdir: str) -> Loaded:
    refuse_without_upsert(config)

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
    session.execute("CREATE TABLE kv (k INT PRIMARY KEY, v STRING)")
    rows = int(config["rows"])
    t0 = time.time()
    keys = np.arange(rows, dtype=np.int64)
    alphabet = np.array(list(config["alphabet"]), dtype=object)
    table = node._sql_catalog.tables["kv"]
    # ascending keys: each chunk lands as it is, no device sort
    table.bulk_load({"k": keys, "v": alphabet[value_index(seed, keys)]},
                    chunk=int(config["load_chunk_rows"]), presorted=True)
    info = {"n_rows": rows, "runs": len(engine.runs),
            "bulk_load_s": round(time.time() - t0, 3),
            "run_capacities": sorted(int(r.capacity) for r in engine.runs)}
    # the guarantee the configuration states, held by the comparison that
    # decides `correct`: the served engine syncs its WAL
    pinned = [{"name": "wal_fsync_armed",
               "value": float(bool(engine.wal_fsync
                                   and engine._wal is not None)),
               "limit": 1.0, "op": ">="}]
    return Loaded(node, session, info, pinned)
