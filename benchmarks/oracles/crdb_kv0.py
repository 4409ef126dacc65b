"""The reference for CockroachDB's kv workload at --read-percent=0 (kv0):
`oracles/crdb_kv.py`'s dict (`Reference`: the preload recomputed from --seed
in Python integers, then the UPSERTs the server acknowledged) and its
read-back statement, under the comparisons of that file less the one a mix
without reads cannot give (`reads_checked >= 1`): here `acked_writes >= 1`
stands in its place, so that an idle window cannot pass.

A kv0 window acknowledges thousands of keys where kv95's acknowledges
hundreds, so they are read back 64 keys a statement (`k IN (...)`, under the
plan's PointLookup.MAX_KEYS of 128), as the preloaded sample is, and over
CONNECTIONS fresh connections at once: a point read is 25-45 ms of round
trips on the chip whatever the statement carries it, so one connection
read 10,500 keys in 302 s (my chip run, PR 45, step 0), and the node serves
sixteen sessions' reads side by side as it serves the window's sixty-four.
Only a batch that fails is read again key by key, to count the keys an
intent blocks.
Keys are integers and values strings, so every limit is 0:

  acked_missing,        after the window, on a fresh connection, every key
  acked_different       whose UPSERT was acknowledged reads back, with the
                        value of its parameter set
  preloaded_changed     a sample of the preloaded keys (sample_keys of the
                        configuration, drawn from the seed) reads back
                        unchanged
  intent_blocked_reads  no intent is left: the read of every key a client
                        tried to write, acknowledged or not, returns
                        without an error
  statements_failed     no statement of the window failed: the source's
                        workload has no client retry and stops on an error

With `control`: the acknowledged keys again with ONE acknowledged write
withheld from the reference, which has to come out as not correct.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from oracles.crdb_kv import Reference, _kind, _read_back

_M64 = (1 << 64) - 1
BATCH = 64
CONNECTIONS = 16


def _read_many(conn, keys: list[int]):
    """-> ({key text: value}, [keys whose read failed]) of one `k IN (...)`
    statement; a failed batch is read again key by key."""
    _n, rows, err = conn.query(
        "SELECT k, v FROM kv WHERE k IN ("
        + ", ".join(str(k) for k in keys) + ")")
    if err is None:
        return {r[0]: r[1] for r in rows}, []
    got, blocked = {}, []
    for k in keys:
        rows, err = _read_back(conn, k)
        if err is not None:
            blocked.append(k)
        else:
            got.update({r[0]: r[1] for r in rows})
    return got, blocked


def _read_all(ctx, parts: list[list[int]]):
    """Every batch of `parts` read once, over CONNECTIONS connections ->
    ({key text: value}, {keys whose read failed})."""
    got: dict[str, str] = {}
    blocked: set[int] = set()
    lanes = [parts[i::CONNECTIONS] for i in range(CONNECTIONS)]

    def lane(batches):
        if not batches:
            return []
        conn = ctx.connect()
        try:
            return [_read_many(conn, part) for part in batches]
        finally:
            conn.close()

    with ThreadPoolExecutor(CONNECTIONS) as pool:
        for answers in pool.map(lane, lanes):
            for rows, bad in answers:
                got.update(rows)
                blocked.update(bad)
    return got, blocked


def check(ctx) -> list[dict]:
    cfg = ctx.config
    ref = Reference(cfg["alphabet"], ctx.seed, int(cfg["rows"]))
    failed = [r for r in ctx.records if r["err"] is not None]
    acked: dict[int, str] = {}
    tried: dict[int, str] = {}  # key -> the value its writers send
    for r in ctx.records:
        if _kind(ctx, r) == "write":
            tried[int(r["p"]["k"])] = r["p"]["v"]
            if r["err"] is None:
                acked[int(r["p"]["k"])] = r["p"]["v"]
    for k, v in acked.items():
        ref.upsert(k, v)
    held_back = next(iter(sorted(acked)), None)

    keys = sorted(tried)
    rng = np.random.default_rng([ctx.seed & _M64, 41])
    sample = sorted({int(k) for k in rng.integers(
        0, int(cfg["rows"]), size=int(cfg["sample_keys"]))} - set(tried))
    parts = [ks[i:i + BATCH] for ks in (keys, sample)
             for i in range(0, len(ks), BATCH)]
    got, blocked_keys = _read_all(ctx, parts)
    blocked = sum(1 for k in keys if k in blocked_keys)
    missing = different = control_different = 0
    for k in acked:
        if k in blocked_keys:
            continue
        if str(k) not in got:
            missing += 1
        elif [[str(k), got[str(k)]]] != ref.read(k):
            different += 1
        # the reference without this write holds no such row
        if k == held_back and str(k) in got:
            control_different += 1
    want = {str(k): ref.read(k)[0][1] for k in sample}
    changed = sum(1 for k in want if got.get(k) != want[k])
    # a row between the preload and the written keys belongs to nobody
    changed += sum(1 for k in got if k not in want and int(k) not in tried)

    out = [
        {"name": "acked_writes", "value": float(len(acked)), "limit": 1.0,
         "op": ">="},
        {"name": "acked_missing", "value": float(missing), "limit": 0.0},
        {"name": "acked_different", "value": float(different), "limit": 0.0},
        {"name": "preloaded_checked", "value": float(len(sample)),
         "limit": 1.0, "op": ">="},
        {"name": "preloaded_changed", "value": float(changed), "limit": 0.0},
        {"name": "intent_blocked_reads", "value": float(blocked),
         "limit": 0.0},
        {"name": "statements_failed", "value": float(len(failed)),
         "limit": 0.0},
    ]
    if ctx.control:
        out.append({"name": "control.acked_different_one_write_withheld",
                    "value": float(control_different), "limit": 0.0,
                    "control": True})
    return out
