"""The reference for CockroachDB's kv workload: a plain Python dict,
independent of the engine. Its contents are the preload, recomputed here
from --seed in Python integers (row k holds alphabet[(k + seed) * M mod 2^64
>> 58], the loader's numpy formula written again), then the UPSERTs the
server acknowledged in the window (all writers of a key write the value of
its parameter set, so the answer is determined whatever their order).

Five comparisons decide `correct`; keys are integers and values strings, so
every limit is 0:

  reads_wrong           (a) a read in the window returned other than exactly
                        [[k, value]] of the reference (a key some client was
                        writing at the time may also still be absent)
  acked_missing,        (b) after the window, on a fresh connection, every
  acked_different       key whose UPSERT was acknowledged reads back, with
                        the value of its parameter set
  preloaded_changed     (c) a sample of the preloaded keys (sample_keys of
                        the configuration, drawn from the seed) reads back
                        unchanged, 64 keys a statement
  intent_blocked_reads  (d) no intent is left: the point read of every key
                        a client tried to write, acknowledged or not,
                        returns without an error (a left intent is waited
                        for 16 times and then surfaces as 40001)
  statements_failed     (e) no statement of the window failed: the source's
                        workload has no client retry and stops on an error

With `control`: comparison (b) again with ONE acknowledged write withheld
from the reference, which has to come out as not correct (the store holds a
row the reference does not know).
"""

import numpy as np

_MULT = 0x9E3779B97F4A7C15
_M64 = (1 << 64) - 1


def preload_value(alphabet: str, seed: int, k: int) -> str:
    return alphabet[(((k + seed) & _M64) * _MULT & _M64) >> 58]


class Reference:
    """The dict: preload (made on demand, key by key) + acknowledged
    UPSERTs."""

    def __init__(self, alphabet: str, seed: int, rows: int):
        self.alphabet, self.seed, self.rows = alphabet, seed, rows
        self.written: dict[int, str] = {}

    def upsert(self, k: int, v: str) -> None:
        self.written[k] = v

    def read(self, k: int) -> list:
        if k in self.written:
            return [[str(k), self.written[k]]]
        if 0 <= k < self.rows:
            return [[str(k), preload_value(self.alphabet, self.seed, k)]]
        return []


def _kind(ctx, rec) -> str:
    return ctx.mix["templates"][rec["t"]]["name"]


def _read_back(conn, k: int):
    """-> (rows, error) of the cell's own read statement for one key."""
    _names, rows, err = conn.query(f"SELECT k, v FROM kv WHERE k IN ({k})")
    return rows, err


def check(ctx) -> list[dict]:
    cfg = ctx.config
    ref = Reference(cfg["alphabet"], ctx.seed, int(cfg["rows"]))
    done = [r for r in ctx.records if r["err"] is None]
    failed = [r for r in ctx.records if r["err"] is not None]
    acked: dict[int, str] = {}
    tried: dict[int, str] = {}  # key -> the value its writers send
    for r in ctx.records:
        if _kind(ctx, r) == "write":
            tried[int(r["p"]["k"])] = r["p"]["v"]
            if r["err"] is None:
                acked[int(r["p"]["k"])] = r["p"]["v"]

    # (a) the window's reads, against the reference before the window's
    # writes for a key nobody wrote, and either state for one in flight
    reads = wrong = 0
    for r in done:
        if _kind(ctx, r) != "read":
            continue
        reads += 1
        k = int(r["p"]["k"])
        ok = [ref.read(k)]
        if k in tried:
            ok.append([[str(k), tried[k]]])
        if r["rows"] not in ok or r["names"] != ["k", "v"]:
            wrong += 1
    before = Reference(cfg["alphabet"], ctx.seed, int(cfg["rows"]))
    for k, v in acked.items():
        ref.upsert(k, v)

    conn = ctx.connect()
    try:
        # (b) and (d): every key a client tried to write is read once
        missing = different = blocked = 0
        held_back = next(iter(sorted(acked)), None)
        control_different = 0
        for k in sorted(tried):
            rows, err = _read_back(conn, k)
            if err is not None:
                blocked += 1
                continue
            if k in acked:
                if not rows:
                    missing += 1
                elif rows != ref.read(k):
                    different += 1
                if k == held_back and rows != before.read(k):
                    control_different += 1
        # (c) a sample of the preload, 64 keys a statement
        rng = np.random.default_rng([ctx.seed & _M64, 41])
        sample = sorted({int(k) for k in rng.integers(
            0, int(cfg["rows"]), size=int(cfg["sample_keys"]))} - set(tried))
        changed = 0
        for i in range(0, len(sample), 64):
            part = sample[i:i + 64]
            _n, rows, err = conn.query(
                "SELECT k, v FROM kv WHERE k IN ("
                + ", ".join(str(k) for k in part) + ")")
            want = {str(k): ref.read(k)[0][1] for k in part}
            got = {} if err else {r[0]: r[1] for r in rows}
            changed += sum(1 for k in want if got.get(k) != want[k])
            changed += sum(1 for k in got if k not in want)
    finally:
        conn.close()

    out = [
        {"name": "reads_checked", "value": float(reads), "limit": 1.0,
         "op": ">="},
        {"name": "reads_wrong", "value": float(wrong), "limit": 0.0},
        {"name": "acked_writes", "value": float(len(acked)), "limit": 0.0,
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
