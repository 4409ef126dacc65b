"""The reference for sysbench oltp_read_only over sbtest1: the table is a
function of --seed, written here in Python integers, independent of the
engine and of the loader's numpy (benchmarks/loaders/sysbench_oltp.py makes
the same rows vectorized; benchmarks/tests/test_sysbench_cell.py holds the
two together row for row).

    group(id, seed, p) = h(id, seed, p) mod 10^11, eleven digits
    c   = groups 0..9 joined by '-'   (119 characters)
    pad = groups 10..14 joined by '-' (59 characters)
    k   = 1 + h(id, seed, 15) mod table_size
    h   = a 64-bit multiplicative hash: ((id + seed*M2 + p*M3) * M1, folded
          by a shift, times M2, folded again), all mod 2^64

Answers, by the template's name: point -> [[c]]; range -> the 100 c as a
multiset; sum -> [[sum of k]]; order -> the c sorted bytewise; distinct ->
sorted and de-duplicated. Strings and integers, so every limit is 0:

  points_wrong, ranges_wrong, sums_wrong, orders_wrong, distincts_wrong
  statements_failed   no statement of the window failed
  range_rows_min      (>= range_size) every answer of the kinds range,
                      sum and order covers its 100 rows: the rows returned
                      for range and order, and for a sum that equals the
                      reference's the rows the reference summed; an empty
                      answer cannot pass

With `control`: the point answers again against the reference of seed + 1,
which has to come out as not correct.

`TOUCHES` and `touched_bytes_per_statement` are for the roofline reader
(readers/hbm_roofline.py through touched_bytes.py): the bytes a mean
statement has to read at the device at the least, which is the rows a
statement of each kind needs (1 for a point select, range_size for a range)
times a stored row (key slot + value slot + the 30 B of ts, seq, txn, tomb,
vlen and mask), weighted by the source's statement mix. At the
configuration's sizes: (10 x 1 + 4 x 100) / 14 rows x (64 + 256 + 30) B =
10,250 B a statement, 12.5 ns at 819 GB/s.
"""

_M64 = (1 << 64) - 1
_M1 = 0x9E3779B97F4A7C15
_M2 = 0xBF58476D1CE4E5B9
_M3 = 0x94D049BB133111EB
GROUP = 10 ** 11
C_GROUPS, PAD_GROUPS, K_POS = 10, 5, 15

# the statement kinds whose rows a statement reads; column_bytes of the
# loader's `Loaded` hands them to touched_bytes_per_statement
TOUCHES = {"sbtest1": ["point", "range", "sum", "order", "distinct"]}


def h(i: int, seed: int, p: int) -> int:
    x = ((i + seed * _M2 + p * _M3) & _M64) * _M1 & _M64
    x ^= x >> 32
    x = x * _M2 & _M64
    return x ^ (x >> 29)


def c_of(i: int, seed: int) -> str:
    return "-".join(f"{h(i, seed, p) % GROUP:011d}" for p in range(C_GROUPS))


def pad_of(i: int, seed: int) -> str:
    return "-".join(f"{h(i, seed, p) % GROUP:011d}"
                    for p in range(C_GROUPS, C_GROUPS + PAD_GROUPS))


def k_of(i: int, seed: int, table_size: int) -> int:
    return 1 + h(i, seed, K_POS) % table_size


def row(i: int, seed: int, table_size: int) -> dict:
    return {"id": i, "k": k_of(i, seed, table_size), "c": c_of(i, seed),
            "pad": pad_of(i, seed)}


def touched_bytes_per_statement(config: dict, kinds=None) -> float:
    """Bytes a mean statement of the configuration's mix reads at the
    least: rows needed by kind x stored row bytes, by weight."""
    e = config["engine"]
    row_bytes = (int(e["key_width"]) + int(e["val_width"])
                 + int(config["row_overhead_bytes"]))
    weights = config["statement_mix"]
    kinds = list(kinds or weights)
    rows = {k: 1 if k == "point" else int(config["range_size"])
            for k in kinds}
    total = float(sum(weights[k] for k in kinds))
    return sum(weights[k] * rows[k] for k in kinds) / total * row_bytes


class Reference:
    def __init__(self, seed: int, table_size: int):
        self.seed, self.n = seed, table_size
        self._c: dict[int, str] = {}

    def c(self, i: int) -> str:
        got = self._c.get(i)
        if got is None:
            got = self._c[i] = c_of(i, self.seed)
        return got

    def ids(self, lo: int, hi: int) -> range:
        return range(max(lo, 1), min(hi, self.n) + 1)

    def answer(self, kind: str, p: dict) -> list[list[str]]:
        if kind == "point":
            i = int(p["id"])
            return [[self.c(i)]] if 1 <= i <= self.n else []
        lo, hi = int(f"{p['b']}00"), int(f"{p['b']}99")
        ids = self.ids(lo, hi)
        if kind == "sum":
            if not len(ids):
                return [[None]]
            return [[str(sum(k_of(i, self.seed, self.n) for i in ids))]]
        cs = [self.c(i) for i in ids]
        if kind == "range":
            return [[c] for c in cs]
        if kind == "order":
            return [[c] for c in sorted(cs, key=lambda s: s.encode())]
        if kind == "distinct":
            return [[c] for c in sorted(set(cs), key=lambda s: s.encode())]
        raise ValueError(f"unknown statement kind {kind!r}")


def _matches(kind: str, rows, want) -> bool:
    if kind == "range":  # a multiset: the statement asks for no order
        return sorted(map(tuple, rows)) == sorted(map(tuple, want))
    return [list(r) for r in rows] == want


def check(ctx) -> list[dict]:
    cfg = ctx.config
    n, size = int(cfg["table_size"]), int(cfg["range_size"])
    ref = Reference(ctx.seed, n)
    shifted = Reference(ctx.seed + 1, n)
    wrong = {k: 0 for k in ("point", "range", "sum", "order", "distinct")}
    checked = dict(wrong)
    rows_min = None
    control_wrong = 0
    failed = 0
    for r in ctx.records:
        if r["err"] is not None:
            failed += 1
            continue
        kind = ctx.mix["templates"][r["t"]]["name"]
        want = ref.answer(kind, r["p"])
        ok = _matches(kind, r["rows"], want)
        checked[kind] += 1
        wrong[kind] += not ok
        if kind in ("range", "order"):
            got = len(r["rows"])
        elif kind == "sum":
            lo = int(f"{r['p']['b']}00")
            got = len(ref.ids(lo, lo + size - 1)) if ok else 0
        else:
            got = None
        if got is not None:
            rows_min = got if rows_min is None else min(rows_min, got)
        if ctx.control and kind == "point":
            control_wrong += not _matches(
                kind, r["rows"], shifted.answer(kind, r["p"]))
    out = [{"name": f"{k}s_checked", "value": float(checked[k]),
            "limit": 0.0, "op": ">="} for k in wrong]
    out += [{"name": f"{k}s_wrong", "value": float(wrong[k]), "limit": 0.0}
            for k in wrong]
    out += [
        {"name": "statements_failed", "value": float(failed), "limit": 0.0},
        {"name": "range_rows_min",
         "value": float(size if rows_min is None else rows_min),
         "limit": float(size), "op": ">="},
    ]
    if ctx.control:
        out.append({"name": "control.points_wrong_seed_shifted_by_one",
                    "value": float(control_wrong), "limit": 0.0,
                    "control": True})
    return out
