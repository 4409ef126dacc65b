"""The cell tpch_sf1.q21 and its configuration tpch_sf1_q21 (PR 39): the
oracle against a hand-written loop on a fixture that holds each edge once,
the control (the frame without its NOT EXISTS) failing by its counts, an
answer short of the LIMIT never passing, the cell's files resolving from
BENCHMARK.json (its metrics looked up BY NAME: later PRs append), and the
cell's command end to end at tiny size on the CPU from a manifest of its
own."""

import json
import os
import re
import types

import pandas as pd

from helpers import BENCH, HERE, ROOT, run_cell
from oracles import tpch_q21

Q21_TINY = os.path.join(HERE, "manifest_q21_tiny.json")
CELL, CONFIG, MIX = "tpch_sf1.q21", "tpch_sf1_q21", "q21_stream"
# metric -> (span, tag) its file reads through `span_totals`
METRICS = {
    "flow.exists_agg_streamed_tiles_per_stmt": ("flow/pull",
                                                "agg_streamed_tiles"),
    "flow.exists_agg_merge_rows_per_stmt": ("flow/pull", "agg_merge_rows"),
    "flow.agg_build_rows_per_stmt": ("flow/pull", "join_build_rows"),
    "flow.null_extended_join_tiles_per_stmt": ("flow/pull",
                                               "join_null_extended_tiles"),
    "flow.exists_join_probe_tile_rows_per_stmt": ("flow/pull",
                                                  "join_probe_tile_rows"),
    "plancache.nation_tables_bound_per_stmt": ("query",
                                               "lookup_tables_bound"),
    "kernels.existsjoin_hbm_roofline_share": None,
}
NATIONS = [
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES"]

LATE, ON_TIME = (100, 90), (80, 90)  # (receipt, commit) days
# order -> (status, [(supplier, late?), ...]); suppliers 1-3 are PERU's,
# supplier 4 is KENYA's
ORDERS = {
    1: ("F", [(1, True), (2, False)]),             # counts 1 for supplier 1
    2: ("F", [(1, True)]),                         # one supplier: no EXISTS
    3: ("F", [(1, True), (2, True)]),              # two late: NOT EXISTS fails
    4: ("F", [(1, True), (1, True), (3, False)]),  # two late lines: counts 2
    5: ("O", [(1, True), (2, False)]),             # status not 'F'
    6: ("F", [(4, True), (1, False)]),             # late, of another nation
    7: ("F", [(2, False), (3, False)]),            # nobody late
    8: ("F", [(2, True), (2, False), (3, False)]),  # late and on time: 1
    9: ("P", [(3, True), (1, False)]),             # status not 'F'
    10: ("F", [(3, True), (3, True)]),             # one supplier, two lines
    11: ("F", [(1, False), (2, False), (3, True)]),  # counts 1 for 3
    12: ("F", [(1, True), (2, False), (3, True)]),  # two of three late
}
SUPPLIERS = {1: ("Supplier#1", 17), 2: ("Supplier#2", 17),
             3: ("Supplier#3", 17), 4: ("Supplier#4", 14)}
NATION_KEYS = {17: "PERU", 14: "KENYA", 3: "CANADA"}


class _Fixture:
    def __init__(self):
        lines = [(o, s, *(LATE if late else ON_TIME))
                 for o, (_st, ls) in ORDERS.items() for s, late in ls]
        self.t = {
            "lineitem": pd.DataFrame(lines, columns=[
                "l_orderkey", "l_suppkey", "l_receiptdate", "l_commitdate"]),
            "orders": pd.DataFrame({
                "o_orderkey": list(ORDERS),
                "o_orderstatus": [st for st, _ in ORDERS.values()]}),
            "supplier": pd.DataFrame({
                "s_suppkey": list(SUPPLIERS),
                "s_name": [n for n, _ in SUPPLIERS.values()],
                "s_nationkey": [k for _, k in SUPPLIERS.values()]}),
            "nation": pd.DataFrame({"n_nationkey": list(NATION_KEYS),
                                    "n_name": list(NATION_KEYS.values())}),
        }

    def frame(self, table, cols):
        return self.t[table][cols].copy()


def _by_hand(nation, not_exists=True):
    counts: dict = {}
    for _o, (status, ls) in ORDERS.items():
        for s1, late1 in ls:
            name, nkey = SUPPLIERS[s1]
            if status != "F" or not late1 or NATION_KEYS[nkey] != nation:
                continue
            if not any(s2 != s1 for s2, _ in ls):
                continue  # EXISTS
            if not_exists and any(s3 != s1 and late3 for s3, late3 in ls):
                continue  # NOT EXISTS
            counts[name] = counts.get(name, 0) + 1
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:100]


def _rows(frame):
    return [(str(a), int(b)) for a, b in zip(frame.s_name, frame.numwait)]


def test_oracle_against_a_loop_by_hand():
    fx = _Fixture()
    assert _by_hand("PERU") == [("Supplier#1", 3), ("Supplier#2", 1),
                                ("Supplier#3", 1)]
    for nation in ("PERU", "KENYA", "CANADA"):
        got = tpch_q21.answer(fx, {"nation": nation})
        assert list(got.columns) == ["s_name", "numwait"]
        assert _rows(got) == _by_hand(nation), nation
    assert tpch_q21.KEYS == ["s_name"] and tpch_q21.VALUES == ["numwait"]
    assert tpch_q21.LIMIT == 100


def test_the_control_without_not_exists_fails_by_its_counts():
    from oracles import tpch

    fx = _Fixture()
    want = tpch_q21.answer(fx, {"nation": "PERU"})
    low = tpch_q21.answer(fx, {"nation": "PERU"}, not_exists=False)
    assert _rows(low) == _by_hand("PERU", not_exists=False)
    # orders 3 and 12 now count for both of their late suppliers
    assert _rows(low) == [("Supplier#1", 5), ("Supplier#2", 2),
                          ("Supplier#3", 2)]
    rows = [[str(v) for v in r] for r in low.itertuples(index=False)]
    bad, rel = tpch._compare(list(low.columns), rows, want, tpch_q21)
    assert bad == 0 and rel > 0.5  # the same names, other counts
    rows = [[str(v) for v in r] for r in want.itertuples(index=False)]
    assert tpch._compare(list(want.columns), rows, want, tpch_q21) == (0, 0.0)


def test_an_answer_short_of_the_limit_never_passes(monkeypatch):
    from oracles import tpch

    monkeypatch.setattr(tpch, "check", lambda ctx, query: [])
    names = ["s_name", "numwait"]
    whole = {"err": None, "names": names, "rows": [["s", "1"]] * 100}
    short = {"err": None, "names": names, "rows": [["s", "1"]] * 99}
    ctx = types.SimpleNamespace(records=[whole, short], control=False,
                                config={"answer_rows_min": 100})
    (c,) = tpch_q21.check(ctx)
    assert (c["name"], c["value"], c["limit"], c["op"]) == (
        "answer_rows_min", 99.0, 100.0, ">=")
    ctx.records = [whole, whole, {"err": "x", "names": None, "rows": []}]
    assert tpch_q21.check(ctx)[0]["value"] == 100.0
    ctx.records = []
    assert tpch_q21.check(ctx)[0]["value"] == 0.0
    ctx.config = {}  # a configuration that states no floor: the LIMIT
    assert tpch_q21.check(ctx)[0]["limit"] == 100.0


def test_the_cells_files_resolve_and_say_what_the_issue_asks():
    import traffic

    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["source"] == entry["source"] and "2.4.21" in cfg["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["scale_factor"]
    assert cfg["scale_factor"] == 1.0 and cfg["rel_tolerance"] == 0.0
    assert cfg["answer_rows_min"] == tpch_q21.LIMIT
    assert set(cfg["guarantees"]) == {"answers", "isolation", "plans"}
    assert "PLACEHOLDER" not in json.dumps(cfg)
    assert os.path.exists(os.path.join(BENCH, "loaders",
                                       cfg["loader"] + ".py"))
    mix = traffic.load_mix(MIX)
    assert mix["oracle"] == "tpch_q21" and mix["clients"] == 1
    assert mix["param_sets"] == 4 and mix["trace_seconds"] == 40.0
    (t,) = mix["templates"]
    assert t["params"] == {"nation": {"gen": "choice", "values": NATIONS}}
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL

    assert t["sql"] == " ".join(TPCH_SQL["q21"].split()).replace(
        "'SAUDI ARABIA'", "'{nation}'")
    s = traffic.Stream(mix, 2**31 + 39, 0)
    assert len(s.warmup()) == 2
    for _j, p, text in s.warmup() + [s.next() for _ in range(16)]:
        assert p["nation"] in NATIONS
        assert f"n_name = '{p['nation']}'" in text
        assert "not exists ( select * from lineitem as l3" in text
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmts_per_s", name


def test_metric_files_agree_with_their_manifest_entries_by_name():
    for path in (os.path.join(ROOT, "BENCHMARK.json"), Q21_TINY):
        got = {m["name"]: m for m in json.load(open(path))["per_layer"]}
        for name, reads in METRICS.items():
            spec = json.load(open(os.path.join(BENCH, "metrics",
                                               name + ".json")))
            for k in ("layer", "unit", "better", "source", "moves"):
                assert spec[k] == got[name][k], (path, name, k)
            assert os.path.exists(os.path.join(BENCH, "readers",
                                               spec["reader"] + ".py"))
            if reads is None:
                assert spec["reader"] == "hbm_roofline" and not spec["args"]
            else:
                assert spec["reader"] == "span_totals"
                assert spec["args"] == {"names": [reads[0]], "tag": reads[1],
                                        "per": "stmt"}


def test_the_touched_bytes_are_the_docstrings():
    """0.162 GB at SF1 by hand: the docstring's sum, from TOUCHES;
    lineitem once."""
    rows = {"lineitem": 6_002_051, "orders": 1_500_000, "supplier": 10_000,
            "nation": 25}
    width = {"l_orderkey": 8, "l_suppkey": 8, "l_commitdate": 4,
             "l_receiptdate": 4, "o_orderkey": 8, "o_orderstatus": 4,
             "s_suppkey": 8, "s_name": 4, "s_nationkey": 8,
             "n_nationkey": 8, "n_name": 4}
    total = sum(rows[t] * width[c] for t, cols in tpch_q21.TOUCHES.items()
                for c in cols)
    assert total == 162_249_524
    said = re.search(r"= ([\d,]+) B =", tpch_q21.__doc__).group(1)
    assert int(said.replace(",", "")) == total


def test_q21_cell_rehearsal():
    """The cell's own mix at SF0.01 on the CPU: 100 suppliers, 60,000
    lines, one tile a table; the decorrelated aggregates are the dense
    scatter ones here (15,000 order keys), the ordered streaming ones on
    the chip at SF1 (tests/test_tpch_q21_served.py runs that route)."""
    rc, lines, err = run_cell("tpch_sf001_q21.q21", seed=2**31 + 3939,
                              manifest=Q21_TINY, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    m = last["metrics"]
    assert m["flow.unique_join_tiles_per_stmt"]["value"] == 5.0
    assert m["flow.general_join_tiles_per_stmt"]["value"] == 0.0
    assert m["flow.null_extended_join_tiles_per_stmt"]["value"] == 1.0
    # two builds from an aggregate's whole output, 15,000 groups and the
    # late lines' groups: the ladder's 65,536 each
    assert m["flow.agg_build_rows_per_stmt"]["value"] == 2 * 65536.0
    assert m["flow.exists_agg_streamed_tiles_per_stmt"]["value"] == 0.0
    assert m["flow.exists_agg_merge_rows_per_stmt"]["value"] == 0.0
    assert m["flow.exists_join_probe_tile_rows_per_stmt"]["value"] > 65536
    # n_name = 'NATION' binds as the literal's dictionary code in a
    # numeric slot, not as a table
    assert m["plancache.nation_tables_bound_per_stmt"]["value"] == 0.0
    assert "kernels.existsjoin_hbm_roofline_share" not in m  # no chip
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    assert compares["key_mismatches"]["value"] == 0
    assert compares["max_rel_err"]["value"] == 0.0
    assert compares["max_rel_err"]["limit"] == 0.0
    assert compares["answer_rows_min"]["limit"] == 1.0
    assert compares["answer_rows_min"]["value"] >= 1.0
    control = compares["control.count_mismatch_without_not_exists"]
    assert control["control_failed_as_it_must"] and control["value"] > 0
    assert "control.min_rel_err_float32" not in compares
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
