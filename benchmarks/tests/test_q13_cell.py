"""The cell tpch_sf1.q13 and its configuration tpch_sf1_q13 (PR 34): the
oracle against a hand-written loop on a small fixture, the inner-join
control failing by its keys, an answer without the customers that place no
order never passing, the cell's files resolving from BENCHMARK.json (its
metrics looked up BY NAME: later PRs append), and the cell's command end to
end at tiny size on the CPU from a manifest of its own."""

import json
import os
import re
import types

import numpy as np
import pandas as pd

from helpers import BENCH, HERE, ROOT, run_cell
from oracles import tpch_q13

Q13_TINY = os.path.join(HERE, "manifest_q13_tiny.json")
CELL, CONFIG, MIX = "tpch_sf1.q13", "tpch_sf1_q13", "q13_stream"
# metric -> (span, tag) its file reads through `span_totals`
METRICS = {
    "flow.fanout_join_tiles_per_stmt": ("flow/pull", "join_general_tiles"),
    "flow.fanout_join_emit_rows_per_stmt": ("flow/pull",
                                            "join_emit_tile_rows"),
    "flow.join_overflow_reruns_per_stmt": ("flow/pull",
                                           "join_overflow_reruns"),
    "kernels.outerjoin_hbm_roofline_share": None,
    "plancache.word_tables_bound_per_stmt": ("query", "lookup_tables_bound"),
    "flow.fanout_join_probe_rows_per_stmt": ("flow/pull",
                                             "join_probe_tile_rows"),
}
WORDS1 = ["special", "pending", "unusual", "express"]
WORDS2 = ["packages", "requests", "accounts", "deposits"]


class _Fixture:
    """12 customers, every third without an order; 60 orders whose comments
    are three words, some holding a WORD1 before a WORD2."""

    def __init__(self):
        rng = np.random.default_rng(34)
        words = np.array(WORDS1 + WORDS2 + ["slyly", "wake"])
        cust = np.arange(1, 13)
        self.t = {
            "customer": pd.DataFrame({"c_custkey": cust}),
            "orders": pd.DataFrame({
                "o_orderkey": np.arange(1, 61),
                "o_custkey": rng.choice(cust[cust % 3 != 0], 60),
                "o_comment": [" ".join(w) for w in
                              rng.choice(words, size=(60, 3))]}),
        }

    def frame(self, table, cols):
        return self.t[table][cols].copy()


def _by_hand(fx, word1, word2, outer=True):
    counts = {int(k): 0 for k in fx.t["customer"].c_custkey} if outer else {}
    for r in fx.t["orders"].itertuples():
        at = r.o_comment.find(word1)
        if at >= 0 and r.o_comment.find(word2, at + len(word1)) >= 0:
            continue
        counts[r.o_custkey] = counts.get(r.o_custkey, 0) + 1
    dist: dict = {}
    for n in counts.values():
        dist[n] = dist.get(n, 0) + 1
    return sorted(dist.items(), key=lambda kv: (-kv[1], -kv[0]))


def test_oracle_against_a_loop_by_hand():
    fx = _Fixture()
    removed = 0
    for w1 in WORDS1:
        for w2 in WORDS2:
            got = tpch_q13.answer(fx, {"word1": w1, "word2": w2})
            assert list(got.columns) == ["c_count", "custdist"]
            assert [tuple(r) for r in got.itertuples(index=False)] == \
                _by_hand(fx, w1, w2)
            assert int(got.custdist.sum()) == 12
            removed += 60 - int((got.c_count * got.custdist).sum())
    assert removed > 10  # the patterns do remove orders here
    assert tpch_q13.KEYS == ["c_count"] and tpch_q13.VALUES == ["custdist"]


def test_the_inner_join_control_fails_by_its_keys():
    from oracles import tpch

    fx = _Fixture()
    want = tpch_q13.answer(fx, {"word1": "special", "word2": "requests"})
    low = tpch_q13.answer(fx, {"word1": "special", "word2": "requests"},
                          how="inner")
    assert [tuple(r) for r in low.itertuples(index=False)] == \
        _by_hand(fx, "special", "requests", outer=False)
    assert 0 in set(want.c_count) and 0 not in set(low.c_count)
    assert len(low) == len(want) - 1
    rows = [[str(v) for v in r] for r in low.itertuples(index=False)]
    bad, _rel = tpch._compare(list(low.columns), rows, want, tpch_q13)
    assert bad >= 1
    rows = [[str(v) for v in r] for r in want.itertuples(index=False)]
    assert tpch._compare(list(want.columns), rows, want, tpch_q13) == (0, 0.0)


def test_an_answer_without_the_zero_row_never_passes(monkeypatch):
    from oracles import tpch

    monkeypatch.setattr(tpch, "check", lambda ctx, query: [])
    names = ["c_count", "custdist"]
    whole = {"err": None, "names": names, "rows": [["9", "5"], ["0", "4"]]}
    dropped = {"err": None, "names": names, "rows": [["9", "5"]]}
    ctx = types.SimpleNamespace(records=[whole, dropped], control=False)
    (c,) = tpch_q13.check(ctx)
    assert (c["name"], c["value"], c["limit"], c["op"]) == (
        "zero_order_customers_min", 0.0, 1.0, ">=")
    ctx.records = [whole, whole, {"err": "x", "names": None, "rows": []}]
    assert tpch_q13.check(ctx)[0]["value"] == 4.0
    ctx.records = []
    assert tpch_q13.check(ctx)[0]["value"] == 0.0


def test_the_cells_files_resolve_and_say_what_the_issue_asks():
    import traffic

    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["source"] == entry["source"] and "2.4.13" in cfg["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["scale_factor"]
    assert cfg["scale_factor"] == 1.0 and cfg["rel_tolerance"] == 0.0
    assert set(cfg["guarantees"]) == {"answers", "isolation", "plans"}
    assert os.path.exists(os.path.join(BENCH, "loaders",
                                       cfg["loader"] + ".py"))
    mix = traffic.load_mix(MIX)
    assert mix["oracle"] == "tpch_q13" and mix["clients"] == 1
    assert mix["param_sets"] == 4
    (t,) = mix["templates"]
    assert t["params"] == {
        "word1": {"gen": "choice", "values": WORDS1},
        "word2": {"gen": "choice", "values": WORDS2}}
    s = traffic.Stream(mix, 2**31 + 34, 0)
    assert len(s.warmup()) == 2
    for _j, p, text in s.warmup() + [s.next() for _ in range(16)]:
        assert p["word1"] in WORDS1 and p["word2"] in WORDS2
        assert f"not like '%{p['word1']}%{p['word2']}%'" in text
        assert "left outer join orders" in text
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL], name
    # no metric that was there lists the new cell: only its own six do
    assert {m["name"] for m in man["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS)


def test_metric_files_agree_with_their_manifest_entries_by_name():
    for path in (os.path.join(ROOT, "BENCHMARK.json"), Q13_TINY):
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
    """31.2 MB at SF1 by hand: the docstring's sum, from TOUCHES."""
    rows = {"customer": 150_000, "orders": 1_500_000}
    width = {"c_custkey": 8, "o_orderkey": 8, "o_custkey": 8, "o_comment": 4}
    total = sum(rows[t] * width[c] for t, cols in tpch_q13.TOUCHES.items()
                for c in cols)
    assert total == 31_200_000
    said = re.search(r"= ([\d,]+) B =", tpch_q13.__doc__).group(1)
    assert int(said.replace(",", "")) == total


def test_q13_cell_rehearsal():
    """The cell's own mix at SF0.01 on the CPU: 1,500 customers, 15,000
    orders, one tile each; the GROUP BY c_custkey is the dense scatter
    aggregate, as on the chip at SF1."""
    rc, lines, err = run_cell("tpch_sf001_q13.q13", seed=2**31 + 3434,
                              manifest=Q13_TINY, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    m = last["metrics"]
    assert m["plancache.compiles_in_window"]["value"] == 0
    assert m["plancache.lookup_tables_bound_per_stmt"]["value"] == 1.0
    assert m["flow.fanout_join_tiles_per_stmt"]["value"] == 1.0
    assert m["flow.general_join_tiles_per_stmt"]["value"] == 1.0
    # 15,000 joined rows: the ladder's 65,536, learned in the warm-up
    assert m["flow.fanout_join_emit_rows_per_stmt"]["value"] == 65536.0
    assert m["flow.join_overflow_reruns_per_stmt"]["value"] == 0.0
    assert m["plancache.word_tables_bound_per_stmt"]["value"] == 1.0
    # customer's one probe tile: 1,500 rows on the ladder's 8,192 rung
    assert m["flow.fanout_join_probe_rows_per_stmt"]["value"] == 8192.0
    assert m["flow.join_probe_tile_rows_per_stmt"]["value"] == 8192.0
    assert "kernels.outerjoin_hbm_roofline_share" not in m  # no chip, no time
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    assert compares["key_mismatches"]["value"] == 0
    assert compares["max_rel_err"]["value"] == 0.0
    assert compares["max_rel_err"]["limit"] == 0.0
    assert compares["zero_order_customers_min"]["value"] >= 500
    control = compares["control.key_mismatches_inner_join"]
    assert control["control_failed_as_it_must"] and control["value"] >= 1
    assert "control.min_rel_err_float32" not in compares
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
