"""The cell tpch_sf1.q18 and its configuration tpch_sf1_q18 (PR 32): the
oracle against a hand-written loop on a small fixture, the float32 control
missing the limit by o_totalprice alone, an empty answer never passing for a
checked one, the cell's files resolving from BENCHMARK.json, and the cell's
command end to end at tiny size on the CPU from a manifest of its own."""

import json
import os
import shutil
import types

import numpy as np
import pandas as pd
import pytest

from helpers import BENCH, HERE, ROOT, run_cell
from oracles import tpch_q18

Q18_TINY = os.path.join(HERE, "manifest_q18_tiny.json")
CELL, CONFIG, MIX = "tpch_sf1.q18", "tpch_sf1_q18", "q18_stream"
METRICS = ["flow.ordered_agg_tiles_per_stmt", "flow.agg_merge_rows_per_stmt",
           "flow.agg_spills_per_stmt",
           "flow.semijoin_probe_tile_rows_per_stmt",
           "kernels.groupby_hbm_roofline_share"]


class _Fixture:
    """40 orders of 1 to 7 lines over 9 customers; prices like SF1's."""

    def __init__(self):
        rng = np.random.default_rng(32)
        n_lines = rng.integers(1, 8, 40)
        okey = np.repeat(np.arange(1, 41), n_lines)
        self.t = {
            "customer": pd.DataFrame({
                "c_custkey": np.arange(1, 10),
                "c_name": [f"Customer#{k:09d}" for k in range(1, 10)]}),
            "orders": pd.DataFrame({
                "o_orderkey": np.arange(1, 41),
                "o_custkey": rng.integers(1, 10, 40),
                "o_orderdate": rng.integers(8036, 10400, 40).astype(np.int32),
                "o_totalprice": np.round(rng.uniform(900, 560000, 40), 2)}),
            "lineitem": pd.DataFrame({
                "l_orderkey": okey,
                "l_quantity": rng.integers(1, 51, len(okey)).astype(
                    np.float64)}),
        }

    def frame(self, table, cols):
        return self.t[table][cols].copy()


def _by_hand(fx, quantity):
    t = fx.t
    qty: dict = {}
    for r in t["lineitem"].itertuples():
        qty[r.l_orderkey] = qty.get(r.l_orderkey, 0.0) + r.l_quantity
    name = dict(zip(t["customer"].c_custkey, t["customer"].c_name))
    rows = [(name[r.o_custkey], r.o_custkey, r.o_orderkey, r.o_orderdate,
             r.o_totalprice, qty[r.o_orderkey])
            for r in t["orders"].itertuples() if qty[r.o_orderkey] > quantity]
    return sorted(rows, key=lambda r: (-r[4], r[3]))[:100]


def test_oracle_against_a_loop_by_hand():
    fx = _Fixture()
    for quantity in (100, 150, 200, 400):
        want = _by_hand(fx, quantity)
        got = tpch_q18.answer(fx, {"quantity": quantity})
        assert list(got.columns) == tpch_q18.KEYS + ["sum_qty"]
        assert [tuple(r) for r in got.itertuples(index=False)] == want
    assert len(_by_hand(fx, 100)) >= 10 and _by_hand(fx, 400) == []
    assert set(tpch_q18.VALUES) == {"o_totalprice", "sum_qty"}


def test_float32_control_misses_by_totalprice_alone():
    fx = _Fixture()
    want = tpch_q18.answer(fx, {"quantity": 100})
    low = tpch_q18.answer(fx, {"quantity": 100}, precision="float32")
    assert list(low.o_orderkey) == list(want.o_orderkey)
    # at most 350 a group: exact one precision down
    assert (low.sum_qty.to_numpy().astype(np.float64)
            == want.sum_qty.to_numpy()).all()
    rel = np.abs(low.o_totalprice.to_numpy().astype(np.float64)
                 - want.o_totalprice.to_numpy()) / want.o_totalprice.to_numpy()
    assert 1e-9 < rel.max() < 1e-6  # the configuration's rel_tolerance


def test_an_empty_answer_never_counts_as_checked(monkeypatch):
    from oracles import tpch

    monkeypatch.setattr(tpch, "check", lambda ctx, query: [])
    rec = {"err": None, "rows": [["a"]]}
    ctx = types.SimpleNamespace(records=[rec, {"err": None, "rows": []},
                                         {"err": "x", "rows": []}])
    (c,) = tpch_q18.check(ctx)
    assert (c["name"], c["value"], c["limit"], c["op"]) == (
        "answer_rows_min", 0.0, 1.0, ">=")
    ctx.records = [rec, rec]
    assert tpch_q18.check(ctx)[0]["value"] == 1.0
    ctx.records = []
    assert tpch_q18.check(ctx)[0]["value"] == 0.0


def test_the_cells_files_resolve_and_say_what_the_issue_asks():
    import traffic

    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["source"] == entry["source"] and "2.4.18" in cfg["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["scale_factor"]
    assert cfg["scale_factor"] == 1.0 and cfg["rel_tolerance"] == 1e-9
    assert set(cfg["guarantees"]) == {"answers", "isolation", "plans"}
    assert os.path.exists(os.path.join(BENCH, "loaders",
                                       cfg["loader"] + ".py"))
    mix = traffic.load_mix(MIX)
    assert mix["oracle"] == "tpch_q18" and mix["clients"] == 1
    assert mix["param_sets"] == 4
    (t,) = mix["templates"]
    assert t["params"] == {"quantity": {"gen": "uniform_int", "lo": 312,
                                        "hi": 315}}
    s = traffic.Stream(mix, 2**31 + 32, 0)
    assert [p["quantity"] for _j, p, _sql in s.warmup()] == [312, 315]
    drawn = [s.next() for _ in range(16)]
    assert all(312 <= p["quantity"] <= 315
               and f"> {p['quantity']})" in sql for _j, p, sql in drawn)
    assert [m["name"] for m in man["per_layer"][-5:]] == METRICS
    for m in man["per_layer"][-5:]:
        assert m["workloads"] == [CELL]


def test_metric_files_agree_with_the_tiny_manifest():
    man = json.load(open(Q18_TINY))
    got = {m["name"]: m for m in man["per_layer"]}
    for name in METRICS:
        spec = json.load(open(os.path.join(BENCH, "metrics", name + ".json")))
        for k in ("layer", "unit", "better", "source", "moves"):
            assert spec[k] == got[name][k], (name, k)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_q18_cell_rehearsal(tmp_path):
    """The cell's own mix keeps no order at SF0.01 (QUANTITY 312 to 315 is
    sized for SF1's 1.5M orders), so the rehearsal's mix is the cell's with
    the thresholds that scale gives answers for, written into a copy of
    benchmarks/ as any new mix is: a file, no edit."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.load(open(bench / "traffic" / (MIX + ".json")))
    mix["templates"][0]["params"]["quantity"].update(lo=250, hi=280)
    (bench / "traffic" / "q18_stream_tiny.json").write_text(json.dumps(mix))
    rc, lines, err = run_cell("tpch_sf001_q18.q18", seed=2**31 + 3232,
                              manifest=Q18_TINY, run_py=str(bench / "run.py"),
                              extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    m = last["metrics"]
    assert m["plancache.compiles_in_window"]["value"] == 0
    assert m["flow.general_join_tiles_per_stmt"]["value"] == 0.0
    # orders' one 65,536-row tile, probed below both joins
    assert m["flow.semijoin_probe_tile_rows_per_stmt"]["value"] == 65536.0
    assert m["flow.late_emit_join_tiles_per_stmt"]["value"] == 1.0
    assert m["flow.agg_spills_per_stmt"]["value"] == 0.0
    # 15,000 order keys fit the CPU's dense scatter aggregate, which has no
    # spool: the ordered route is the chip's at SF1 (tests/
    # test_tpch_q18_served.py runs it here with the dense budget lowered)
    assert m["flow.ordered_agg_tiles_per_stmt"]["value"] == 0.0
    assert "kernels.groupby_hbm_roofline_share" not in m  # no chip, no time
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    assert compares["answer_rows_min"]["value"] >= 1
    assert compares["joins_below_the_in_filter"]["value"] == 0
    assert compares["key_mismatches"]["value"] == 0
    assert compares["max_rel_err"]["value"] <= 1e-9
    control = compares["control.min_rel_err_float32"]
    assert control["control_failed_as_it_must"] and control["value"] > 1e-9
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4


PARENT_PLAN = """-> limit 100
  -> group-by keys=[0] aggs=['sum(5)']  [pipeline 4]
    -> hash-join (semi) probe=[16] build=[0] (unique build)  [pipeline 4]
      -> hash-join (inner) probe=[17] build=[0] (unique build)  [pipeline 4]
        -> hash-join (inner) probe=[0] build=[0] (unique build)
          -> scan lineitem
          -> scan orders
        -> scan customer
      -> project ['l_orderkey']  [pipeline 2]
        -> hash-join (inner) probe=[0] build=[0]
          -> scan lineitem
          -> scan part"""


def test_the_loader_counts_the_joins_below_the_in_filter():
    from loaders import tpch_filter_first as ff

    assert ff.joins_below_the_in_filter(PARENT_PLAN) == 2  # not the build's
    assert ff.joins_below_the_in_filter("-> scan orders") == -1
    assert ff.joins_below_the_in_filter(
        "-> hash-join (inner)\n  -> hash-join (semi)\n    -> scan orders\n"
        "    -> project\n  -> scan customer") == 0
    assert ff.probe(2**31 + 32) == 0  # this tree, EXPLAIN over SF0.001
    assert "> 313)" in ff._served_text()


def test_the_loader_refuses_a_program_that_joins_first(monkeypatch):
    """What the parent commit plans (two joins in the semi-join's probe
    side): the run ends non-zero before any data is made."""
    from cockroach_tpu.sql import binder
    from loaders import tpch, tpch_filter_first as ff

    monkeypatch.setattr(binder.Binder, "_semi_filter_source",
                        lambda self, sub_join, scope: False)
    monkeypatch.setattr(tpch, "load", lambda *a: pytest.fail("data made"))
    with pytest.raises(SystemExit) as e:
        ff.load({"name": "tpch_sf1_q18"}, 7, "/nonexistent")
    assert e.value.code not in (0, None)
    assert "2 join(s) below" in str(e.value.code)
