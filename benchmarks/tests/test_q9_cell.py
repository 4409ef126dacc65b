"""The cell tpch_sf1.q9 and its configuration tpch_sf1_q9 (PR 28): the
oracle against a hand-written loop on a 50-row fixture, the float32 control
missing the limit, the loader holding a program to the configuration's
`plans` guarantee before it makes the data, and the cell's command end to
end at tiny size on the CPU from a manifest of its own."""

import datetime
import os

import numpy as np
import pandas as pd
import pytest

from helpers import HERE, run_cell
from oracles import tpch_q9

Q9_TINY = os.path.join(HERE, "manifest_q9_tiny.json")
_NAMES = ["spring green almond", "dark red", "lime green", "pale ivory",
          "forest greenish tan", "navy"]
_NATIONS = ["PERU", "FRANCE", "KENYA"]


def _days(y, m, d):
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


class _Fixture:
    """50 lineitem rows over 6 parts, 4 suppliers, 3 nations, 10 orders."""

    def __init__(self):
        rng = np.random.default_rng(28)
        n = 50
        part = rng.integers(1, 7, n)
        supp = rng.integers(1, 5, n)
        self.t = {
            "part": pd.DataFrame({"p_partkey": np.arange(1, 7),
                                  "p_name": _NAMES}),
            "supplier": pd.DataFrame({"s_suppkey": np.arange(1, 5),
                                      "s_nationkey": [0, 1, 2, 1]}),
            "nation": pd.DataFrame({"n_nationkey": [0, 1, 2],
                                    "n_name": _NATIONS}),
            "partsupp": pd.DataFrame(
                [(p, s, round(1.0 + 0.37 * p + 11.13 * s, 2))
                 for p in range(1, 7) for s in range(1, 5)],
                columns=["ps_partkey", "ps_suppkey", "ps_supplycost"]),
            "orders": pd.DataFrame({
                "o_orderkey": np.arange(1, 11),
                "o_orderdate": [_days(1992 + k % 4, 1 + k, 3 + k)
                                for k in range(10)]}),
            "lineitem": pd.DataFrame({
                "l_orderkey": rng.integers(1, 11, n), "l_partkey": part,
                "l_suppkey": supp,
                "l_quantity": rng.integers(1, 51, n).astype(np.float64),
                "l_extendedprice": np.round(rng.uniform(900, 95000, n), 2),
                "l_discount": np.round(rng.integers(0, 11, n) / 100.0, 2)}),
        }

    def frame(self, table, cols):
        return self.t[table][cols].copy()


def _by_hand(fx, color):
    t = fx.t
    name = dict(zip(t["part"].p_partkey, t["part"].p_name))
    nation_of = dict(zip(t["supplier"].s_suppkey, t["supplier"].s_nationkey))
    nname = dict(zip(t["nation"].n_nationkey, t["nation"].n_name))
    cost = {(r.ps_partkey, r.ps_suppkey): r.ps_supplycost
            for r in t["partsupp"].itertuples()}
    date = dict(zip(t["orders"].o_orderkey, t["orders"].o_orderdate))
    out: dict = {}
    for r in t["lineitem"].itertuples():
        if color not in name[r.l_partkey]:
            continue
        year = (datetime.date(1970, 1, 1)
                + datetime.timedelta(days=int(date[r.l_orderkey]))).year
        k = (nname[nation_of[r.l_suppkey]], year)
        out[k] = out.get(k, 0.0) + (
            r.l_extendedprice * (1 - r.l_discount)
            - cost[(r.l_partkey, r.l_suppkey)] * r.l_quantity)
    return sorted(out.items(), key=lambda kv: (kv[0][0], -kv[0][1]))


def test_oracle_against_a_loop_by_hand():
    fx = _Fixture()
    for color in ("green", "red", "navy", "no such colour"):
        want = _by_hand(fx, color)
        got = tpch_q9.answer(fx, {"color": color})
        assert list(got.columns) == tpch_q9.KEYS + tpch_q9.VALUES
        assert [(n, int(y)) for n, y in zip(got.nation, got.o_year)] == \
            [k for k, _v in want]
        np.testing.assert_allclose(got.sum_profit.to_numpy(),
                                   [v for _k, v in want], rtol=1e-13)
    assert len(_by_hand(fx, "green")) >= 6
    assert _by_hand(fx, "no such colour") == []


def test_float32_control_misses_the_limit():
    fx = _Fixture()
    want = tpch_q9.answer(fx, {"color": "green"})
    low = tpch_q9.answer(fx, {"color": "green"}, precision="float32")
    assert list(low.nation) == list(want.nation)
    rel = np.abs(low.sum_profit.to_numpy().astype(np.float64)
                 - want.sum_profit.to_numpy()) / np.abs(
                     want.sum_profit.to_numpy())
    assert rel.max() > 1e-9  # the configuration's rel_tolerance


def test_the_mix_is_the_one_q9_template_with_four_colours():
    import traffic

    mix = traffic.load_mix("q9_stream")
    assert [t["name"] for t in mix["templates"]] == ["q9"]
    s = traffic.Stream(mix, 2**31 + 7, 0)
    drawn = [s.next() for _ in range(64)]
    words = mix["templates"][0]["params"]["color"]["values"]
    assert len(words) == len(set(words)) == 92
    # four draws a run (with replacement), cycled
    assert {p["color"] for _j, p, _sql in drawn} == {
        p["color"] for p in s.sets[0]} <= set(words)
    assert len(s.sets[0]) == 4
    assert all(f"'%{p['color']}%'" in sql for _j, p, sql in drawn)
    assert len(s.warmup()) == 2  # two statements a warm-up pass


def test_the_loader_refuses_a_program_that_compiles_for_a_pattern(
        monkeypatch):
    """What the parent commit does (two programs for a new pattern on
    `nation`): the run ends non-zero before any data is made."""
    from loaders import tpch, tpch_rebind

    monkeypatch.setattr(tpch_rebind, "compiles_for_a_new_pattern",
                        lambda seed: 2)
    monkeypatch.setattr(tpch, "load", lambda *a: pytest.fail("data made"))
    with pytest.raises(SystemExit) as e:
        tpch_rebind.load({"name": "tpch_sf1_q9"}, 7, "/nonexistent")
    assert e.value.code not in (0, None)
    assert "compiled 2 program(s)" in str(e.value.code)


def test_q9_cell_rehearsal():
    rc, lines, err = run_cell("tpch_sf001_q9.q9", seed=2**31 + 2828,
                              manifest=Q9_TINY, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    m = last["metrics"]
    assert m["plancache.compiles_in_window"]["value"] == 0
    assert m["plancache.lookup_tables_bound_per_stmt"]["value"] == 1.0
    assert m["flow.general_join_tiles_per_stmt"]["value"] == 0.0
    # SF0.01: one 65,536-row tile into the part join, four compacted tiles
    assert 65536 < m["flow.join_probe_tile_rows_per_stmt"]["value"] < 5 * 65536
    assert "kernels.multijoin_hbm_roofline_share" not in m  # no chip, no time
    compares = [ln for ln in lines if ln.get("step") == "compare"]
    controls = [c for c in compares if c.get("control")]
    assert controls and all(c["control_failed_as_it_must"] for c in controls)
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
    assert {c["name"] for c in compares} >= {
        "max_rel_err", "key_mismatches", "compiles_for_a_new_pattern"}
