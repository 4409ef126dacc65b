"""The cell sysbench_oltp.read_only and its configuration sysbench_oltp
(PR 45): the manifest's entries looked up BY NAME (later PRs append), the
mix's five templates and weights, the reference against the loader's rows on
three seeds, the bytes the roofline reader is given, the loader's refusal of
a program without the range route, and the cell's command end to end on the
CPU at 4,000 rows and 4 clients from a manifest of its own, with the control
(the reference's seed shifted by one) coming out as not correct."""

import json
import os
import types

import numpy as np
import pytest

from helpers import BENCH, HERE, ROOT, run_cell
from loaders import sysbench_oltp as loader
from oracles import sysbench_oltp as oracle

TINY = os.path.join(HERE, "manifest_sysbench_tiny.json")
CELL, CONFIG, MIX = "sysbench_oltp.read_only", "sysbench_oltp", \
    "oltp_read_only"
COUNTERS = {
    "storage.range_reads_per_stmt": "sql_kv_range_reads",
    "storage.range_rows_per_stmt": "sql_kv_range_rows",
    "storage.range_window_rows_per_stmt": "sql_kv_range_window_rows",
    "storage.oltp_point_reads_per_stmt": "sql_kv_point_reads",
    "storage.oltp_table_decodes_per_stmt": "sql_kv_table_decodes",
}
SPANS = {"storage.range_read_ms_per_stmt": None,
         "storage.range_read_held_ms_per_stmt": "held_ms"}
ROOFLINE = "kernels.rangeread_hbm_roofline_share"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_cells_entries_by_name_say_what_the_issue_asks():
    man = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _json(ROOT, entry["file"])
    assert entry["file"] == "benchmarks/configs/sysbench_oltp.json"
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    for word in ("oltp_read_only.lua", "oltp_common.lua", "--range_size=100",
                 "--skip_trx=on", "--rand-type=uniform", "roachtest"):
        assert word in entry["source"], word
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == [
        "tables", "table_size", "nodes"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert (cfg["tables"], cfg["table_size"], cfg["nodes"],
            cfg["range_size"]) == (1, 4_000_000, 1, 100)
    assert cfg["engine"] == {"key_width": 64, "val_width": 256,
                             "wal_fsync": True}
    assert "c CHAR(120) NOT NULL" in cfg["schema"]
    assert "pad CHAR(60) NOT NULL" in cfg["schema"]
    assert set(cfg["guarantees"]) == {"answers", "isolation", "plans",
                                      "durability"}
    assert len(cfg["assumed"]) >= 7
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in [*COUNTERS, *SPANS, ROOFLINE]:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmts_per_s", name
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert CELL not in e2e["latency_p95_ms"]["workloads"]
    # the accepted cell of the other route stands as it was
    assert by_name["storage.point_reads_per_stmt"]["workloads"] == [
        "crdb_kv.kv95"]


def test_metric_files_agree_with_their_manifest_entries_by_name():
    for path in (os.path.join(ROOT, "BENCHMARK.json"), TINY):
        got = {m["name"]: m for m in _json(path)["per_layer"]}
        for name in [*COUNTERS, *SPANS, ROOFLINE]:
            spec = _json(BENCH, "metrics", name + ".json")
            for k in ("layer", "unit", "better", "source", "moves"):
                assert spec[k] == got[name][k], (path, name, k)
    for name, counter in COUNTERS.items():
        spec = _json(BENCH, "metrics", name + ".json")
        assert spec["reader"] == "registry_counters"
        assert spec["args"] == {"num": [counter], "per": "stmt"}
    for name, tag in SPANS.items():
        spec = _json(BENCH, "metrics", name + ".json")
        want = {"names": ["storage/engine.range_read"], "per": "stmt"}
        if tag:
            want["tag"] = tag
        assert (spec["reader"], spec["args"]) == ("span_totals", want)
    assert _json(BENCH, "metrics", ROOFLINE + ".json")["reader"] == \
        "hbm_roofline"


def test_the_mix_is_oltp_read_only():
    import traffic

    mix = traffic.load_mix(MIX)
    cfg = _json(BENCH, "configs", "sysbench_oltp.json")
    assert (mix["clients"], mix["oracle"], mix["param_sets"],
            mix["trace_seconds"]) == (64, "sysbench_oltp", 8192, 10.0)
    assert {t["name"]: t["weight"] for t in mix["templates"]} == \
        cfg["statement_mix"] == {"point": 10, "range": 1, "sum": 1,
                                 "order": 1, "distinct": 1}
    point, *ranges = mix["templates"]
    assert point["sql"] == "SELECT c FROM sbtest1 WHERE id={id}"
    assert point["params"] == {"id": {"gen": "uniform_int", "lo": 1,
                                      "hi": 4_000_000}}
    between = " WHERE id BETWEEN {b}00 AND {b}99"
    assert [t["sql"] for t in ranges] == [
        "SELECT c FROM sbtest1" + between,
        "SELECT SUM(k) FROM sbtest1" + between,
        "SELECT c FROM sbtest1" + between + " ORDER BY c",
        "SELECT DISTINCT c FROM sbtest1" + between + " ORDER BY c"]
    for t in ranges:
        assert t["params"] == {"b": {"gen": "uniform_int", "lo": 1,
                                     "hi": 39_999}}
    s = traffic.Stream(mix, 2**31 + 45, 3)
    kinds = [s.next()[0] for _ in range(7000)]
    assert 0.68 < kinds.count(0) / len(kinds) < 0.75
    warm = [sql for _j, _p, sql in s.warmup()]
    assert len(warm) == 10 and "id=1" in warm[0] and "id=4000000" in warm[1]
    assert "BETWEEN 100 AND 199" in warm[2]
    assert "BETWEEN 3999900 AND 3999999" in warm[3]
    tiny = traffic.load_mix("oltp_read_only_tiny")
    assert [(t["name"], t["weight"], t["sql"]) for t in tiny["templates"]
            ] == [(t["name"], t["weight"], t["sql"])
                  for t in mix["templates"]]


@pytest.mark.parametrize("seed", [0, 2**31 + 4545, 2**32 + 7])
def test_the_reference_and_the_loader_agree_row_for_row(seed):
    n = 4000
    cols = loader.make_rows(seed, 1, 401, n)
    assert cols["c"].shape == (400, 119) and cols["pad"].shape == (400, 59)
    assert cols["k"].min() >= 1 and cols["k"].max() <= n
    for j, i in enumerate(cols["id"]):
        want = oracle.row(int(i), seed, n)
        assert int(cols["k"][j]) == want["k"]
        assert cols["c"][j].tobytes().decode() == want["c"]
        assert cols["pad"][j].tobytes().decode() == want["pad"]
    assert len(oracle.c_of(1, seed)) == 119
    assert len(oracle.pad_of(1, seed)) == 59
    assert oracle.c_of(1, seed) != oracle.c_of(1, seed + 1)
    ref = oracle.Reference(seed, n)
    cs = [ref.c(i) for i in range(100, 200)]
    assert ref.answer("point", {"id": 150}) == [[cs[50]]]
    assert ref.answer("point", {"id": n + 1}) == []
    assert ref.answer("range", {"b": 1}) == [[c] for c in cs]
    assert ref.answer("order", {"b": 1}) == [[c] for c in sorted(cs)]
    assert ref.answer("distinct", {"b": 1}) == [
        [c] for c in sorted(set(cs))]
    assert ref.answer("sum", {"b": 1}) == [[str(sum(
        oracle.k_of(i, seed, n) for i in range(100, 200)))]]


def test_touched_bytes_is_the_mixs_rows_at_the_stored_width():
    cfg = _json(BENCH, "configs", "sysbench_oltp.json")
    assert oracle.touched_bytes_per_statement(cfg) == pytest.approx(
        (10 * 1 + 4 * 100) / 14 * (64 + 256 + 30))
    assert oracle.touched_bytes_per_statement(cfg, ["point"]) == 350
    assert oracle.touched_bytes_per_statement(cfg, ["sum"]) == 35_000
    from touched_bytes import touched_bytes

    loaded = loader.Loaded.__new__(loader.Loaded)
    loaded.config = cfg
    assert touched_bytes(loaded, "sysbench_oltp") == 10_250


def _ctx(records, control=False):
    mix = {"templates": [{"name": n} for n in
                         ("point", "range", "sum", "order", "distinct")]}
    return types.SimpleNamespace(
        config={"table_size": 4000, "range_size": 100}, seed=9, mix=mix,
        records=records, control=control)


def _rec(t, p, rows, err=None):
    return {"t": t, "p": p, "rows": rows, "err": err}


def test_the_oracles_comparisons():
    ref = oracle.Reference(9, 4000)
    b = {"b": 7}
    good = [_rec(0, {"id": 5}, ref.answer("point", {"id": 5})),
            _rec(1, b, list(reversed(ref.answer("range", b)))),  # any order
            _rec(2, b, ref.answer("sum", b)),
            _rec(3, b, ref.answer("order", b)),
            _rec(4, b, ref.answer("distinct", b))]
    got = {c["name"]: c for c in oracle.check(_ctx(good, control=True))}
    for k in ("points", "ranges", "sums", "orders", "distincts"):
        assert got[f"{k}_wrong"]["value"] == 0.0 == got[f"{k}_wrong"]["limit"]
        assert got[f"{k}_checked"]["value"] == 1.0
    assert got["range_rows_min"]["value"] == 100.0
    assert got["statements_failed"]["value"] == 0.0
    control = got["control.points_wrong_seed_shifted_by_one"]
    assert control["control"] and control["value"] == 1.0
    bad = [_rec(0, {"id": 5}, [["x"]]),
           _rec(1, b, ref.answer("range", b)[:-1]),
           _rec(2, b, [["1"]]),
           _rec(3, b, list(reversed(ref.answer("order", b)))),
           _rec(4, b, ref.answer("distinct", b) * 2),
           _rec(0, {"id": 6}, [], err="ERROR 40001")]
    got = {c["name"]: c["value"] for c in oracle.check(_ctx(bad))}
    assert [got[f"{k}_wrong"] for k in ("points", "ranges", "sums",
                                         "orders", "distincts")] == [1] * 5
    assert got["statements_failed"] == 1 and got["range_rows_min"] == 0.0
    empty = oracle.check(_ctx([_rec(1, b, [])]))
    assert {c["name"]: c["value"] for c in empty}["range_rows_min"] == 0.0


def test_the_loader_refuses_a_program_without_the_route(monkeypatch):
    from cockroach_tpu.plan import indexopt

    cfg = _json(HERE, "configs", "sysbench_oltp_tiny.json")
    assert loader.refuse_without_range_route(cfg) == 0  # this program
    assert len(loader.range_statements(cfg)) == 4
    monkeypatch.setattr(indexopt, "_pk_range", lambda *a: None)
    with pytest.raises(SystemExit) as e:
        loader.load(cfg, 1, "/nonexistent")  # before any node or data
    assert "plans 4 of its range statements" in str(e.value)
    monkeypatch.undo()
    from cockroach_tpu.sql import session

    monkeypatch.setattr(session.T, "CHAR", lambda n: session.T.STRING)
    with pytest.raises(SystemExit) as e:
        loader.refuse_without_range_route(cfg)
    assert "CHAR(120) raw" in str(e.value) and "STRING" in str(e.value)


def test_sysbench_cell_rehearsal():
    """The cell's command on the CPU at 4,000 rows and 4 clients (mix
    oltp_read_only_tiny: the mix's templates and weights over the smaller
    ranges): `correct`, nothing failed, both routes serve every statement,
    no table decode, no compile for another id or range; the control
    fails."""
    rc, lines, err = run_cell("sysbench_oltp_tiny.read_only",
                              seed=2**31 + 4545, manifest=TINY,
                              extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 50
    m = last["metrics"]
    assert 0.15 <= m["storage.range_reads_per_stmt"]["value"] <= 0.45
    assert m["storage.range_rows_per_stmt"]["value"] == pytest.approx(
        100 * m["storage.range_reads_per_stmt"]["value"])
    assert m["storage.range_window_rows_per_stmt"]["value"] == \
        pytest.approx(128 * m["storage.range_reads_per_stmt"]["value"])
    assert m["storage.oltp_point_reads_per_stmt"]["value"] == \
        pytest.approx(1 - m["storage.range_reads_per_stmt"]["value"])
    assert m["storage.oltp_table_decodes_per_stmt"]["value"] == 0.0
    assert m["plancache.compiles_in_window"]["value"] == 0.0
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    for name in ("points_wrong", "ranges_wrong", "sums_wrong",
                 "orders_wrong", "distincts_wrong", "statements_failed",
                 "range_statements_planned_as_scans"):
        assert compares[name]["value"] == 0.0 == compares[name]["limit"]
    assert compares["wal_fsync_armed"]["value"] == 1.0
    assert compares["range_rows_min"]["value"] == 100.0
    assert compares["points_checked"]["value"] > 20
    control = compares["control.points_wrong_seed_shifted_by_one"]
    assert control["control_failed_as_it_must"]
    assert control["value"] == compares["points_checked"]["value"]
    load = next(ln for ln in lines if ln.get("step") == "load")
    assert load["n_rows"] == 4000 and load["run_capacities"] == [4096]
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
