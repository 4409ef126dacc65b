"""A new mix, a new configuration and a new per-layer metric are files plus
one manifest entry each: no file that is there is edited."""

import json
import os
import shutil

from helpers import BENCH, ROOT, TINY, run_cell


def test_new_cell_is_files_only(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # a mix: Q1 from two clients with a narrower DELTA
    mix = json.load(open(bench / "traffic" / "q1_stream.json"))
    mix["clients"] = 2
    mix["templates"][0]["params"]["delta"] = {"gen": "choice",
                                              "values": [70, 80]}
    (bench / "traffic" / "q1_pair.json").write_text(json.dumps(mix))
    # a configuration: another scale
    cfg = json.load(open(bench / "tests" / "configs" / "tpch_sf001.json"))
    cfg.update(name="tpch_sf002", scale_factor=0.02, lineitem_rows=120000,
               lineitem_rows_tolerance=4000)
    (bench / "configs" / "tpch_sf002.json").write_text(json.dumps(cfg))
    # a metric: a counter nothing read before, through a reader of its own
    (bench / "readers" / "cache_hits.py").write_text(
        "def begin(ctx):\n"
        "    from cockroach_tpu.flow import dispatch\n"
        "    return dispatch.kernel_cache_hits()\n\n"
        "def read(ctx, state):\n"
        "    from cockroach_tpu.flow import dispatch\n"
        "    return float(dispatch.kernel_cache_hits() - state)\n")
    (bench / "metrics" / "flow.kernel_cache_hits.json").write_text(json.dumps(
        {"layer": "flow", "unit": "count", "better": "higher",
         "source": "program_counter", "moves": "stmts_per_s",
         "reader": "cache_hits", "args": {}}))
    man = json.load(open(TINY))
    man["configs"].append({"name": "tpch_sf002", "source": "tests only",
                           "file": "benchmarks/configs/tpch_sf002.json",
                           "reduced": ["scale_factor"], "why": "test"})
    man["workloads"].append({"name": "tpch_sf002.q1_pair",
                             "config": "tpch_sf002", "traffic": "q1_pair",
                             "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "flow.kernel_cache_hits",
                             "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "flow",
                             "moves": "stmts_per_s",
                             "workloads": ["tpch_sf002.q1_pair"]})
    (tmp_path / "manifest.json").write_text(json.dumps(man))

    rc, lines, err = run_cell("tpch_sf002.q1_pair",
                              manifest=str(tmp_path / "manifest.json"),
                              run_py=str(bench / "run.py"))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True
    assert "flow.kernel_cache_hits" in last["metrics"]
    load = next(ln for ln in lines if ln.get("step") == "load")
    assert 110000 < load["n_rows"] < 130000
    assert {r for ln in lines if ln.get("step") == "compare"
            for r in [ln["name"]]} >= {"max_rel_err", "key_mismatches"}
    # nothing that was there changed
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_metric_files_agree_with_the_manifest():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        for k in ("layer", "unit", "better", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    for c in man["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["platform"] == "tpu" and len(cfg["source"]) <= 200
        assert "assumed" in cfg and "guarantees" in cfg
    for w in man["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
