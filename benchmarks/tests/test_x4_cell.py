"""The cell tpch_sf1_x4.q3_shuffle and its configuration tpch_sf1_x4 (PR 48):
the loader's plan probes on EXPLAIN text, the byte function by hand, the cell's files resolving from
BENCHMARK.json (entries looked up BY NAME: later PRs append), and the cell's
command end to end at SF0.01 on four virtual CPU devices from a manifest of
its own."""

import json
import os

from helpers import BENCH, HERE, ROOT, run_cell
from loaders import tpch_mesh

X4_TINY = os.path.join(HERE, "manifest_x4_tiny.json")
CELL, CONFIG, MIX = "tpch_sf1_x4.q3_shuffle", "tpch_sf1_x4", "q3_stream"
# metric -> the flow/pull tag its file reads through `span_totals`
TAGS = {
    "flow.exchange_stages_per_stmt": "exchange_stages",
    "flow.exchange_rows_per_stmt": "exchange_rows",
    "flow.exchange_offchip_rows_per_stmt": "exchange_offchip_rows",
    "flow.exchange_send_slots_per_stmt": "exchange_send_slots",
    "flow.mesh_overflow_reruns_per_stmt": "mesh_overflow_reruns",
}
OTHERS = {"plancache.mesh_runs_per_stmt": "registry_counters",
          "kernels.mesh_hbm_roofline_share": "mesh_roofline"}

PLAN = """-> limit 10
  -> top-k k=10 keys=['1 desc', '2']
    -> gather (all-gather)
      -> top-k k=10 keys=['1 desc', '2']  [pipeline 1]
        -> group-by keys=[0, 1, 2] aggs=['sum(3)'] mode=final
          -> exchange (all-to-all) keys=[0, 1, 2]
            -> group-by keys=[0, 1, 2] aggs=['sum(3)'] mode=partial
              -> hash-join (inner) probe=[0] build=[0] (unique build)
                -> exchange (all-to-all) keys=[0]
                  -> scan lineitem columns=['l_orderkey', 'l_extendedprice', 'l_discount', 'l_shipdate']
                -> broadcast (all-gather)
                  -> scan orders columns=['o_orderkey', 'o_custkey']"""


def test_the_plan_probes_read_explains_tree():
    assert tpch_mesh.exchanges(PLAN) == {"all": 2, "join_key": 1,
                                         "group_key": 1}
    assert tpch_mesh.scan_columns(PLAN, "lineitem") == 4
    assert tpch_mesh.scan_columns(PLAN, "orders") == 2
    assert tpch_mesh.scan_columns(PLAN, "customer") == 0
    local = "distribution: local (distsql=off)\n-> scan lineitem columns=['a']"
    assert tpch_mesh.exchanges(local)["all"] == 0
    broadcast_only = PLAN.replace(
        "-> exchange (all-to-all) keys=[0]\n", "-> broadcast (all-gather)\n")
    assert tpch_mesh.exchanges(broadcast_only)["join_key"] == 0


def test_the_cell_resolves_by_name():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": MIX,
                           "chips": 4}
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    cfgs = {c["name"]: c for c in man["configs"]}
    cfg = json.load(open(os.path.join(ROOT, cfgs[CONFIG]["file"])))
    assert cfg["loader"] == "tpch_mesh" and cfg["chips"] == 4
    assert cfg["source"] == cfgs[CONFIG]["source"]
    assert len(cfg["source"]) <= 200
    assert cfg["reduced"] == cfgs[CONFIG]["reduced"] == ["scale_factor",
                                                         "nodes"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert set(cfg["guarantees"]) == {"answers", "isolation", "plans",
                                      "placement"}
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in list(TAGS) + list(OTHERS):
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmts_per_s", name
    # latency_p95_ms lists q1 alone and stays so
    lat = next(e for e in man["end_to_end"] if e["name"] == "latency_p95_ms")
    assert lat["workloads"] == ["tpch_sf1.q1"]


def test_metric_files_agree_with_their_manifest_entries_by_name():
    for path in (os.path.join(ROOT, "BENCHMARK.json"), X4_TINY):
        got = {m["name"]: m for m in json.load(open(path))["per_layer"]}
        for name in list(TAGS) + list(OTHERS):
            spec = json.load(open(os.path.join(BENCH, "metrics",
                                               name + ".json")))
            for k in ("layer", "unit", "better", "source", "moves"):
                assert spec[k] == got[name][k], (path, name, k)
            if name in TAGS:
                assert spec["reader"] == "span_totals"
                assert spec["args"] == {"names": ["flow/pull"],
                                        "tag": TAGS[name], "per": "stmt"}
            else:
                assert spec["reader"] == OTHERS[name]
            assert os.path.exists(os.path.join(BENCH, "readers",
                                               spec["reader"] + ".py"))


def test_the_wire_bytes_are_the_docstrings():
    import mesh_bytes

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    rows = 2_430_000
    sent = rows * 3 * (8 + mesh_bytes.VALID_BYTES)
    assert sent == 65_610_000  # 65.6 MB over four chips
    ms = mesh_bytes.least_ici_ms(sent, 4, peaks)
    assert abs(ms - 0.082) < 0.0005
    assert len(mesh_bytes.Q3_STAGES) == 5


def test_x4_cell_rehearsal(monkeypatch):
    """The cell's own mix at SF0.01 on four virtual CPU devices: counts and
    `correct`. The tiny configuration states `broadcast_rows` 0, so orders
    and customer are hash-routed as they are at SF1: five all-to-all
    stages a statement."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    rc, lines, err = run_cell("tpch_sf001_x4.q3_shuffle", seed=2**31 + 4848,
                              manifest=X4_TINY, extra=["--control", "1"])
    assert rc == 0, err[-3000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["count"] == 4
    m = last["metrics"]
    assert m["plancache.mesh_runs_per_stmt"]["value"] == 1.0
    assert m["flow.mesh_overflow_reruns_per_stmt"]["value"] == 0.0
    assert m["flow.exchange_stages_per_stmt"]["value"] == 5.0
    rows = m["flow.exchange_rows_per_stmt"]["value"]
    off = m["flow.exchange_offchip_rows_per_stmt"]["value"]
    slots = m["flow.exchange_send_slots_per_stmt"]["value"]
    assert 0 < off < rows <= slots
    assert 0.6 < off / rows < 0.9  # about 3/4 under a uniform hash
    assert m["plancache.compiles_in_window"]["value"] == 0.0
    assert "kernels.mesh_hbm_roofline_share" not in m  # no chip
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    assert compares["key_mismatches"]["value"] == 0
    assert compares["max_rel_err"]["value"] <= 1e-9
    assert compares["statements_planned_without_exchange"]["value"] == 0.0
    assert compares["mesh_devices"]["value"] == 4.0
    assert compares["mesh_devices"]["ok"] is True
    assert compares["lineitem_shard_rows_spread"]["value"] <= 4096
    control = compares["control.min_rel_err_float32"]
    assert control["control_failed_as_it_must"]
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
