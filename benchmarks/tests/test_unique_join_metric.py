"""`flow.unique_join_tiles_per_stmt` (PR 26), read end to end: the manifest's
entry laid over the tiny manifest, the q3 cell run on the CPU (counts only
there), and the reader on a program that never writes the tag."""

import json
import os
import types

from helpers import ROOT, TINY, run_cell

NAME = "flow.unique_join_tiles_per_stmt"


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert per_layer[-1]["name"] == NAME  # appended, nothing moved
    return per_layer[-1]


def test_the_entry_lists_q3_and_moves_throughput():
    m = _entry()
    assert m["workloads"] == ["tpch_sf1.q3"]
    assert (m["layer"], m["unit"], m["better"], m["moves"]) == (
        "flow", "count", "higher", "stmts_per_s")


def test_cpu_rehearsal_counts_unique_probe_tiles_in_q3(tmp_path):
    with open(TINY) as f:
        man = json.load(f)
    man["per_layer"].append(dict(_entry(), workloads=["tpch_sf001.q3"]))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(man))
    rc, lines, err = run_cell("tpch_sf001.q3", manifest=str(path))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True
    # two joins, each over the tiny lineitem's one tile: 2 a statement
    assert last["metrics"][NAME] == {"value": 2.0, "unit": "count"}


def test_a_program_without_the_tag_reads_zero(monkeypatch):
    """The driver lays this file over the parent's checkout too: there
    flow/pull closes without the tag, and 0.0 is true of the parent."""
    from cockroach_tpu.utils import tracing
    from readers import span_totals

    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           NAME + ".json")) as f:
        args = json.load(f)["args"]
    monkeypatch.setattr(tracing, "totals", lambda: {
        "flow/pull": {"count": 9, "total_s": 1.0, "self_s": 1.0,
                      "tags": {"jit_dispatches": 27.0}, "cpu_s": 0.0}})
    ctx = types.SimpleNamespace(statements=5, window_s=2.0)
    st = span_totals.begin(ctx, **args)
    assert span_totals.read(ctx, st, **args) == 0.0
