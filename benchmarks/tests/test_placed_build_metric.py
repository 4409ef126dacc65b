"""`flow.placed_build_tiles_per_stmt` (PR 40), looked up BY NAME (later PRs
append): its manifest entry and its file agree, the reader over a live
`Session` on the route the chip takes at SF1 in small (ordered streaming
aggregates, six `lineitem` tiles: 14 a q21 statement, 0 a q18 and a q13
statement), and the reader on a program that never writes the tag."""

import json
import os
import types

import pytest

from helpers import BENCH, ROOT

NAME = "flow.placed_build_tiles_per_stmt"
CELLS = ["tpch_sf1.q21", "tpch_sf1.q18", "tpch_sf1.q13"]


def _spec():
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as f:
        return json.load(f)


def test_the_entry_and_its_file_agree_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (m,) = [m for m in man["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == CELLS
    assert set(CELLS) <= {w["name"] for w in man["workloads"]}
    spec = _spec()
    for k in ("layer", "unit", "better", "source", "moves"):
        assert spec[k] == m[k], k
    assert (m["layer"], m["unit"], m["better"], m["source"], m["moves"]) == (
        "flow", "count", "higher", "program_counter", "stmts_per_s")
    assert spec["reader"] == "span_totals"
    assert spec["args"] == {"names": ["flow/pull"],
                            "tag": "join_build_placed_tiles", "per": "stmt"}
    assert os.path.exists(os.path.join(BENCH, "readers", "span_totals.py"))


@pytest.mark.parametrize("query,placed", [("q21", 14.0), ("q18", 0.0),
                                          ("q13", 0.0)])
def test_the_reader_over_a_session_on_the_streamed_route(query, placed):
    from cockroach_tpu.bench import tpch
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.sql import Session
    from cockroach_tpu.utils import settings
    from readers import span_totals

    args = _spec()["args"]
    settings.set("sql.distsql.dense_agg_states", 64)
    settings.set("sql.distsql.tile_size", 1024)
    s = Session(tpch.gen_tpch(sf=0.001, seed=2**31 + 40))
    try:
        text = " ".join(TPCH_SQL[query].split())
        s.execute(text)  # the plan's first run
        ctx = types.SimpleNamespace(statements=2, window_s=1.0)
        st = span_totals.begin(ctx, **args)
        s.execute(text)
        s.execute(text)
        assert span_totals.read(ctx, st, **args) == placed
    finally:
        s.close()
        settings.reset("sql.distsql.dense_agg_states")
        settings.reset("sql.distsql.tile_size")


def test_a_program_without_the_tag_reads_zero(monkeypatch):
    """The driver lays this file over the parent's checkout too: there
    flow/pull closes without the tag, and 0.0 is true of the parent."""
    from cockroach_tpu.utils import tracing
    from readers import span_totals

    args = _spec()["args"]
    monkeypatch.setattr(tracing, "totals", lambda: {
        "flow/pull": {"count": 9, "total_s": 1.0, "self_s": 1.0,
                      "tags": {"join_build_rows": 4194304.0}, "cpu_s": 0.0}})
    ctx = types.SimpleNamespace(statements=5, window_s=2.0)
    st = span_totals.begin(ctx, **args)
    assert span_totals.read(ctx, st, **args) == 0.0
