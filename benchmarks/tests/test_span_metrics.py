"""The per-layer metrics PR 25 added, read end to end: the manifest's eight
new entries laid over the tiny manifest, one cell run on the CPU (counts
only there), and the readers on a program that lacks what they read."""

import json
import os
import types

import pytest

from helpers import ROOT, TINY, run_cell

NEW = ["frontend.wire_ms_per_stmt", "flow.dispatch_ms_per_stmt",
       "flow.readback_ms_per_stmt", "node.loops_busy_ms_per_s",
       "node.loop_compiles_in_window", "device.idle_ms_per_stmt.frontend",
       "device.idle_ms_per_stmt.flow", "device.idle_ms_per_stmt.readback"]


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_has_the_eight_after_the_seven():
    names = [m["name"] for m in _manifest()["per_layer"]]
    assert names[7:15] == NEW


def test_cpu_rehearsal_prints_the_new_count(tmp_path):
    with open(TINY) as f:
        man = json.load(f)
    for m in _manifest()["per_layer"]:
        if m["name"] in NEW:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = ["tpch_sf001.q1"]
            man["per_layer"].append(m)
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(man))
    rc, lines, err = run_cell("tpch_sf001.q1", manifest=str(path))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True
    # a CPU rehearsal prints counts only: the new count is among them, the
    # timings are not, and the existing counts still read
    assert last["metrics"]["node.loop_compiles_in_window"]["value"] >= 0
    assert last["metrics"]["flow.dispatches_per_stmt"]["value"] > 0
    assert all(m["unit"] == "count" for m in last["metrics"].values())


def test_readers_give_nothing_on_a_program_without_the_tracer_side(
        monkeypatch):
    """The driver lays these files over the parent's checkout too: there
    `tracing.totals` and `compiles_by_owner` do not exist."""
    from cockroach_tpu.utils import tracing
    from readers import compile_owners, span_totals

    monkeypatch.delattr(tracing, "totals")
    monkeypatch.delattr(tracing, "compiles_by_owner")
    ctx = types.SimpleNamespace(statements=5, window_s=2.0)
    st = span_totals.begin(ctx, names=["pgwire.read"], per="stmt")
    assert st is None
    assert span_totals.read(ctx, st, names=["pgwire.read"],
                            per="stmt") is None
    st = compile_owners.begin(ctx, prefix="node.")
    assert compile_owners.read(ctx, st, prefix="node.") is None


def test_span_totals_gives_nothing_where_the_records_lack_the_field(
        monkeypatch):
    from cockroach_tpu.utils import tracing
    from readers import span_totals

    monkeypatch.setattr(tracing, "totals", lambda: {
        "node.adopt": {"count": 1, "total_s": 0.5, "self_s": 0.5,
                       "tags": {}}})
    ctx = types.SimpleNamespace(statements=5, window_s=2.0)
    assert span_totals.begin(ctx, names=["node."], per="window_s",
                             field="cpu_s") is None
    assert span_totals.begin(ctx, names=["node."],
                             per="window_s") == pytest.approx(500.0)


def test_span_totals_reads_seconds_tags_and_prefixes():
    import time

    from cockroach_tpu.utils import tracing
    from readers import span_totals

    ctx = types.SimpleNamespace(statements=4, window_s=2.0)
    args = [dict(names=["t.wire_a", "t.wire_b"], per="stmt"),
            dict(names=["t.pull"], tag="jit_dispatch_ms", per="stmt"),
            dict(names=["t.loop."], per="window_s"),
            dict(names=["t.loop."], per="window_s", field="cpu_s")]
    states = [span_totals.begin(ctx, **a) for a in args]
    with tracing.timed("t.wire_a"):
        pass
    with tracing.timed("t.wire_b"):
        pass
    with tracing.span("t.root"):
        for ms in (1.5, 2.5):
            with tracing.leaf_span("t.pull") as sp:
                sp.inc_tag("jit_dispatch_ms", ms)
    with tracing.timed("t.loop.heartbeat"):
        time.sleep(0.05)  # a wait: wall seconds, not the thread's CPU
    wire, dispatch_ms, loops, loops_cpu = (
        span_totals.read(ctx, st, **a) for st, a in zip(states, args))
    assert 0 < wire < 10
    assert dispatch_ms == pytest.approx(1.0)  # 4.0 ms over 4 statements
    assert 25 <= loops < 100  # 50 ms and more over 2 s of window
    assert 0 <= loops_cpu < 10


def test_idle_by_layer_needs_exactly_one_profile(tmp_path, monkeypatch,
                                                 capsys):
    import tempfile
    import time

    from readers import idle_by_layer

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    ctx = types.SimpleNamespace(trace={"window_s": 1.0},
                                window_wall0=time.time() - 5)
    assert idle_by_layer.read(ctx, None, part="flow") is None
    assert "0 profiles" in capsys.readouterr().err
    # no trace at all (an untraced run): no search, no reading
    ctx = types.SimpleNamespace(trace=None, window_wall0=0.0)
    assert idle_by_layer.read(ctx, None, part="flow") is None


def test_idle_by_layer_reads_the_recorded_trace(tmp_path, monkeypatch):
    import shutil
    import tempfile
    import time

    from readers import idle_by_layer

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "v5e_spans.xplane.pb")
    if not os.path.exists(src):
        pytest.skip("no recorded trace in this checkout")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    t0 = time.time() - 1
    dst = tmp_path / "bench_x" / "trace" / "plugins" / "profile" / "t"
    dst.mkdir(parents=True)
    shutil.copy(src, dst / "vm.xplane.pb")
    ctx = types.SimpleNamespace(trace={"window_s": 1.0}, window_wall0=t0)
    parts = {p: idle_by_layer.read(ctx, None, part=p)
             for p in ("frontend", "flow", "readback")}
    r = ctx.spans_by_layer
    assert sum(parts.values()) == pytest.approx(
        1e3 * (r["window_s"] - r["busy_s"]) / r["statements"])
    assert abs(r["idle_unattributed_s"]) < 1e-9


def test_idle_by_layer_refuses_covers_that_do_not_tile(capsys):
    from readers import idle_by_layer

    r = {"idle_total_s": 1.0, "idle_unattributed_s": 0.0005,
         "idle_s": {"frontend": 0.2, "flow": 0.7, "readback": 0.0995}}
    assert idle_by_layer._checked(r) is r
    r["idle_unattributed_s"] = 0.02
    assert idle_by_layer._checked(r) is None
    assert "leave" in capsys.readouterr().err
