"""reduce_spans.py on planes written by hand (two threads, nested
annotations, an idle interval that straddles two spans, a loop's annotation
on a second thread) and on a small trace recorded on a v5e with the
program's tracer mirroring into the profiler (data/v5e_spans.xplane.pb,
made by record_spans_fixture.py, PR 25)."""

import os

import pytest

import reduce_spans
import reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, (end_ms - start_ms) * MS)


def _planes():
    serving = [
        ev("pgwire.read", 0, 2),
        ev("sql.execute", 2, 58),
        ev("sql.parse", 3, 5),
        ev("query", 6, 56),
        ev("cockroach_tpu.query", 6, 56),
        ev("flow/pull", 7, 55),
        ev("flow.dispatch", 8, 9),
        ev("PjitFunction(groupagg_fold_step)", 8.2, 8.8),  # XLA's own
        ev("flow.readback", 25, 45),
        ev("pgwire.encode", 58, 60),
        # 60..70: no statement open (the wait for the client)
        ev("pgwire.read", 70, 71),
        ev("sql.execute", 71, 100),
        ev("query", 72, 98),
        ev("cockroach_tpu.query", 72, 98),
        ev("flow/pull", 73, 97),
    ]
    # a loop's thread: its own SQL (a flow/pull of the loop's, while the
    # serving thread waits for the client) is no statement's
    loops = [ev("node.heartbeat", 62, 66),
             ev("sql.execute", 62.5, 65.5),
             ev("flow/pull", 63, 65),
             ev("Some::NativeThing", 0, 100)]
    return {
        "/device:TPU:0": {
            "XLA Ops": [ev("fusion.1", 10, 20), ev("sort.2", 40, 50),
                        ev("fusion.1", 80, 90)],
            "XLA Modules": [ev("jit_groupagg_fold_step(7)", 10, 20)],
        },
        "/host:CPU": {"python/11": serving, "python/12": loops},
    }


def test_parts_split_each_idle_interval_and_sum_to_window_minus_busy():
    r = reduce_spans.reduce_events(_planes())
    # the stretch is 6..98; busy 10..20, 40..50, 80..90
    assert r["statements"] == 2
    assert r["window_s"] == pytest.approx(0.092)
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["idle_total_s"] == pytest.approx(0.062)
    # idle 20..40 straddles flow/pull (20..25) and flow.readback (25..40);
    # idle 50..80 straddles flow (50..56), the front end with the wait for
    # the client (56..72) and the next statement's flow (72..80)
    assert r["idle_s"]["flow"] == pytest.approx(0.031)
    assert r["idle_s"]["readback"] == pytest.approx(0.015)
    assert r["idle_s"]["frontend"] == pytest.approx(0.016)
    # each part from its own cover, and together they tile the stretch
    assert r["idle_unattributed_s"] == pytest.approx(0.0, abs=1e-12)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_a_loop_threads_own_spans_are_not_a_serving_layer():
    planes = _planes()
    with_loop = reduce_spans.reduce_events(planes)
    # the same events on a thread that carries no node.* section would be
    # a second serving thread: 63..65 of the wait for the client goes to
    # flow
    planes["/host:CPU"]["python/12"] = planes["/host:CPU"]["python/12"][1:]
    without = reduce_spans.reduce_events(planes)
    assert without["idle_s"]["flow"] == pytest.approx(
        with_loop["idle_s"]["flow"] + 0.002)
    assert without["idle_s"]["frontend"] == pytest.approx(
        with_loop["idle_s"]["frontend"] - 0.002)


def test_covers_that_do_not_tile_show_as_unattributed(monkeypatch):
    """An attribution fault (here: the nesting loses a parent's tail) is
    seen, because no part is a remainder."""
    real = reduce_spans.innermost
    monkeypatch.setattr(
        reduce_spans, "innermost",
        lambda evs: [s for s in real(evs) if s != ("frontend", 56 * MS,
                                                   58 * MS)])
    r = reduce_spans.reduce_events(_planes())
    assert r["idle_unattributed_s"] == pytest.approx(0.002)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["idle_total_s"] - 0.002)


def test_it_agrees_with_reduce_trace_on_window_and_busy():
    planes = _planes()
    a, b = reduce_trace.reduce_events(planes), reduce_spans.reduce_events(
        planes)
    assert a["window_s"] == pytest.approx(b["window_s"])
    assert a["busy_s"] == pytest.approx(b["busy_s"])
    assert a["query_annotations"] == b["statements"]


def test_a_trace_that_closes_no_statement_gives_no_result():
    planes = _planes()
    planes["/host:CPU"]["python/11"] = [
        e for e in planes["/host:CPU"]["python/11"]
        if e[0] != "cockroach_tpu.query"]
    assert reduce_spans.reduce_events(planes) is None


def test_a_program_that_does_not_mirror_gives_no_result():
    planes = _planes()
    planes["/host:CPU"] = {"python/11": [
        e for e in planes["/host:CPU"]["python/11"]
        if e[0] == "cockroach_tpu.query"]}
    assert reduce_trace.reduce_events(planes)["query_annotations"] == 2
    assert reduce_spans.reduce_events(planes) is None


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        reduce_spans.reduce_events({"/host:CPU": {"t": [ev("x", 0, 1)]}})


def test_two_serving_threads_flow_wins_then_readback():
    planes = {
        "/device:TPU:0": {"XLA Ops": [ev("f", 0, 1)]},
        "/host:CPU": {
            "python/1": [ev("cockroach_tpu.query", 0, 40),
                         ev("flow.readback", 10, 30)],
            "python/2": [ev("sql.execute", 0, 40),
                         ev("flow/pull", 20, 35)],
        },
    }
    r = reduce_spans.reduce_events(planes)
    # idle 1..40: flow where either is in flow and the other is not in
    # flow (1..10 t1 query; 20..35 t2 pull; 30..40 t1 query) = 9 + 20,
    # readback only where nobody is in flow (10..20)
    assert r["idle_s"]["readback"] == pytest.approx(0.010)
    assert r["idle_s"]["flow"] == pytest.approx(0.029)
    assert r["idle_s"]["frontend"] == pytest.approx(0.0)


def test_innermost_segments_of_one_thread():
    segs = reduce_spans.innermost([
        ("frontend", 0.0, 10.0), ("flow", 2.0, 8.0), ("readback", 3.0, 5.0),
        ("frontend", 12.0, 13.0)])
    assert segs == [("frontend", 0.0, 2.0), ("flow", 2.0, 3.0),
                    ("readback", 3.0, 5.0), ("flow", 5.0, 8.0),
                    ("frontend", 8.0, 10.0), ("frontend", 12.0, 13.0)]


def test_cover_arithmetic():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 22.0), (25.0, 26.0)]
    assert reduce_spans._overlap(a, b) == [(5.0, 10.0), (20.0, 22.0),
                                           (25.0, 26.0)]
    assert reduce_spans._minus(a, b) == [(0.0, 5.0), (22.0, 25.0),
                                         (26.0, 30.0)]


@pytest.mark.parametrize("name,layer", [
    ("pgwire.read", "frontend"), ("sql.bind", "frontend"),
    ("query", "flow"), ("cockroach_tpu.query", "flow"),
    ("flow/pull", "flow"), ("flow.dispatch", "flow"),
    ("flow.readback", "readback"), ("node.adopt", "loops"),
    ("kv.send", None), ("PjitFunction(query)", None)])
def test_layer_of(name, layer):
    assert reduce_spans.layer_of(name) == layer


def test_recorded_v5e_trace_with_the_mirror_on():
    path = os.path.join(DATA, "v5e_spans.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    threads = reduce_spans.load_threads(path)
    ours = [{n for n, _s, _d in evs if reduce_spans.layer_of(n)}
            for evs in threads]
    serving = [s for s in ours if "sql.execute" in s]
    assert serving == [{"pgwire.read", "pgwire.encode",
                        "sql.execute", "query", "cockroach_tpu.query",
                        "flow/pull", "flow.dispatch", "flow.readback"}]
    assert {"node.heartbeat"} in ours  # on a thread of its own
    r = reduce_spans.reduce(path)
    t = reduce_trace.reduce(path)
    assert r["statements"] == t["query_annotations"] == 3
    assert r["window_s"] == pytest.approx(t["window_s"])
    assert r["busy_s"] == pytest.approx(t["busy_s"], rel=1e-6)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert all(v > 0 for v in r["idle_s"].values())
    assert abs(r["idle_unattributed_s"]) < 1e-9
    assert t["device_ops"][0][0] == "jit_fixture_step"
