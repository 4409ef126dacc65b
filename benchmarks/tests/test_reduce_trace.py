"""reduce_trace.py on events written by hand and on a small trace recorded
on a v5e (data/v5e_small.xplane.pb, made by record_fixture.py, PR 24)."""

import os

import pytest

import reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union():
    assert reduce_trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_reduce_events_by_hand():
    ms = 1e6
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 10 * ms, 5 * ms),
                        ("fusion.2", 12 * ms, 5 * ms),   # overlaps: 10..17
                        ("sort.3", 40 * ms, 10 * ms)],   # 40..50
            "XLA Modules": [("jit_step(7)", 10 * ms, 7 * ms),
                            ("jit_sort_block(9)", 40 * ms, 10 * ms)],
        },
        "/host:CPU": {"python": [
            ("cockroach_tpu.query", 0 * ms, 20 * ms),
            ("cockroach_tpu.query", 35 * ms, 20 * ms),
            ("other", 55 * ms, 45 * ms)]},     # read inside 0..55
    }
    r = reduce_trace.reduce_events(planes)
    assert r["busy_s"] == pytest.approx(0.017)
    assert r["window_s"] == pytest.approx(0.055)
    assert r["query_annotations"] == 2
    assert r["device_ops"][0] == ["jit_sort_block", pytest.approx(0.010)]
    assert r["device_ops"][1] == ["jit_step", pytest.approx(0.007)]
    gaps = r["idle_gaps"]
    # 17..40 (middle 28.5: between), 0..10 (middle 5: inside the first
    # query), 50..55 (middle 52.5: inside the second)
    assert gaps[0] == ["between_queries", pytest.approx(0.023)]
    assert gaps[1] == ["inside_query", pytest.approx(0.010)]
    assert gaps[2] == ["inside_query", pytest.approx(0.005)]
    assert r["idle_inside_query_s"] == pytest.approx(0.015)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        reduce_trace.reduce_events({"/host:CPU": {"t": [("x", 0.0, 1.0)]}})


def test_recorded_v5e_trace():
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    planes = reduce_trace.load_events(path)
    assert any(p.startswith("/device:TPU:") for p in planes)
    r = reduce_trace.reduce_events(planes)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["query_annotations"] == 3
    assert r["device_ops"] and r["device_ops"][0][0].startswith("jit_")
    assert {g[0] for g in r["idle_gaps"]} <= {"inside_query",
                                              "between_queries"}
