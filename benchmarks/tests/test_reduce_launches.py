"""reduce_launches.py on events written by hand (two operators sharing one
kernel name, a `while` that holds its body, a launch no dispatch claims,
a dispatch nothing links to a launch) and on a small trace recorded on a v5e with the program's
operator sections labelling every dispatch (data/v5e_launches.xplane.pb,
made by record_launches_fixture.py, PR 37)."""

import os
import types

import pytest

import reduce_launches
import reduce_trace
from readers import launches_by_operator

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "v5e_launches.xplane.pb")
MS = 1e6


def ev(name, start_ms, end_ms, **stats):
    return (name, start_ms * MS, (end_ms - start_ms) * MS, stats or None)


def _by_hand():
    """One statement, 0..100 ms. The host is a millisecond AHEAD of the
    device, as in a v5e trace: no timestamp pairs a dispatch with its
    launch. `join.1` and `join.2` both dispatch `emit`."""
    python = [
        ev("cockroach_tpu.query", 0, 100),
        ev("flow/pull", 1, 99),
        ev("flow.dispatch", 10, 11, kernel="emit", op="join.1"),
        ev("flow.dispatch", 12, 13, kernel="emit", op="join.2"),
        ev("flow.dispatch", 14, 15, kernel="fold", op="agg.0"),
        ev("flow.readback", 50, 90),
    ]
    modules = [
        ev("jit_emit(11)", 9, 19),
        ev("jit_emit(11)", 19, 24),
        ev("jit_add(5)", 24, 25),
        ev("jit_fold(7)", 25, 45),
    ]
    ops = [
        ev("%gather_fusion.3 = u32[1024]{0} fusion(u32[1024]{0} %p), "
           "kind=kLoop", 9, 15),
        ev("%sort.1 = (s32[1024]{0}, s32[1024]{0}) sort(s32[1024]{0} %a)",
           15, 19),
        ev("%gather_fusion.4 = u32[1024]{0} fusion(u32[1024]{0} %p), "
           "kind=kLoop", 19, 24),
        ev("%add.9 = s32[]{} add(s32[]{} %x, s32[]{} %y)", 24, 25),
        ev("%while.2 = (s32[], s32[4096]{0}) while((s32[], s32[4096]{0}) "
           "%t), condition=%c, body=%b", 25, 45),
        ev("%scatter_fusion = s32[4096]{0} fusion(s32[4096]{0} %a), "
           "kind=kInput", 26, 34),
        ev("%scatter_fusion = s32[4096]{0} fusion(s32[4096]{0} %a), "
           "kind=kInput", 35, 43),
    ]
    return {"threads": [python], "modules": modules, "ops": ops}


def _with_links(tr):
    """The by-hand trace with the v5e's chain: python's linkage -> the
    runtime thread's Execute -> DoEnqueueProgram inside it -> the launch."""
    tr["threads"][0] += [ev("Execute linkage", 10.2, 10.3, _pt=14, _p=1),
                         ev("Execute linkage", 12.2, 12.3, _pt=14, _p=2),
                         ev("Execute linkage", 14.2, 14.3, _pt=14, _p=3)]
    tr["threads"].append([
        ev("Execute", 10.4, 10.9, _ct=14, _c=1),
        ev("DoEnqueueProgram", 10.5, 10.8, _pt=12, _p=-71),
        ev("Execute", 12.4, 12.9, _ct=14, _c=2),
        ev("DoEnqueueProgram", 12.5, 12.8, _pt=12, _p=-72),
        ev("Execute", 14.4, 14.9, _ct=14, _c=3),
        ev("DoEnqueueProgram", 14.5, 14.8, _pt=12, _p=-73)])
    for mi, c in ((0, -71), (1, -72), (3, -73)):
        n, s, d, _ = tr["modules"][mi]
        tr["modules"][mi] = (n, s, d, {"_ct": 12, "_c": c})
    return tr


def test_two_operators_share_a_kernel_name():
    r = reduce_launches.reduce_events(_with_links(_by_hand()))
    assert r["dispatches"] == 3 and r["statements"] == 1
    ops = r["operators"]
    assert list(ops) == ["agg.0", "join.1", "join.2"]  # by device time
    assert ops["join.1"]["kernels"]["emit"]["device_s"] == pytest.approx(.010)
    assert ops["join.2"]["kernels"]["emit"]["device_s"] == pytest.approx(.005)
    assert ops["agg.0"]["device_s"] == pytest.approx(0.020)
    # the launch nobody dispatched is the rest, and the parts are busy_s
    assert r["unattributed_launches"] == [["jit_add", 1, pytest.approx(.001)]]
    assert r["unattributed_s"] == pytest.approx(0.001)
    assert r["attributed_s"] + r["unattributed_s"] == pytest.approx(
        r["busy_s"])
    # a while's self time leaves its body out; groups sum to the launch
    hlo = dict(ops["agg.0"]["kernels"]["fold"]["hlo_ms_per_launch"])
    assert hlo == {"scatter_fusion s32[4096]": pytest.approx(16.0),
                   "while s32[]": pytest.approx(4.0)}
    hlo = dict(ops["join.1"]["kernels"]["emit"]["hlo_ms_per_launch"])
    assert hlo == {"gather_fusion u32[1024]": pytest.approx(6.0),
                   "sort s32[1024]": pytest.approx(4.0)}


def test_a_dispatch_with_no_launch_linked_to_it_refuses_the_reading():
    # no links at all: nothing is paired by a name or a timestamp
    with pytest.raises(reduce_launches.Refused, match=(
            "2 dispatches of 'emit' and 0 launches linked to them; "
            "1 dispatches of 'fold' and 0 launches")):
        reduce_launches.reduce_events(_by_hand())
    # one chain cut: the kernel's two counts differ, and say so
    tr = _with_links(_by_hand())
    tr["threads"][1] = [e for e in tr["threads"][1]
                        if (e[3] or {}).get("_p") != -72]
    with pytest.raises(reduce_launches.Refused, match=(
            "^2 dispatches of 'emit' and 1 launches linked to them: ")):
        reduce_launches.reduce_events(tr)
    # two dispatches led to one launch: the second has none of its own
    tr = _with_links(_by_hand())
    n, s, d, _ = tr["modules"][1]
    tr["modules"][1] = (n, s, d, {"_ct": 12, "_c": -71})
    with pytest.raises(reduce_launches.Refused, match="2 dispatches of "
                       "'emit' and 1 launches"):
        reduce_launches.reduce_events(tr)


def test_a_launch_nobody_dispatched_is_unattributed_inside_the_stretch():
    tr = _with_links(_by_hand())
    tr["modules"].append(ev("jit_emit(11)", 46, 47))
    tr["ops"].append(ev("%add.9 = s32[]{} add(s32[]{} %x, s32[]{} %y)",
                        46, 47))
    r = reduce_launches.reduce_events(tr)
    assert r["dispatches"] == 3
    assert r["operators"]["join.2"]["launches"] == 1
    assert sorted(n for n, _c, _s in r["unattributed_launches"]) == [
        "jit_add", "jit_emit"]
    assert r["unattributed_s"] == pytest.approx(0.002)
    # outside the stretch it is nobody's business
    tr["modules"][-1] = ev("jit_emit(11)", 146, 147)
    tr["ops"][-1] = ev("%add.9 = s32[]{} add(s32[]{} %x, s32[]{} %y)",
                       146, 147)
    r = reduce_launches.reduce_events(tr)
    assert r["unattributed_s"] == pytest.approx(0.001)
    assert [n for n, _c, _s in r["unattributed_launches"]] == ["jit_add"]


def test_a_program_without_operator_sections_gives_no_reading():
    tr = _by_hand()
    tr["threads"][0] = [
        (n, s, d, {"kernel": st["kernel"]} if st else None)
        for n, s, d, st in tr["threads"][0]]
    with pytest.raises(reduce_launches.Refused, match="no op="):
        reduce_launches.reduce_events(tr)
    tr["threads"][0] = [e for e in tr["threads"][0]
                        if e[0] != "cockroach_tpu.query"]
    with pytest.raises(reduce_launches.Refused, match="no whole statement"):
        reduce_launches.reduce_events(tr)


def test_links_pair_where_the_clocks_do_not_agree():
    """The device's clock runs early: the statement's first launch starts
    before the statement does, so the stretch holds two dispatches of
    `emit` and one launch. The links do not look at the clocks."""
    tr = _with_links(_by_hand())
    n, s, d, st = tr["modules"][0]
    tr["modules"][0] = (n, -5 * MS, d, st)
    n, s, d, st = tr["ops"][0]
    tr["ops"][0] = (n, -5 * MS, 4 * MS, st)
    n, s, d, st = tr["ops"][1]
    tr["ops"][1] = (n, -1 * MS, 4 * MS, st)
    r = reduce_launches.reduce_events(tr)
    assert r["operators"]["join.1"]["device_s"] == pytest.approx(0.008)
    assert r["operators"]["join.2"]["device_s"] == pytest.approx(0.005)
    # the stretch is widened on the device's clock to hold the launch
    assert r["attributed_s"] + r["unattributed_s"] == pytest.approx(
        r["busy_s"])


def test_a_link_outweighs_the_launch_s_name():
    """Two programs that compile to one executable are loaded once and both
    launch under the first one's name (q13's trace, PR 37:
    `sort_spool_fused` launched as `jit_hashagg_finalize`): the link says
    whose launch it is, and the kernel's row says what it went by."""
    tr = _with_links(_by_hand())
    tr["threads"][0][4] = ev("flow.dispatch", 14, 15, kernel="spool",
                             op="sort.0")
    r = reduce_launches.reduce_events(tr)
    spool = r["operators"]["sort.0"]["kernels"]["spool"]
    assert spool["device_s"] == pytest.approx(0.020)
    assert spool["launched_as"] == "jit_fold"
    assert "spool x1 20.000 as jit_fold" in reduce_launches.table(r)
    assert "launched_as" not in r["operators"]["join.1"]["kernels"]["emit"]
    # the order of a kernel's launches is the links' to say as well: the
    # two `emit` launches swapped between the two joins
    tr = _with_links(_by_hand())
    for mi, c in ((0, -72), (1, -71)):
        n, s, d, _ = tr["modules"][mi]
        tr["modules"][mi] = (n, s, d, {"_ct": 12, "_c": c})
    r = reduce_launches.reduce_events(tr)
    assert r["operators"]["join.1"]["device_s"] == pytest.approx(0.005)
    assert r["operators"]["join.2"]["device_s"] == pytest.approx(0.010)


def test_hlo_group_names():
    g = reduce_launches.hlo_group
    assert g("%sort.6 = (s32[65536]{0:T(1024)S(1)}, s32[65536]{0:T(1024)}) "
             "sort(s32[65536]{0:T(1024)S(1)} %m)") == "sort s32[65536]"
    assert g("%multiply_add_fusion = s32[65536]{0:T(1024)S(1)} fusion("
             "s32[65536]{0:T(1024)} %x.1), kind=kLoop") == (
        "multiply_add_fusion s32[65536]")
    assert g("%copy = s32[512,128]{0,1:T(8,128)S(1)} copy(s32[512,128]"
             "{1,0:T(8,128)S(1)} %bitcast.5)") == "copy s32[512]"
    assert g("%while.2 = (u32[], pred[8]{0}) while((u32[], pred[8]{0}) %t)"
             ) == "while u32[]"
    assert g("something else") == "something"


@pytest.fixture(scope="module")
def recorded():
    return reduce_launches.load(FIXTURE)


def test_recorded_v5e_every_dispatch_finds_its_launch_by_links(recorded):
    r = reduce_launches.reduce_events(recorded)
    assert r["statements"] == 3 and r["dispatches"] == 12
    ops = r["operators"]
    assert set(ops) == {"join.1", "join.2"}
    # the two operators share the name fixture_step: one launch a
    # statement at 65,536 rows, two at 16,384
    one, two = ops["join.1"]["kernels"], ops["join.2"]["kernels"]
    assert one["fixture_step"]["launches"] == 3
    assert two["fixture_step"]["launches"] == 6
    assert one["fixture_loop"]["launches"] == 3
    assert [g for g, _ms in one["fixture_step"]["hlo_ms_per_launch"]][0] == (
        "sort s32[65536]")
    assert [g for g, _ms in two["fixture_step"]["hlo_ms_per_launch"]][0] == (
        "sort s32[16384]")
    # the eager add outside every section has no dispatch
    assert [n for n, _c, _s in r["unattributed_launches"]] == ["jit_add"]
    assert r["unattributed_launches"][0][1] == 3
    assert r["unattributed_s"] > 0


def test_recorded_v5e_parts_are_busy_s_and_groups_sum_to_their_launches(
        recorded):
    r = reduce_launches.reduce_events(recorded)
    assert r["attributed_s"] + r["unattributed_s"] == pytest.approx(
        r["busy_s"], rel=0.01)
    # reduce_trace.py's busy_s (the reader holds a real run to 1% of it)
    # plus what a claimed launch ran before the first statement opens on
    # the host's clock: the device's clock is about a millisecond early
    lo = min(s for evs in recorded["threads"] for n, s, _d, _st in evs
             if n == reduce_trace.QUERY_ANNOTATION)
    early = sum(e - s for s, e in reduce_trace.union(
        [(s, s + d) for _n, s, d, _st in recorded["ops"] if s + d <= lo]))
    assert r["busy_s"] == pytest.approx(
        reduce_trace.reduce(FIXTURE)["busy_s"] + early / 1e9, rel=0.01)
    for row in r["operators"].values():
        for k in row["kernels"].values():
            groups = sum(ms for _g, ms in k["hlo_ms_per_launch"])
            assert groups == pytest.approx(
                1e3 * k["device_s"] / k["launches"], rel=0.01)
    # the while's own time is a sliver: its body's sorts are counted once
    loop = dict(r["operators"]["join.1"]["kernels"]["fixture_loop"][
        "hlo_ms_per_launch"])
    assert loop["while u32[]"] < 0.05 * loop["sort s32[16384]"]


def test_recorded_v5e_without_its_links_is_refused(recorded):
    bare = dict(recorded, threads=[
        [(n, s, d, st if n == reduce_launches.DISPATCH else None)
         for n, s, d, st in evs] for evs in recorded["threads"]])
    with pytest.raises(reduce_launches.Refused, match=(
            "9 dispatches of 'fixture_step' and 0 launches linked to them; "
            "3 dispatches of 'fixture_loop' and 0 launches")):
        reduce_launches.reduce_events(bare)


def test_the_reader_gives_both_parts_a_statement_and_refuses_aloud(capsys):
    busy = reduce_trace.reduce(FIXTURE)
    # three statements: a launch ahead of the first one on the host's
    # clock is a large share here, under 0.1% of a real run
    busy["busy_s"] = reduce_launches.reduce(FIXTURE)["busy_s"]
    ctx = types.SimpleNamespace(trace=busy, window_wall0=0.0)
    ctx.launches_by_operator = launches_by_operator._checked(ctx, FIXTURE)
    r = ctx.launches_by_operator
    top = launches_by_operator.read(ctx, None, "top")
    rest = launches_by_operator.read(ctx, None, "unattributed")
    assert top == pytest.approx(
        1e3 * max(o["device_s"] for o in r["operators"].values()) / 3)
    assert rest == pytest.approx(1e3 * r["unattributed_s"] / 3)
    assert "join.1" in capsys.readouterr().err
    # parts that miss the trace's busy_s: nothing, and stderr says so
    ctx.trace = dict(busy, busy_s=2 * busy["busy_s"])
    assert launches_by_operator._checked(ctx, FIXTURE) is None
    assert "miss the trace's busy_s" in capsys.readouterr().err
    # the parent's trace (no op=) reads as nothing, and does not raise
    ctx = types.SimpleNamespace(trace=busy, window_wall0=0.0)
    ctx.launches_by_operator = launches_by_operator._checked(
        ctx, os.path.join(DATA, "v5e_spans.xplane.pb"))
    assert launches_by_operator.read(ctx, None, "top") is None
    assert "no op=" in capsys.readouterr().err


def test_operator_host_ms_reads_the_sections_self_time_or_nothing(
        monkeypatch):
    from cockroach_tpu.utils import tracing
    from readers import operator_host_ms

    totals = {"flow/pull": {"count": 4, "total_s": 2.0, "self_s": 1.0}}
    monkeypatch.setattr(tracing, "totals", lambda: totals)
    ctx = types.SimpleNamespace(statements=4)
    # a program without operator sections: no reading, not 0.0
    assert operator_host_ms.begin(ctx) is None
    assert operator_host_ms.read(ctx, None) is None
    totals["flow.op.groupagg"] = {"count": 8, "total_s": 0.5, "self_s": 0.1}
    totals["flow.op.scan"] = {"count": 8, "total_s": 0.3, "self_s": 0.02}
    state = operator_host_ms.begin(ctx)
    assert state == pytest.approx(120.0)
    totals["flow.op.scan"] = {"count": 12, "total_s": 0.5, "self_s": 0.06}
    assert operator_host_ms.read(ctx, state) == pytest.approx(10.0)
    ctx.statements = 0
    assert operator_host_ms.read(ctx, state) is None
    monkeypatch.delattr(tracing, "totals")
    assert operator_host_ms.begin(ctx) is None


def test_the_host_metric_lists_the_one_cell_the_host_bounds():
    import json

    root = os.path.dirname(os.path.dirname(DATA))
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[
            "flow.operator_host_ms_per_stmt"]
    assert entry["workloads"] == ["tpch_sf1.q1"]
    with open(os.path.join(root, "metrics",
                           "flow.operator_host_ms_per_stmt.json")) as f:
        assert json.load(f)["reader"] == "operator_host_ms"
