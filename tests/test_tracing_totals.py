"""The tracer's whole-window side (utils/tracing.py): per-name totals, timed
sections, compile ownership, the profiler mirror; and the stable device
program names flow/dispatch.jit gives (README "End-to-end distributed
tracing")."""

import ast
import json
import logging
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import pytest

from cockroach_tpu.flow import dispatch
from cockroach_tpu.lint import core as lintcore
from cockroach_tpu.lint import rawjit
from cockroach_tpu.utils import settings, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the closure names the profiler showed before programs were named (PERF.md,
# PR 24): no module may carry one again
CLOSURE_NAMES = {"jit(_lambda)", "jit(<lambda>)", "jit(fn)", "jit(kern)",
                 "jit(build_fn)", "jit(merge_fn)", "jit(chain)"}


class _Clock:
    """perf_counter that advances only when the script says so."""

    def __init__(self):
        self.t = 100.0
        self.cpu = 5.0  # this thread's CPU seconds, scripted apart

    def perf_counter(self):
        return self.t

    def thread_time(self):
        return self.cpu

    def time(self):
        return 1.7e9 + self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter=c.perf_counter, thread_time=c.thread_time,
        time=c.time))
    return c


def test_totals_exact_for_a_scripted_tree(clock):
    tr = tracing.Tracer()
    with tr.span("root", k=1):
        clock.t += 1.0
        with tr.span("child", rows=3, ms=0.5, flag=True, label="x") as c:
            clock.t += 2.0
            c.inc_tag("ms", 1.25)
        clock.t += 0.5
        with tr.span("child", rows=4):
            clock.t += 4.0
            with tr.leaf_span("leaf"):
                clock.t += 1.0
    t = tr.totals()
    assert t["root"] == {"count": 1, "total_s": 8.5, "self_s": 1.5,
                         "tags": {"k": 1}, "cpu_s": 0.0}
    # booleans and strings are not summed; numeric tags are
    assert t["child"] == {"count": 2, "total_s": 7.0, "self_s": 6.0,
                          "tags": {"rows": 7, "ms": 1.75}, "cpu_s": 0.0}
    assert t["leaf"] == {"count": 1, "total_s": 1.0, "self_s": 1.0,
                         "tags": {}, "cpu_s": 0.0}


def test_self_time_counts_children_dropped_past_the_cap(clock):
    tr = tracing.Tracer()
    n = tracing.MAX_CHILDREN + 22
    with tr.span("root") as root:
        for _ in range(n):
            with tr.leaf_span("hot"):
                clock.t += 0.25
        clock.t += 3.0
    assert len(root.children) == tracing.MAX_CHILDREN
    assert root.tags["dropped_children"] == 22
    t = tr.totals()
    assert t["hot"]["count"] == n
    assert t["root"]["total_s"] == pytest.approx(0.25 * n + 3.0)
    # the tree alone would put the 22 dropped children's 5.5 s into self
    assert t["root"]["self_s"] == pytest.approx(3.0)


def test_totals_is_a_snapshot_and_a_window_is_a_difference(clock):
    tr = tracing.Tracer()
    with tr.span("a", n=1):
        clock.t += 1.0
    before = tr.totals()
    with tr.span("a", n=2):
        clock.t += 2.0
    after = tr.totals()
    assert before["a"]["count"] == 1 and before["a"]["tags"] == {"n": 1}
    assert after["a"]["total_s"] - before["a"]["total_s"] == 2.0
    assert after["a"]["tags"]["n"] - before["a"]["tags"]["n"] == 2


def test_timed_section_feeds_totals_but_grows_no_tree(clock):
    tr = tracing.Tracer()
    with tr.timed("node.heartbeat"):
        clock.t += 0.5   # half a second in the section,
        clock.cpu += 0.125  # an eighth of it on the CPU: the rest waits
        assert tr.current() is None
        assert tr.context() is None
        assert tr.inflight() == []
        with tr.leaf_span("wal.append") as leaf:
            assert leaf is None
    assert tr.finished == []
    assert tr.totals() == {"node.heartbeat": {
        "count": 1, "total_s": 0.5, "self_s": 0.5, "tags": {},
        "cpu_s": 0.125}}


def test_a_timed_sections_cpu_seconds_leave_its_waits_out():
    """On the real clocks: a section that sleeps has wall seconds and next
    to no CPU seconds; one that computes has both."""
    tr = tracing.Tracer()
    with tr.timed("waits"):
        time.sleep(0.05)
    with tr.timed("works"):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.05:
            sum(range(1000))
    t = tr.totals()
    assert t["waits"]["total_s"] >= 0.05 > 10 * t["waits"]["cpu_s"]
    assert t["works"]["cpu_s"] >= 0.05


def test_timed_section_inside_a_span_leaves_the_tree_alone(clock):
    tr = tracing.Tracer()
    with tr.span("root") as root:
        with tr.timed("side"):
            clock.t += 1.0
            assert tr.current() is root
    assert root.children == []
    assert tr.totals()["root"]["self_s"] == 1.0


def _compile_something(k: int) -> None:
    # a shape and a constant no other test uses: always one backend compile
    jax.jit(lambda x: x * k + 1)(  # crlint: allow-raw-jit(test)
        jnp.ones(1000 + k)).block_until_ready()


def test_compiles_by_owner_sums_to_a_listeners_own_count():
    import jax.monitoring

    seen = []

    def on(event, _secs, **_kw):
        if event == tracing.COMPILE_EVENT:
            seen.append(event)

    before = tracing.compiles_by_owner()  # installs the tracer's listener
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        with tracing.timed("node.test_loop"):
            _compile_something(31)
        with tracing.span("sql.execute"):
            _compile_something(37)
            with tracing.timed("node.inner"):  # innermost section wins
                _compile_something(41)
        _compile_something(43)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    after = tracing.compiles_by_owner()
    delta = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    assert set(delta) == {"node.test_loop", "node.inner",
                          tracing.OWNER_STATEMENT, tracing.OWNER_OTHER}
    assert sum(delta.values()) == len(seen) >= 4


def test_compile_listener_is_installed_once():
    tracing.install_compile_listener()
    tracing.install_compile_listener()
    before = sum(tracing.compiles_by_owner().values())
    _compile_something(47)
    _compile_something(47)  # cached: no second compile
    assert sum(tracing.compiles_by_owner().values()) - before >= 1
    from jax._src import monitoring as m

    mine = [cb for cb in m.get_event_duration_listeners()
            if getattr(cb, "__self__", None) is tracing.DEFAULT]
    assert len(mine) == 1


@pytest.fixture
def annotations(monkeypatch):
    """Every profiler annotation the tracer asks for, as (name, args)."""
    made = []

    class _Ann:
        def __init__(self, name, **args):
            made.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_profiler_annotation", _Ann)
    yield made
    settings.reset("sql.trace.xla_profile")


def _spans_sections_and_hot_sites():
    with tracing.span("sql.execute"):
        with tracing.leaf_span("flow/pull"):
            with tracing.annotation("flow.dispatch", kernel="topk_tile"):
                pass
    with tracing.timed("pgwire.encode"):
        pass


def test_mirror_writes_nothing_with_the_setting_off(annotations):
    assert settings.get("sql.trace.xla_profile") is False
    _spans_sections_and_hot_sites()
    assert annotations == []


def test_mirror_follows_the_setting(annotations):
    settings.set("sql.trace.xla_profile", True)
    _spans_sections_and_hot_sites()
    assert annotations == [
        ("sql.execute", {}), ("flow/pull", {}),
        ("flow.dispatch", {"kernel": "topk_tile"}),
        ("pgwire.encode", {})]
    del annotations[:]
    settings.reset("sql.trace.xla_profile")
    _spans_sections_and_hot_sites()
    assert annotations == []


def test_mirror_enters_real_annotations_without_a_profiler_session():
    settings.set("sql.trace.xla_profile", True)
    try:
        _spans_sections_and_hot_sites()  # TraceAnnotation with no trace on
    finally:
        settings.reset("sql.trace.xla_profile")


# -- device program names ----------------------------------------------------

def test_dispatch_jit_refuses_a_missing_or_dynamic_name():
    for bad in ({}, {"name": None}, {"name": "Scan lineitem#42"}):
        with pytest.raises(ValueError):
            dispatch.jit(lambda x: x + 1, **bad)  # crlint: allow-raw-jit(the refusal under test)
    with pytest.raises(ValueError):
        dispatch.jit(**{"name": None})(lambda x: x + 1)  # crlint: allow-raw-jit(the refusal under test)


def test_dispatch_jit_names_the_module_and_scopes_the_body():
    f = dispatch.jit(lambda x: x * 2 + 1, name="unit_double")
    text = f._jitted.lower(jnp.ones(8)).as_text(debug_info=True)
    assert "module @jit_unit_double" in text
    assert "unit_double/" in text  # jax.named_scope on the body's ops


def test_every_dispatch_jit_in_the_tree_has_a_static_name():
    sites = 0
    for src in lintcore.load_files([os.path.join(ROOT, "cockroach_tpu")]):
        if src.rel.startswith("cockroach_tpu/lint/") or src.rel == (
                "cockroach_tpu/flow/dispatch.py"):
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and (
                    rawjit._is_dispatch_jit(node.func) or (
                        node.args and rawjit._is_dispatch_jit(node.args[0]))):
                sites += 1
                assert not rawjit._unnamed_jit(node), (src.rel, node.lineno)
    assert sites >= 40


def test_raw_jit_pass_flags_nameless_and_dynamic_names(tmp_path):
    from cockroach_tpu.lint import run_lint

    pkg = tmp_path / "cockroach_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "thing.py").write_text(
        "import functools\n"
        "from ..flow import dispatch\n"
        "class Op:\n"
        "    KERNEL = 'thing'\n"
        "    def __init__(self, table, tag):\n"
        "        self.a = dispatch.jit(lambda v: v, name='thing_tile')\n"
        "        self.b = dispatch.jit(lambda v: v,\n"
        "                              name=f'{self.KERNEL}_{tag}_x')\n"
        "        self.c = dispatch.jit(lambda v: v)\n"
        "        self.d = dispatch.jit(lambda v: v, name=table.name)\n"
        "        self.e = dispatch.jit(lambda v: v, name=f'q_{table.name}')\n"
        "        self.f = functools.partial(dispatch.jit, static_argnums=0)\n"
        "        self.g = dispatch.jit(lambda v: v, name='Bad Name')\n")
    found = run_lint([str(tmp_path)], rules=("raw-jit",))
    assert sorted(f.line for f in found) == [9, 10, 11, 12, 13]
    assert all("static name=" in f.message for f in found)


class _Compiled(logging.Handler):
    """Names of the programs jax lowers (`Compiling jit(<name>) ...`)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (\S+) with global shapes",
                     record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.fixture(scope="module")
def tiny_tpch():
    from cockroach_tpu.bench import tpch

    return tpch.gen_tpch(sf=0.01, seed=20250925)


def _mix_sql(mix: str, **params) -> str:
    with open(os.path.join(ROOT, "benchmarks", "traffic", mix + ".json")) as f:
        return json.load(f)["templates"][0]["sql"].format(**params)


@pytest.mark.parametrize("mix,params,must_have,must_not", [
    ("q1_stream", {"delta": 90}, {"jit(groupagg_fold_seed)"}, set()),
    # q3's builds are proven unique by the binder (PR 26): analytic probes,
    # no sorted build index
    ("q3_stream", {"segment": "BUILDING", "date": "1995-03-15"},
     {"jit(hashjoin_emit)"}, {"jit(hashjoin_build)"}),
])
def test_benchmark_statements_lower_to_named_modules(tiny_tpch, mix, params,
                                                      must_have, must_not):
    from cockroach_tpu.sql import Session

    dispatch.clear_kernel_cache()  # shared wrappers would skip the lowering
    log = logging.getLogger("jax._src.interpreters.pxla")
    handler, level = _Compiled(), log.level
    log.addHandler(handler)
    log.setLevel(logging.DEBUG)
    from cockroach_tpu.sql import sqlstats

    try:
        out = Session(catalog=tiny_tpch).execute(_mix_sql(mix, **params))
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
        # the registry is the process's: tests elsewhere look their own
        # statement up by a piece of its text (q3's has `group by l_orderkey`)
        sqlstats.DEFAULT.clear()
    assert len(next(iter(out.values()))) > 0
    names = set(handler.names)
    assert must_have <= names and not must_not & names, names
    assert not names & CLOSURE_NAMES, names
    # whatever went through dispatch.jit is <operator>_<role>
    for n in names:
        inner = n[len("jit("):-1]
        assert re.fullmatch(r"[A-Za-z0-9_]+", inner), n


def test_storage_kernels_stay_outside_the_flow_budget_under_their_own_names():
    """The storage plane's kernels are raw jax.jit on purpose: the dispatch
    budget scopes the SQL flow, and their modules carry function names."""
    from cockroach_tpu.storage import lsm, mvcc

    t0 = dispatch.total()
    eng = lsm.Engine(key_width=16, val_width=16)
    for i in range(8):
        eng.put(b"k%03d" % i, b"v%03d" % i, ts=10 + i)
    eng.flush()
    assert eng.get(b"k003", ts=100) == b"v003"
    assert dispatch.total() == t0
    assert mvcc.sort_block.__name__ == "sort_block"
    assert mvcc.merge_blocks.__name__ == "merge_blocks"


# -- the statement's tree is what it was ---------------------------------------

def test_sql_execute_is_still_a_root_with_the_same_direct_children(tiny_tpch):
    """benchmarks' frontend.host_ms_per_stmt reads sql.execute's self time
    from the ring: a new child or a parent would redefine it. The sets are
    those of the parent commit (PR 24)."""
    from cockroach_tpu.sql import Session

    s = Session(catalog=tiny_tpch)
    sql = "select count(*) as n from nation where n_nationkey < 7"
    tracing.DEFAULT.finished.clear()
    s.execute(sql)
    s.execute(sql)
    roots = [r for r in tracing.DEFAULT.finished if r.name == "sql.execute"]
    assert len(roots) == 2 and all(r.parent_id == 0 for r in roots)
    first, memo = roots
    assert [c.name for c in first.children] == [
        "sql.parse", "sql.bind", "sql.plancache.lookup", "query"]
    assert [c.name for c in memo.children] == ["query"]
    assert memo.children[0].tags["cache"] == "memo"
    assert [c.name for c in memo.children[0].children] == ["flow/pull"]
    # a tag, not a child
    assert memo.tags["admission_wait_ms"] >= 0.0
    assert tracing.current() is None


def test_the_ring_holds_a_thousand_roots():
    assert tracing.MAX_FINISHED == 1024
    tr = tracing.Tracer()
    for i in range(tracing.MAX_FINISHED + 5):
        with tr.span("r", i=i):
            pass
    assert len(tr.finished) == tracing.MAX_FINISHED
    assert tr.finished[0].tags["i"] == 5
    assert tr.totals()["r"]["count"] == tracing.MAX_FINISHED + 5


def test_pgwire_times_the_wire_and_the_node_times_its_loops():
    import time

    from cockroach_tpu.server.node import Node
    from test_pgwire import MiniPg

    before = tracing.totals()
    node = Node(heartbeat_interval_s=0.05, metrics_interval_s=0.05,
                adopt_interval_s=0.05).start(pg_port=0)
    try:
        conn = MiniPg(node.pg.addr)
        try:
            for _ in range(3):
                rows, _names, _tag, err = conn.query("select 1 as one")
                assert err is None and rows == [["1"]]
        finally:
            conn.close()
        deadline = time.time() + 20
        want = {"node.heartbeat", "node.tsdb_scrape", "node.adopt"}
        while time.time() < deadline and not all(
                tracing.totals().get(n, {"count": 0})["count"]
                > before.get(n, {"count": 0})["count"] for n in want):
            time.sleep(0.05)
    finally:
        node.stop()
    after = tracing.totals()

    def grew(name):
        return after.get(name, {"count": 0})["count"] - before.get(
            name, {"count": 0})["count"]

    assert grew("pgwire.read") >= 3 and grew("pgwire.encode") >= 3
    assert grew("sql.execute") == 3
    for n in want:
        assert grew(n) >= 1, n
    # no loop grew a root: the ring's roots are statements and whatever
    # else asked for a span, never a timed section
    assert not [r for r in tracing.DEFAULT.finished
                if r.name.startswith(("node.", "pgwire."))]
