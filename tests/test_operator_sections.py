"""Every launch on the served path belongs to a plan operator (PR 37):
operator labels from plan/builder.py, operator sections and the statement's
operator record from flow/dispatch.py, on the served TPC-H text through
Session.execute (what the benchmark's cells send) and on synthetic
operators with sleeps."""

import re
import time

import jax.numpy as jnp
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.flow import dispatch
from cockroach_tpu.plan import builder
from cockroach_tpu import sql
from cockroach_tpu.sql import Session
from cockroach_tpu.utils import settings, tracing

# a second literal of each served text: the same plan-cache entry
LITERALS = {
    "q1": ("- 90", "- 75"),
    "q3": ("'1995-03-15'", "'1995-03-09'"),
    "q9": ("%green%", "%ivory%"),
    "q13": ("%special%requests%", "%pending%deposits%"),
    "q18": ("> 300", "> 290"),
}
QUERIES = tuple(LITERALS)
# dispatch.note() sites a statement can pass outside any dispatch.jit
# wrapper: flow/runtime.py _ReadbackShrink.shrink (`compact` of an output
# tile of 65,536 rows or more). None of the five texts answers with such a
# tile at 1,024-row tiles, so here the process counter's delta IS the rows'
# sum.
NOTE_SITES = 0


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.001, seed=3)


@pytest.fixture(autouse=True)
def _tiles():
    settings.set("sql.distsql.tile_size", 1024)
    yield
    settings.reset("sql.distsql.tile_size")


def _text(q, literal=0):
    old, new = LITERALS[q]
    text = " ".join(TPCH_SQL[q].split())
    assert old in text
    return text.replace(old, new) if literal else text


def _execute(sess, text):
    """-> (the statement's flow/pull spans, dispatch.total() delta)"""
    d0 = dispatch.total()
    sess.execute(text)
    delta = dispatch.total() - d0
    root = [s for s in tracing.DEFAULT.finished
            if s.name == "sql.execute"][-1]
    return [s for s in root.walk() if s.name == "flow/pull"], delta


def _rows(pull):
    (rec,) = pull.records
    return rec["operators"], rec["pull_self_ms"]


@pytest.mark.parametrize("q", QUERIES)
def test_every_dispatch_of_a_served_statement_has_an_operator(cat, q):
    sess = Session(cat)
    try:
        first, _ = _execute(sess, _text(q))
        for pull in first:
            rows, _ = _rows(pull)
            calls = sum(r["dispatches"] for r in rows)
            # a first run compiles: those calls are in the rows, not in the
            # span's jit_dispatches
            assert calls >= pull.tags.get("jit_dispatches", 0)
            assert any("compile_ms" in r for r in rows)
        for _ in range(2):  # learn, settle
            _execute(sess, _text(q))
        pulls, delta = _execute(sess, _text(q))
        (pull,) = pulls
        rows, pull_self_ms = _rows(pull)
        labels = [r["label"] for r in rows]
        assert dispatch.NO_OPERATOR not in labels
        assert len(set(labels)) == len(labels)
        assert all(re.fullmatch(r"[a-z]+\.\d+", lb) for lb in labels)
        calls = sum(r["dispatches"] for r in rows)
        assert calls == pull.tags["jit_dispatches"]
        assert delta - calls == NOTE_SITES
        for r in rows:
            assert sum(r["kernels"].values()) == r["dispatches"]
            assert "compile_ms" not in r
        # the span's wall is tiled: operators' host self time, the jitted
        # calls, the readback, the pull loop's own time. The record closes
        # on the span's clock a moment before the span does: never more
        # than the span's wall (but for the rounding), and short of it only
        # by that moment
        parts = (sum(r["host_self_ms"] + r["jit_ms"] for r in rows)
                 + pull.tags.get("readback_ms", 0.0) + pull_self_ms)
        wall_ms = pull.duration * 1e3
        assert wall_ms - 50 <= parts <= wall_ms + 0.001 * (2 * len(rows) + 2)
        # the same seconds, rounded a call there and a row here
        assert sum(r["jit_ms"] for r in rows) == pytest.approx(
            pull.tags["jit_dispatch_ms"], abs=0.001 * (calls + len(rows)))
        # the cached entry again, then a new literal: the same operators
        again, _ = _execute(sess, _text(q))
        assert [r["label"] for r in _rows(again[0])[0]] == labels
        other, _ = _execute(sess, _text(q, literal=1))
        got = _rows(other[-1])[0]
        assert [(r["label"], r["what"]) for r in got] == [
            (r["label"], r["what"]) for r in rows]
        # bundles, /_status/spans and the debug zip take it from to_dict
        assert pull.to_dict()["records"][0]["operators"] == rows
    finally:
        sess.close()


def test_what_names_a_scan_s_table_and_a_join_s_sources(cat):
    sess = Session(cat)
    try:
        for _ in range(3):
            pulls, _ = _execute(sess, _text("q3"))
        what = {r["label"].split(".")[0] + ":" + r["what"]
                for r in _rows(pulls[0])[0]}
        assert "scan:lineitem" in what and "scan:orders" in what
        joins = sorted(w for w in what if w.startswith("hashjoin:"))
        assert len(joins) == 2
        assert any("probe=lineitem build=orders unique" in w for w in joins)
        assert any("build=customer unique" in w and "probe=hashjoin." in w
                   for w in joins)
        assert any(w.startswith("groupagg:dense keys=3") for w in what)
    finally:
        sess.close()


@pytest.mark.parametrize("q", QUERIES)
def test_explain_text_is_what_it_was_without_labels(cat, q, monkeypatch):
    # sql.explain is what q18's loader (tpch_filter_first) parses
    with_labels = sql.explain(cat, _text(q))
    monkeypatch.setattr(builder, "_label_operators", lambda root: None)
    assert sql.explain(cat, _text(q)) == with_labels
    assert not re.search(r"\b(scan|hashjoin|hashagg|groupagg)\.\d", with_labels)
    assert with_labels.startswith("-> ")


class _Op:
    def __init__(self, label, kernel="synthetic", what=""):
        self.label, self.KERNEL, self.what = label, kernel, what


def test_a_consumer_s_self_time_leaves_out_its_child_and_the_jitted_calls():
    step = dispatch.jit(lambda x: x + 1, name="synthetic_step")
    x = jnp.arange(8)
    step(x).block_until_ready()
    consumer, child = _Op("synthetic.0"), _Op("synthetic.1", what="below")

    def tiles():
        for _ in range(3):
            time.sleep(0.01)  # the child's own work, inside its section
            yield step(x)

    before = tracing.totals().get("flow.op.synthetic", {
        "count": 0, "self_s": 0.0, "total_s": 0.0})
    with tracing.span("sql.execute"):
        with tracing.leaf_span("flow/pull") as psp, \
                dispatch.operator_record(psp) as live:
            with dispatch.section(consumer):
                for _t in dispatch.sectioned(child, tiles()):
                    time.sleep(0.02)  # the consumer's, the child closed
                step(x)
            time.sleep(0.005)  # the pull loop's own
            step(x)  # outside every section
    (rec,) = psp.records
    rows = {r["label"]: r for r in rec["operators"]}
    assert rows["synthetic.1"]["what"] == "below"
    assert rows["synthetic.1"]["dispatches"] == 3
    assert rows["synthetic.0"]["dispatches"] == 1
    assert rows[dispatch.NO_OPERATOR]["dispatches"] == 1
    assert rows[dispatch.NO_OPERATOR]["host_self_ms"] == 0.0
    # the rows' own sums, exactly: what covers a consumer is its child's
    # wall and its own jitted calls, and self time is the rest
    up, down = live.rows["synthetic.0"], live.rows["synthetic.1"]
    assert up.child_s == down.wall_s + up.jit_s
    assert down.child_s == down.jit_s
    assert up.self_s() == up.wall_s - down.wall_s - up.jit_s
    assert down.self_s() == down.wall_s - down.jit_s
    assert live.top_s == up.wall_s
    # a sleep lasts at least what it was asked: three of the child's, three
    # of the consumer's with the child closed, one of the pull loop's
    assert rows["synthetic.1"]["host_self_ms"] >= 30 - 0.001
    assert rows["synthetic.0"]["host_self_ms"] >= 60 - 0.001
    assert up.self_s() <= up.wall_s - 0.030  # the child's sleeps are out
    assert rec["pull_self_ms"] >= 5 - 0.001
    parts = (sum(r["host_self_ms"] + r["jit_ms"] for r in rows.values())
             + rec["pull_self_ms"])
    wall_ms = psp.duration * 1e3
    assert wall_ms - 50 <= parts <= wall_ms + 0.001 * (2 * len(rows) + 2)
    # the totals: one name a KERNEL, one close a row, wall and self seconds
    after = tracing.totals()["flow.op.synthetic"]
    assert after["count"] - before["count"] == 2
    assert after["self_s"] - before["self_s"] == pytest.approx(
        up.self_s() + down.self_s(), abs=1e-9)
    assert after["total_s"] - before["total_s"] == pytest.approx(
        up.wall_s + down.wall_s, abs=1e-9)
    assert after["tags"] == {}
    assert "flow.op." + dispatch.NO_OPERATOR not in tracing.totals()
    assert psp.tags["jit_dispatches"] == 5


def test_explain_analyze_s_operator_time_is_the_section_s_wall():
    """One clock an operator: ComponentStats.time_s is the wall of the
    section that next_batch opens, the row count's sync inside it, whether
    or not a statement's record is open."""
    from cockroach_tpu.flow.operator import Operator

    class _Slow(Operator):
        KERNEL = "synthetic_slow"

        def __init__(self):
            super().__init__()
            self.left = 2

        def _next(self):
            if not self.left:
                return None
            self.left -= 1
            time.sleep(0.01)
            return None if not self.left else _Tile()

    class _Tile:
        mask = jnp.ones(4, dtype=bool)
        capacity, cols = 4, ()

    op = _Slow()
    assert dispatch.section(op) is dispatch._NULL  # no statement is traced
    op.collect_stats()
    assert op.next_batch() is not None and op.next_batch() is None
    assert op.stats.rows == 4 and op.stats.batches == 1
    untraced = op.stats.time_s
    assert untraced >= 0.02
    op = _Slow()
    op.collect_stats()
    with tracing.span("sql.execute"):
        with tracing.leaf_span("flow/pull") as psp, \
                dispatch.operator_record(psp) as live:
            op.next_batch(), op.next_batch()
    assert op.stats.time_s == live.rows["synthetic_slow"].wall_s >= 0.02


def test_a_section_enters_no_annotation_and_records_nothing_without_a_span(
        monkeypatch):
    entered = []

    class _Ann:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_profiler_annotation",
                        lambda name, **kw: _Ann(name, **kw))
    step = dispatch.jit(lambda x: x * 2, name="synthetic_quiet")
    x = jnp.arange(8)
    op = _Op("synthetic.7", kernel="quiet")
    # no span current: a null context, no record, nothing in the totals
    with tracing.leaf_span("flow/pull") as psp, \
            dispatch.operator_record(psp) as rec:
        assert psp is None and rec is None
        assert dispatch.section(op) is dispatch._NULL
        with dispatch.section(op):
            step(x)
        assert list(dispatch.sectioned(op, (i for i in (1, 2)))) == [1, 2]
    assert "flow.op.quiet" not in tracing.totals()
    assert entered == []
    # a span, sql.trace.xla_profile off: rows, and still no annotation
    with tracing.span("sql.execute"):
        with tracing.leaf_span("flow/pull") as psp, \
                dispatch.operator_record(psp):
            with dispatch.section(op):
                step(x)
    assert psp.records[0]["operators"][0]["label"] == "synthetic.7"
    assert entered == []
    # on: the spans mirror and flow.dispatch names kernel and operator,
    # but the section itself is no region
    seen = []
    monkeypatch.setattr(
        tracing, "_profiler_annotation",
        lambda name, **kw: seen.append((name, kw)) or _Ann(name))
    settings.set("sql.trace.xla_profile", True)
    try:
        with tracing.span("sql.execute"):
            with tracing.leaf_span("flow/pull") as psp, \
                    dispatch.operator_record(psp):
                with dispatch.section(op):
                    step(x)
                step(x)
    finally:
        settings.reset("sql.trace.xla_profile")
    assert [n for n, _kw in seen] == ["sql.execute", "flow/pull",
                                      "flow.dispatch", "flow.dispatch"]
    assert seen[2][1] == {"kernel": "synthetic_quiet", "op": "synthetic.7"}
    assert seen[3][1] == {"kernel": "synthetic_quiet",
                          "op": dispatch.NO_OPERATOR}


def test_compile_seconds_by_program_tells_a_cache_load_from_a_compile():
    tr = tracing.Tracer()
    tr._on_duration(tracing.COMPILE_EVENT, 2.0, fun_name="jit_a")
    tr._on_duration(tracing.CACHE_LOAD_EVENT, 0.01)
    tr._on_duration(tracing.COMPILE_EVENT, 0.25, fun_name="jit_b")
    tr._on_duration(tracing.COMPILE_EVENT, 0.5, fun_name="jit_a")
    tr._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0,
                    fun_name="jit_a")
    assert tr.compile_seconds() == {
        "jit_a": {"compiles": 2, "compile_s": 2.5, "cache_loads": 0,
                  "cache_load_s": 0.0},
        "jit_b": {"compiles": 0, "compile_s": 0.0, "cache_loads": 1,
                  "cache_load_s": 0.25}}
    assert tr.compiles_by_owner() == {tracing.OWNER_OTHER: 3}
    # the process's listener names a real program
    tracing.install_compile_listener()
    fn = dispatch.jit(lambda x: x - 3, name="synthetic_named")
    fn(jnp.arange(4)).block_until_ready()
    got = tracing.compile_seconds()["jit(synthetic_named)"]
    assert got["compiles"] + got["cache_loads"] == 1
    assert got["compile_s"] + got["cache_load_s"] > 0
