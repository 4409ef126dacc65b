"""A Session that reaches the mesh (PR 48): a node that spans four of the
virtual CPU devices row-shards its tables over them, and a served statement
runs across all four through the normal path: Session -> plan cache ->
sql/distsql.py `place` -> one SPMD program (parallel/planner.py) under
`runtime.run_operator`'s bookkeeping. Held to the same Session on one
device, to the benchmark's pandas oracle, and the exchange itself to a
plain numpy reference written here (hash, `% D`, stable partition)."""

import os
import sys

import jax
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.catalog import Catalog
from cockroach_tpu.coldata import types as T
from cockroach_tpu.coldata.batch import from_host
from cockroach_tpu.flow import dispatch, runtime
from cockroach_tpu.ops import aggregation as agg_ops
from cockroach_tpu.ops.hashing import hash_columns
from cockroach_tpu.parallel import mesh as mesh_mod
from cockroach_tpu.parallel.dist import shard_batch
from cockroach_tpu.parallel.planner import DistributedQuery, MeshOp
from cockroach_tpu.parallel.shuffle import exchange, route
from cockroach_tpu.plan import spec as S
from cockroach_tpu.server.node import Node
from cockroach_tpu.sql import Session, distsql, explain, sql
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import metric, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED = 2**31 + 48
Q3 = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as "
      "revenue, o_orderdate, o_shippriority from customer, orders, lineitem "
      "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey and "
      "l_orderkey = o_orderkey and o_orderdate < date '{date}' and "
      "l_shipdate > date '{date}' group by l_orderkey, o_orderdate, "
      "o_shippriority order by revenue desc, o_orderdate limit 10")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def served():
    """(node over four devices, its serving catalog, a catalog of the same
    tables on one device)."""
    cat = tpch.gen_tpch(sf=0.002, seed=SEED)
    node = Node(devices=4).start(pg_port=0)
    one = Catalog()
    for name, table in cat.tables.items():
        node._sql_catalog.tables[name] = table
        one.tables[name] = table
    yield node, node._sql_catalog, one
    node.stop()


def _same(got, want):
    assert list(got) == list(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.shape == w.shape, (k, g.shape, w.shape)
        if w.dtype.kind == "f" or g.dtype.kind == "f":
            np.testing.assert_allclose(g.astype(np.float64),
                                       w.astype(np.float64), rtol=1e-9,
                                       err_msg=k)
        else:
            assert [str(v) for v in g] == [str(v) for v in w], k


def _pull_tags():
    return dict(tracing.totals().get("flow/pull", {"tags": {}})["tags"])


@pytest.mark.parametrize("q", ["q1", "q3", "q9", "q18", "q13", "q21"])
def test_served_tpch_text_on_a_four_device_node(served, q):
    """The six TPC-H cells' served texts: across the mesh where
    `distsql=auto` places them there, equal to one device; a text that
    stays local says so in EXPLAIN (DISTSQL), loudly."""
    _node, mesh_cat, one = served
    text = " ".join(TPCH_SQL[q].split())
    plan = explain(mesh_cat, "EXPLAIN (DISTSQL) " + text)
    runs0 = metric.PLAN_CACHE_MESH_RUNS.value
    got = Session(mesh_cat).execute(text)
    ran = metric.PLAN_CACHE_MESH_RUNS.value - runs0
    if plan.startswith("distribution: local"):
        assert ran == 0, plan
    else:
        assert ran >= 1, plan
    _same(got, Session(one).execute(text))


def test_q3_with_both_joins_hash_routed_equals_pandas(served):
    """`broadcast_rows` 0: orders and customer are hash-routed as at SF1
    (five all-to-all stages), the plan no smaller test runs; against the
    benchmark's float64 pandas oracle over the whole host data."""
    _node, mesh_cat, _one = served
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from loaders.tpch import Loaded
    from oracles import tpch_q3

    class Host:
        tables = dict(mesh_cat.tables)
        frame = lambda self, t, cols: Loaded.frame(self, t, cols)  # noqa: E731

    rel = sql(mesh_cat, Q3.format(date="1995-03-15"))
    text = rel.explain_distributed(broadcast_rows=0)
    assert text.count("exchange (all-to-all)") == 5
    # the served plan: pruned scans and a top-K, not a full sort
    assert ("scan lineitem columns=['l_orderkey', 'l_extendedprice', "
            "'l_discount', 'l_shipdate']") in text
    assert "scan customer columns=['c_custkey', 'c_mktsegment']" in text
    assert "top-k k=10" in text and "-> sort" not in text
    root = distsql.place(rel.optimized_plan(), mesh_cat, "on",
                         broadcast_rows=0)
    assert isinstance(root, MeshOp)
    got = runtime.run_operator(root)
    want = tpch_q3.answer(Host(), {"date": "1995-03-15"})
    assert len(want) == 10
    np.testing.assert_array_equal(np.asarray(got["l_orderkey"]),
                                  want.l_orderkey.to_numpy())
    np.testing.assert_array_equal(np.asarray(got["o_orderdate"]),
                                  want.o_orderdate.to_numpy())
    np.testing.assert_allclose(np.asarray(got["revenue"], np.float64),
                               want.revenue.to_numpy(), rtol=1e-9)
    stages = root.exchange_stages
    assert len(stages) == 5
    assert [len(s["keys"]) for s in stages] == [1, 1, 1, 1, 3]
    for s in stages:  # what a stage delivered fits what it could carry
        assert 0 < s["offchip_rows"] <= s["rows"] <= s["send_slots"]
    # the byte function the benchmark keeps says the same of every stage
    import mesh_bytes

    host = Host()
    for s, (_what, cols) in zip(stages, mesh_bytes.Q3_STAGES):
        assert s["offchip_bytes"] == s["offchip_rows"] * mesh_bytes.row_bytes(
            host, cols)


def test_a_second_date_compiles_nothing(served):
    """The entry's Param slots ride into the mesh program as replicated
    device arguments: another DATE binds the same compiled program, by the
    program's counter and by JAX's own."""
    _node, mesh_cat, _one = served
    sess = Session(mesh_cat)
    # the first run learns its caps, the second compiles the fitted program
    for date in ("1995-03-01", "1995-03-31"):
        sess.execute(Q3.format(date=date))
    import threading

    seen, me = [], threading.get_ident()

    def on(event, seconds, **_kw):
        # this thread's: the fixture node's background loops compile small
        # eager operations of their own all the time (PERF.md, PR 24)
        if event == COMPILE_EVENT and threading.get_ident() == me:
            seen.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        c0, r0 = dispatch.compiles(), metric.PLAN_CACHE_MESH_RUNS.value
        t0 = _pull_tags()
        first = sess.execute(Q3.format(date="1995-03-09"))
        second = sess.execute(Q3.format(date="1995-03-22"))
        assert dispatch.compiles() == c0
        assert not seen
        assert metric.PLAN_CACHE_MESH_RUNS.value - r0 == 2
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    tags = _pull_tags()
    assert tags.get("mesh_overflow_reruns", 0) == t0.get(
        "mesh_overflow_reruns", 0)
    # orders and customer are broadcast at this scale: one exchange, the
    # aggregate's, a statement
    assert tags["exchange_stages"] - t0.get("exchange_stages", 0) == 2
    assert tags["exchange_rows"] > t0.get("exchange_rows", 0)
    assert (np.asarray(first["revenue"]) != np.asarray(second["revenue"])
            ).any()
    one = Catalog()
    one.tables.update(mesh_cat.tables)
    _same(second, Session(one).execute(Q3.format(date="1995-03-22")))


def test_status_vars_carry_the_mesh_runs_counter(served):
    _node, mesh_cat, _one = served
    Session(mesh_cat).execute(Q3.format(date="1995-03-15"))
    line = next(ln for ln in metric.DEFAULT.scrape().splitlines()
                if ln.startswith("sql_plan_cache_mesh_runs "))
    assert float(line.split()[1]) >= 1


def test_distsql_off_is_honoured_and_keys_the_entry(served):
    _node, mesh_cat, _one = served
    sess = Session(mesh_cat)
    text = Q3.format(date="1995-03-11")
    on = sess.execute(text)
    sess.execute("set distsql = off")
    r0 = metric.PLAN_CACHE_MESH_RUNS.value
    off = sess.execute(text)  # the same text: another entry, one device
    assert metric.PLAN_CACHE_MESH_RUNS.value == r0
    _same(off, on)
    sess.execute("set distsql = auto")
    sess.execute(text)
    assert metric.PLAN_CACHE_MESH_RUNS.value == r0 + 1
    with pytest.raises(Exception, match="distsql"):
        sess.execute("set distsql = sideways")


def test_explain_analyze_renders_the_exchange(served):
    _node, mesh_cat, _one = served
    text = explain(mesh_cat, "EXPLAIN ANALYZE " + Q3.format(date="1995-03-15"))
    assert text.startswith("distribution: mesh (4 devices)")
    line = next(ln for ln in text.splitlines()
                if "exchange (all-to-all)" in ln)
    for field in ("rows=", "offchip_rows=", "send_cap=", "send_slots="):
        assert field in line, line


# -- the exchange against a plain reference ---------------------------------


def _partition_reference(h, mask, D, local):
    """{(source, destination): row indices, in order}: hash, `% D`, stable
    partition of each source's contiguous shard."""
    out = {}
    for s in range(D):
        rows = np.arange(s * local, (s + 1) * local)
        rows = rows[mask[rows]]
        dest = (h[rows] % np.uint64(D)).astype(np.int64)
        for d in range(D):
            out[s, d] = rows[dest == d]
    return out


@pytest.mark.parametrize("D", [2, 4, 8])
def test_exchange_against_numpy_reference(D):
    """Per destination the same rows, masks and validity bits as the
    reference, at 2, 4 and 8 devices, with NULL keys, NULL payloads, dead
    rows and a BYTES(n) column."""
    rng = np.random.default_rng(D)
    local, send_cap = 1024, 768
    n = D * local
    schema = T.Schema.of(k=T.INT64, v=T.DECIMAL(12, 2), s=T.BYTES(5))
    k = rng.integers(0, 97, n)
    arrays = {"k": k, "v": rng.integers(-10**6, 10**6, n),
              "s": rng.integers(0, 255, (n, 5)).astype(np.uint8)}
    valids = {"k": rng.random(n) > 0.1, "v": rng.random(n) > 0.2,
              "s": rng.random(n) > 0.3}
    batch = from_host(schema, arrays, valids=valids, capacity=n)
    mask = rng.random(n) > 0.25
    batch = batch.with_mask(batch.mask & np.asarray(mask))
    keys, types = (0,), [schema.types[0]]
    h = np.asarray(hash_columns([batch.cols[0]], types, None))
    want = _partition_reference(h, mask, D, local)

    mesh = mesh_mod.make_mesh(D)

    def local_fn(b):
        _h, bucket = route(b, keys, types, None, D)
        out, counts = exchange(b, bucket, D, send_cap)
        return out, counts[None]

    # crlint: allow-raw-jit(test harness: the kernel under test, nothing is counted)
    fn = jax.jit(shard_map(local_fn, mesh=mesh, in_specs=(P("d"),),
                           out_specs=(P("d"), P("d")), check_vma=False))
    out, counts = fn(shard_batch(batch, mesh))
    counts = np.asarray(counts)
    got_mask = np.asarray(out.mask).reshape(D, D, send_cap)  # [dest, src]
    for s in range(D):
        for d in range(D):
            rows = want[s, d]
            assert counts[s, d] == len(rows) <= send_cap
            assert got_mask[d, s].sum() == len(rows)
            assert got_mask[d, s, :len(rows)].all()  # a live prefix
            for c, name in zip(out.cols, ("k", "v", "s")):
                data = np.asarray(c.data).reshape(
                    (D, D, send_cap) + c.data.shape[1:])[d, s, :len(rows)]
                valid = np.asarray(c.valid).reshape(
                    D, D, send_cap)[d, s, :len(rows)]
                np.testing.assert_array_equal(valid, valids[name][rows])
                np.testing.assert_array_equal(
                    data[valid], np.asarray(arrays[name])[rows][valid])
            # nothing valid past the live prefix
            for c in out.cols:
                assert not np.asarray(c.valid).reshape(
                    D, D, send_cap)[d, s, len(rows):].any()


def test_a_skewed_key_overflows_reruns_and_answers_exactly(served):
    """A window must ship raw rows, so a constant partition key funnels the
    whole table to one device: the first attempt's fair-share buckets
    overflow, the statement re-runs on the counts it read, the tag counts
    it, and the answer is exact."""
    from cockroach_tpu.ops import expr as ex

    _node, mesh_cat, one = served
    rel = (Rel.scan(mesh_cat, "lineitem", ("l_orderkey", "l_quantity"))
           .project([("k", ex.Const(7, T.INT64)), ("o", ex.ColRef(0)),
                     ("q", ex.ColRef(1))])
           .window(["k"], [("o", False)], [("s", "sum", "q")]))
    root = distsql.place(rel.optimized_plan(), mesh_cat, "always")
    t0 = _pull_tags()
    with tracing.span("statement"):  # a Session's: leaf spans need a parent
        got = runtime.run_operator(root)
    tags = _pull_tags()
    assert tags.get("mesh_overflow_reruns", 0) - t0.get(
        "mesh_overflow_reruns", 0) >= 1
    assert tags["join_overflow_reruns"] - t0.get(
        "join_overflow_reruns", 0) >= 1  # run_operator's own count
    assert root.query.reruns >= 1
    want = Rel.scan(one, "lineitem", ("l_orderkey", "l_quantity")).project(
        [("k", ex.Const(7, T.INT64)), ("o", ex.ColRef(0)),
         ("q", ex.ColRef(1))]).window(
        ["k"], [("o", False)], [("s", "sum", "q")]).run()
    assert len(got["s"]) == len(want["s"])
    np.testing.assert_array_equal(np.unique(np.asarray(got["s"])),
                                  np.unique(np.asarray(want["s"])))
    # the caps it learned hold: the next run overflows nothing
    t0 = _pull_tags()
    with tracing.span("statement"):
        runtime.run_operator(root)
    assert _pull_tags().get("mesh_overflow_reruns", 0) == t0.get(
        "mesh_overflow_reruns", 0)
    assert _pull_tags()["exchange_rows"] > t0["exchange_rows"]


def test_a_node_of_one_device_places_nothing_off_device_zero():
    """`Node()` on a host that shows eight devices: the nine accepted
    cells' route. No mesh, no sharded column, every array on device 0."""
    cat = tpch.gen_tpch(sf=0.002, seed=SEED)
    node = Node().start(pg_port=0)
    try:
        assert node.mesh is None and node._sql_catalog.mesh is None
        for name, table in cat.tables.items():
            node._sql_catalog.tables[name] = table
        r0 = metric.PLAN_CACHE_MESH_RUNS.value
        plan = explain(node._sql_catalog, "EXPLAIN (DISTSQL) "
                       + Q3.format(date="1995-03-15"))
        got = Session(node._sql_catalog).execute(Q3.format(date="1995-03-15"))
        assert len(got["revenue"]) == 10
        assert metric.PLAN_CACHE_MESH_RUNS.value == r0
        first = jax.devices()[0]
        for table in cat.tables.values():
            assert table._mesh_device is None
            for key, held in (table._device or {}).items():
                for leaf in jax.tree_util.tree_leaves(held):
                    if hasattr(leaf, "devices"):
                        assert leaf.devices() == {first}, (table.name, key)
        assert node._sql_catalog.tables["lineitem"]._device is not None
        # EXPLAIN (DISTSQL) of a catalog without a mesh shows what every
        # visible device WOULD run, as it always has
        assert "all-to-all" in plan
    finally:
        node.stop()


def test_placement_is_contiguous_quarters_kept_with_the_table(served):
    _node, mesh_cat, _one = served
    Session(mesh_cat).execute(Q3.format(date="1995-03-15"))
    li = mesh_cat.tables["lineitem"]
    held = li.mesh_shard_rows()
    assert sorted(held) == [d.id for d in jax.devices()[:4]]
    assert sum(held.values()) == li.num_rows
    assert max(held.values()) - min(held.values()) < 4 * 1024
    # only the columns a statement read were uploaded, once
    cols = {k for k in li._mesh_device[1] if not k.startswith("__")}
    assert {"l_orderkey", "l_extendedprice", "l_discount",
            "l_shipdate"} <= cols
    assert "l_comment" not in cols
    before = li._mesh_device[1]["l_orderkey"]
    Session(mesh_cat).execute(Q3.format(date="1995-03-16"))
    assert li._mesh_device[1]["l_orderkey"] is before
    # contiguous primary-key order: device i holds the i-th share of the
    # rows as a live prefix of its tile
    shards = sorted(before.data.addressable_shards,
                    key=lambda sh: sh.index[0].start)
    flat = np.concatenate([np.asarray(sh.data)[:held[sh.device.id]]
                           for sh in shards])
    np.testing.assert_array_equal(flat,
                                  np.asarray(li.columns["l_orderkey"]))


def test_the_shards_partial_aggregates_add_up(served):
    """model-configs section 4's "the shares add up": each device's partial
    aggregate of its shard, merged on the host in float64, equals the
    aggregate of the whole table."""
    import pandas as pd

    _node, mesh_cat, _one = served
    scan = S.TableScan("lineitem", ("l_suppkey", "l_quantity"))
    partial = S.Aggregate(
        scan, (0,), (agg_ops.AggSpec("sum", 1, "q"),
                     agg_ops.AggSpec("count_rows", None, "n")),
        mode="partial")
    q = DistributedQuery(partial, mesh_cat, mesh_cat.mesh,
                         already_distributed=True)
    assert not q.root.replicated  # every device's own partial rows
    got = pd.DataFrame({k: np.asarray(v, np.float64)
                        for k, v in q.run().items()})
    keys, states = got.columns[0], list(got.columns[1:])
    li = mesh_cat.tables["lineitem"]
    want = pd.DataFrame({
        "k": np.asarray(li.columns["l_suppkey"], np.float64),
        "q": np.asarray(li.columns["l_quantity"], np.float64)})
    whole = want.groupby("k").agg(q=("q", "sum"), n=("q", "size"))
    # more partial rows than groups: a supplier's lines lie on several chips
    assert len(got) > len(whole)
    merged = got.groupby(keys)[states].sum()
    np.testing.assert_array_equal(merged.index.to_numpy(),
                                  whole.index.to_numpy())
    # l_quantity is DECIMAL(12, 2): the host holds it scaled by 100
    np.testing.assert_allclose(merged[states[0]].to_numpy() * 100,
                               whole.q.to_numpy(), rtol=1e-12)
    np.testing.assert_array_equal(merged[states[-1]].to_numpy(),
                                  whole.n.to_numpy().astype(np.float64))
