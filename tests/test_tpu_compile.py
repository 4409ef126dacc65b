"""Ask the v5e compiler, without a chip, whether the main path's kernels
compile at real widths: the two storage Pallas kernels, the fused q1 tile
step, the hash shuffle across four chips, and the packed-key sort.

The TPU compiler is installed here and compiles for a chip that is described
and not attached. Nothing runs, so these say nothing about results or times;
they refuse what the chip would refuse (a relayout Mosaic cannot do, a tile
that does not fit VMEM, a program that cannot be partitioned).

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture (never at import, in a skipif or in parametrize
arguments), every compile happens in this process, and all of it lives in
this one file. The persistent compile cache is switched off around the
compiles: an entry written for a described chip cannot be read back here.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from cockroach_tpu.storage import mvcc


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kvblock_shape(n, sharding, key_width=16, val_width=16):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    return mvcc.KVBlock(
        key=s((n, key_width), jnp.uint8), ts=s((n,), jnp.int64),
        seq=s((n,), jnp.int64), txn=s((n,), jnp.int64),
        tomb=s((n,), jnp.bool_), value=s((n, val_width), jnp.uint8),
        vlen=s((n,), jnp.int32), mask=s((n,), jnp.bool_))


@pytest.mark.parametrize("B,window", [(128, 128), (64, 1024)])
def test_scan_filter_kernel_compiles(one_chip, B, window):
    """storage/pallas_scan.py at the batched-scan window layout, 16-byte
    keys — what `auto` selects on a chip for the YCSB engine."""
    from cockroach_tpu.storage.pallas_scan import pallas_scan_filter

    scalar = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    compiled = pallas_scan_filter.lower(
        _kvblock_shape(B * window, one_chip), scalar, scalar, window=window,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [1024, 32768])
def test_merge_kernel_compiles(one_chip, rows):
    """storage/pallas_merge.py merging two sorted runs; 32768+32768 is the
    largest eligible pair under MAX_MERGE_ROWS."""
    from cockroach_tpu.storage import pallas_merge as pm

    blk = _kvblock_shape(rows, one_chip)
    assert pm.eligible((blk, blk))
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    compiled = jax.jit(pm.merge_pair).lower(blk, blk).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_q1_tile_step_compiles(one_chip):
    """__graft_entry__.entry(): filter -> decimal projection -> dense-state
    group-by, at the default 1 << 20-row scan tile."""
    import __graft_entry__ as graft

    fn, (batch,) = graft.entry()
    tile = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1 << 20,) + x.shape[1:], x.dtype,
                                       sharding=one_chip), batch)
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    compiled = jax.jit(fn).lower(tile).compile()
    print("q1 tile step memory:", compiled.memory_analysis())


def test_hash_shuffle_compiles_on_four_chips(topo):
    """parallel/shuffle.make_shuffle on a 4-device mesh of the described
    chips: the repartitioning must lower to an all-to-all."""
    from cockroach_tpu import coldata as cd
    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.parallel import mesh as mesh_mod
    from cockroach_tpu.parallel.shuffle import make_shuffle

    assert len(topo.devices) == 4
    mesh = mesh_mod.make_mesh(devices=list(topo.devices))
    rows = NamedSharding(mesh, P(mesh_mod.AXIS))
    schema = cd.Schema.of(k=cd.INT64, v=cd.DECIMAL(12, 2), d=cd.DATE)
    local_cap = 1 << 18
    n = 4 * local_cap

    def col(dt):
        return Column(data=jax.ShapeDtypeStruct((n,), dt, sharding=rows),
                      valid=jax.ShapeDtypeStruct((n,), jnp.bool_,
                                                 sharding=rows))

    batch = Batch(cols=(col(jnp.int64), col(jnp.int64), col(jnp.int32)),
                  mask=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows))
    shuffle = make_shuffle(mesh, schema, (0,), local_cap)
    compiled = shuffle._jitted.lower(batch).compile()
    assert "all-to-all" in compiled.as_text()
    print("shuffle memory per device:", compiled.memory_analysis())


def test_exchange_at_sf1s_first_stage_moves_no_row_by_index(topo):
    """parallel/shuffle.exchange at the shapes of the four-chip Q3's first
    stage (a 1,507,328-row `lineitem` shard of three int64 columns, send
    buckets at the first-guess cap): four `rows_to_front` compactions and
    the all-to-all, with no sort, no scatter and no gather of the tile (the
    first design: a stable two-operand sort and two tile-sized scatters a
    column)."""
    from cockroach_tpu import coldata as cd
    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.parallel import mesh as mesh_mod
    from cockroach_tpu.parallel.shuffle import exchange, route
    from jax import shard_map

    mesh = mesh_mod.make_mesh(devices=list(topo.devices))
    rows = NamedSharding(mesh, P(mesh_mod.AXIS))
    schema = cd.Schema.of(k=cd.INT64, p=cd.DECIMAL(12, 2),
                          d=cd.DECIMAL(12, 2))
    local_cap, send_cap = 1_507_328, 471_040
    n = 4 * local_cap

    def col():
        return Column(data=jax.ShapeDtypeStruct((n,), jnp.int64,
                                                sharding=rows),
                      valid=jax.ShapeDtypeStruct((n,), jnp.bool_,
                                                 sharding=rows))

    batch = Batch(cols=(col(), col(), col()),
                  mask=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows))

    def local_fn(b):
        _h, bucket = route(b, (0,), [schema.types[0]], None, 4)
        out, counts = exchange(b, bucket, 4, send_cap)
        return out, counts[None]

    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    compiled = jax.jit(shard_map(
        local_fn, mesh=mesh, in_specs=(P(mesh_mod.AXIS),),
        out_specs=(P(mesh_mod.AXIS), P(mesh_mod.AXIS)),
        check_vma=False)).lower(batch).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo
    for op in (" sort(", " scatter(", " gather("):
        assert op not in hlo, op
    print("exchange memory per device:", compiled.memory_analysis())


def test_packed_key_sort_compiles(one_chip):
    """The engine's canonical sort: one packed u64 key word plus the
    permutation operand (ops/keys.py). Kept small: the same compile took
    37 s at 1 << 16 rows and 55 s at 1 << 20 in this sandbox (a scratch
    script, PR 22), against under a second here."""
    n = 1 << 12
    keys = jax.ShapeDtypeStruct((n,), jnp.uint64, sharding=one_chip)
    perm = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    compiled = jax.jit(
        lambda k, p: jax.lax.sort([k, p], num_keys=1)).lower(
            keys, perm).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["prefix_live", "under_a_filter"])
def test_streaming_ordered_aggregate_tile_kernel_compiles(one_chip,
                                                         monkeypatch,
                                                         filtered):
    """AggregateOp's per-tile kernel in streaming mode (presorted partial,
    the carried group met with it, finalize) and its tail, on the branch an
    accelerator traces (`use_scans` asks the default backend, which is the
    CPU here, so the test steers it), for a `sum` + `avg` plan with and
    without a Filter below. Kept small: the parent's kernel took 194 s to
    compile at 1 << 20 rows in this sandbox (a scratch script, PR 47; 115.8 s
    in PR 33) and 76-78 s on the chip's host, this one 7 s there; 2-4 s at
    4,096 here.

    What keeps the found cost out (PR 47; the parent paid 120-208 ms a
    1,048,576-row tile, all but 8 of them in gathers of 9-28 ms each): the
    kernel moves nothing of a tile's size by index. No gather (the parent
    had one a key word, a state word and a valid bitmap), no sort (neither
    the parent's stable one, nor a second under a Filter: no dead-row
    compaction), no scatter, no loop; the groups reach the front by
    `segscan.rows_to_front`'s conditional shifts."""
    from cockroach_tpu.catalog import Catalog, Table
    from cockroach_tpu.coldata import DECIMAL, INT64, Schema
    from cockroach_tpu.coldata.batch import empty_batch
    from cockroach_tpu.ops import expr as ex
    from cockroach_tpu.ops import segscan
    from cockroach_tpu.plan import builder
    from cockroach_tpu.sql.rel import Rel

    monkeypatch.setattr(segscan, "use_scans", lambda: True)
    n = 5000
    cat = Catalog()
    cat.add(Table.from_strings(
        "li", Schema.of(k=INT64, q=DECIMAL(12, 2)),
        {"k": np.arange(n, dtype=np.int64) // 4 * 7_000_003,
         "q": np.arange(n, dtype=np.int64)}, ordering=("k",)))
    rel = Rel.scan(cat, "li")
    if filtered:
        rel = rel.filter(ex.Cmp("gt", rel.c("q"), ex.lit(5)))
    op = builder.build(
        rel.groupby(["k"], [("s", "sum", "q"), ("a", "avg", "q")]).plan, cat)
    assert op.streaming and op.prefix_live != filtered
    op.init()

    def described(batch, rows):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                           sharding=one_chip), batch)

    tile = described(empty_batch(op.base_schema, 8), 4096)
    carry = described(op._no_carry, 1)
    out, carried = jax.eval_shape(op._stream_fn._jitted, tile, carry)
    assert out.capacity == 4096 and carried.capacity == 1
    text = op._stream_fn._jitted.lower(tile, carry).compile().as_text()
    # opcodes, not words: the module's table of traced frames names
    # whatever function first traced a cached scan (`dense_scatter_states`
    # when a test of the dense aggregate ran before in this process)
    for opcode in ("scatter", "gather", "sort", "while"):
        assert not re.search(rf"\b{opcode}\(", text), opcode
    assert op._stream_tail_fn._jitted.lower(carry).compile().as_text()


def test_unsorted_partial_gathers_no_bitmap(one_chip, monkeypatch):
    """`sort_groupby` over a tile in no key order, on the accelerator's
    branch: the key sort is its ONE sort (the parent ran a second, stable
    one to take its slots), every gather reads through that sort's
    permutation, and none of them is a `pred` one: the mask and the valid
    bitmaps follow in one shared uint32 word (`_sorted_by_keys`). No scatter."""
    from cockroach_tpu.coldata import INT64, Schema
    from cockroach_tpu.coldata.batch import empty_batch
    from cockroach_tpu.ops import aggregation as agg_ops
    from cockroach_tpu.ops import segscan

    monkeypatch.setattr(segscan, "use_scans", lambda: True)
    schema = Schema.of(a=INT64, b=INT64, x=INT64, unread=INT64)
    specs = (agg_ops.AggSpec("sum", 2, "s"), agg_ops.AggSpec("min", 2, "m"))
    tile = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((4096,) + x.shape[1:], x.dtype,
                                       sharding=one_chip),
        empty_batch(schema, 8))
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    text = jax.jit(lambda b: agg_ops.sort_groupby(
        b, schema, (0, 1), specs, out_capacity=4096)).lower(
            tile).compile().as_text()
    assert len(re.findall(r"\bsort\(", text)) == 1
    assert not re.search(r"\bscatter\(", text)
    gathers = [ln for ln in text.splitlines() if re.search(r"\bgather\(", ln)]
    # a, b, x: two 32-bit words each; the flags' word; `unread` stays put
    assert len(gathers) == 7, gathers
    assert not any("pred[" in ln.split(" gather(")[0] for ln in gathers)


def test_general_join_emit_at_q13_shapes_has_no_loop(one_chip):
    """`hash_join_general` as `hashjoin_emit` runs it for the served Q13 at
    SF1: `customer`'s one 524,288-row probe tile (8 columns) LEFT-joined to
    the 2,097,152-row build of `orders` (9 columns) under an exact
    one-segment key, the build index passed in, at the first speculative
    capacity. Under an exact key the emission is a run-length expansion:
    the compiled program holds no `while` (the opcode, not a frame's name)
    and one scatter, the owner index."""
    from cockroach_tpu.coldata import DATE, DECIMAL, INT64, STRING, Schema
    from cockroach_tpu.coldata.batch import empty_batch
    from cockroach_tpu.ops import join as jn

    dec = DECIMAL(38, 2)
    pschema = Schema.of(c_custkey=INT64, c_name=STRING, c_address=STRING,
                        c_nationkey=INT64, c_phone=STRING, c_acctbal=dec,
                        c_mktsegment=STRING, c_comment=STRING)
    bschema = Schema.of(o_orderkey=INT64, o_custkey=INT64,
                        o_orderstatus=STRING, o_totalprice=dec,
                        o_orderdate=DATE, o_orderpriority=STRING,
                        o_clerk=STRING, o_shippriority=INT64,
                        o_comment=STRING)
    layout = jn.ExactKeyLayout((("int", 1, 18),), 18)
    spec = jn.JoinSpec("left", False)
    prows, brows, cap = 1 << 19, 1 << 21, 1 << 21

    def described(schema, rows):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                           sharding=one_chip),
            empty_batch(schema, 8))

    def emit(p, b, index):
        return jn.hash_join_general(p, pschema, (0,), b, bschema, (1,), spec,
                                    cap, index=index, exact_layout=layout)

    index = (jax.ShapeDtypeStruct((brows,), jnp.uint64, sharding=one_chip),
             jax.ShapeDtypeStruct((brows,), jnp.int32, sharding=one_chip))
    probe, build = described(pschema, prows), described(bschema, brows)
    out, total = jax.eval_shape(emit, probe, build, index)
    assert out.capacity == cap and len(out.cols) == 17 and total.shape == ()
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    compiled = jax.jit(emit).lower(probe, build, index).compile()
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(r"\bscatter\(", text)) == 1
    print("q13 emit memory:", compiled.memory_analysis())


def test_placed_join_build_at_q21_shapes_has_one_scatter(one_chip):
    """`hashjoin_lut` as the served Q21 builds it at SF1 from a streaming
    aggregate's output (PR 40): six 1,048,576-row tiles and the 1,024-row
    tail, three INT64 columns, into 2,097,152 rows under a 23-bit key.
    Placed by `concat_prefix` the program holds one scatter, the LUT's, and
    a dynamic-update-slice a tile (`concat`'s holds a scatter for every
    tile, column and bitmap, and compiles ten times as long)."""
    from cockroach_tpu.coldata import INT64, Schema
    from cockroach_tpu.coldata.batch import concat_prefix, empty_batch
    from cockroach_tpu.ops import join as jn

    schema = Schema.of(l_orderkey=INT64, lo=INT64, hi=INT64)
    layout = jn.ExactKeyLayout((("int", 1, 23),), 23)
    cap = 1 << 21
    tiles = tuple(
        jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                           sharding=one_chip),
            empty_batch(schema, 8))
        for rows in (1 << 20,) * 6 + (1024,))

    def lut(ts):
        big = concat_prefix(list(ts), capacity=cap)
        return big, jn.build_dense_lut(big, (0,), layout, None)

    big, index = jax.eval_shape(lut, tiles)
    assert big.capacity == cap and index.shape == (1 << 23,)
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    text = jax.jit(lut).lower(tiles).compile().as_text()
    assert len(re.findall(r"\bscatter\(", text)) == 1
    assert len(re.findall(r"\bdynamic-update-slice\(", text)) >= 7


def test_compaction_index_at_a_tile_and_q3s_cap_is_one_plain_sort(one_chip):
    """`live_index` at the shapes every join cell's emit runs it (a
    1,048,576-row mask, q3's 65,536 cap; PR 46): the chip's compiler keeps
    ONE sort of ONE s32 operand and no scatter (the library's sized
    `nonzero` is a scatter-add of 1,048,576 updates). The gotcha it met:
    asked for a STABLE sort, XLA:TPU adds an iota operand as the tie-break
    (`sort(x, iota), is_stable=true`) and compiles in 21 s where this
    compiles in 3 (this sandbox, PR 46); the live positions are distinct
    and the dead rows all carry the fill, so stability buys nothing."""
    from cockroach_tpu.coldata.batch import live_index

    mask = jax.ShapeDtypeStruct((1 << 20,), jnp.bool_, sharding=one_chip)
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    text = jax.jit(live_index, static_argnames="capacity").lower(
        mask, capacity=1 << 16).compile().as_text()
    sorts = re.findall(r"= (\S+) sort\(([^)]*)\)", text)
    assert len(sorts) == 1
    shape, operands = sorts[0]
    assert shape.startswith("s32[1048576]") and "," not in operands
    assert not re.search(r"\bscatter\(", text)


def test_host_planned_run_order_at_node_store_shapes_has_no_sort(one_chip):
    """The engine's write path at the node store's widths (64-byte keys,
    128-byte values): a compaction's output is one gather by a permutation
    planned on the host (mvcc.sort_block_host) and its GC filter, at a run
    four flushed memtables make. Neither program holds a sort: `sort_block`
    itself at these widths took the chip's compiler 18.7 s at 4,096 rows and
    650.3 s at 16,384 in this sandbox (a scratch script, PR 41), held under
    the store's mutex; these take seconds at any size."""
    n = 16384
    blk = _kvblock_shape(n, one_chip, key_width=64, val_width=128)
    perm = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    take = mvcc._take_rows.lower(blk, perm, live, cap=n).compile()
    ts = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    gc = mvcc.mvcc_gc_filter.lower(blk, ts, bottom=False).compile()
    for compiled in (take, gc):
        assert not re.search(r"\bsort\(", compiled.as_text())


def test_range_read_programs_at_sysbench_shapes_compile(one_chip):
    """What a sysbench range statement launches (PR 45), at the cell's
    shapes: a 128-row window of a store with 64-byte keys and 256-byte
    values through the fused MVCC filter and row decode (`pkrange_decode`:
    byte slices of the value slot for CHAR(120), shift sums for the
    integers), and the ORDER BY over the decoded CHAR(120) column (fifteen
    big-endian words and a null bit packed into sixteen sort operands; at
    128 rows the sort compiles in seconds, where PR 22 met minutes at
    65,536). Neither holds a loop or a program over the table's rows."""
    from cockroach_tpu.coldata import types as T
    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.kv.table import KVTable
    from cockroach_tpu.ops import sort as sort_ops

    n = 128
    schema = T.Schema.of(id=T.INT64, k=T.INT64, c=T.CHAR(120),
                         pad=T.CHAR(60))
    table = KVTable.__new__(KVTable)  # the program reads these alone
    table.schema, table.pk_idx, table._range_programs = schema, 0, {}
    view = _kvblock_shape(n, one_chip, key_width=64, val_width=256)
    scalar = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    words = jax.ShapeDtypeStruct((8,), jnp.uint64, sharding=one_chip)
    for idxs in ((2,), (1,), (0, 1, 2, 3)):
        text = table._range_program(idxs)._jitted.lower(
            view, scalar, scalar, words, words).compile().as_text()
        assert not re.search(r"\bwhile\(", text)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    batch = Batch(cols=(Column(data=s((n, 120), jnp.uint8),
                               valid=s((n,), jnp.bool_)),),
                  mask=s((n,), jnp.bool_))
    out = schema.select((2,))
    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    text = jax.jit(lambda b: sort_ops.sort_batch(
        b, out, (sort_ops.SortKey(0),))).lower(batch).compile().as_text()
    assert re.search(r"\bsort\(", text)


def test_described_devices_are_not_attached(topo):
    """The process still runs on the CPU mesh: describing a chip must not
    change what jax.devices() reports to the rest of the suite."""
    assert jax.devices()[0].platform == "cpu"
    assert np.all([d.platform == "tpu" for d in topo.devices])


def test_char_predicates_at_sysbench_widths_compile(one_chip):
    """A filter over a raw CHAR(120) column (PR 45): the literal of `c =
    'x'` rides as one zero-padded row (a Param of the cached plan), `<`
    decides by the first of fifteen big-endian words that differs, LIKE
    walks the 120 byte columns once with one state a pattern position (a
    loop over W, never over the rows)."""
    from cockroach_tpu.coldata import types as T
    from cockroach_tpu.coldata.batch import Column
    from cockroach_tpu.ops import expr as ex

    n = 128
    schema = T.Schema.of(c=T.CHAR(120))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    col = Column(data=s((n, 120), jnp.uint8), valid=s((n,), jnp.bool_))
    lit = s((120,), jnp.uint8)

    def pred(c, v):
        with ex.param_scope((v,)):
            p = ex.and_(
                ex.Cmp("lt", ex.ColRef(0), ex.Param(0, T.CHAR(120))),
                ex.Cmp("ne", ex.ColRef(0), ex.Const(b"abc", T.CHAR(120))),
                ex.BytesLike(ex.ColRef(0), "1%-_9%".encode(), True),
                ex.Cmp("gt", ex.BytesLen(ex.ColRef(0)), ex.lit(3)))
            return ex.eval_expr(p, (c,), schema)

    # crlint: allow-raw-jit(AOT compile for a described chip: nothing is dispatched)
    text = jax.jit(pred).lower(col, lit).compile().as_text()
    assert text.count("while(") <= 2  # LIKE's pass over the byte columns
