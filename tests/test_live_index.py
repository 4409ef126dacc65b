"""`coldata.batch.live_index`, the one index every compaction gathers
through (compact, every spool's concat, a join's late emit): the same
(idx, n) as numpy's nonzero at every shape, and a program that holds no
scatter over the tile's rows (the library's sized `nonzero` is a scatter-add
of one update a row, which the chip runs serially: 50-77 ms a tile)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu import coldata as cd
from cockroach_tpu.coldata.batch import live_index
from cockroach_tpu.ops import join as J

# crlint: allow-raw-jit(the helper alone, traced as its callers trace it: no plan's kernel)
_live_index = jax.jit(live_index, static_argnames="capacity")

_LENGTHS = [1, 128, 1_000, 65_536, 1_048_576]
_DENSITIES = ["all_dead", "one_row", "2.6%", "50%", "all_live"]


def _mask(rng, length, density):
    if density == "all_dead":
        return np.zeros(length, bool)
    if density == "all_live":
        return np.ones(length, bool)
    if density == "one_row":
        m = np.zeros(length, bool)
        m[rng.integers(length)] = True
        return m
    return rng.random(length) < float(density.rstrip("%")) / 100.0


def _capacity(length, cap_is):
    return {"below": max(1, length // 16), "equal": length,
            "above": 2 * length}[cap_is]


@pytest.mark.parametrize("density", _DENSITIES)
@pytest.mark.parametrize("cap_is", ["below", "equal", "above"])
@pytest.mark.parametrize("length", _LENGTHS)
def test_live_index_is_numpys_nonzero(length, cap_is, density):
    """The first n of min(len, capacity) slots hold the set rows' positions
    in order, the rest len(mask); n is the TRUE count, so a count above the
    capacity keeps the FIRST capacity positions and still reports all."""
    rng = np.random.default_rng(length + len(density))
    mask = _mask(rng, length, density)
    capacity = _capacity(length, cap_is)
    idx, n = _live_index(jnp.asarray(mask), capacity=capacity)
    slots = min(length, capacity)
    want = np.nonzero(mask)[0]
    assert idx.dtype == jnp.int32 and n.dtype == jnp.int32
    assert idx.shape == (slots,)
    assert int(n) == len(want)
    kept = min(len(want), slots)
    np.testing.assert_array_equal(np.asarray(idx)[:kept], want[:kept])
    assert (np.asarray(idx)[kept:] == length).all()


def test_a_count_above_the_capacity_keeps_the_first_positions():
    mask = np.ones(1_000, bool)
    mask[::7] = False
    idx, n = _live_index(jnp.asarray(mask), capacity=100)
    want = np.nonzero(mask)[0]
    assert int(n) == len(want) > 100
    np.testing.assert_array_equal(np.asarray(idx), want[:100])


def _tile(rng, cap, density):
    """A tile whose live rows lie anywhere: an INT64 column whose valid bits
    stay set on dead rows, a 2-D BYTES column, a NULL-carrying FLOAT64."""
    mask = _mask(rng, cap, density)
    nulls = rng.random(cap) < 0.3
    cols = (
        cd.Column(jnp.asarray(rng.integers(1, 1 << 40, cap)),
                  jnp.ones((cap,), jnp.bool_)),
        cd.Column(jnp.asarray(rng.integers(1, 255, (cap, 5)).astype(np.uint8)),
                  jnp.asarray(mask)),
        cd.Column(jnp.asarray(rng.random(cap) + 1.0), jnp.asarray(~nulls)),
    )
    return cd.Batch(cols=cols, mask=jnp.asarray(mask))


def _live_rows(tiles):
    """numpy's answer: each column's live rows, tile after tile."""
    out = []
    for i in range(len(tiles[0].cols)):
        data = np.concatenate([np.asarray(t.cols[i].data)[np.asarray(t.mask)]
                               for t in tiles])
        valid = np.concatenate([np.asarray(t.cols[i].valid)[np.asarray(t.mask)]
                                for t in tiles])
        out.append((data, valid))
    return out


def _assert_compacted(got, tiles, capacity):
    want = _live_rows(tiles)
    total = len(want[0][0])
    np.testing.assert_array_equal(np.asarray(got.mask),
                                  np.arange(capacity) < total)
    for c, (data, valid) in zip(got.cols, want):
        assert c.data.shape[0] == capacity
        np.testing.assert_array_equal(np.asarray(c.data)[:total], data)
        np.testing.assert_array_equal(np.asarray(c.valid)[:total], valid)
        assert not np.asarray(c.data)[total:].any()
        assert not np.asarray(c.valid)[total:].any()


@pytest.mark.parametrize("cap,density,capacity", [
    (1_000, "2.6%", 64), (1_000, "50%", 1_000), (128, "all_live", 512),
    (128, "all_dead", 16), (4_096, "one_row", 128)])
def test_compact_moves_numpys_live_rows(cap, density, capacity):
    tile = _tile(np.random.default_rng(cap), cap, density)
    _assert_compacted(cd.compact(tile, capacity=capacity), [tile], capacity)


# (capacity, density) a tile, then the output's capacity
_CONCAT_CASES = {
    "mixed_tile_sizes": ([(1_000, "50%"), (128, "2.6%"), (4_096, "2.6%")],
                         1_024),
    "a_tile_with_no_live_row": ([(256, "50%"), (256, "all_dead"),
                                 (256, "one_row")], 512),
    "tiles_wider_than_the_output": ([(4_096, "one_row"), (1_000, "2.6%")],
                                    64),
    "all_live_tiles_fill_the_output": ([(128, "all_live")] * 3, 384),
    "no_live_row_at_all": ([(128, "all_dead"), (1_000, "all_dead")], 128),
}


@pytest.mark.parametrize("case", list(_CONCAT_CASES))
def test_concat_gathers_numpys_live_rows_in_tile_order(case):
    shape, capacity = _CONCAT_CASES[case]
    rng = np.random.default_rng(len(case))
    tiles = [_tile(rng, cap, density) for cap, density in shape]
    # crlint: allow-raw-jit(the helper alone, traced as its callers trace it: no plan's kernel)
    got = jax.jit(cd.concat, static_argnames="capacity")(
        tiles, capacity=capacity)
    _assert_compacted(got, tiles, capacity)


# -- structure: the nonzero cannot come back by a refactor -------------------

_TILE, _CAP = 1_048_576, 65_536
_DEF = re.compile(r"^\s*(?:ROOT )?(\S+) = (\S+?)[{ ]")
_CALL = re.compile(r" (scatter|sort)\(([^)]*)\)")


def _scatters_and_sorts(hlo: str):
    """-> ([rows of every scatter's updates], [operand count of every
    sort]) of an HLO text, the update shapes looked up by operand name."""
    shapes, calls = {}, []
    for line in hlo.splitlines():
        d = _DEF.match(line)
        if d:
            shapes[d.group(1)] = d.group(2)
        c = _CALL.search(line)
        if c:
            calls.append((c.group(1), [a.strip().lstrip("%")
                                       for a in c.group(2).split(",")]))

    def rows(name):
        dims = re.search(r"\[(\d*)", shapes[name]).group(1)
        return int(dims) if dims else 1

    scatters = [rows(args[-1]) for op, args in calls if op == "scatter"]
    sorts = [len(args) for op, args in calls if op == "sort"]
    return scatters, sorts


def _hlo(fn, *args, **static):
    # crlint: allow-raw-jit(lowered only, never run: the program's text is what is read)
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).as_text(dialect="hlo")


def _col(dtype, rows):
    return cd.Column(jax.ShapeDtypeStruct((rows,), dtype),
                     jax.ShapeDtypeStruct((rows,), jnp.bool_))


def test_the_reader_of_the_programs_text_sees_the_librarys_scatter():
    """The control: over the library's sized nonzero the reader finds the
    one scatter of a tile's worth of updates that this file keeps out."""
    def nonzero(mask):
        return jnp.nonzero(mask, size=_CAP, fill_value=_TILE)

    scatters, _ = _scatters_and_sorts(
        _hlo(nonzero, jax.ShapeDtypeStruct((_TILE,), jnp.bool_)))
    assert scatters == [_TILE]


def test_live_index_is_one_single_operand_sort_and_no_scatter():
    hlo = _hlo(live_index, jax.ShapeDtypeStruct((_TILE,), jnp.bool_),
               capacity=_CAP)
    scatters, sorts = _scatters_and_sorts(hlo)
    assert scatters == [] and sorts == [1]
    assert "is_stable=true" not in hlo  # a stable sort grows an iota operand
    assert "s64[" not in hlo and "u64[" not in hlo  # the index is int32


def test_compact_holds_no_scatter_over_the_tiles_rows():
    tile = cd.Batch(cols=(_col(jnp.int64, _TILE), _col(jnp.int32, _TILE)),
                    mask=jax.ShapeDtypeStruct((_TILE,), jnp.bool_))
    scatters, sorts = _scatters_and_sorts(
        _hlo(cd.compact.__wrapped__, tile, capacity=_CAP))
    assert scatters == [] and sorts == [1]


@pytest.mark.parametrize("join_type", ["inner", "left"])
def test_the_late_emit_holds_no_scatter_over_the_tiles_rows(join_type):
    probe = cd.Batch(cols=(_col(jnp.int64, _TILE), _col(jnp.int32, _TILE)),
                     mask=jax.ShapeDtypeStruct((_TILE,), jnp.bool_))
    build = cd.Batch(cols=(_col(jnp.int64, 2 * _TILE),),
                     mask=jax.ShapeDtypeStruct((2 * _TILE,), jnp.bool_))

    def emit(probe, build, found_idx, found):
        return J.emit_unique_compact(
            probe, build, J.JoinSpec(join_type=join_type), found_idx, found,
            _CAP)

    scatters, sorts = _scatters_and_sorts(
        _hlo(emit, probe, build,
             jax.ShapeDtypeStruct((_TILE,), jnp.int32),
             jax.ShapeDtypeStruct((_TILE,), jnp.bool_)))
    assert scatters == [] and sorts == [1]


def test_concat_scatters_only_what_each_tile_may_keep():
    """`concat`'s data scatters stay (not this file's business), at the
    slots a tile may fill: min(tile, capacity) updates, never the tile's."""
    tiles = [cd.Batch(cols=(_col(jnp.int64, _TILE),),
                      mask=jax.ShapeDtypeStruct((_TILE,), jnp.bool_))
             for _ in range(2)]
    scatters, sorts = _scatters_and_sorts(
        _hlo(cd.concat, tiles, capacity=_CAP))
    assert scatters and set(scatters) == {_CAP}
    assert sorts == [1, 1]
