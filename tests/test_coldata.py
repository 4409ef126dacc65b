"""Columnar substrate tests (reference analog: pkg/col/coldata tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu import coldata as cd


def make_batch(n=10, cap=16):
    schema = cd.Schema.of(a=cd.INT64, b=cd.FLOAT64, s=cd.STRING)
    arrays = {
        "a": np.arange(n, dtype=np.int64),
        "b": np.arange(n, dtype=np.float64) * 0.5,
        "s": np.arange(n, dtype=np.int32) % 3,
    }
    return schema, cd.from_host(schema, arrays, capacity=cap)


def test_from_host_roundtrip():
    schema, b = make_batch()
    assert b.capacity == 16
    assert int(b.length()) == 10
    out = cd.to_host(b, schema)
    np.testing.assert_array_equal(out["a"], np.arange(10))
    np.testing.assert_allclose(out["b"], np.arange(10) * 0.5)


def test_mask_and_compact():
    schema, b = make_batch()
    keep = jnp.asarray(np.arange(16) % 2 == 0) & b.mask
    b2 = b.with_mask(keep)
    assert int(b2.length()) == 5
    c = cd.compact(b2)
    m = np.asarray(c.mask)
    assert m[:5].all() and not m[5:].any()
    out = cd.to_host(c, schema)
    np.testing.assert_array_equal(out["a"], [0, 2, 4, 6, 8])


def test_compact_shrink_capacity():
    schema, b = make_batch(n=4, cap=64)
    c = cd.compact(b, capacity=8)
    assert c.capacity == 8
    out = cd.to_host(c, schema)
    np.testing.assert_array_equal(out["a"], np.arange(4))


def test_nulls_roundtrip():
    schema = cd.Schema.of(x=cd.INT64)
    v = np.array([True, False, True])
    b = cd.from_host(schema, {"x": np.array([1, 2, 3])}, valids={"x": v}, capacity=8)
    out = cd.to_host(b, schema)
    assert out["x"][0] == 1 and out["x"][1] is None and out["x"][2] == 3


def test_concat():
    schema, b1 = make_batch(n=3, cap=8)
    _, b2 = make_batch(n=4, cap=8)
    c = cd.concat([b1, b2], capacity=16)
    assert int(c.length()) == 7
    out = cd.to_host(c, schema)
    np.testing.assert_array_equal(out["a"], [0, 1, 2, 0, 1, 2, 3])


def _prefix_tile(rng, cap, n):
    """A tile whose live rows are its first ``n``: an INT64 column whose
    valid bits stay set on dead rows (as a computed column's do), a 2-D
    BYTES column, a NULL-carrying FLOAT64; dead rows hold garbage."""
    mask = np.arange(cap) < n
    nulls = rng.random(cap) < 0.3
    cols = (
        cd.Column(jnp.asarray(rng.integers(1, 1 << 40, cap)),
                  jnp.ones((cap,), jnp.bool_)),
        cd.Column(jnp.asarray(rng.integers(1, 255, (cap, 5)).astype(np.uint8)),
                  jnp.asarray(mask)),
        cd.Column(jnp.asarray(rng.random(cap) + 1.0),
                  jnp.asarray(~nulls & mask)),
    )
    return cd.Batch(cols=cols, mask=jnp.asarray(mask))


# (capacity, live rows) a tile, then the output's capacity
_PREFIX_CASES = {
    "seven_full_tiles_into_twice_a_tile": ([(16, 4)] * 7, 32),
    "seven_all_live_tiles": ([(16, 16)] * 7, 112),
    "empty_one_row_and_full_tiles_mixed":
        ([(16, 0), (16, 1), (16, 16), (16, 0), (16, 1), (16, 7)], 32),
    "total_lands_on_capacity": ([(16, 16), (16, 10), (16, 6)], 32),
    # off_k + cap_in passes the output: a clamped start would shift the
    # last tile down over the rows before it
    "last_tile_passes_capacity": ([(16, 15), (16, 14), (16, 2)], 32),
    "streamed_tiles_and_a_one_row_tail": ([(16, 9), (16, 11), (4, 1)], 32),
    "tiles_wider_than_the_output": ([(64, 3), (64, 0), (64, 4)], 8),
    "single_tile_cut": ([(16, 5)], 8),
    "single_tile_padded": ([(16, 16)], 64),
    "no_live_row": ([(16, 0), (16, 0)], 8),
}


@pytest.mark.parametrize("case", list(_PREFIX_CASES))
def test_concat_prefix_places_what_concat_gathers(case):
    """Over live-prefix tiles the placing helper is `concat`, bit for bit:
    the live rows in tile order (data, 2-D BYTES, NULLs), the mask, and
    zero / invalid slots past the total."""
    shape, capacity = _PREFIX_CASES[case]
    rng = np.random.default_rng(len(case))
    tiles = [_prefix_tile(rng, cap, n) for cap, n in shape]
    want = cd.concat(tiles, capacity=capacity)
    # crlint: allow-raw-jit(the helper alone, traced as its callers trace it: no plan's kernel)
    got = jax.jit(cd.concat_prefix, static_argnames="capacity")(
        tiles, capacity=capacity)
    total = sum(n for _, n in shape)
    np.testing.assert_array_equal(np.asarray(got.mask),
                                  np.arange(capacity) < total)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for c in got.cols:
        assert not np.asarray(c.valid)[total:].any()
        assert not np.asarray(c.data)[total:].any()


def test_dictionary():
    d = cd.Dictionary(np.array(["cherry", "apple", "banana"], dtype=object))
    assert d.code_of("apple") == 1
    assert d.code_of("missing") == -1
    # ranks reflect sorted byte order
    assert d.ranks[1] < d.ranks[2] < d.ranks[0]
    dec = d.decode(np.array([2, 0, -1]))
    assert list(dec[:2]) == ["banana", "cherry"] and dec[2] is None


def test_dictionary_hash_cross_table():
    d1 = cd.Dictionary(np.array(["x", "y"], dtype=object))
    d2 = cd.Dictionary(np.array(["y", "x"], dtype=object))
    assert d1.hashes[0] == d2.hashes[1]
    assert d1.hashes[1] == d2.hashes[0]
    assert d1.hashes[0] != d1.hashes[1]
