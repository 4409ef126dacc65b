"""ops/segscan.py against numpy: the segmented scans (Hillis-Steele passes
for every lane whose op cannot see the order of association, the strided
recursion a float lane keeps), the per-segment broadcast on both of its
routes, and `rows_to_front`, the compaction that moves rows by conditional
shifts. Sizes straddle powers of two: the rounds are unrolled from the
length."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.ops import segscan

SIZES = [1, 2, 7, 64, 1000, 4096]


def _segments(rng, n, mean=4):
    b = rng.random(n) < 1.0 / mean
    return b


def _ref_scan(op, vals, boundary, reverse=False):
    """Row by row, in scan direction; row 0 (in that direction) starts a
    segment whatever the flag says."""
    vals = np.asarray(vals)
    out = vals.copy()
    order = range(len(vals) - 1, -1, -1) if reverse else range(len(vals))
    acc = None
    for i in order:
        acc = vals[i] if acc is None or boundary[i] else op(acc, vals[i])
        out[i] = acc
    return out


@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
@pytest.mark.parametrize("n", SIZES)
def test_exact_lanes_equal_a_row_by_row_scan(n, reverse):
    """Integer add (wrapping), min, max, or, and a copy lane (the op
    `totals_everywhere` scans with), sharing one flag lane."""
    rng = np.random.default_rng(n + reverse)
    b = _segments(rng, n)
    big = rng.integers(-2**62, 2**62, n)
    small = rng.integers(-50, 50, n).astype(np.int32)
    flag = rng.random(n) > 0.7
    ops = [jnp.add, jnp.minimum, jnp.maximum, jnp.logical_or,
           lambda acc, v: acc]
    vals = [big, small, big, flag, small]
    got = segscan.seg_scan_multi(ops, [jnp.asarray(v) for v in vals],
                                 jnp.asarray(b), reverse)
    with np.errstate(over="ignore"):
        want = [_ref_scan(np.add, big, b, reverse),
                _ref_scan(np.minimum, small, b, reverse),
                _ref_scan(np.maximum, big, b, reverse),
                _ref_scan(np.logical_or, flag, b, reverse),
                _ref_scan(lambda acc, v: acc, small, b, reverse)]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), w)
    one = segscan.seg_scan(jnp.minimum, jnp.asarray(small), jnp.asarray(b),
                           reverse)
    assert np.array_equal(np.asarray(one), want[1])


@pytest.mark.parametrize("n", [7, 1000, 4096])
def test_a_float_lane_keeps_the_strided_recursions_bits(n):
    """Beside integer lanes in one call: the float sum is bit for bit
    `jax.lax.associative_scan`'s (sums that round, so an order shows), and
    differs from the row-by-row order somewhere in its last bits."""
    rng = np.random.default_rng(n)
    b = _segments(rng, n, mean=50)
    x = rng.random(n) * 1e3 + rng.random(n) * 1e-4
    k = rng.integers(0, 9, n)
    got_k, got_x = segscan.seg_scan_multi(
        [jnp.add, jnp.add], [jnp.asarray(k), jnp.asarray(x)], jnp.asarray(b))

    def combine(a, c):
        return a[0] | c[0], jnp.where(c[0], c[1], a[1] + c[1])

    _, want_x = jax.lax.associative_scan(
        combine, (jnp.asarray(b), jnp.asarray(x)))
    assert np.asarray(got_x).tobytes() == np.asarray(want_x).tobytes()
    assert np.array_equal(np.asarray(got_k), _ref_scan(np.add, k, b))
    seq = _ref_scan(np.add, x, b)
    assert np.allclose(np.asarray(got_x), seq, rtol=1e-12)
    if n >= 1000:
        assert np.asarray(got_x).tobytes() != seq.tobytes()


@pytest.mark.parametrize("n", [5, 64, 1000])
def test_seg_bcast_agrees_on_both_routes(monkeypatch, n):
    """The MVCC visibility pass takes per-key minima and maxima through
    `seg_bcast`: the accelerator's scans and the CPU's segment_* give the
    same array (live rows first, dead rows last, as its contract says)."""
    rng = np.random.default_rng(n)
    live_n = n - n // 5
    live = np.arange(n) < live_n
    b = _segments(rng, n) & live
    b[0] = True
    raw = rng.integers(-1000, 1000, n).astype(np.int32)
    for op, segop, identity in ((jnp.minimum, jax.ops.segment_min, 2**31 - 1),
                                (jnp.maximum, jax.ops.segment_max, -2**31)):
        # a dead row carries the op's identity, as the callers' do
        vals = np.where(live, raw, identity).astype(np.int32)
        want = segscan.seg_bcast(op, segop, jnp.asarray(vals),
                                 jnp.asarray(b), jnp.asarray(live))
        monkeypatch.setattr(segscan, "use_scans", lambda: True)
        got = segscan.seg_bcast(op, segop, jnp.asarray(vals),
                                jnp.asarray(b), jnp.asarray(live))
        monkeypatch.undo()
        assert np.array_equal(np.asarray(got)[:live_n],
                              np.asarray(want)[:live_n])


@pytest.mark.parametrize("density", [0.0, 0.03, 0.25, 0.9, 1.0])
@pytest.mark.parametrize("n", SIZES)
def test_rows_to_front_against_numpy(n, density):
    """Wanted rows in row order as a prefix, for int64, int32 and uint8
    lanes, a [n, 5] BYTES-like lane, and 40 boolean lanes (two shared
    words); nothing is asked of the rows past the count."""
    rng = np.random.default_rng(n * 7 + int(density * 100))
    wanted = rng.random(n) < density
    if density == 1.0:
        wanted[:] = True
    arrays = [rng.integers(-2**62, 2**62, n),
              rng.integers(-9, 9, n).astype(np.int32),
              rng.integers(0, 255, (n, 5)).astype(np.uint8),
              rng.integers(0, 255, n).astype(np.uint8)]
    arrays += [rng.random(n) > 0.5 for _ in range(40)]
    got = segscan.rows_to_front(jnp.asarray(wanted),
                                [jnp.asarray(a) for a in arrays])
    k = int(wanted.sum())
    assert len(got) == len(arrays)
    for g, a in zip(got, arrays):
        assert g.dtype == a.dtype and g.shape == a.shape
        assert np.array_equal(np.asarray(g)[:k], a[wanted])


def test_bits_pack_thirty_two_to_a_word():
    rng = np.random.default_rng(0)
    flags = [rng.random(50) > 0.5 for _ in range(70)]
    words = segscan.pack_bits([jnp.asarray(f) for f in flags])
    assert len(words) == 3 and all(w.dtype == jnp.uint32 for w in words)
    back = segscan.unpack_bits(words, 70)
    for f, g in zip(flags, back):
        assert g.dtype == jnp.bool_ and np.array_equal(np.asarray(g), f)
