"""Pallas bitonic-merge parity vs the concat+sort path (interpret mode on
CPU; on the chip it is reached by the compactions of chip_smoke.py --full's
YCSB-E run)."""

import numpy as np
import pytest

import jax.numpy as jnp

from cockroach_tpu.storage import mvcc
from cockroach_tpu.storage import pallas_merge as pm


def _random_sorted_run(rng, n, cap=None, nkeys=25, val_width=8):
    """A sorted KVBlock run with random keys/versions, some dead rows and
    a dead pad tail (exactly what LSM flush produces)."""
    cap = cap or int(2 ** np.ceil(np.log2(max(n, 4))))
    keys = np.zeros((cap, 16), np.uint8)
    ts = np.zeros(cap, np.int64)
    seq = np.zeros(cap, np.int64)
    txn = np.zeros(cap, np.int64)
    tomb = np.zeros(cap, bool)
    value = np.zeros((cap, val_width), np.uint8)
    vlen = np.zeros(cap, np.int32)
    mask = np.zeros(cap, bool)
    for i in range(n):
        kb = b"user%07d" % rng.integers(0, nkeys)
        keys[i, : len(kb)] = np.frombuffer(kb, np.uint8)
        ts[i] = rng.integers(1, 1000)
        seq[i] = rng.integers(1, 1 << 40)  # globally unique w.h.p.
        txn[i] = rng.integers(0, 2)
        tomb[i] = rng.random() < 0.15
        value[i, : 4] = np.frombuffer(np.int32(i).tobytes(), np.uint8)
        vlen[i] = 4
        mask[i] = rng.random() < 0.95
    blk = mvcc.KVBlock(
        key=jnp.asarray(keys), ts=jnp.asarray(ts), seq=jnp.asarray(seq),
        txn=jnp.asarray(txn), tomb=jnp.asarray(tomb),
        value=jnp.asarray(value), vlen=jnp.asarray(vlen),
        mask=jnp.asarray(mask),
    )
    return mvcc.sort_block(blk)


def _live_tuples(blk):
    """Ordered (key, ts, seq, txn, tomb, value) tuples of live rows —
    the observable content, in sorted order."""
    m = np.asarray(blk.mask)
    rows = []
    for i in np.flatnonzero(m):
        rows.append((
            bytes(np.asarray(blk.key[i])),
            int(blk.ts[i]), int(blk.seq[i]), int(blk.txn[i]),
            bool(blk.tomb[i]),
            bytes(np.asarray(blk.value[i]))[: int(blk.vlen[i])],
        ))
    return rows


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("sizes", [
    # each size shape is its own 15-20s XLA compile on this host: tier-1
    # keeps the square case, the odd shapes ride the slow tier
    pytest.param((30, 50), marks=pytest.mark.slow),
    (64, 64),
    pytest.param((5, 120), marks=pytest.mark.slow),
    pytest.param((1, 1), marks=pytest.mark.slow),
])
def test_merge_pair_matches_sort(seed, sizes):
    rng = np.random.default_rng(seed)
    a = _random_sorted_run(rng, sizes[0])
    b = _random_sorted_run(rng, sizes[1])
    got = pm.merge_pair(a, b, interpret=True)
    total = a.capacity + b.capacity
    want = mvcc.merge_blocks((a, b), cap=total)
    assert _live_tuples(got) == _live_tuples(want)


# ~1-2 min of pallas-interpret tracing per k on the CI box; tier-1 keeps
# the pairwise kernels, `-m slow` covers the tournament tree
@pytest.mark.slow
@pytest.mark.parametrize("k", [3, 4, 5])
def test_merge_tournament_matches_sort(k):
    rng = np.random.default_rng(7 + k)
    runs = tuple(
        _random_sorted_run(rng, int(rng.integers(10, 90))) for _ in range(k)
    )
    assert pm.eligible(runs)
    got = pm.merge_runs(runs, interpret=True)
    want = mvcc.merge_blocks(runs, cap=sum(r.capacity for r in runs))
    assert _live_tuples(got) == _live_tuples(want)


def test_eligibility_bound():
    rng = np.random.default_rng(3)
    small = tuple(_random_sorted_run(rng, 8) for _ in range(2))
    assert pm.eligible(small)
    big = mvcc.empty_block(pm.MAX_MERGE_ROWS, 16, 8)
    assert not pm.eligible((big, big))
    assert not pm.eligible((small[0],))


@pytest.mark.slow
def test_engine_compaction_uses_kernel_result():
    """Engine.compact with the pallas merge enabled (interpret mode)
    produces the same live content as the sort path."""
    from cockroach_tpu.storage.lsm import Engine

    def build(pallas):
        eng = Engine(key_width=16, val_width=8, l0_trigger=64)
        eng._pallas_merge_interpret = True
        eng.pallas_merge = pallas
        rng = np.random.default_rng(11)
        for i in range(300):
            eng.put(b"k%05d" % rng.integers(0, 60), b"v%06d" % i, ts=i + 1)
            if i % 90 == 89:
                eng.flush_mem_only()
        eng.compact(bottom=False)
        eng.compact(bottom=True)
        return eng.scan(None, None, ts=1 << 40)

    assert build(True) == build(False)
