"""Pallas MVCC scan-filter parity vs the jnp filter (interpret mode on
CPU; the real-chip run happens in chip_smoke.py's kv phase)."""

import numpy as np
import pytest

import jax.numpy as jnp

from cockroach_tpu.storage import mvcc
from cockroach_tpu.storage.pallas_scan import pallas_scan_filter


def _window_block(rng, B=4, window=256, nkeys=40, read_ts=50):
    """Random MVCC windows in the multi_scan layout: each row holds sorted
    (key asc, ts desc, seq desc) entries with dead tails."""
    rows = []
    for b in range(B):
        entries = []
        for _ in range(rng.integers(5, nkeys)):
            key = b"k%06d" % rng.integers(0, 30)
            for _ in range(rng.integers(1, 4)):
                entries.append((
                    key,
                    int(rng.integers(1, 100)),        # ts
                    int(rng.integers(0, 3)),          # txn (0 committed)
                    bool(rng.random() < 0.2),         # tombstone
                ))
        entries.sort(key=lambda e: (e[0], -e[1]))
        entries = entries[:window]
        rows.append(entries)
    n = B * window
    keys = np.zeros((n, 16), np.uint8)
    ts = np.zeros(n, np.int64)
    txn = np.zeros(n, np.int64)
    tomb = np.zeros(n, bool)
    mask = np.zeros(n, bool)
    for b, entries in enumerate(rows):
        for i, (key, t, x, tb) in enumerate(entries):
            j = b * window + i
            keys[j, :len(key)] = np.frombuffer(key, np.uint8)
            ts[j], txn[j], tomb[j], mask[j] = t, x, tb, True
    blk = mvcc.KVBlock(
        key=jnp.asarray(keys), ts=jnp.asarray(ts),
        seq=jnp.zeros(n, jnp.int64), txn=jnp.asarray(txn),
        tomb=jnp.asarray(tomb), value=jnp.zeros((n, 8), jnp.uint8),
        vlen=jnp.zeros(n, jnp.int32), mask=jnp.asarray(mask),
    )
    return blk


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_pallas_filter_matches_jnp(seed):
    rng = np.random.default_rng(seed)
    blk = _window_block(rng)
    for read_ts, reader in ((50, 0), (10, 0), (50, 1), (200, 2)):
        want_sel, want_conf = mvcc.mvcc_scan_filter(
            blk, jnp.int64(read_ts), jnp.int64(reader), window=256)
        got_sel, got_conf = pallas_scan_filter(
            blk, jnp.int64(read_ts), jnp.int64(reader), window=256,
            interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got_sel), np.asarray(want_sel),
            err_msg=f"selected mismatch at {(read_ts, reader)}")
        np.testing.assert_array_equal(
            np.asarray(got_conf), np.asarray(want_conf),
            err_msg=f"conflict mismatch at {(read_ts, reader)}")


def test_pallas_filter_edge_windows():
    # empty windows, all-tombstone windows, single huge key run
    window = 128
    n = 3 * window
    keys = np.zeros((n, 16), np.uint8)
    ts = np.zeros(n, np.int64)
    tomb = np.zeros(n, bool)
    mask = np.zeros(n, bool)
    # window 0: empty. window 1: one key, all versions tombstoned
    for i in range(20):
        j = window + i
        keys[j, :4] = np.frombuffer(b"aaaa", np.uint8)
        ts[j] = 100 - i
        tomb[j] = True
        mask[j] = True
    # window 2: one key run spanning the whole window
    for i in range(window):
        j = 2 * window + i
        keys[j, :4] = np.frombuffer(b"bbbb", np.uint8)
        ts[j] = 10_000 - i
        mask[j] = True
    blk = mvcc.KVBlock(
        key=jnp.asarray(keys), ts=jnp.asarray(ts),
        seq=jnp.zeros(n, jnp.int64), txn=jnp.zeros(n, jnp.int64),
        tomb=jnp.asarray(tomb), value=jnp.zeros((n, 8), jnp.uint8),
        vlen=jnp.zeros(n, jnp.int32), mask=jnp.asarray(mask),
    )
    want = mvcc.mvcc_scan_filter(blk, jnp.int64(50_000), jnp.int64(0),
                                 window=window)
    got = pallas_scan_filter(blk, jnp.int64(50_000), jnp.int64(0),
                             window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_scan_batch_through_pallas_filter(monkeypatch):
    """End-to-end batched scans with the Pallas filter forced on
    (interpret mode on CPU) must equal the jnp-filtered results."""
    from cockroach_tpu.storage.lsm import Engine
    from cockroach_tpu.utils import settings

    monkeypatch.setattr(mvcc, "PALLAS_FILTER_INTERPRET", True)

    def build():
        eng = Engine(key_width=16, val_width=8, memtable_size=1 << 20)
        for i in range(400):
            eng.put(b"k%08d" % i, b"v%d" % i, ts=5)
        for i in range(0, 400, 7):
            eng.put(b"k%08d" % i, b"w%d" % i, ts=9)
        for i in range(0, 400, 31):
            eng.delete(b"k%08d" % i, ts=10)
        eng.flush()
        return eng

    eng = build()
    starts = [b"k%08d" % s for s in (0, 13, 100, 399)]
    settings.set("storage.pallas_filter", "off")
    try:
        want = eng.scan_batch(starts, ts=11, max_keys=20)
        settings.set("storage.pallas_filter", "on")
        got = eng.scan_batch(starts, ts=11, max_keys=20)
    finally:
        settings.reset("storage.pallas_filter")
    assert got == want


def test_on_never_interprets_by_itself():
    """`storage.pallas_filter = on` compiles the kernel for the backend in
    use. On the CPU that compile is refused, and the refusal surfaces: the
    program neither interprets nor falls back to jnp on its own."""
    from cockroach_tpu.storage.lsm import Engine
    from cockroach_tpu.utils import settings

    eng = Engine(key_width=16, val_width=8, memtable_size=1 << 20)
    for i in range(40):
        eng.put(b"k%08d" % i, b"v%d" % i, ts=5)
    eng.flush()
    before = mvcc.KERNEL_CALLS["scan_filter.jnp"]
    settings.set("storage.pallas_filter", "on")
    try:
        with pytest.raises(Exception, match="(?i)interpret|cpu"):
            eng.scan_batch([b"k%08d" % 3], ts=11, max_keys=4)
    finally:
        settings.reset("storage.pallas_filter")
    assert mvcc.KERNEL_CALLS["scan_filter.jnp"] == before
    assert eng.scan_batch([b"k%08d" % 3], ts=11, max_keys=4)[0][0][0] \
        == b"k%08d" % 3
    assert mvcc.KERNEL_CALLS["scan_filter.jnp"] == before + 1
