"""Bulk ingest — device-built sorted runs (the AddSSTable client half).

Reference: CockroachDB's bulk loaders (IMPORT, index backfill, RESTORE)
never write row-at-a-time — they build whole SSTs client-side
(bulk/sst_batcher.go) and link them into Pebble with AddSSTable, paying
one WAL record per file instead of one per key. DPG (PAPERS.md) shows the
accelerator-native shape of the same idea: sorted-run construction is a
device-side sort, not a host loop.

``RunBuilder`` is that path here. Column batches (keys + encoded values
from ``rowcodec.encode_rows``) buffer on host; at ``target_rows`` they
upload once, sort per-batch with ``mvcc.sort_block``, merge with the
bitonic ``pallas_merge`` kernel when eligible (lax.sort concat merge
otherwise), dedup in one vectorized pass, and land in the LSM as a single
run through ``Engine.ingest(presorted=True)`` — memtable and per-key WAL
bypassed, crash safety via the engine's side-file + WAL link record.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import keys as K
from . import mvcc
from .lsm import _pad


def enabled() -> bool:
    """Route bulk loads through the run builder?"""
    from ..utils import settings

    return bool(settings.get("storage.bulk_ingest.enabled"))


@jax.jit  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _dedup_sorted(block: mvcc.KVBlock) -> mvcc.KVBlock:
    """Mask away same-key duplicates in a canonically sorted block,
    keeping the FIRST row of each key group. Rows carry their batch
    arrival index as a provisional seq, and canonical order is seq-desc
    within a key — so the survivor is the latest-added batch's row
    (AddSSTable's last-write-wins within one ingestion). All rows of a
    builder run share one timestamp, so key equality is version
    equality."""
    words = K.key_words(block.key)
    same = (K.words_cmp_eq(words[1:], words[:-1])
            & block.mask[1:] & block.mask[:-1])
    dup = jnp.concatenate([jnp.zeros((1,), jnp.bool_), same])
    return dataclasses.replace(block, mask=block.mask & ~dup)


class RunBuilder:
    """Accumulate host column batches into device-built sorted runs.

    ``add()`` buffers batches; each time ``target_rows`` accumulate they
    become ONE run in the engine. ``finish()`` flushes the tail and
    reports what landed. Later-added batches win duplicate keys, matching
    the order-dependent semantics of the per-row write path it replaces.
    """

    def __init__(self, engine, ts: int, target_rows: int = 1 << 18):
        self.engine = engine
        self.ts = int(ts)
        self.target_rows = int(target_rows)
        self._batches: list[tuple[np.ndarray, np.ndarray,
                                  np.ndarray | None]] = []
        self._pending = 0
        self.rows = 0
        self.runs = 0

    def add(self, keys, values, vlens=None) -> None:
        keys = np.asarray(keys, dtype=np.uint8)
        values = np.asarray(values, dtype=np.uint8)
        if len(keys) == 0:
            return
        if keys.shape[1] > self.engine.key_width:
            raise ValueError(
                f"key width {keys.shape[1]} > engine {self.engine.key_width}")
        if values.shape[1] > self.engine.val_width:
            raise ValueError(
                f"val width {values.shape[1]} > engine {self.engine.val_width}")
        vl = None if vlens is None else np.asarray(vlens, dtype=np.int32)
        self._batches.append((keys, values, vl))
        self._pending += len(keys)
        if self._pending >= self.target_rows:
            self._flush()

    def _block_for(self, kb_in, vb_in, vl_in, seq: int) -> mvcc.KVBlock:
        eng = self.engine
        n = len(kb_in)
        cap = _pad(n)
        from ..flow import memory as flowmem

        # host padding buffers live only until jnp.asarray copies them to
        # device; the merged run's residency is charged by Engine.ingest
        est = cap * (eng.key_width + eng.val_width + 4)
        with flowmem.staged("storage/ingest-staging", est):
            kb = np.zeros((cap, eng.key_width), np.uint8)
            kb[:n, : kb_in.shape[1]] = kb_in
            vb = np.zeros((cap, eng.val_width), np.uint8)
            vb[:n, : vb_in.shape[1]] = vb_in
            vl = np.zeros(cap, np.int32)
            vl[:n] = vb_in.shape[1] if vl_in is None else vl_in
            return mvcc.KVBlock(
                key=jnp.asarray(kb),
                ts=jnp.full((cap,), self.ts, jnp.int64),
                seq=jnp.full((cap,), seq, jnp.int64),
                txn=jnp.zeros((cap,), jnp.int64),
                tomb=jnp.zeros((cap,), jnp.bool_),
                value=jnp.asarray(vb),
                vlen=jnp.asarray(vl),
                mask=jnp.asarray(np.arange(cap) < n),
            )

    def _merge(self, blocks: tuple) -> mvcc.KVBlock:
        if len(blocks) == 1:
            return blocks[0]
        # the compaction merge picker: bitonic pallas kernel when
        # eligible, concat + lax.sort otherwise
        return self.engine._merge_for_compaction(
            blocks, sum(b.capacity for b in blocks))

    def _flush(self) -> None:
        if not self._batches:
            return
        blocks = tuple(
            mvcc.sort_block(self._block_for(kb, vb, vl, seq=i + 1))
            for i, (kb, vb, vl) in enumerate(self._batches))
        self._batches.clear()
        self._pending = 0
        merged = _dedup_sorted(self._merge(blocks))
        # materialize the live rows on host (boolean select preserves the
        # canonical order) — the engine needs host arrays for the WAL
        # side file anyway
        m = np.asarray(merged.mask)
        keys = np.asarray(merged.key)[m]
        if len(keys) == 0:
            return
        vals = np.asarray(merged.value)[m]
        vlens = np.asarray(merged.vlen)[m]
        self.engine.ingest(keys, vals, self.ts, vlens=vlens, presorted=True)
        self.rows += len(keys)
        self.runs += 1

    def finish(self) -> dict:
        """Flush the tail batch and report {rows, runs} landed."""
        self._flush()
        return {"rows": self.rows, "runs": self.runs}
