"""Secondary indexes — key codec, write maintenance, Streamer fetch.

Reference: secondary-index keys are table/index-prefixed, order-preserving
encodings of the indexed columns with the primary key as suffix
(pkg/sql/rowenc/index_encoding.go); index joins read the matched primary
rows through batched, memory-budgeted KV reads
(pkg/sql/rowexec/joinreader.go driving
pkg/kv/kvclient/kvstreamer/streamer.go:517); CREATE INDEX backfills run as
chunked, checkpointed jobs (pkg/sql/backfill.go).

TPU-first divergences:

- The Streamer is not N parallel point RPCs: a request's primary keys
  upload once and membership resolves as ONE vectorized searchsorted over
  the engine's merged device view, followed by a gather that compacts the
  hits into a batch whose capacity is sized by the REQUEST, not the table
  — downstream kernels compile at lookup-result shape.
- Index entries are presence-only (empty value); the fetch always goes
  back to the primary (no covering indexes yet).
- Single indexed column, fixed-width families; STRING columns index their
  dictionary codes (equality-only semantics — codes are not ordered).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..coldata.types import Family
from ..storage import rowcodec

# index entry: 1 prefix byte + 10 value bytes + 10 pk bytes = 21 bytes.
# Fits the engine's 24-byte default key width (storage.keys.
# DEFAULT_KEY_WIDTH) — plan_create_index rejects engines provisioned
# narrower, since every entry write would fail mid-backfill otherwise.
ENTRY_BYTES = 1 + 2 * rowcodec.PK_BYTES


@dataclass(frozen=True)
class IndexDesc:
    name: str
    col: str
    index_id: int  # its own keyspace prefix, allocated like a table id


def _enc_val(v: int) -> bytes:
    """Order-preserving, NUL-free 10-byte encoding of one int64 (the same
    7-bit-group scheme as rowcodec.encode_pk, sans prefix)."""
    u = (int(v) & 0xFFFFFFFFFFFFFFFF) ^ (1 << 63)
    out = bytearray()
    for i in range(rowcodec.PK_BYTES - 1, -1, -1):
        out.append(0x01 + ((u >> (7 * i)) & 0x7F))
    return bytes(out)


def encode_entry(index_id: int, val: int, pk: int) -> bytes:
    assert 0 <= index_id <= rowcodec.MAX_TABLE_ID
    return bytes([0x01 + index_id]) + _enc_val(val) + _enc_val(pk)


def decode_entry(key: bytes) -> tuple[int, int]:
    """(value, pk) from an index entry key."""

    def dec(b: bytes) -> int:
        u = 0
        for x in b:
            u = (u << 7) | (x - 0x01)
        u ^= 1 << 63
        return u - (1 << 64) if u >= (1 << 63) else u

    n = rowcodec.PK_BYTES
    return dec(key[1:1 + n]), dec(key[1 + n:1 + 2 * n])


def value_span(index_id: int, lo: int | None, hi: int | None
               ) -> tuple[bytes, bytes]:
    """[start, end) covering entries with value in [lo, hi] (inclusive;
    None = unbounded on that side)."""
    assert 0 <= index_id <= rowcodec.MAX_TABLE_ID
    prefix = bytes([0x01 + index_id])
    start = prefix + _enc_val(lo) if lo is not None else prefix
    # entry bytes are in [0x01, 0x80], so 0x81 sorts after every pk suffix
    end = (prefix + _enc_val(hi) + b"\x81" if hi is not None
           else bytes([0x02 + index_id]))
    return start, end


def encode_entries(index_id: int, vals: np.ndarray,
                   pks: np.ndarray) -> np.ndarray:
    """Vectorized entry encode: [N] vals + [N] pks -> [N, ENTRY_BYTES]."""
    n = len(vals)
    out = np.empty((n, ENTRY_BYTES), dtype=np.uint8)
    out[:, 0] = 0x01 + index_id
    for src, off in ((vals, 1), (pks, 1 + rowcodec.PK_BYTES)):
        u = np.asarray(src, dtype=np.int64).astype(np.uint64) ^ np.uint64(
            1 << 63)
        for i in range(rowcodec.PK_BYTES):
            shift = np.uint64(7 * (rowcodec.PK_BYTES - 1 - i))
            out[:, off + i] = ((u >> shift) & np.uint64(0x7F)).astype(
                np.uint8) + 0x01
    return out


# -- write-path maintenance (called from KVTable inside the row's txn) ------


def entries_for_row(indexes, schema, row: dict, pk: int) -> list[bytes]:
    """Index entry keys for one encoded row (values already codes/ints;
    NULL indexed values produce no entry — filters are null-rejecting)."""
    out = []
    for ix in indexes:
        v = row.get(ix.col)
        if v is None:
            continue
        out.append(encode_entry(ix.index_id, int(v), pk))
    return out


def maintain_row(t, indexes, schema, new_row: dict | None,
                 old_row: dict | None, pk: int) -> None:
    """Delete stale + write fresh index entries for one primary row
    (new_row/old_row: value-encoded dicts; None = absent)."""
    old = set(entries_for_row(indexes, schema, old_row, pk)) if old_row else set()
    new = set(entries_for_row(indexes, schema, new_row, pk)) if new_row else set()
    for k in old - new:
        t.delete(k)
    for k in new - old:
        t.put(k, b"")


# -- the Streamer: batched primary-row fetch --------------------------------


class Streamer:
    """Vectorized out-of-order primary-row fetch (kvstreamer.Streamer:517 /
    joinreader role). Given the primary keys an index scan matched, resolve
    all of them in one device pass over the engine's merged view:
    searchsorted membership + compacting gather, output capacity sized by
    the request."""

    def __init__(self, table):
        self.table = table

    def fetch(self, pks: np.ndarray, names: tuple[str, ...]):
        """-> Batch of the requested columns for rows whose pk is in
        `pks`, at the table's read context. Output capacity = padded
        len(pks) (missing pks leave masked-off rows)."""
        from ..coldata.batch import Batch, Column, empty_batch, live_index
        from ..storage import keys as K
        from ..storage import mvcc
        from ..storage.lsm import WriteIntentError

        tbl = self.table
        idxs = tuple(tbl.schema.index(n) for n in names)
        schema = tbl.schema.select(idxs)
        cap_out = max(128, 1 << int(np.ceil(np.log2(max(1, len(pks))))))
        if len(pks) == 0:
            return empty_batch(schema, cap_out)
        eng = tbl.db.engine
        with eng.mu:
            view = eng._merged_view()
        if view is None:
            return empty_batch(schema, cap_out)
        ts = tbl.read_ts if tbl.read_ts is not None else tbl.db.clock.now()
        spks = np.sort(np.asarray(pks, dtype=np.int64))
        lo, hi = int(spks[0]), int(spks[-1])
        sw = K.encode_bound(rowcodec.encode_pk(tbl.table_id, lo),
                            eng.key_width)
        ew = K.encode_bound(
            rowcodec.encode_pk(tbl.table_id, hi) + b"\x01", eng.key_width)
        sel, conflict = mvcc.mvcc_scan_filter(
            view, jnp.int64(ts), jnp.int64(tbl.reader_txn),
            jnp.asarray(sw), jnp.asarray(ew),
        )
        cnp = np.asarray(conflict)
        if cnp.any():
            hit = np.nonzero(cnp)[0]
            raise WriteIntentError(
                K.decode_keys(np.asarray(view.key)[hit]),
                [int(x) for x in np.asarray(view.txn)[hit]],
            )
        # vectorized membership: view pk in the sorted request set
        vpk = rowcodec.decode_pk_column(view.key)
        dpks = jnp.asarray(spks)
        pos = jnp.searchsorted(dpks, vpk)
        posc = jnp.clip(pos, 0, len(spks) - 1)
        sel = sel & (dpks[posc] == vpk)
        # compacting gather: hits land in [0, cap_out)
        dest, _ = live_index(sel, cap_out)
        dest = jnp.pad(dest, (0, cap_out - dest.shape[0]),
                       constant_values=sel.shape[0])
        batch = rowcodec.decode_columns(view.value, sel, tbl.schema, idxs)

        def take(col):
            pad = jnp.zeros((1,) + col.shape[1:], dtype=col.dtype)
            return jnp.concatenate([col, pad])[dest]

        cols = []
        mask = take(sel)
        for pos_i, i in enumerate(idxs):
            c = batch.cols[pos_i]
            if i == tbl.pk_idx:
                cols.append(Column(data=take(vpk), valid=mask))
            else:
                cols.append(Column(data=take(c.data), valid=take(c.valid)))
        return Batch(cols=tuple(cols), mask=mask)


# -- index scan (host side of the read path) --------------------------------


def scan_pks(table, index: IndexDesc, lo: int | None, hi: int | None,
             max_keys: int | None = None) -> np.ndarray:
    """Primary keys whose indexed value falls in [lo, hi], read from the
    index keyspace at the table's read context (ts + txn visibility)."""
    start, end = value_span(index.index_id, lo, hi)
    ts = table.read_ts if table.read_ts is not None else table.db.clock.now()
    rows = table.db.engine.scan(start, end, ts=ts, txn=table.reader_txn,
                                max_keys=max_keys)
    return np.array([decode_entry(k)[1] for k, _ in rows], dtype=np.int64)


# -- CREATE INDEX backfill job ----------------------------------------------

CHUNK_ROWS = 512


def plan_create_index(catalog, db, stmt,
                      id_range: tuple[int, int] | None = None) -> dict:
    """Validate CREATE INDEX and build the job payload (the index id is
    allocated NOW so a crash-resume lands entries in the final span).
    id_range confines the id to a tenant's keyspace slice, the
    create_kv_table.alloc discipline — an index keyspace must never land
    inside a foreign tenant's reserved slice."""
    from ..sql.binder import BindError
    from .table import KVTable
    from .tenant import _SYSTEM_RANGE

    tbl = catalog.tables.get(stmt.table)
    if tbl is None:
        raise BindError(f"unknown table {stmt.table!r}")
    if not isinstance(tbl, KVTable):
        raise BindError("CREATE INDEX targets KV-backed tables")
    if db.engine.key_width < ENTRY_BYTES:
        raise BindError(
            f"engine key_width {db.engine.key_width} cannot hold "
            f"{ENTRY_BYTES}-byte index entries (provision the store with "
            f"key_width >= {ENTRY_BYTES})"
        )
    if any(ix.name == stmt.name for ix in tbl.indexes):
        raise BindError(f"index {stmt.name!r} already exists")
    if stmt.col not in tbl.schema.names:
        raise BindError(f"unknown column {stmt.col!r}")
    fam = tbl.schema.type_of(stmt.col).family
    if fam in (Family.FLOAT, Family.BYTES, Family.JSON):
        raise BindError(
            f"indexes on {fam.name} columns are not supported (order-"
            "preserving int encoding only)"
        )
    lo, hi = id_range if id_range is not None else _SYSTEM_RANGE
    used = set()
    for other in catalog.tables.values():
        if isinstance(other, KVTable):
            used.add(other.table_id)
            if other.dict_table_id is not None:
                used.add(other.dict_table_id)
            used.update(ix.index_id for ix in other.indexes)
    index_id = max([i for i in used if lo <= i <= hi], default=lo - 1) + 1
    if index_id > hi:
        raise BindError(f"tenant keyspace [{lo},{hi}] exhausted")
    return {"table": stmt.table, "index": stmt.name, "col": stmt.col,
            "index_id": index_id}


def backfill_index(reg, job, catalog) -> None:
    """The create_index resumer: chunked entry writes + checkpoint + a
    fenced descriptor swap that makes the index visible (the
    schemachange.py discipline; concurrent DML is out of scope, as there).

    With storage.bulk_ingest.enabled, each chunk's entries encode
    vectorized and land as a device-built run through the RunBuilder —
    the reference's backfiller writes AddSSTables, not per-row txn puts.
    The checkpoint/resume discipline is identical either way; re-running
    a chunk after a crash just re-lands the same entries at a newer
    timestamp."""
    from ..sql.schemachange import _fenced_job_read
    from ..storage import ingest as bulk
    from .table import KVTable, write_descriptor

    payload = job.payload
    durable = reg.load(job.job_id)
    if durable is not None:
        job.progress.update(durable.progress)
        if durable.progress.get("swapped"):
            return
    tbl: KVTable = catalog.tables[payload["table"]]
    ix = IndexDesc(payload["index"], payload["col"], payload["index_id"])
    db = reg.db
    use_bulk = (bulk.enabled()
                and db.engine.key_width >= ENTRY_BYTES)
    start, end = rowcodec.table_span(tbl.table_id)
    last_pk = job.progress.get("last_pk")
    while True:
        lo = (rowcodec.encode_pk(tbl.table_id, last_pk + 1)
              if last_pk is not None else start)
        rows = db.scan(lo, end, max_keys=CHUNK_ROWS)
        if not rows:
            break

        if use_bulk:
            pks_l, vals_l = [], []
            done = None
            for k, v in rows:
                pk = rowcodec.decode_pk(k)
                done = pk
                row = rowcodec.decode_row(tbl.schema, v)
                val = row.get(ix.col)
                if val is not None:
                    pks_l.append(pk)
                    vals_l.append(int(val))
            if vals_l:
                ik = encode_entries(ix.index_id,
                                    np.asarray(vals_l, np.int64),
                                    np.asarray(pks_l, np.int64))
                rb = bulk.RunBuilder(db.engine, db.clock.now())
                rb.add(ik, np.zeros((len(ik), 0), np.uint8))
                rb.finish()
            last_pk = done
        else:
            def write_chunk(t, rows=rows):
                done = None
                for k, v in rows:
                    pk = rowcodec.decode_pk(k)
                    done = pk
                    row = rowcodec.decode_row(tbl.schema, v)
                    val = row.get(ix.col)
                    if val is not None:
                        t.put(encode_entry(ix.index_id, int(val), pk), b"")
                return done

            last_pk = db.txn(write_chunk)
        job.progress["last_pk"] = int(last_pk)
        reg.checkpoint(job)

    def swap(t):
        _fenced_job_read(reg, job, t)
        tbl.indexes.append(ix)
        write_descriptor(db, tbl, writer=t)
        job.progress["swapped"] = True
        reg._write(t, job)

    try:
        db.txn(swap)
    except BaseException:
        if any(i.name == ix.name for i in tbl.indexes):
            tbl.indexes.remove(ix)
        raise


def drop_index(catalog, db, table_name: str, index_name: str) -> None:
    """DROP INDEX: remove from the descriptor first (readers stop routing
    through it), then delete the entry span in chunks."""
    from ..sql.binder import BindError
    from .table import write_descriptor

    tbl = catalog.tables[table_name]
    ix = next((i for i in tbl.indexes if i.name == index_name), None)
    if ix is None:
        raise BindError(f"unknown index {index_name!r}")
    tbl.indexes.remove(ix)
    write_descriptor(db, tbl)
    start, end = value_span(ix.index_id, None, None)
    while True:
        rows = db.scan(start, end, max_keys=1024)
        if not rows:
            break

        def rm(t, rows=rows):
            for k, _ in rows:
                t.delete(k)

        db.txn(rm)


def register_create_index_job(registry, catalog) -> None:
    registry.register(
        "create_index", lambda reg, job: backfill_index(reg, job, catalog))
