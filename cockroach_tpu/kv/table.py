"""KV-backed SQL tables — the TableReader path over the MVCC engine.

Reference: SQL reads flow through colfetcher's ColBatchScan -> cFetcher ->
kv.Txn (pkg/sql/colfetcher/colbatch_scan.go:200), decoding KV pairs into
coldata.Batch; writes encode rows and go through kv.Txn.Put. Here KVTable
is both:

- the write surface: ``insert``/``delete_pk`` run inside a kv transaction
  (intents, refresh validation, retries — kv/txn.py), encoding rows via
  storage/rowcodec.py;
- the read surface: ``device_batch`` produces a columnar Batch straight
  from the engine's device-resident merged view — mvcc_scan_filter picks
  newest-visible versions, rowcodec.decode_columns unpacks values — the
  "direct columnar scan" default path (pkg/storage/col_mvcc.go:25-90).

KVTable quacks like catalog.Table (schema / num_rows / dict_by_index /
device_batch), so ScanOp, the flow engine and sql() work unchanged on
KV-backed tables. Fixed-width column families (INT/DECIMAL/DATE/TIMESTAMP/
INTERVAL/FLOAT/BOOL); STRING is dictionary-coded in the row with its
dictionary in a companion key space; CHAR(n) (coldata.types.CHAR) is stored
raw in the row's value slot and decodes on the device as fixed-width bytes.

Two reads never decode the table: ``point_rows`` (the plan's PointLookup)
and ``range_batches`` (its PKRange: a span of the primary key, sought in the
store and decoded window by window on the device).
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, Column, Dictionary
from ..coldata.types import Family, Schema
from ..storage import rowcodec
from ..storage.lsm import Engine, WriteIntentError
from .txn import DB, Txn

# a window of a range read holds at most this many rows of each source; a
# wider range streams page by page (the engine's pagination)
RANGE_PAGE_ROWS = 2048


def _unsupported(t) -> bool:
    return t.family is Family.JSON or (
        t.family is Family.BYTES and not t.text)


def _merged_view(eng):
    """The engine's merged view, built under its mutex: the build reads the
    memtable's lists, which a writer of another session appends to (an
    unlocked build met a list longer than the length it had read: ROADMAP
    D11 (a), found by tests/test_kv95.py's 64 sessions)."""
    with getattr(eng, "mu", contextlib.nullcontext()):
        return eng._merged_view()


class _TableDict:
    """Growable per-column string dictionary for a KV table.

    Codes live in the row payload (int32 slots); the code -> string mapping
    persists in the SAME engine under a companion dictionary table id, so a
    restore rebuilds it by scanning that span — the system-table discipline
    (the reference keeps descriptors/interning in system ranges). Query
    plans take an immutable Dictionary snapshot at bind time."""

    def __init__(self, values: list[str] | None = None):
        self.values: list[str] = list(values or [])
        self._code: dict[str, int] = {v: i for i, v in enumerate(self.values)}
        self._snapshot = None

    def code_of(self, v: str) -> int | None:
        return self._code.get(v)

    def add(self, v: str) -> int:
        code = len(self.values)
        self.values.append(v)
        self._code[v] = code
        self._snapshot = None
        return code

    def snapshot(self) -> Dictionary:
        if self._snapshot is None or len(self._snapshot) != len(self.values):
            self._snapshot = Dictionary(
                np.array(self.values, dtype=object)
            )
        return self._snapshot


class KVTable:
    def __init__(self, db: DB, name: str, schema: Schema, pk: str,
                 table_id: int, dict_table_id: int | None = None,
                 indexes: list | None = None):
        for t in schema.types:
            if _unsupported(t):
                raise TypeError(
                    f"KV tables support fixed-width columns only, got {t}"
                )
        if not 0 <= table_id <= rowcodec.MAX_TABLE_ID:
            raise ValueError(
                f"table_id must be in [0, {rowcodec.MAX_TABLE_ID}]"
            )
        self.db = db
        self.name = name
        self.schema = schema
        self.pk = pk
        self.pk_idx = schema.index(pk)
        self.table_id = table_id
        self._count_cache = None  # ((engine seq, gen), row count)
        need = rowcodec.value_width(schema)
        if db.engine.val_width < need:
            raise ValueError(
                f"engine val_width {db.engine.val_width} < row width {need}"
            )
        # snapshot timestamp for reads; None = now() at device_batch time.
        # reader_txn makes columnar scans run AS a transaction: its own
        # intents are visible, other txns' intents conflict (the session's
        # explicit-txn SELECT path sets both around each statement)
        self.read_ts: int | None = None
        self.reader_txn: int = 0
        # the transaction itself, for reads made key by key through it
        # (point_rows); None = autocommit
        self.reader: Txn | None = None
        # STRING columns: dictionary-coded in the value slots; the mapping
        # persists in a companion key space of the same engine
        self._string_cols = tuple(
            i for i, t in enumerate(schema.types)
            if t.family is Family.STRING
        )
        self.dict_table_id = dict_table_id
        # secondary indexes (kv/index.IndexDesc); maintained inside every
        # row write's txn, visible to the planner via plan/indexopt.py
        self.indexes: list = list(indexes or [])
        self._dicts: dict[int, _TableDict] = {}
        self._range_programs: dict = {}  # columns -> pkrange_decode
        if self._string_cols:
            if dict_table_id is None:
                raise ValueError(
                    "STRING columns need a dict_table_id (companion key "
                    "space for the persistent dictionary)"
                )
            self._load_dicts()

    # -- persistent dictionaries --------------------------------------------

    @staticmethod
    def _dict_pk(col: int, code: int) -> int:
        return (col << 40) | code

    def _load_dicts(self) -> None:
        """Rebuild dictionaries from the companion span (restore path)."""
        start, end = rowcodec.table_span(self.dict_table_id)
        rows = self.db.scan(start, end)
        by_col: dict[int, list[tuple[int, str]]] = {}
        for k, v in rows:
            pk = rowcodec.decode_pk(k)
            col, code = pk >> 40, pk & ((1 << 40) - 1)
            ln = int.from_bytes(v[:2], "little")
            by_col.setdefault(col, []).append(
                (code, v[2:2 + ln].decode("utf-8"))
            )
        for i in self._string_cols:
            entries = sorted(by_col.get(i, []))
            if [c for c, _ in entries] != list(range(len(entries))):
                raise ValueError(
                    f"corrupt string dictionary for {self.name!r} column "
                    f"{i}: codes {[c for c, _ in entries]} have holes"
                )
            self._dicts[i] = _TableDict([s for _, s in entries])

    def _encode_strings(self, t: Txn, row: dict) -> dict:
        """Replace str values with dictionary codes, persisting new entries
        in the same transaction (atomic with the row write).

        New codes stay PENDING on the transaction until commit: the
        in-memory dictionary must roll back with the txn, or a retry/abort
        would leave it permanently ahead of the engine's companion span
        (committed rows referencing codes the persistent dictionary lost)."""
        if not self._string_cols:
            return row
        out = dict(row)
        vw = self.db.engine.val_width
        slots = self._pending_slots(t)  # col -> {str: pending code}
        for i in self._string_cols:
            name = self.schema.names[i]
            v = out.get(name)
            if v is None:
                continue
            if isinstance(v, (int, np.integer)):
                continue  # already a code
            out[name] = self._txn_code(t, slots, i, str(v), vw)
        return out

    def _txn_code(self, t: Txn, slots: dict, i: int, v: str,
                  vw: int) -> int:
        """Dictionary code for one string value, allocating a txn-pending
        code (and its companion-span write) on first sight."""
        d = self._dicts.setdefault(i, _TableDict())
        slot = slots.setdefault(i, {})
        code = d.code_of(v)
        if code is None:
            code = slot.get(v)
        if code is None:
            enc = v.encode("utf-8")
            if len(enc) > 0xFFFF:
                raise ValueError(
                    f"string of {len(enc)} bytes exceeds the 64KiB "
                    "dictionary-entry bound (2-byte length header)"
                )
            code = len(d.values) + len(slot)
            slot[v] = code
            t.put(
                rowcodec.encode_pk(self.dict_table_id,
                                   self._dict_pk(i, code)),
                len(enc).to_bytes(2, "little") + enc,
            )
        return code

    def _pending_slots(self, t: Txn) -> dict:
        pending = getattr(t, "_dict_pending", None)
        if pending is None:
            pending = t._dict_pending = {}
        slots = pending.get(id(self))
        if slots is None:
            slots = pending[id(self)] = {}
            t.on_commit(lambda: self._commit_pending(slots))
        return slots

    def insert_rows(self, t: Txn, columns: dict[str, np.ndarray],
                    valids: dict[str, np.ndarray] | None = None) -> int:
        """Vectorized transactional INSERT (the colenc role: the write
        path encodes columns, not rows — sql/colenc in the reference).
        Keys and values encode in batched numpy passes; string columns
        dictionary-encode per UNIQUE value through the same txn-pending
        discipline as insert(); the txn takes one prepared put per row."""
        cols = dict(columns)
        valids = dict(valids or {})
        n = len(next(iter(cols.values())))
        vw = self.db.engine.val_width
        if self._string_cols:
            slots = self._pending_slots(t)
            for i in self._string_cols:
                name = self.schema.names[i]
                a = cols.get(name)
                if a is None:
                    continue
                arr = np.asarray(a)
                if arr.dtype.kind in ("i", "u"):
                    continue  # already codes
                vmask = valids.get(name)
                strs = np.array(
                    ["" if (vmask is not None and not vmask[j])
                     else str(x) for j, x in enumerate(arr)], dtype=str,
                )
                uvals, inverse = np.unique(strs, return_inverse=True)
                codes = np.empty(len(uvals), dtype=np.int64)
                for j, v in enumerate(uvals):
                    codes[j] = self._txn_code(t, slots, i, str(v), vw)
                cols[name] = codes[inverse]
        pks = np.asarray(cols[self.pk], dtype=np.int64)
        keys = rowcodec.encode_pk_batch(self.table_id, pks)
        values = rowcodec.encode_rows(self.schema, cols, valids)
        kb = keys.tobytes()
        vb = values.tobytes()
        kw = keys.shape[1]
        vw_row = values.shape[1]
        # upsert discipline: old rows must be read BEFORE the puts land
        # (afterwards t.get returns the txn's own fresh intent and the
        # stale-entry tombstone below would never fire)
        old_rows: dict[int, dict] = {}
        if self.indexes:
            for r in range(n):
                old_v = t.get(kb[r * kw:(r + 1) * kw])
                if old_v is not None:
                    old_rows[r] = rowcodec.decode_row(self.schema, old_v)
        for r in range(n):
            t.put(kb[r * kw:(r + 1) * kw], vb[r * vw_row:(r + 1) * vw_row])
        if self.indexes:
            from . import index as ixm

            for r in range(n):
                new_row = {}
                for name in self.schema.names:
                    a = cols.get(name)
                    if a is None:
                        continue
                    vmask = valids.get(name)
                    if vmask is not None and not vmask[r]:
                        continue
                    new_row[name] = a[r]
                ixm.maintain_row(t, self.indexes, self.schema, new_row,
                                 old_rows.get(r), int(pks[r]))
        self._count_cache = None
        return n

    def _commit_pending(self, slots: dict) -> None:
        for i, mapping in slots.items():
            d = self._dicts.setdefault(i, _TableDict())
            for v, code in sorted(mapping.items(), key=lambda x: x[1]):
                got = d.add(v)
                if got != code:
                    raise RuntimeError(
                        f"dictionary code drift: {v!r} got {got}, "
                        f"txn assigned {code}"
                    )

    # -- write surface ------------------------------------------------------

    def bulk_load(self, columns: dict[str, np.ndarray],
                  valids: dict[str, np.ndarray] | None = None,
                  chunk: int = 1 << 18, presorted: bool = False) -> int:
        """Bulk-load typed host columns through the AddSSTable path: string
        columns dictionary-encode vectorized (np.unique + merge), values
        encode in one numpy pass (rowcodec.encode_rows), keys batch-encode,
        and each chunk lands as ONE sorted engine run — the IMPORT
        discipline (bulk writes skip the memtable/WAL and the per-row txn
        machinery; the load is atomic per chunk and idempotent to re-run
        at a higher timestamp). ``presorted=True`` promises primary keys
        that ascend strictly (the key encoding keeps their order): each
        chunk then lands as it is, with no device sort at all (an
        11-operand sort of a 262,144-row chunk is minutes of compile on
        the chip)."""
        cols = dict(columns)
        valids = valids or {}
        n = len(next(iter(cols.values())))
        # vectorized dictionary encoding for STRING columns
        for i in self._string_cols:
            name = self.schema.names[i]
            a = np.asarray(cols[name])
            if a.dtype.kind in ("O", "U", "S"):
                d = self._dicts.setdefault(i, _TableDict())
                uvals, inverse = np.unique(a.astype(str),
                                           return_inverse=True)
                remap = np.empty(len(uvals), dtype=np.int32)
                new_entries = []
                for j, v in enumerate(uvals):
                    code = d.code_of(str(v))
                    if code is None:
                        code = d.add(str(v))
                        new_entries.append((code, str(v)))
                    remap[j] = code
                cols[name] = remap[inverse]
                for code, v in new_entries:  # persist the dictionary
                    enc = v.encode("utf-8")
                    self.db.put(
                        rowcodec.encode_pk(self.dict_table_id,
                                           self._dict_pk(i, code)),
                        len(enc).to_bytes(2, "little") + enc,
                    )
        ts = self.db.clock.now()
        pks = np.asarray(cols[self.pk], dtype=np.int64)
        keys = rowcodec.encode_pk_batch(self.table_id, pks)
        values = rowcodec.encode_rows(self.schema, cols, valids)
        from ..storage import ingest as bulk

        use_bulk = bulk.enabled()
        if presorted:
            if n > 1 and not (np.diff(pks) > 0).all():
                raise ValueError("presorted bulk_load needs primary keys "
                                 "that ascend strictly")
            for lo in range(0, n, chunk):
                hi = min(lo + chunk, n)
                self.db.engine.ingest(keys[lo:hi], values[lo:hi], ts=ts,
                                      presorted=True)
        elif use_bulk:
            # run-builder route: chunks accumulate into device-built
            # sorted/deduped runs (storage/ingest.py) and link into the
            # LSM with one WAL record per run
            rb = bulk.RunBuilder(self.db.engine, ts)
            for lo in range(0, n, chunk):
                hi = min(lo + chunk, n)
                rb.add(keys[lo:hi], values[lo:hi])
            rb.finish()
        else:
            for lo in range(0, n, chunk):
                hi = min(lo + chunk, n)
                self.db.engine.ingest(keys[lo:hi], values[lo:hi], ts=ts)
        if self.indexes:
            # index runs ingest alongside the rows (IMPORT assumes fresh
            # pks — the insert path handles upsert tombstoning)
            from . import index as ixm

            for ix in self.indexes:
                a = cols.get(ix.col)
                if a is None:
                    continue
                vmask = valids.get(ix.col)
                keep = (np.asarray(vmask, dtype=bool) if vmask is not None
                        else np.ones(n, dtype=bool))
                ik = ixm.encode_entries(
                    ix.index_id, np.asarray(a, dtype=np.int64)[keep],
                    pks[keep])
                iv = np.zeros((len(ik), 0), dtype=np.uint8)
                if use_bulk:
                    # the builder sorts device-side — no host lexsort
                    rb = bulk.RunBuilder(self.db.engine, ts)
                    for lo in range(0, len(ik), chunk):
                        hi = min(lo + chunk, len(ik))
                        rb.add(ik[lo:hi], iv[lo:hi])
                    rb.finish()
                else:
                    # entries must land SORTED (ingest builds one run)
                    order = np.lexsort(ik.T[::-1])
                    ik = ik[order]
                    for lo in range(0, len(ik), chunk):
                        hi = min(lo + chunk, len(ik))
                        self.db.engine.ingest(ik[lo:hi], iv[lo:hi], ts=ts)
        self._count_cache = None
        return n

    def insert(self, t: Txn, row: dict) -> None:
        row = self._encode_strings(t, row)
        pk = int(row[self.pk])
        key = rowcodec.encode_pk(self.table_id, pk)
        if self.indexes:
            # MVCC puts are upserts: a replaced row's stale index entries
            # must tombstone in the same txn (rowenc secondary-index
            # maintenance; the reference reads the old row for updates too)
            from . import index as ix

            old_v = t.get(key)
            old = (rowcodec.decode_row(self.schema, old_v)
                   if old_v is not None else None)
            ix.maintain_row(t, self.indexes, self.schema, row, old, pk)
        t.put(key, rowcodec.encode_row(self.schema, row))

    def delete_pk(self, t: Txn, pk: int) -> None:
        key = rowcodec.encode_pk(self.table_id, int(pk))
        if self.indexes:
            from . import index as ix

            old_v = t.get(key)
            if old_v is not None:
                ix.maintain_row(t, self.indexes, self.schema, None,
                                rowcodec.decode_row(self.schema, old_v),
                                int(pk))
        t.delete(key)

    def get_row_txn(self, t: Txn, pk: int) -> dict | None:
        """Transactional row read: goes through Txn.get so the read lands in
        the txn's read spans (commit-time refresh validation), observes the
        txn's snapshot, and converts intent conflicts to retryable errors —
        the difference between a real multi-statement transaction and a
        dirty read (kv.Txn.Get semantics)."""
        v = t.get(rowcodec.encode_pk(self.table_id, int(pk)))
        if v is None:
            return None
        row = rowcodec.decode_row(self.schema, v)
        for i in self._string_cols:
            name = self.schema.names[i]
            code = row.get(name)
            if code is not None:
                row[name] = self._dicts[i].values[int(code)]
        return row

    def point_rows(self, pks, names: tuple[str, ...]):
        """The rows whose primary key is in `pks`, read key by key through
        the transaction: kv.Txn.Get inside one (the read lands in its read
        spans, a foreign intent is its retryable conflict), else the
        autocommit read that waits a foreign intent out and retries
        (kv.DB.get_committed). Never a decode of the table. -> (host
        columns, valid masks) of `names` for the keys found, in the order
        asked and once each; STRING columns stay dictionary codes, CHAR(n)
        columns are [n, width] zero-padded bytes."""
        from ..utils import metric

        t = self.reader
        rows = []
        for pk in dict.fromkeys(int(p) for p in pks):
            key = rowcodec.encode_pk(self.table_id, pk)
            v = t.get(key) if t is not None else self.db.get_committed(key)
            if v is not None:
                rows.append(rowcodec.decode_row(self.schema, v))
        metric.KV_POINT_READS.inc(len(pks))
        arrays, valids = {}, {}
        for n in names:
            vals = [r[n] for r in rows]
            t = self.schema.type_of(n)
            valids[n] = np.array([v is not None for v in vals], dtype=bool)
            if t.family is Family.BYTES:  # CHAR(n): the device's bytes
                arrays[n] = rowcodec.char_matrix(
                    np.array(["" if v is None else v for v in vals],
                             dtype=object), t.width)
                continue
            arrays[n] = np.array([0 if v is None else v for v in vals],
                                 dtype=t.dtype)
        return arrays, valids

    def _range_program(self, idxs: tuple[int, ...]):
        """The device program of a range read over columns `idxs`: MVCC
        filter and row decode of a candidate view in one launch ->
        (Batch, any conflict, rows selected). One a (schema, columns),
        shared by every table and operator tree with them (kept here by
        columns: a statement takes no process-wide lock to find it)."""
        prog = self._range_programs.get(idxs)
        if prog is not None:
            return prog
        from ..flow import dispatch
        from ..storage import mvcc

        schema, pk_idx = self.schema, self.pk_idx

        def pkrange_decode(view, ts, txn, sw, ew):
            sel, conflict = mvcc.mvcc_scan_filter(view, ts, txn, sw, ew)
            batch = rowcodec.decode_columns(view.value, sel, schema, idxs)
            if pk_idx in idxs:  # from the key, as device_batch reads it
                cols = list(batch.cols)
                cols[idxs.index(pk_idx)] = Column(
                    data=rowcodec.decode_pk_column(view.key), valid=sel)
                batch = Batch(cols=tuple(cols), mask=sel)
            return (batch, jnp.any(conflict),
                    jnp.sum(sel, dtype=jnp.int32))

        prog = self._range_programs[idxs] = dispatch.jit(
            pkrange_decode, name="pkrange_decode",
            key=("pkrange_decode", schema, idxs, pk_idx))
        return prog

    def range_batches(self, lo: int, hi: int, names: tuple[str, ...]):
        """The rows whose primary key is in [lo, hi], as DEVICE tiles, in
        key order, a page of at most RANGE_PAGE_ROWS rows of a source at a
        time: the span is sought in the store (Engine.range_read: both
        bounds on the host, one window a source), filtered and decoded on
        the device, and no row comes to the host. Inside a transaction the
        pages are read through it (its snapshot, its own intents visible,
        the span in its read set for the commit's refresh, a foreign intent
        its retryable conflict); outside, through the autocommit read that
        waits a foreign intent out and retries (kv.DB.range_committed).
        Every page of one call is read at ONE timestamp. Never a decode of
        the table."""
        from ..utils import metric

        if lo > hi:
            return
        start = rowcodec.encode_pk(self.table_id, lo)
        end = (rowcodec.encode_pk(self.table_id, hi + 1)
               if hi < (1 << 63) - 1 else rowcodec.table_span(self.table_id)[1])
        idxs = tuple(self.schema.index(n) for n in names)
        page = {"limit_rows": RANGE_PAGE_ROWS,
                "decode": self._range_program(idxs)}
        t = self.reader
        if t is not None:
            t.note_read_span(start, end)
        else:
            ts = (self.read_ts if self.read_ts is not None
                  else self.db.clock.now())
        while True:
            got = (t.range_read(start, end, **page) if t is not None
                   else self.db.range_committed(start, end, ts, **page))
            metric.KV_RANGE_READS.inc()
            metric.KV_RANGE_ROWS.inc(got.rows)
            metric.KV_RANGE_WINDOW_ROWS.inc(got.window_rows)
            if got.out is not None:
                yield got.out
            if got.boundary is None:
                return
            start = got.boundary.rstrip(b"\x00")  # the next page's first key

    def get_row(self, pk: int, ts: int | None = None) -> dict | None:
        v = self.db.get(rowcodec.encode_pk(self.table_id, int(pk)), ts=ts)
        if v is None:
            return None
        row = rowcodec.decode_row(self.schema, v)
        for i in self._string_cols:
            name = self.schema.names[i]
            code = row.get(name)
            if code is not None:
                row[name] = self._dicts[i].values[int(code)]
        return row

    # -- Table facade (catalog.Table duck type) ------------------------------

    @property
    def num_rows(self) -> int:
        """Row-count estimate used only for planning (join ordering,
        broadcast decisions): a device-side count of newest-visible rows —
        no host materialization, and intents don't fail planning. Cached
        per engine write sequence so repeated binds don't re-scan."""
        from ..storage import keys as K
        from ..storage import mvcc

        eng: Engine = self.db.engine
        key = (eng._seq, eng._gen)  # _gen catches intent resolutions,
        # which change visibility without consuming a write sequence
        if self._count_cache is not None and self._count_cache[0] == key:
            return self._count_cache[1]
        view = _merged_view(eng)
        if view is None:
            n = 0
        else:
            start, end = rowcodec.table_span(self.table_id)
            sel, _ = mvcc.mvcc_scan_filter(
                view, jnp.int64(self.db.clock.now()), jnp.int64(0),
                jnp.asarray(K.encode_bound(start, eng.key_width)),
                jnp.asarray(K.encode_bound(end, eng.key_width)),
            )
            n = int(np.asarray(jnp.sum(sel)))
        self._count_cache = (key, n)
        return n

    # -- statistics (sql/stats) ---------------------------------------------

    def set_stats(self, st) -> None:
        """Install ANALYZE statistics; (lo, hi) bounds feed col_stats for
        exact-key planning, row_count feeds estimated_rows."""
        self.table_stats = st

    def estimated_rows(self) -> int:
        """What planning reads at every bind (join ordering): ANALYZE's
        count, else the store's host-side count of the span's versions
        (an upper bound; no device work, no merged view), else, on a
        backend without that count, the exact ``num_rows``."""
        st = getattr(self, "table_stats", None)
        if st is not None:
            return st.row_count
        estimate = getattr(self.db.engine, "span_versions_estimate", None)
        if estimate is None:
            return self.num_rows
        return estimate(*rowcodec.table_span(self.table_id))

    def col_stats(self) -> dict[str, tuple]:
        st = getattr(self, "table_stats", None)
        if st is None:
            return {}
        return {
            n: (c.lo, c.hi)
            for n, c in st.cols.items()
            if c.lo is not None and c.hi is not None
        }

    def snapshot_live_rows(self) -> int:
        """Live-row count at the CURRENT read context (read_ts/reader_txn)
        — what a scan of this table will actually see. num_rows counts
        newest-visible at now() with no reader; a pinned snapshot or an
        in-txn read can hold MORE rows, and distributed planners must size
        shards for the snapshot, not the present."""
        from ..storage import keys as K
        from ..storage import mvcc
        from ..storage import rowcodec

        eng: Engine = self.db.engine
        view = _merged_view(eng)
        if view is None:
            return 0
        start, end = rowcodec.table_span(self.table_id)
        ts = self.read_ts if self.read_ts is not None else self.db.clock.now()
        sel, _ = mvcc.mvcc_scan_filter(
            view, jnp.int64(ts), jnp.int64(self.reader_txn),
            jnp.asarray(K.encode_bound(start, eng.key_width)),
            jnp.asarray(K.encode_bound(end, eng.key_width)),
        )
        return int(np.asarray(jnp.sum(sel, dtype=jnp.int32)))

    def dict_by_index(self) -> dict:
        return {i: d.snapshot() for i, d in self._dicts.items()}

    @property
    def dictionaries(self) -> dict:
        return {
            self.schema.names[i]: d.snapshot()
            for i, d in self._dicts.items()
        }

    @property
    def valids(self):
        # Nullability is data-dependent (it lives in the engine, not a host
        # bitmap). Raising AttributeError makes this sentinel impossible to
        # misread: duck-typed consumers using getattr(t, "valids", ...) /
        # hasattr fall back safely, while any code that would row-align a
        # host bitmap (arrow conversion, streaming scans) fails loudly
        # instead of silently treating a length-1 marker as real data.
        raise AttributeError(
            "KVTable has no host valid bitmaps; nullability is decoded on "
            "device by device_batch()"
        )

    def device_batch(self, names: tuple[str, ...] | None = None) -> Batch:
        """Columnar snapshot of the newest-visible rows, decoded on device.

        One mvcc_scan_filter pass over the merged view + the rowcodec
        decode kernel; raises WriteIntentError on another txn's intent in
        the span, exactly like the row read path."""
        from ..storage import keys as K
        from ..storage import mvcc
        from ..utils import metric

        metric.KV_TABLE_DECODES.inc()
        names = names or self.schema.names
        idxs = tuple(self.schema.index(n) for n in names)
        ts = self.read_ts if self.read_ts is not None else self.db.clock.now()
        eng: Engine = self.db.engine
        view = _merged_view(eng)
        if view is None:
            from ..coldata.batch import empty_batch

            return empty_batch(self.schema.select(idxs), 1024)
        start, end = rowcodec.table_span(self.table_id)
        sw = K.encode_bound(start, eng.key_width)
        ew = K.encode_bound(end, eng.key_width)
        sel, conflict = mvcc.mvcc_scan_filter(
            view, jnp.int64(ts), jnp.int64(self.reader_txn),
            jnp.asarray(sw), jnp.asarray(ew),
        )
        cnp = np.asarray(conflict)
        if cnp.any():
            hit = np.nonzero(cnp)[0]
            raise WriteIntentError(
                K.decode_keys(np.asarray(view.key)[hit]),
                [int(x) for x in np.asarray(view.txn)[hit]],
            )
        batch = rowcodec.decode_columns(view.value, sel,
                                        self.schema, idxs)
        if self.pk_idx in idxs:
            # the PK also lives in the value payload, but decoding it from
            # the key exercises/validates the key codec path
            pk_col = rowcodec.decode_pk_column(view.key)
            pos = idxs.index(self.pk_idx)
            cols = list(batch.cols)
            cols[pos] = Column(data=pk_col, valid=sel)
            batch = Batch(cols=tuple(cols), mask=batch.mask)
        return batch


_DESC_PREFIX = b"\x01desc"


def _descriptor_key(table_id: int, chunk: int) -> bytes:
    return _DESC_PREFIX + b"%03d|%03d" % (table_id, chunk)


def write_descriptor(db: DB, t: KVTable, writer=None) -> None:
    """Persist the table descriptor in the system keyspace (the
    system.descriptor discipline: schemas are data, so a fresh process over
    the same engine rediscovers every table). The JSON chunks across rows
    so descriptors fit any engine value width. `writer`: an open Txn so a
    caller can make the swap atomic with other writes (schema changes
    commit the descriptor and their completion marker together)."""
    import json

    desc = {
        "name": t.name,
        "names": list(t.schema.names),
        "types": [
            dict({"family": ty.family.name, "width": ty.width,
                  "precision": ty.precision, "scale": ty.scale},
                 **({"text": True} if ty.text else {}))
            for ty in t.schema.types
        ],
        "pk": t.pk,
        "table_id": t.table_id,
        "dict_table_id": t.dict_table_id,
        "indexes": [
            {"name": ix.name, "col": ix.col, "index_id": ix.index_id}
            for ix in t.indexes
        ],
    }
    from .chunked import chunk_blob

    blob = json.dumps(desc).encode("utf-8")
    step = max(16, db.engine.val_width - 1)
    # length-headered chunks: a SHORTER rewrite (DROP COLUMN) leaves the
    # old tail chunks in place and readers truncate past them
    w = writer if writer is not None else db
    for ci, piece in enumerate(chunk_blob(blob, step)):
        w.put(_descriptor_key(t.table_id, ci), piece)


def load_catalog_from_engine(catalog, db: DB,
                             id_range: tuple[int, int] | None = None
                             ) -> list[str]:
    """Rebuild KVTable entries from persisted descriptors (the catalog
    bootstrap / lease-free resolution path). Returns the table names.
    id_range scopes discovery to a tenant's table-id slice (kv/tenant.py):
    a tenant session never even learns other tenants' schemas."""
    import json

    from ..coldata.types import Family as F
    from ..coldata.types import Schema as S
    from ..coldata.types import SQLType

    blobs: dict[bytes, list[tuple[bytes, bytes]]] = {}
    for k, v in db.scan(_DESC_PREFIX, _DESC_PREFIX + b"\xff"):
        tid = k[len(_DESC_PREFIX):].split(b"|")[0]
        blobs.setdefault(tid, []).append((k, v))
    from .chunked import unchunk

    out = []
    for tid in sorted(blobs):
        blob = unchunk([v for _, v in sorted(blobs[tid])])
        desc = json.loads(blob.decode("utf-8"))
        if id_range is not None and not (
            id_range[0] <= desc["table_id"] <= id_range[1]
        ):
            continue
        types = tuple(
            SQLType(F[d["family"]], width=d["width"],
                    precision=d["precision"], scale=d["scale"],
                    text=bool(d.get("text", False)))
            for d in desc["types"]
        )
        from .index import IndexDesc

        t = KVTable(db, desc["name"], S(tuple(desc["names"]), types),
                    desc["pk"], desc["table_id"], desc["dict_table_id"],
                    indexes=[IndexDesc(d["name"], d["col"], d["index_id"])
                             for d in desc.get("indexes", [])])
        catalog.tables[desc["name"]] = t
        out.append(desc["name"])
    return out


def create_kv_table(catalog, db: DB, name: str, schema: Schema, pk: str,
                    table_id: int | None = None,
                    id_range: tuple[int, int] | None = None) -> KVTable:
    """Create + register a KV-backed table in the catalog so sql()/Rel
    scans resolve to it. table_id determines the key-space prefix; ids must
    be unique per engine or spans would overlap. Tables with STRING columns
    get a second id for the persistent dictionary span. id_range confines
    allocation to a tenant's keyspace slice (kv/tenant.py) — the catalog
    then cannot even address another tenant's spans. Unscoped callers
    allocate within the SYSTEM tenant's range (1..127), so a legacy
    session can never squat on a secondary tenant's reserved slice."""
    from .tenant import _SYSTEM_RANGE

    lo, hi = id_range if id_range is not None else _SYSTEM_RANGE
    used = set()
    for t in catalog.tables.values():
        if isinstance(t, KVTable):
            used.add(t.table_id)
            if t.dict_table_id is not None:
                used.add(t.dict_table_id)
            used.update(ix.index_id for ix in t.indexes)

    def alloc() -> int:
        # only ids INSIDE the range matter: a foreign tenant's high id in
        # a shared catalog must neither seed the allocator past `hi` nor
        # fail an otherwise-empty range
        nxt = max([i for i in used if lo <= i <= hi], default=lo - 1) + 1
        if nxt > hi:
            raise ValueError(
                f"tenant keyspace [{lo},{hi}] exhausted"
            )
        return nxt

    if table_id is None:
        table_id = alloc()
    elif table_id in used:
        raise ValueError(f"table_id {table_id} already in use")
    used.add(table_id)
    dict_table_id = None
    if any(tt.family is Family.STRING for tt in schema.types):
        dict_table_id = alloc()
    t = KVTable(db, name, schema, pk, table_id, dict_table_id)
    catalog.tables[name] = t
    write_descriptor(db, t)
    return t
