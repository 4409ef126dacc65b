"""LSM storage engine — the Pebble-wrapper analog (pkg/storage/pebble.go).

Host-side orchestration of device-resident sorted runs:

- writes append to a durable on-disk WAL (write-ahead, pebble's wal/) and a
  host memtable;
- ``flush`` sorts the memtable into an immutable device run (an "SST");
- when runs pile past ``l0_trigger`` a SIZE-TIERED compaction merges only
  the smallest runs (``mvcc.merge_blocks`` + ``mvcc.mvcc_gc_filter`` — the
  Pebble compaction loop as one lane-parallel device pass); a full
  bottom-level compaction runs only on explicit ``compact(bottom=True)``.
  Partial merges are always safe: the global write sequence resolves
  same-(key, ts) winners regardless of which runs have merged;
- reads never mutate the run set. Bounded reads (get / range_read / scans
  with a bound) SEEK each run and the memtable's block on the host (two
  binary searches over its seek keys), take the one window that holds its
  rows of the span from the block cache or a device slice, and merge THOSE
  (the merging-iterator role, pebble_mvcc_scanner.go :381 semantics via
  ``mvcc_scan_filter``), so point/range cost is O(rows of the span), not
  O(total history). Unbounded reads use a merged view cached per run-set
  generation. A point read and a range read hold the engine mutex only to
  take a ``_Snapshot`` of what they will read (the run set, each run's
  seek keys and bloom, the memtable's block when it may hold a key of
  theirs) and run their searches, launches and readback with the mutex
  released;
- ``checkpoint``/``open_checkpoint`` persist runs to .npz files and
  truncate the WAL (pkg/storage/pebble.go:2077 CreateCheckpoint analog);
  a crash between checkpoints recovers by WAL replay at open.

Intents: provisional writes carry a txn id; ``resolve_intents`` commits or
aborts them engine-wide (MVCCResolveWriteIntent). A scan that hits another
txn's visible intent raises WriteIntentError, like the reference.
"""

from __future__ import annotations

import base64
import functools
import json
import os
import struct
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import blockcache
from . import keys as K
from . import mvcc
from ..utils import locks

_RUN_ALIGN = 1024
_CAND_ALIGN = 128  # candidate tiles for bounded reads start smaller
# a memtable block's seek keys go by this token: the next write replaces the
# block, so its windows never enter the block cache (run tokens start at 1)
_MEM_TOKEN = 0
# windows over this many rows are not cached either: one of them would push
# out thousands of point-read windows
_CACHE_MAX_ROWS = 4096

_WAL_MAGIC = b"CTWL"
# kind (0=write, 1=intent resolution, 2=ingest link), ts, seq, txn,
# tomb/commit, klen, vlen
_WAL_REC = struct.Struct("<BqqqBHH")
_REC_WRITE = 0
_REC_RESOLVE = 1
# ingest records carry the side-file name of a durably written run in the
# key field (AddSSTable's link-don't-copy durability: the run file is
# fsynced BEFORE the record is appended, so replay can always reload it)
_REC_INGEST = 2
# import records carry a side-file of full per-row MVCC fields (the
# snapshot-apply half of a range relocation); clear records carry the
# cleared span's [start, end) bounds in key/value (end b"" + flag=False
# means open-ended) — the replica-removal half
_REC_IMPORT = 3
_REC_CLEAR = 4
# batch records carry an ENTIRE stamped RPC mutation batch — ops, the
# (client id, sequence) dedup token, and the wire response — in one
# record. The torn-tail truncation of _arm_wal makes the record
# all-or-nothing across a crash, which is exactly the atomicity the
# exactly-once protocol needs: either the ops AND the replay-cache
# entry survive (a retry dedups) or neither does (a retry re-applies
# onto a store that never saw the batch). There is no window where the
# ops landed but the dedup entry didn't.
_REC_BATCH = 5


def _words_to_bytes(words) -> bytes:
    """Packed big-endian uint64 key words -> the original zero-padded key
    bytes (inverse of keys.encode_bound's word packing)."""
    return b"".join(int(w).to_bytes(8, "big") for w in np.asarray(words))


def _pad(n: int, align: int = _RUN_ALIGN) -> int:
    """Next power-of-2 capacity >= n (min `align`): blocks take only O(log)
    distinct static shapes, so kernels compile a handful of times total."""
    p = align
    while p < n:
        p *= 2
    return p


def _block_nbytes(blk: mvcc.KVBlock) -> int:
    """Logical bytes of a block's arrays — what run residency charges to
    the storage staging monitor (flow/memory.py staging accounts)."""
    return int(sum(int(x.size) * x.dtype.itemsize
                   for x in (blk.key, blk.ts, blk.seq, blk.txn,
                             blk.tomb, blk.value, blk.vlen, blk.mask)))


def _charge_run(run: mvcc.KVBlock) -> None:
    """Run residency joins the monitor tree (PR 8): reserved against the
    node budget, released when compaction drops the run and it is GC'd."""
    from ..flow import memory as flowmem

    flowmem.charge_object("storage/run-residency", run, _block_nbytes(run))


def _shrink(block: mvcc.KVBlock) -> mvcc.KVBlock:
    """Slice a *sorted* block (dead rows last) down to a power-of-2 capacity
    covering its live rows."""
    live = int(np.asarray(jnp.sum(block.mask)))
    cap = _pad(live)
    if cap >= block.capacity:
        return block
    return jax.tree_util.tree_map(lambda x: x[:cap], block)


@jax.jit  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _live_rows(block: mvcc.KVBlock) -> jax.Array:
    return jnp.sum(block.mask, dtype=jnp.int32)


@jax.jit  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _range_mask(block: mvcc.KVBlock, sw, ew):
    """In-range liveness mask + its count, one fused kernel per source
    shape (sw/ew None-ness is static trace structure)."""
    words = K.key_words(block.key)
    m = block.mask & K.words_in_range(words, sw, ew)
    return m, jnp.sum(m, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("size",))  # crlint: allow-raw-jit(storage-plane kernel: dispatch budget scopes the SQL flow layer)
def _slice_window(block: mvcc.KVBlock, pos, size: int) -> mvcc.KVBlock:
    """[pos, pos+size) window of a run — the iterator-seek read (O(size)
    device work regardless of run length)."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice_in_dim(
            x, jnp.clip(pos, 0, max(0, x.shape[0] - size)), size, axis=0
        ),
        block,
    )


def _seek(void_keys: np.ndarray, n_live: int, start: bytes | None,
          end: bytes | None) -> tuple[int, int]:
    """[lo, hi): the positions of a sorted source's live rows whose key is
    in [start, end) (zero-padded key bytes; None = unbounded), by two host
    binary searches over its seek keys: the iterator's SeekGE, for both
    ends of a span."""
    live = void_keys[:n_live]
    lo = 0 if start is None else int(np.searchsorted(
        live, np.frombuffer(start, dtype=void_keys.dtype)[0], side="left"))
    hi = n_live if end is None else int(np.searchsorted(
        live, np.frombuffer(end, dtype=void_keys.dtype)[0], side="left"))
    return lo, hi


class RangeRead(NamedTuple):
    """What `Engine.range_read` hands back: `out` of its decode over the
    candidate view (None where no source holds a row of the span), the
    rows selected, the truncation boundary (None: the whole span was
    read), and what it read for them."""

    out: object
    rows: int
    boundary: bytes | None
    window_rows: int
    sources: int


class WriteIntentError(Exception):
    def __init__(self, keys: list[bytes], txns: list[int]):
        super().__init__(f"conflicting intents on {keys} (txns {txns})")
        self.keys = keys
        self.txns = txns


from ..utils.errors import register_passthrough as _rp  # noqa: E402

_rp(WriteIntentError)  # expected error: crosses the query boundary unwrapped


@dataclass
class MVCCStats:
    """Coarse engine stats (enginepb.MVCCStats analog)."""

    live_count: int = 0
    key_count: int = 0
    val_count: int = 0
    intent_count: int = 0
    runs: int = 0
    compactions: int = 0
    flushes: int = 0


@dataclass
class _Memtable:
    keys: list[bytes] = field(default_factory=list)
    ts: list[int] = field(default_factory=list)
    seq: list[int] = field(default_factory=list)
    txn: list[int] = field(default_factory=list)
    tomb: list[bool] = field(default_factory=list)
    value: list[bytes] = field(default_factory=list)  # inline slot bytes
    vlen: list[int] = field(default_factory=list)  # LOGICAL value length
    # (vlen > engine.val_width marks an overflow pointer record)
    # txn id -> positions of its intents in the lists above: a commit
    # rewrites exactly those rows, without a walk over the memtable
    intents: dict[int, list[int]] = field(default_factory=dict)
    # the keys above as a set: a point read of a key that is not here
    # leaves the memtable out (no block built, nothing uploaded)
    keyset: set[bytes] = field(default_factory=set)
    # the first bytes of the keys above (a table's keys share theirs): a
    # range read of a span none of them can fall in leaves the memtable out
    heads: set[int] = field(default_factory=set)

    def __len__(self) -> int:
        return len(self.ts)

    def may_hold(self, start: bytes | None, end: bytes | None) -> bool:
        """Whether a key in [start, end) may be here, from `heads` alone."""
        lo = start[0] if start else 0
        hi = end[0] if end else 0xFF
        return any(lo <= h <= hi for h in self.heads)

    def append(self, key: bytes, ts: int, seq: int, txn: int, tomb: bool,
               value: bytes, vlen: int) -> None:
        if txn != 0:
            self.intents.setdefault(txn, []).append(len(self.ts))
        self.keyset.add(key)
        self.heads.add(key[0] if key else 0)
        self.keys.append(key)
        self.ts.append(ts)
        self.seq.append(seq)
        self.txn.append(txn)
        self.tomb.append(tomb)
        self.value.append(value)
        self.vlen.append(vlen)

    def resolve(self, txn: int, commit_ts: int, commit: bool) -> int:
        """Commit (ts := commit_ts, txn := 0) or abort (drop) txn's intents
        in place; -> how many rows it touched."""
        rows = self.intents.pop(txn, None)
        if not rows:
            return 0
        if commit:
            for i in rows:
                self.ts[i] = commit_ts
                self.txn[i] = 0
            return len(rows)
        dead = set(rows)
        keep = [i for i in range(len(self.ts)) if i not in dead]
        for name in ("keys", "ts", "seq", "txn", "tomb", "value", "vlen"):
            col = getattr(self, name)
            setattr(self, name, [col[i] for i in keep])
        self.keyset = set(self.keys)
        self.intents = {}
        for i, t in enumerate(self.txn):
            if t != 0:
                self.intents.setdefault(t, []).append(i)
        return len(rows)


class _TsCache:
    """Newest committed write timestamp per key — the kvserver/tscache role
    backing the WriteTooOld check, LSM-shaped so BULK ingest stays O(1)
    python-side: each ingest lands as one sorted numpy key batch (void
    dtype: memcmp order), single writes overlay a dict, and lookups take
    max(overlay, binary search per batch). Batches fold together once the
    list grows, keeping the per-lookup batch count bounded. The prior
    per-key dict build was ~1M tobytes+dict inserts per 1M-key ingest —
    measured as a third of YCSB load time."""

    _MAX_BATCHES = 8

    def __init__(self, key_width: int):
        self.kw = key_width
        self.over: dict[bytes, int] = {}
        self.batches: list[tuple[np.ndarray, np.ndarray]] = []

    def _void(self, keys_u8: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(keys_u8).view(f"V{self.kw}").reshape(-1)

    def bulk(self, keys_u8: np.ndarray, ts) -> None:
        """[N, kw] uint8 keys committed at ts (scalar or [N] array)."""
        if len(keys_u8) == 0:
            return
        v = self._void(keys_u8)
        t = (np.full(len(v), int(ts), np.int64) if np.isscalar(ts)
             else np.asarray(ts, np.int64))
        order = np.argsort(v, kind="stable")
        self.batches.append((v[order], t[order]))
        if len(self.batches) > self._MAX_BATCHES:
            self._fold()

    # crlint: allow-mem-accounting(fold compacts already-resident ts-cache batches: a transient concat whose output is strictly smaller than its inputs)
    def _fold(self) -> None:
        ks = np.concatenate([k for k, _ in self.batches])
        ts = np.concatenate([t for _, t in self.batches])
        order = np.argsort(ks, kind="stable")
        k, t = ks[order], ts[order]
        new = np.concatenate([[True], k[1:] != k[:-1]])
        gid = np.cumsum(new) - 1
        mx = np.zeros(int(gid[-1]) + 1, np.int64)
        np.maximum.at(mx, gid, t)
        self.batches = [(k[new], mx)]

    def get(self, b: bytes, _default: int = 0) -> int:
        t = self.over.get(b, 0)
        if self.batches and len(b) <= self.kw:
            q = np.frombuffer(b.ljust(self.kw, b"\x00"),
                              dtype=f"V{self.kw}")[0]
            for keys, ts in self.batches:
                i = int(np.searchsorted(keys, q))
                if i < len(keys) and keys[i] == q:
                    t = max(t, int(ts[i]))
        return t

    def put(self, b: bytes, ts: int) -> None:
        if ts > self.over.get(b, 0):
            self.over[b] = ts


def _locked(fn):
    """Serialize a public Engine method under the engine mutex.

    The reference sequences concurrent requests through latches + the lock
    table (concurrency_manager.SequenceReq); this engine's reduced analog is
    one reentrant store mutex. Without it, a Node's background threads
    (liveness heartbeats, the tsdb ticker, jobs adoption) race
    resolve_intents' run-set rewrite against concurrent memtable appends and
    leave orphaned intent rows behind (observed: a committed heartbeat's
    intent resurrected by a racing flush).

    Every write, flush, compaction, resolution, ``scan``, ``scan_batch``,
    ``has_committed_writes_in`` and the span / checkpoint methods keep the
    mutex for their whole body. Three do not carry this decorator. ``get``
    and ``range_read`` hold it only while they take a ``_Snapshot`` and do
    their searches, launches and readback on that with the mutex released
    (a caller that already holds it keeps it: the lock is reentrant).
    ``span_versions_estimate`` reads the published run snapshot with no
    mutex at all, and takes it only to build one where the run set has
    just changed: an estimate needs no instant."""
    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        with self.mu:
            return fn(self, *a, **kw)
    return wrapper


class _Snapshot(NamedTuple):
    """What a read reads, as of one instant under the engine mutex: the
    run set, each run's read metadata, and the memtable as a sorted block
    (None where the read leaves the memtable out). Runs and their metadata
    never change once built and a memtable block is a copy, so a reader
    may use a snapshot with the mutex released; the blocks live as long as
    a snapshot refers to them."""

    runs: tuple[mvcc.KVBlock, ...]
    metas: tuple[blockcache.RunMeta, ...]
    memtable: _Memtable  # the host memtable this run set goes with
    mem: mvcc.KVBlock | None = None  # its sorted block, where a read needs it
    # the block's seek keys (token _MEM_TOKEN: its windows are not cached)
    mem_meta: blockcache.RunMeta | None = None


class Engine:
    """MVCC LSM engine over device-resident sorted runs.

    Durability scope: with the default ``wal_fsync=False`` the WAL is written
    through the OS page cache only — acknowledged writes survive PROCESS
    crashes but can be lost on machine/kernel crashes. Pass ``wal_fsync=True``
    for fsync-per-record durability (Pebble's WAL sync default), at a large
    single-writer throughput cost."""

    def __init__(
        self,
        key_width: int = K.DEFAULT_KEY_WIDTH,
        val_width: int = 16,
        l0_trigger: int | None = None,
        memtable_size: int = 4096,
        gc_ts: int = 0,
        wal_path: str | None = None,
        wal_fsync: bool = False,
        compact_width: int = 4,
    ):
        assert key_width % 8 == 0
        # a statement's wait for the store shows in its trace
        self.mu = locks.rlock("storage.engine",
                              wait_span="storage/engine.lock_wait")
        from ..utils import settings

        self.key_width = key_width
        self.val_width = val_width
        # DefaultPebbleOptions L0CompactionThreshold (pebble.go:363)
        self.l0_trigger = (
            l0_trigger if l0_trigger is not None
            else settings.get("storage.l0_compaction_threshold")
        )
        self.memtable_size = memtable_size
        self.gc_ts = gc_ts
        self.compact_width = compact_width
        # admission control: every write path consults the IOGovernor and
        # pays a delay proportional to L0 overload (io_load_listener.go
        # role — slow writers BEFORE read amplification inverts)
        from .. utils.admission import IOGovernor

        self.governor = IOGovernor(self)
        # compaction merge kernel override: None = follow the
        # storage.pallas_merge setting; True/False force it (tests)
        self.pallas_merge: bool | None = None
        self._pallas_merge_interpret = False
        self.mem = _Memtable()
        self.runs: list[mvcc.KVBlock] = []  # sorted device runs, newest first
        self.stats = MVCCStats()
        self._seq = 0  # global write sequence: same-(key, ts) writes resolve
        # newest-sequence-wins (intent rewrites within a txn, TxnSeq analog)
        # host-side lock table (concurrency/lock_table.go analog): key ->
        # txn id holding an intent. Kept in sync by _append/resolve_intents
        # so lock checks are O(1) host lookups, never device merges.
        self._locks: dict[bytes, int] = {}
        # host-side newest-committed-timestamp index (tscache analog): keeps
        # the per-write WriteTooOld check off the device
        self._newest_committed = _TsCache(key_width)
        # read caches, invalidated by generation counters
        self._gen = 0  # bumps whenever anything but an append changes what
        # a read sees: the run set, or intents resolved in the memtable
        self._runs_gen = 0  # bumps only when the run set changes
        # runs that hold intents, by id with a strong run ref (as
        # _run_meta): (run, {txn ids}). Learned where rows with txn != 0
        # enter a run (flush, import, checkpoint restore) and carried
        # across rewrites, so a commit touches only the runs that hold
        # its transaction's intents — none, unless a flush came between
        # the write and the commit
        self._run_intents: dict[int, tuple[mvcc.KVBlock, set[int]]] = {}
        # per-run read-path metadata — seek keys + split-block bloom +
        # the token namespacing the run's block-cache entries
        # (storage/blockcache.py); keyed by id with a strong run ref so
        # ids can't be reused
        self._run_meta: dict[int, tuple[mvcc.KVBlock, blockcache.RunMeta]] = {}
        self._runs_view_cache: tuple[int, mvcc.KVBlock] | None = None
        self._snap_cache: tuple[int, _Snapshot] | None = None
        self._scan_windows: dict[int, int] = {}  # max_keys -> learned window
        self._mem_cache = None  # ((mem len, gen), sorted block)
        self._mem_meta = None  # that block's seek keys
        self._overlay_cache = None  # ((gen, mem len), merged view)
        # variable-width value overflow heap (the WiscKey / pebble
        # value-separation shape): values longer than the fixed inline
        # slot live here, the slot stores an 8-byte offset pointer, and
        # vlen > val_width is the overflow marker. Append-only; dead
        # blobs are reclaimed only by checkpoint+reopen (value-log GC is
        # out of scope, like pebble's is a separate subsystem).
        self._blob = bytearray()
        # RPC replay cache (exactly-once writes): client id -> (last seq,
        # wire response). BatchClient serializes batches per connection,
        # so a window of ONE entry per client suffices — a retry can only
        # ever be for the newest seq. Entries persist via _REC_BATCH WAL
        # records and checkpoint side files; bounded at
        # _REPLAY_CACHE_MAX_CLIENTS with oldest-client eviction.
        self._replay_cache: dict[str, tuple[int, object]] = {}
        # durable write-ahead log
        self.wal_path = wal_path
        self.wal_fsync = wal_fsync
        self._wal = None
        self._replaying = False
        # optional DiskMonitor (storage/disk.py): when set, WAL appends
        # feed its rolling write-latency window
        self.disk_monitor = None
        if wal_path is not None:
            self._arm_wal(wal_path)

    # -- WAL ----------------------------------------------------------------

    def _arm_wal(self, path: str) -> None:
        """Replay any existing records, then open the WAL for appending
        (shared by fresh opens and checkpoint restores). Torn bytes past
        the last complete record are truncated away — appending after
        garbage would corrupt every future replay."""
        valid_off = 0
        if os.path.exists(path) and os.path.getsize(path) > 0:
            valid_off = self._replay_wal(path)
            if valid_off < os.path.getsize(path):
                with open(path, "r+b") as f:
                    f.truncate(valid_off)
        self.wal_path = path
        self._wal = open(path, "ab")
        if os.path.getsize(path) < len(_WAL_MAGIC):
            self._wal.truncate(0)
            self._wal.write(_WAL_MAGIC)
            self._wal.flush()

    def _wal_record(self, kind: int, key: bytes, value: bytes, ts: int,
                    seq: int, txn: int, flag: bool) -> None:
        from ..utils import faults, tracing

        rec = _WAL_REC.pack(kind, ts, seq, txn, 1 if flag else 0,
                            len(key), len(value))
        mon = self.disk_monitor  # one read: may be attached concurrently
        t0 = time.time() if mon is not None else 0.0
        payload = rec + key + value
        # chaos sites (pebble errorfs analog): a `delay` fault models a
        # stalling disk, `error` EIO before any byte lands, `partial` a
        # torn append — half the record hits the file, then the "disk"
        # dies. Replay's torn-tail truncation must recover all three.
        with tracing.leaf_span("storage/wal.append", bytes=len(payload)):
            faults.fire("storage.wal.append")
            frac = faults.partial_fraction("storage.wal.append")
            if frac is not None:
                self._wal.write(payload[:max(1, int(len(payload) * frac))])
                self._wal.flush()
                raise faults.InjectedFault("storage.wal.append", "partial")
            self._wal.write(payload)
            self._wal.flush()
            if self.wal_fsync:
                with tracing.leaf_span("storage/wal.fsync"):
                    faults.fire("storage.wal.fsync")
                    os.fsync(self._wal.fileno())
        if mon is not None:
            # the WAL append IS the write-latency signal the disk monitor
            # tracks (pkg/storage/disk samples the same device)
            mon.observe(time.time() - t0)

    def _replay_wal(self, path: str) -> int:
        """Recover state lost in a crash: re-apply writes above the restored
        sequence high-water mark and ALL intent resolutions, in log order
        (resolutions are idempotent, so re-applying pre-checkpoint ones is
        harmless; skipping one would resurrect a committed txn's intents).
        Returns the offset just past the last COMPLETE record, so the
        caller can truncate torn bytes before appending."""
        with open(path, "rb") as f:
            data = f.read()
        if len(data) < len(_WAL_MAGIC):
            return 0  # torn header: nothing recoverable was logged
        if data[:4] != _WAL_MAGIC:
            raise ValueError(f"corrupt WAL header in {path!r}")
        off = 4
        valid_off = off
        self._replaying = True
        try:
            while off + _WAL_REC.size <= len(data):
                kind, ts, seq, txn, flag, klen, vlen = _WAL_REC.unpack_from(
                    data, off)
                off += _WAL_REC.size
                if off + klen + vlen > len(data):
                    break  # torn tail record: drop (standard WAL semantics)
                key = data[off: off + klen]
                value = data[off + klen: off + klen + vlen]
                off += klen + vlen
                valid_off = off
                if kind == _REC_RESOLVE:
                    self.resolve_intents(txn, ts, commit=bool(flag))
                elif kind == _REC_INGEST:
                    if seq > self._seq:
                        side = os.path.join(os.path.dirname(path) or ".",
                                            key.decode())
                        try:
                            z = np.load(side)
                            n = int(z["n"])
                        except (FileNotFoundError, ValueError, OSError,
                                KeyError, EOFError,
                                __import__("zipfile").BadZipFile) as e:
                            # missing OR torn/corrupt side file: only
                            # reachable after a machine crash with
                            # wal_fsync=False (no durability promise
                            # there — the OS may persist the WAL record
                            # and the npz in either order, or half of
                            # one). Warn and keep the store OPENABLE;
                            # refusing to start would turn that crash
                            # into permanent data loss of everything
                            # else too.
                            from ..utils import log

                            log.warning(log.STORAGE,
                                        "ingest side file missing/torn on "
                                        "replay; run dropped",
                                        file=side, error=str(e))
                            continue
                        # re-link through ingest(): _replaying suppresses
                        # the re-log, so the run lands exactly once
                        self.ingest(z["key"][:n], z["value"][:n], ts,
                                    seq=seq, vlens=z["vlen"][:n])
                elif kind == _REC_IMPORT:
                    if seq > self._seq:
                        side = os.path.join(os.path.dirname(path) or ".",
                                            key.decode())
                        try:
                            z = np.load(side)
                            rows = {f: z[f] for f in (
                                "key", "ts", "seq", "txn", "tomb", "value",
                                "vlen")}
                            if "blob" in z.files:
                                rows["blob"] = z["blob"]
                        except (FileNotFoundError, ValueError, OSError,
                                KeyError, EOFError,
                                __import__("zipfile").BadZipFile) as e:
                            from ..utils import log

                            log.warning(log.STORAGE,
                                        "import side file missing/torn on "
                                        "replay; run dropped",
                                        file=side, error=str(e))
                            continue
                        self.import_rows(rows)
                        # restore the marker allocated at emit time (the
                        # imported rows' own max seq may be lower)
                        self._seq = max(self._seq, seq)
                elif kind == _REC_CLEAR:
                    self.clear_span(key or None,
                                    value if flag else None)
                elif kind == _REC_BATCH:
                    self._replay_batch_record(seq, value)
                elif seq > self._seq:
                    self._raw_append(key, value, ts, seq, txn, bool(flag))
        finally:
            self._replaying = False
        self.flush_mem_only()
        return valid_off

    def _truncate_wal(self) -> None:
        if self._wal is None:
            return
        self._wal.close()
        self._wal = open(self.wal_path, "wb")
        self._wal.write(_WAL_MAGIC)
        self._wal.flush()
        if self.wal_fsync:
            os.fsync(self._wal.fileno())
        self._wal.close()
        self._wal = open(self.wal_path, "ab")

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()
            self._wal = None

    # -- writes -------------------------------------------------------------

    @_locked
    def put(self, key: bytes | str, value: bytes | str, ts: int, txn: int = 0):
        self._append(key, value, ts, txn, tomb=False)

    @_locked
    def delete(self, key: bytes | str, ts: int, txn: int = 0):
        self._append(key, b"", ts, txn, tomb=True)

    def _append(self, key, value, ts: int, txn: int, tomb: bool):
        b = key.encode() if isinstance(key, str) else bytes(key)
        v = value.encode() if isinstance(value, str) else bytes(value)
        if b"\x00" in b:
            # zero-padded fixed-width encoding makes b"a" and b"a\x00"
            # indistinguishable (keys.py precondition) — enforce it here
            raise ValueError(f"key must not contain 0x00 bytes: {b!r}")
        if len(b) > self.key_width:
            raise ValueError(f"key too long ({len(b)} > {self.key_width})")
        if len(v) > self.val_width and self.val_width < 8:
            raise ValueError(
                f"value of {len(v)} bytes needs the overflow heap, which "
                f"requires val_width >= 8 (have {self.val_width})"
            )
        from ..utils import metric

        metric.ENGINE_WRITES.inc()
        self.governor.pace_write()
        seq = self._seq + 1
        if self._wal is not None:  # write-ahead: durable before visible
            self._wal_record(_REC_WRITE, b, v, int(ts), seq, int(txn), tomb)
        self._raw_append(b, v, int(ts), seq, int(txn), tomb)
        if len(self.mem) >= self.memtable_size:
            self.flush()

    def _raw_append(self, b: bytes, v: bytes, ts: int, seq: int, txn: int,
                    tomb: bool) -> None:
        self._seq = max(self._seq, seq)
        if txn != 0:
            self._locks[b] = int(txn)
        else:
            self._newest_committed.put(b, ts)
        n = len(v)
        if n > self.val_width:
            # overflow: payload to the heap, an offset pointer inline.
            # Done HERE (not _append) so WAL replay — which logs the full
            # value and re-runs this path — rebuilds the heap itself.
            off = len(self._blob)
            self._blob += v
            v = off.to_bytes(8, "little")
        self.mem.append(b, ts, seq, txn, tomb, v, n)

    # -- exactly-once RPC batches -------------------------------------------
    # (kvserver's replay protection reduced: the server consults this
    # cache before evaluating a stamped mutation batch, and the batch's
    # ops + dedup token + response persist in ONE atomic WAL record.)

    _REPLAY_CACHE_MAX_CLIENTS = 1024

    @_locked
    def replay_cache_get(self, cid: str, seq: int):
        """The cached wire response if (cid, seq) already applied, else
        None. A hit means the client's retry crossed a window where the
        first attempt DID land (severed response, server restart)."""
        ent = self._replay_cache.get(cid)
        if ent is not None and ent[0] == seq:
            return ent[1]
        return None

    def _set_replay_entry(self, cid: str, seq: int, resp) -> None:
        self._replay_cache.pop(cid, None)  # reinsert = refresh LRU order
        while len(self._replay_cache) >= self._REPLAY_CACHE_MAX_CLIENTS:
            self._replay_cache.pop(next(iter(self._replay_cache)))
        self._replay_cache[cid] = (int(seq), resp)

    @_locked
    def apply_rpc_batch(self, cid: str, seq: int, muts, resp) -> None:
        """Apply a stamped mutation batch exactly once.

        muts: [(key bytes, value bytes, ts, txn, tomb), ...] as evaluated
        by the RPC server; resp: the JSON-serializable wire response to
        replay on a dedup hit. One _REC_BATCH WAL record covers ops +
        dedup entry + response, so crash recovery can never disagree with
        itself about whether the batch applied (see _REC_BATCH note)."""
        from ..utils import metric

        for k, v, _ts, _txn, _tomb in muts:
            if b"\x00" in k:
                raise ValueError(f"key must not contain 0x00 bytes: {k!r}")
            if len(k) > self.key_width:
                raise ValueError(
                    f"key too long ({len(k)} > {self.key_width})")
            if len(v) > self.val_width and self.val_width < 8:
                raise ValueError(
                    f"value of {len(v)} bytes needs the overflow heap, "
                    f"which requires val_width >= 8 (have {self.val_width})")
        self.governor.pace_write()
        base = self._seq + 1
        if self._wal is not None:
            payload = json.dumps({
                "cid": cid, "seq": int(seq),
                "muts": [[base64.b64encode(k).decode(),
                          base64.b64encode(v).decode(),
                          int(ts), int(txn), bool(tomb)]
                         for k, v, ts, txn, tomb in muts],
                "resp": resp,
            }).encode()
            # klen/vlen are uint16: struct.pack rejects a batch payload
            # past 64 KiB, surfacing as a typed error before any byte of
            # WAL or memtable state changes
            self._wal_record(_REC_BATCH, b"", payload, 0, base, 0, False)
        for i, (k, v, ts, txn, tomb) in enumerate(muts):
            metric.ENGINE_WRITES.inc()
            self._raw_append(k, v, int(ts), base + i, int(txn), bool(tomb))
        self._set_replay_entry(cid, seq, resp)
        if len(self.mem) >= self.memtable_size:
            self.flush()

    def _replay_batch_record(self, seq: int, value: bytes) -> None:
        """WAL-replay half of apply_rpc_batch: re-apply ops above the seq
        high-water mark and ALWAYS restore the dedup entry (last record
        per client wins, matching log order)."""
        ent = json.loads(value.decode())
        if seq > self._seq:
            for i, (k64, v64, ts, txn, tomb) in enumerate(ent["muts"]):
                self._raw_append(
                    base64.b64decode(k64), base64.b64decode(v64),
                    int(ts), seq + i, int(txn), bool(tomb))
        self._set_replay_entry(ent["cid"], int(ent["seq"]), ent["resp"])

    def _resolve_value(self, row: np.ndarray, n: int) -> bytes:
        """Inline slot bytes + logical length -> the stored value (follows
        the overflow pointer when n exceeds the inline width)."""
        if n <= self.val_width:
            return bytes(row[:n])
        off = int.from_bytes(bytes(row[:8]), "little")
        return bytes(self._blob[off:off + n])

    # -- flush / compaction -------------------------------------------------

    def _runs_changed(self) -> None:
        self._gen += 1
        self._runs_gen += 1
        self._snap_cache = None  # and its hold on the runs that left

    def _note_run_intents(self, run: mvcc.KVBlock, txns) -> None:
        txns = {int(t) for t in txns if int(t) != 0}
        if txns:
            self._run_intents[id(run)] = (run, txns)

    def _take_run_intents(self, run: mvcc.KVBlock) -> set[int]:
        """The txn ids whose intents `run` holds; the run is leaving the
        run set (merged, rewritten or dropped), so its entry goes."""
        c = self._run_intents.pop(id(run), None)
        return c[1] if c is not None and c[0] is run else set()

    def _mem_block(self) -> mvcc.KVBlock | None:
        if not len(self.mem):
            return None
        n = len(self.mem)
        if self._mem_cache is not None and self._mem_cache[0] == (n, self._gen):
            return self._mem_cache[1]
        self._mem_meta = None
        keys = K.encode_keys(self.mem.keys, self.key_width)
        vals = np.zeros((n, self.val_width), dtype=np.uint8)
        vlen = np.asarray(self.mem.vlen, dtype=np.int32)
        for i, v in enumerate(self.mem.value):
            if len(v):
                vals[i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
        # sort on the HOST (canonical MVCC order: key asc, ts desc, seq
        # desc — _mvcc_sort_operands' ordering): a memtable is <=
        # memtable_size rows, so np.lexsort costs microseconds while the
        # device sort_block this replaces charged a ~10-20ms XLA sort to
        # EVERY scan batch that followed an insert (write-then-read
        # workloads pay one rebuild per batch)
        ts_arr = np.asarray(self.mem.ts, np.int64)
        seq_arr = np.asarray(self.mem.seq, np.int64)
        void_keys = np.ascontiguousarray(keys).view(
            f"V{self.key_width}").reshape(-1)
        order = np.lexsort((-seq_arr, -ts_arr, void_keys))
        blk = mvcc.block_from_host(
            keys[order],
            ts_arr[order],
            np.asarray(self.mem.txn)[order],
            np.asarray(self.mem.tomb)[order],
            vals[order],
            vlen[order],
            # one capacity from the first row to the flush (a memtable of
            # the default size): the kernels that read the block, and the
            # run it becomes, have one shape, not one a power of two
            cap=_pad(max(n, min(self.memtable_size, 4096))),
            seq=seq_arr[order],
        )
        from ..flow import memory as flowmem

        # memtable-block residency (cached until the next write changes
        # the memtable): charged like a run, released when the cache
        # entry is replaced and the old block is GC'd
        flowmem.charge_object("storage/run-residency", blk,
                              _block_nbytes(blk))
        self._mem_cache = ((n, self._gen), blk)
        # the block's seek keys, as a run has them: a bounded read seeks
        # the memtable's block like any other sorted source
        self._mem_meta = blockcache.RunMeta(_MEM_TOKEN, void_keys[order], n)
        return blk

    @_locked
    def ingest(self, keys: np.ndarray, values: np.ndarray, ts: int,
               seq: int | None = None,
               vlens: np.ndarray | None = None,
               presorted: bool = False) -> None:
        """Bulk ingest: land pre-built KV arrays as ONE sorted run — the
        AddSSTable path (kvserver/batcheval/cmd_add_sstable.go role; the
        reference's bulk loaders build SSTs client-side and link them into
        the LSM without touching the memtable/WAL). keys: [N, key_width]
        uint8 zero-padded; values: [N, <=val_width] uint8. All entries land
        committed at `ts`.

        One device sort builds the run; the WriteTooOld index takes the
        whole batch in one vectorized pass — per-row put() would pay host
        encode + append per key (the ingest-vs-write asymmetry the
        reference's IMPORT exists for).

        ``presorted=True`` promises the keys are already unique and in
        canonical run order (the RunBuilder sorted and deduped them
        device-side) — the landing re-sort is skipped."""
        n = len(keys)
        if n == 0:
            return
        self.governor.pace_write()
        if keys.shape[1] > self.key_width:
            raise ValueError("ingest keys wider than engine key width")
        if values.shape[1] > self.val_width:
            raise ValueError("ingest values wider than engine val width")
        if seq is None:
            seq = self._seq + 1
        self._seq = max(self._seq, seq)
        cap = _pad(n)
        kb = np.zeros((cap, self.key_width), dtype=np.uint8)
        kb[:n, : keys.shape[1]] = keys
        vb = np.zeros((cap, self.val_width), dtype=np.uint8)
        vb[:n, : values.shape[1]] = values
        vl = np.concatenate([
            (np.asarray(vlens, dtype=np.int32) if vlens is not None
             else np.full(n, values.shape[1], np.int32)),
            np.zeros(cap - n, np.int32),
        ])
        if self._wal is not None and not self._replaying:
            # durable-before-visible, same as _append: persist the run's
            # host arrays (live prefix only) to a side file, THEN append
            # the WAL record naming it — replay rebuilds the run from the
            # file. fsync (file + directory entry, the checkpoint()
            # discipline) only under wal_fsync, matching _wal_record.
            side = f"{self.wal_path}.ingest{int(seq):012d}.npz"
            with open(side, "wb") as f:
                np.savez(f, key=kb[:n], value=vb[:n], vlen=vl[:n],
                         n=np.int64(n), ts=np.int64(ts), seq=np.int64(seq))
                f.flush()
                if self.wal_fsync:
                    os.fsync(f.fileno())
            if self.wal_fsync:
                dfd = os.open(os.path.dirname(side) or ".", os.O_RDONLY)
                try:
                    os.fsync(dfd)
                finally:
                    os.close(dfd)
            from ..utils import faults

            # chaos: crash window between the durable side file and the
            # WAL link record — the run must stay invisible (replay sees
            # no record; the orphan side file is cleaned at checkpoint)
            # and a retry must land it cleanly
            faults.fire("storage.ingest.link")
            self._wal_record(_REC_INGEST, os.path.basename(side).encode(),
                             b"", int(ts), int(seq), 0, False)
        blk = mvcc.KVBlock(
            key=jnp.asarray(kb),
            ts=jnp.full((cap,), int(ts), jnp.int64),
            seq=jnp.full((cap,), int(seq), jnp.int64),
            txn=jnp.zeros((cap,), jnp.int64),
            tomb=jnp.zeros((cap,), jnp.bool_),
            value=jnp.asarray(vb),
            vlen=jnp.asarray(vl),
            mask=jnp.asarray(np.arange(cap) < n),
        )
        run = blk if presorted else mvcc.sort_block(blk)
        _charge_run(run)
        self.runs.insert(0, run)
        self._runs_changed()
        self.stats.flushes += 1
        self.stats.runs = len(self.runs)
        from ..utils import metric

        metric.ENGINE_INGESTS.inc()
        metric.INGEST_ROWS.inc(n)
        metric.INGEST_BYTES.inc(int(n * self.key_width + int(vl[:n].sum())))
        metric.ENGINE_RUNS.set(len(self.runs))
        self._register_run(run)
        # one sorted-batch tscache insert for the whole ingest (no per-key
        # host work — see _TsCache)
        self._newest_committed.bulk(kb[:n], int(ts))
        self._maybe_compact()

    @_locked
    def flush(self):
        """Memtable -> sorted immutable run (Pebble memtable flush)."""
        self.flush_mem_only()
        self._maybe_compact()

    @_locked
    def flush_mem_only(self):
        blk = self._mem_block()
        if blk is None:
            return
        self.runs.insert(0, blk)
        self._note_run_intents(blk, self.mem.intents)
        self.mem = _Memtable()
        self._mem_cache = None
        self._runs_changed()
        self.stats.flushes += 1
        self.stats.runs = len(self.runs)
        from ..utils import metric

        metric.ENGINE_FLUSHES.inc()
        metric.ENGINE_RUNS.set(len(self.runs))
        self._register_run(blk)

    def _maybe_compact(self) -> None:
        """Size-tiered compaction trigger behind the IOGovernor's pacing
        decision: small debt may be deferred (storage.compaction.pacing.*)
        so back-to-back merges can't starve foreground reads; debt past
        max_debt_runs always compacts immediately."""
        if (len(self.runs) > self.l0_trigger
                and self.governor.pace_compaction()):
            self.compact(bottom=False)

    @_locked
    def compact(self, bottom: bool = True):
        """Compaction. bottom=True merges everything and elides bottom-level
        tombstones (a full/manual compaction); bottom=False is the
        size-tiered incremental pass: merge only the `compact_width`
        smallest runs (pebble's tiered L0->Lbase compaction picking)."""
        from ..utils import tracing

        self.flush_mem_only()
        if len(self.runs) < 2:
            return
        with tracing.leaf_span("storage/compaction", bottom=bottom,
                               runs=len(self.runs)):
            if bottom:
                picked = list(range(len(self.runs)))
            else:
                by_size = sorted(
                    range(len(self.runs)),
                    key=lambda i: self.runs[i].capacity
                )
                picked = sorted(by_size[: max(2, self.compact_width)])
            blocks = tuple(self.runs[i] for i in picked)
            total = sum(r.capacity for r in blocks)
            merged = self._merge_for_compaction(blocks, total)
            keep = mvcc.mvcc_gc_filter(merged, jnp.int64(self.gc_ts),
                                       bottom)
            merged = mvcc.KVBlock(
                key=merged.key, ts=merged.ts, seq=merged.seq,
                txn=merged.txn, tomb=merged.tomb, value=merged.value,
                vlen=merged.vlen, mask=merged.mask & keep,
            )
            # dead rows last at the power of two over the live ones; the
            # order is planned on the host (see mvcc.host_order)
            merged = mvcc.sort_block_host(merged, _pad)
            kept = [r for i, r in enumerate(self.runs)
                    if i not in set(picked)]
            # the merged run replaces its sources at the oldest picked
            # position
            kept.insert(min(len(kept), picked[0]), merged)
            self.runs = kept
            self._runs_changed()
            from ..utils import faults

            try:
                # chaos: the run-set swap is visible but the cache/bloom
                # bookkeeping hasn't happened yet — invalidation MUST
                # still run (finally) or readers could be served stale
                # cached windows of the replaced runs
                faults.fire("storage.compaction.swap")
            finally:
                # the output run rebuilds its bloom; its inputs drop
                # their metadata and ONLY their own block-cache entries
                for b in blocks:
                    self._drop_run_meta(b)
                self._register_run(merged)
                self._note_run_intents(merged, set().union(
                    *(self._take_run_intents(b) for b in blocks)))
            self.stats.compactions += 1
            from ..utils import log, metric

            metric.ENGINE_COMPACTIONS.inc()
            log.debug(log.STORAGE, "compaction", runs=len(self.runs),
                      bottom=bottom)
            self.stats.runs = len(self.runs)
            self.governor.note_compaction()

    def _merge_for_compaction(self, blocks, total: int) -> mvcc.KVBlock:
        """Pick the compaction merge: the bitonic-merge Pallas kernel
        (pallas_merge.py — pebble mergingIter role, log2(N) stages over
        pre-sorted runs) when enabled and VMEM-sized, else a concat whose
        order is planned on the host (mvcc.merge_blocks_host: no device
        sort, whose compile at 64-byte keys is minutes a shape).
        Kernel output capacity is the padded power of two; the post-GC
        sort+_shrink in compact() trims it either way."""
        from ..utils import settings
        from . import pallas_merge as pm

        use = self.pallas_merge
        if use is None:
            use = mvcc.pallas_wanted(settings.get("storage.pallas_merge"))
        if use and self.key_width == 16 and pm.eligible(blocks):
            mvcc.KERNEL_CALLS["merge.pallas"] += 1
            return pm.merge_runs(blocks,
                                 interpret=self._pallas_merge_interpret)
        mvcc.KERNEL_CALLS["merge.jnp"] += 1
        return mvcc.merge_blocks_host(blocks, cap=_pad(total))

    # -- read views ---------------------------------------------------------

    def _runs_view(self) -> mvcc.KVBlock | None:
        """One sorted device view over all runs, cached per generation;
        never mutates the run set."""
        if not self.runs:
            return None
        if (self._runs_view_cache is not None
                and self._runs_view_cache[0] == self._runs_gen):
            return self._runs_view_cache[1]
        if len(self.runs) == 1:
            view = self.runs[0]
        else:
            total = sum(r.capacity for r in self.runs)
            view = _shrink(
                mvcc.merge_blocks(tuple(self.runs), cap=_pad(total))
            )
        self._runs_view_cache = (self._runs_gen, view)
        return view

    def _merged_view(self) -> mvcc.KVBlock | None:
        """Sorted view over memtable + runs (the read path's merging
        iterator). Cached per (run-set generation, memtable length) so a
        write-then-N-reads workload pays one overlay merge, not N; the run
        set itself is never rewritten by reads."""
        rv = self._runs_view()
        mb = self._mem_block()
        if mb is None:
            return rv
        if rv is None:
            return mb
        key = (self._gen, len(self.mem))
        if (self._overlay_cache is not None
                and self._overlay_cache[0] == key):
            return self._overlay_cache[1]
        view = mvcc.merge_blocks(
            (mb, rv), cap=_pad(mb.capacity + rv.capacity)
        )
        self._overlay_cache = (key, view)
        return view

    def _published_snapshot(self) -> _Snapshot | None:
        """The cached run snapshot if it is of the current run set, else
        None; one attribute read, safe with or without the mutex."""
        c = self._snap_cache
        return c[1] if c is not None and c[0] == self._runs_gen else None

    def _run_snapshot(self) -> _Snapshot:
        """The run set and each run's read metadata as of now, built once
        a run-set generation (``_meta_for``'s pruning and block-cache
        invalidation happen here); the caller holds the mutex."""
        snap = self._published_snapshot()
        if snap is None:
            from ..utils import metric

            runs = tuple(self.runs)
            snap = _Snapshot(
                runs, tuple(self._meta_for(r) for r in runs), self.mem)
            self._snap_cache = (self._runs_gen, snap)
            metric.ENGINE_SNAPSHOT_BUILDS.inc()
        return snap

    def _snapshot(self, point: bytes | None = None,
                  span: tuple | None = None) -> _Snapshot:
        """What a read reads, as of now; the caller holds the mutex. A
        point read of a key the memtable does not hold leaves the memtable
        out (no block built, nothing uploaded), and so does a read of a
        ``span`` (start, end) that no key of the memtable can fall in.
        Runs, the key tests and the memtable's block come from ONE hold of
        the mutex: an old run tuple paired with the empty memtable a flush
        left behind would lose the flushed rows."""
        snap = self._run_snapshot()
        if point is not None and point not in self.mem.keyset:
            return snap
        if span is not None and not self.mem.may_hold(*span):
            return snap
        mb = self._mem_block()
        return snap if mb is None else snap._replace(
            mem=mb, mem_meta=self._mem_meta)

    def _bounded_view(self, snap: _Snapshot, sw, ew,
                      limit_rows: int | None = None,
                      point: bytes | None = None,
                      note: dict | None = None):
        """Candidate view for a bounded read: every source of the snapshot
        it is handed is SOUGHT on the host (two binary searches over its
        seek keys, `_seek`: a run's, or the memtable block's), the window
        that holds its rows of the span comes from the block cache or one
        device slice, and the windows are merged: cost scales with the
        rows of the span, never with a source's length, and a source with
        no row of the span costs no device work at all. A window is handed
        on as it lies (a few rows of the neighbouring keys ride along):
        every caller applies the bounds again (`mvcc_scan_filter`). Reads
        nothing of the engine that a writer changes, so it runs with or
        without the mutex.

        limit_rows clamps each source to its first limit_rows entries of
        the span (the pebbleMVCCScanner pagination discipline): a scan with
        max_keys must not gather half the keyspace just because its end
        bound is open. Returns (view, boundary): rows at or past `boundary`
        (the smallest truncation point across sources) are INCOMPLETE: some
        of their versions may have been cut, and callers must not emit
        them. boundary None means nothing was truncated. ``note``, where
        given, takes what was read: `window_rows`, `sources`."""
        sources = []
        if snap.mem is not None:
            sources.append((snap.mem, snap.mem_meta))
        sources.extend(zip(snap.runs, snap.metas))
        start = None if sw is None else _words_to_bytes(sw)
        end = None if ew is None else _words_to_bytes(ew)
        parts = []
        boundary: bytes | None = None
        for src, meta in sources:
            is_run = meta.token != _MEM_TOKEN
            if (point is not None and is_run
                    and not self._bloom_might_contain(meta, point)):
                # per-run bloom filter: the key is definitely absent —
                # skip the run's seek and window entirely (pebble's
                # table-filter point-read pruning)
                from ..utils import metric

                metric.BLOOM_SKIPS.inc()
                continue
            lo, hi = _seek(meta.void_keys, meta.n_live, start, end)
            if hi <= lo:
                continue  # no row of the span here
            want = hi - lo if limit_rows is None else min(hi - lo,
                                                          limit_rows)
            size = min(_pad(want, _CAND_ALIGN), src.capacity)
            cpos = min(lo, max(0, src.capacity - size))
            if size == src.capacity:
                win = src  # the whole source: nothing to slice or copy
            elif is_run and size <= _CACHE_MAX_ROWS:
                # block cache: runs are immutable, so a (token, pos,
                # size) window's contents never change — consult the
                # node cache before dispatching the device slice
                cache = blockcache.node_cache()
                win = cache.get(meta.token, cpos, size)
                if win is None:
                    win = _slice_window(src, cpos, size)
                    cache.put(meta.token, cpos, size, win)
            else:
                win = _slice_window(src, cpos, size)
            if cpos + size < hi:
                # the window ends inside the span: its last key's versions
                # may go on past it
                cut = bytes(meta.void_keys[cpos + size - 1].tobytes())
                if boundary is None or cut < boundary:
                    boundary = cut
            parts.append(win)
        if note is not None:
            note["sources"] = len(parts)
            note["window_rows"] = sum(p.capacity for p in parts)
        if not parts:
            return None, None
        if len(parts) == 1:
            return parts[0], boundary
        total = sum(p.capacity for p in parts)
        # the tiles' order is planned on the host: a device merge is one
        # sort shape for every count and size of sources (the tsdb's prune
        # scans its whole span: a 128-row tile and a 16,384-row run made a
        # sort of 32,768 rows that the chip's compiler had not finished
        # after 16 minutes, under the store's mutex: my chip run, PR 41)
        return (mvcc.merge_blocks_host(tuple(parts),
                                       cap=_pad(total, _CAND_ALIGN)),
                boundary)

    # -- per-run read metadata (blockcache.RunMeta: seek keys + bloom) ------

    def _meta_for(self, run: mvcc.KVBlock) -> blockcache.RunMeta:
        """Read-path metadata for a run. Built eagerly by _register_run at
        run construction (ingest/flush/compaction output); built lazily
        here for the rewrite paths (intent resolution, span clears) whose
        per-txn run churn would make eager bloom rebuilds a commit tax.
        Stale entries prune as the run set turns over — dropping a meta
        also invalidates its block-cache entries, or dead runs would pin
        cache bytes forever."""
        c = self._run_meta.get(id(run))
        if c is None or c[0] is not run:
            kb = np.asarray(run.key)
            void = np.ascontiguousarray(kb).view(
                f"V{kb.shape[1]}").reshape(-1)
            n_live = int(np.asarray(jnp.sum(run.mask, dtype=jnp.int32)))
            if len(self._run_meta) > 4 * max(1, len(self.runs)):
                live_ids = {id(r) for r in self.runs}
                cache = blockcache.node_cache()
                for k in [k for k in self._run_meta if k not in live_ids]:
                    cache.invalidate_run(self._run_meta[k][1].token)
                    del self._run_meta[k]
            c = self._run_meta[id(run)] = (
                run, blockcache.build_meta(void, n_live))
        return c[1]

    def _register_run(self, run: mvcc.KVBlock) -> None:
        """Eager metadata build for a newly constructed run — run
        construction is where the reference builds its table filters, so
        the first point read never pays the build."""
        self._meta_for(run).bloom()

    def _drop_run_meta(self, run: mvcc.KVBlock) -> None:
        c = self._run_meta.pop(id(run), None)
        if c is not None:
            blockcache.node_cache().invalidate_run(c[1].token)

    def _bloom_might_contain(self, meta: blockcache.RunMeta,
                             key: bytes) -> bool:
        """Per-run split-block bloom probe (pebble's table-filter role).
        False is a CRC-backed proof of absence; a filterless or corrupt
        run always answers maybe (and so does a lazy filter another
        reader is still building)."""
        bloom = meta.bloom()
        if bloom is None:
            return True
        kb = np.zeros((1, self.key_width), np.uint8)  # crlint: allow-mem-accounting(single-key probe buffer, key_width bytes)
        raw = np.frombuffer(key, np.uint8)
        kb[0, :len(raw)] = raw
        h1, h2 = blockcache.bloom_hashes(
            np.ascontiguousarray(kb).view(f"V{self.key_width}").reshape(-1)
        )
        return bloom.might_contain(int(h1[0]), int(h2[0]))

    def _view_for(self, sw, ew) -> mvcc.KVBlock | None:
        if sw is None and ew is None:
            return self._merged_view()
        return self._bounded_view(self._snapshot(), sw, ew)[0]

    # -- reads --------------------------------------------------------------

    @_locked
    def scan(
        self,
        start: bytes | str | None,
        end: bytes | str | None,
        ts: int,
        txn: int = 0,
        max_keys: int | None = None,
    ) -> list[tuple[bytes, bytes]]:
        """[start, end) snapshot scan at `ts` -> [(key, value)] host pairs.

        With max_keys, candidate gathering is CLAMPED per sorted run
        (pebbleMVCCScanner pagination): rows at/past the smallest
        truncation boundary are withheld (their version sets may be
        incomplete) and the clamp grows geometrically until max_keys
        complete rows emerge."""
        from ..utils import metric

        metric.ENGINE_SCANS.inc()
        sw = K.encode_bound(start, self.key_width)
        ew = K.encode_bound(end, self.key_width)
        limit = None
        if max_keys is not None and (sw is not None or ew is not None):
            limit = max(16, 4 * max_keys)
        while True:
            if limit is not None:
                view, boundary = self._bounded_view(self._snapshot(), sw,
                                                    ew, limit)
            else:
                view, boundary = self._view_for(sw, ew), None
            if view is None:
                return []
            sel, conflict = mvcc.mvcc_scan_filter(
                view, jnp.int64(ts), jnp.int64(txn),
                None if sw is None else jnp.asarray(sw),
                None if ew is None else jnp.asarray(ew),
            )
            conflict_np = np.asarray(conflict)
            if conflict_np.any():
                idx = np.nonzero(conflict_np)[0]
                ck = K.decode_keys(np.asarray(view.key)[idx])
                ct = [int(t) for t in np.asarray(view.txn)[idx]]
                raise WriteIntentError(ck, ct)
            sel_np = np.asarray(sel)
            idx = np.nonzero(sel_np)[0]
            if boundary is not None:
                # emit only rows strictly below the truncation point
                keys_np = np.asarray(view.key)[idx]
                # crlint: allow-mem-accounting(one bool per candidate row of a truncated scan batch — bounded by the scan limit)
                below = np.array(
                    [bytes(k) < boundary for k in keys_np], dtype=bool
                )
                kept = idx[below]
                if max_keys is not None and len(kept) < max_keys:
                    # truncation occurred and complete rows don't cover the
                    # limit: more keys may hide past the boundary
                    limit *= 4
                    continue
                idx = kept
            if max_keys is not None:
                idx = idx[:max_keys]
            ks = K.decode_keys(np.asarray(view.key)[idx])
            vals = np.asarray(view.value)[idx]
            vls = np.asarray(view.vlen)[idx]
            return [(k, self._resolve_value(v, int(n)))
                    for k, v, n in zip(ks, vals, vls)]

    @_locked
    def scan_batch(
        self,
        starts: list[bytes | str],
        ts: int,
        txn: int = 0,
        max_keys: int = 64,
    ) -> list[list[tuple[bytes, bytes]]]:
        """B forward scans of up to max_keys rows each, in ONE device pass
        over the resident merged view — the kv Streamer analog (reference:
        pkg/kv/kvclient/kvstreamer; pebbleMVCCScanner per-scan semantics
        preserved). A serial scan() pays a dispatch+sync round trip per op
        (its cost is not measured on an attached chip); batching B scans
        amortizes that to one, so a scan-heavy workload (YCSB-E) is not
        held to one op per round trip."""
        from ..utils import metric

        if not starts:
            return []
        metric.ENGINE_SCANS.inc(len(starts))
        # sorted sources, merged lazily per WINDOW (mergingIter shape): the
        # per-batch cost scales with the windows, never with the store — no
        # store-wide overlay re-sort when the memtable changed
        sources = []
        mb = self._mem_block()
        if mb is not None:
            sources.append(mb)
        sources.extend(self.runs)
        if not sources:
            return [[] for _ in starts]
        enc = [
            (s.encode() if isinstance(s, str) else bytes(s)) for s in starts
        ]
        starts_words = jnp.asarray(K.encode_bounds(enc, self.key_width))
        B = len(enc)
        max_cap = max(s.capacity for s in sources)
        # sticky converged window (keyed by max_keys): version-dense key
        # ranges force window growth past the initial 2*max_keys, and
        # re-learning the growth by retrying EVERY batch would pay the
        # whole ladder of extra device passes per call. 2x (not 4x): the
        # common case is ~1 visible version per key, and halving the
        # window halves every per-batch gather/merge/filter pass; dense
        # histories converge via the sticky growth after one retry
        window = self._scan_windows.get(
            max_keys, _pad(max(16, 2 * max_keys), _CAND_ALIGN)
        )
        while True:
            win, sel, conflict, complete, truncated = (
                mvcc.multi_scan_sources(
                    tuple(sources), starts_words, jnp.int64(ts),
                    jnp.int64(txn), window=window,
                )
            )
            # device-side: compact selected rows to [B, max_keys] BEFORE
            # materializing — the host sees B*max_keys rows, never the
            # full windows
            keys_d, vals_d, vlen_d, counts_d = mvcc._emit_stage(
                win, sel & complete, B, max_keys
            )
            if bool(np.asarray(jnp.any(conflict))):
                cidx = np.nonzero(np.asarray(conflict))[0]
                raise WriteIntentError(
                    K.decode_keys(np.asarray(win.key)[cidx]),
                    [int(t) for t in np.asarray(win.txn)[cidx]],
                )
            counts = np.asarray(counts_d)
            # a truncated window with a short result must page forward even
            # if nothing in it was selected (e.g. a run of tombstones)
            truncated_np = np.asarray(truncated)
            if (truncated_np & (counts < max_keys)).any() and (
                window < max_cap
            ):
                window = min(_pad(window * 4, _CAND_ALIGN), _pad(max_cap))
                self._scan_windows[max_keys] = window
                continue
            keys_np = np.asarray(keys_d)
            vals_np = np.asarray(vals_d)
            vlen_np = np.asarray(vlen_d)
            out: list[list[tuple[bytes, bytes]]] = []
            for b in range(B):
                k = min(int(counts[b]), max_keys)
                ks = K.decode_keys(keys_np[b][:k])
                out.append([
                    (key, self._resolve_value(v, int(n)))
                    for key, v, n in zip(ks, vals_np[b][:k], vlen_np[b][:k])
                ])
            return out

    def get(self, key: bytes | str, ts: int, txn: int = 0) -> bytes | None:
        """Point read. The full consult order is bloom -> block cache ->
        device slice: each surviving run is seeked to a small candidate
        window (O(window), not O(run)) and the window is served from the
        node block cache when hot — a point read on a cached key set
        dispatches no device gather at all. A window cut inside the
        key's version set (boundary) grows geometrically, the pagination
        discipline scan() uses.

        The mutex is held for the snapshot only: the read sees the store
        as of that instant (its linearization point; ``ts`` was taken
        before it, and a write that lands after it either commits above
        ``ts`` or is an intent the read must not see), and the searches,
        launches and the readback run on immutable blocks with the mutex
        released. A caller that holds the mutex itself keeps it."""
        from ..utils import metric, tracing

        b = key.encode() if isinstance(key, str) else bytes(key)
        sw = K.encode_bound(b, self.key_width)
        ew = K.bound_next(sw)
        with tracing.leaf_span("storage/engine.get") as span:
            with self.mu:
                t0 = time.perf_counter()
                snap = self._snapshot(point=b)
                if span is not None:
                    span.tags["held_ms"] = 1e3 * (time.perf_counter() - t0)
            metric.ENGINE_SNAPSHOT_READS.inc()
            limit = 8
            while True:
                view, boundary = self._bounded_view(
                    snap, sw, ew, limit_rows=limit, point=b)
                if boundary is None:
                    break
                # some run's window was cut inside [key, next(key)) — a
                # version of this key may be missing; widen and retry
                limit *= 4
            if view is None:
                return None
            # host values go in as they are and the jitted call moves
            # them: an eager jnp.asarray / jnp.int64 is a dispatch of its
            # own through the interpreter
            sel, conflict = mvcc.mvcc_scan_filter(
                view, np.int64(ts), np.int64(txn), sw, ew)
            # one wait for the device, not one an array
            conflict, sel, vlen, value = jax.device_get(
                (conflict, sel, view.vlen, view.value))
        if conflict.any():
            idx = np.nonzero(conflict)[0]
            raise WriteIntentError(
                K.decode_keys(np.asarray(view.key)[idx]),
                [int(t) for t in np.asarray(view.txn)[idx]],
            )
        idx = np.nonzero(sel)[0]
        if not len(idx):
            return None
        i = idx[0]
        # _blob is append-only and an overflow pointer's bytes are there
        # before its row is visible: safe to follow without the mutex
        return self._resolve_value(value[i], int(vlen[i]))

    def range_read(self, start: bytes, end: bytes, ts: int, decode,
                   txn: int = 0, limit_rows: int | None = None) -> RangeRead:
        """[start, end) snapshot read at `ts` that stays on the device: the
        span's windows are sought and merged (`_bounded_view`, both bounds)
        and ``decode(view, ts, txn, start_words, end_words) -> (out, any
        conflict, rows selected)`` runs over the candidate view: the
        caller's program, which applies `mvcc_scan_filter` and unpacks what
        it wants of the rows (KVTable's fused filter-and-decode). Nothing of the rows comes to the
        host: the one sync is the readback of the conflict flag and the
        row count. A foreign intent in the span raises WriteIntentError.

        ``limit_rows`` pages the read: each source gives at most that many
        rows of the span, and where one was cut the result's `boundary` is
        the key the next page starts at (rows at or past it are left out
        of this one); it grows by itself where a page's first key alone
        has more versions than the limit.

        The mutex is held for the snapshot only, as in `get`; a span no
        key of the memtable can fall in leaves the memtable out."""
        from ..utils import tracing

        sw = K.encode_bound(start, self.key_width)
        ew = K.encode_bound(end, self.key_width)
        first = bytes(start).ljust(self.key_width, b"\x00")
        with tracing.leaf_span("storage/engine.range_read") as span:
            with self.mu:
                t0 = time.perf_counter()
                snap = self._snapshot(span=(start, end))
                if span is not None:
                    span.tags["held_ms"] = 1e3 * (time.perf_counter() - t0)
            note: dict = {}
            limit = limit_rows
            while True:
                view, boundary = self._bounded_view(
                    snap, sw, ew, limit_rows=limit, note=note)
                if boundary is None or boundary > first:
                    break
                limit *= 4  # the page would end inside its first key
            if span is not None:
                span.tags["window_rows"] = note["window_rows"]
                span.tags["sources"] = note["sources"]
            if view is None:
                return RangeRead(None, 0, None, 0, 0)
            if boundary is not None:
                ew = K.encode_bound(boundary, self.key_width)
            out, conflict, rows = decode(
                view, np.int64(ts), np.int64(txn), sw, ew)
            conflict, rows = jax.device_get((conflict, rows))
        if conflict:
            _sel, hit = mvcc.mvcc_scan_filter(
                view, np.int64(ts), np.int64(txn), sw, ew)
            idx = np.nonzero(np.asarray(hit))[0]
            raise WriteIntentError(
                K.decode_keys(np.asarray(view.key)[idx]),
                [int(t) for t in np.asarray(view.txn)[idx]],
            )
        return RangeRead(out, int(rows), boundary, note["window_rows"],
                         note["sources"])

    # -- intents ------------------------------------------------------------

    @_locked
    def resolve_intents(self, txn: int, commit_ts: int, commit: bool):
        """Commit or abort all of txn's intents, where they are: in the
        memtable in place (ts := commit_ts, txn := 0; an abort drops the
        rows), and in a run only if that run holds an intent of this
        transaction, which happens when a flush came between the write
        and the commit (``_run_intents``). No flush, and no run is
        rewritten or re-sorted, in the common case.
        WAL-logged: without a resolution record, crash replay would
        resurrect an acknowledged commit's writes as unresolved intents."""
        from ..utils import metric

        txn, commit_ts = int(txn), int(commit_ts)
        if self._wal is not None and not self._replaying:
            self._wal_record(_REC_RESOLVE, b"", b"", commit_ts, 0, txn,
                             commit)
        if commit:
            metric.ENGINE_COMMITS.inc()
            for k, t in self._locks.items():
                if t == txn:
                    self._newest_committed.put(k, commit_ts)
        self._locks = {k: t for k, t in self._locks.items() if t != txn}
        touched = self.mem.resolve(txn, commit_ts, commit)
        for i, r in enumerate(self.runs):
            held = self._run_intents.get(id(r))
            if held is None or held[0] is not r or txn not in held[1]:
                continue
            mvcc.KERNEL_CALLS["resolve.sort_block"] += 1
            metric.ENGINE_RESOLVE_RUN_SORTS.inc()
            new = mvcc.sort_block_host(mvcc.resolve_intents(
                r, jnp.int64(txn), jnp.int64(commit_ts), commit))
            self.runs[i] = new
            # the run object was replaced: retire its read metadata (and
            # block-cache entries); the rebuild stays lazy, see _meta_for
            self._drop_run_meta(r)
            self._note_run_intents(new, self._take_run_intents(r) - {txn})
            self._runs_gen += 1
            touched += 1
        if touched:
            self._gen += 1

    @_locked
    def has_committed_writes_in(
        self, start: bytes | None, end: bytes | None, ts_lo: int, ts_hi: int,
        point: bool = False,
    ) -> bool:
        """Any committed version in (ts_lo, ts_hi] within [start, end)?
        The read-refresh check (kvcoord txn_interceptor_span_refresher
        semantics). ``point=True`` checks exactly the key `start`."""
        sw = K.encode_bound(start, self.key_width)
        ew = K.bound_next(sw) if point else K.encode_bound(end, self.key_width)
        view = self._view_for(sw, ew)
        if view is None:
            return False
        words = K.key_words(view.key)
        in_range = view.mask & K.words_in_range(
            words,
            None if sw is None else jnp.asarray(sw),
            None if ew is None else jnp.asarray(ew),
        )
        hit = (
            in_range & (view.txn == 0)
            & (view.ts > ts_lo) & (view.ts <= ts_hi)
        )
        return bool(np.asarray(jnp.any(hit)))

    @_locked
    def other_intent(self, key: bytes, txn: int) -> int | None:
        """Txn id of another transaction's intent on `key`, if any —
        the lock-table point lookup the write path does before laying an
        intent (concurrency_manager.SequenceReq's lock check). A pure host
        dict lookup: no device work on the write hot path."""
        b = key.encode() if isinstance(key, str) else bytes(key)
        holder = self._locks.get(b)
        return holder if holder is not None and holder != txn else None

    @_locked
    def newest_committed_ts(self, key: bytes) -> int:
        """Timestamp of the newest committed version of `key` (0 if none) —
        powers the WriteTooOld check. O(1) HOST lookup: the engine indexes
        newest-committed timestamps as writes land (like the reference's
        timestamp cache, kvserver/tscache) — a device point-read per write
        would re-upload the memtable per call and made ingest quadratic.
        open_checkpoint rebuilds the index per key from the restored runs."""
        b = key.encode() if isinstance(key, str) else bytes(key)
        return self._newest_committed.get(b, 0)

    @_locked
    def intent_keys(self, txn: int) -> list[bytes]:
        return sorted(k for k, t in self._locks.items() if t == txn)

    # -- range relocation (snapshot-rebalance primitives) -------------------

    @_locked
    def span_stats(self, start: bytes | None, end: bytes | None) -> dict:
        """Authoritative size accounting for [start, end) — the SpanStats
        RPC role feeding the split/merge size decision. Counts every live
        version's logical footprint (key width + stored value length), so
        MVCC history weighs in exactly as it does on disk."""
        view = self._merged_view()
        if view is None:
            return {"versions": 0, "logical_bytes": 0}
        sw = K.encode_bound(start, self.key_width)
        ew = K.encode_bound(end, self.key_width)
        m, _ = _range_mask(view,
                           None if sw is None else jnp.asarray(sw),
                           None if ew is None else jnp.asarray(ew))
        mask = np.asarray(m)
        n = int(mask.sum())
        vbytes = int(np.asarray(view.vlen)[mask].sum()) if n else 0
        return {"versions": n, "logical_bytes": n * self.key_width + vbytes}

    def span_versions_estimate(self, start: bytes, end: bytes) -> int:
        """Versions in [start, end), counted on the host: two binary
        searches over each run's seek keys and a walk of the memtable's
        key list. An upper bound on the span's live keys (old versions,
        tombstones and intents count too) that costs no device work and
        merges nothing: what planning asks before every statement over a
        KV-backed table (KVTable.estimated_rows). It reads the published
        run snapshot and its memtable's key list with no mutex at all (the
        mutex only to build a snapshot where the run set has just changed):
        an estimate needs no instant, a key appended during the walk is one
        more or one less, and a bind that queued at the mutex was one of
        the two turns a statement stood in line for."""
        snap = self._published_snapshot()
        if snap is None:
            with self.mu:
                snap = self._run_snapshot()
        n = sum(1 for k in snap.memtable.keys if start <= k < end)
        first, last = (b.ljust(self.key_width, b"\x00")
                       for b in (start, end))
        for m in snap.metas:
            lo, hi = _seek(m.void_keys, m.n_live, first, last)
            n += hi - lo
        return n

    @_locked
    def export_span(self, start: bytes | None, end: bytes | None) -> dict:
        """Every VERSION in [start, end) — committed history, tombstones
        and intents included — as host arrays (the raft-snapshot payload
        role for kv/dist.py's move_range). Keys keep engine width."""
        view = self._merged_view()
        empty = {
            "key": np.zeros((0, self.key_width), np.uint8),
            "ts": np.zeros((0,), np.int64), "seq": np.zeros((0,), np.int64),
            "txn": np.zeros((0,), np.int64),
            "tomb": np.zeros((0,), np.bool_),
            "value": np.zeros((0, self.val_width), np.uint8),
            "vlen": np.zeros((0,), np.int32),
            "blob": np.zeros((0,), np.uint8),
        }
        if view is None:
            return empty
        sw = K.encode_bound(start, self.key_width)
        ew = K.encode_bound(end, self.key_width)
        m, _ = _range_mask(view,
                           None if sw is None else jnp.asarray(sw),
                           None if ew is None else jnp.asarray(ew))
        idx = np.nonzero(np.asarray(m))[0]
        if not len(idx):
            return empty
        vals_np = np.asarray(view.value)[idx]
        vlen_np = np.asarray(view.vlen)[idx]
        out = {
            "key": np.asarray(view.key)[idx],
            "ts": np.asarray(view.ts)[idx],
            "seq": np.asarray(view.seq)[idx],
            "txn": np.asarray(view.txn)[idx],
            "tomb": np.asarray(view.tomb)[idx],
            "value": vals_np,
            "vlen": vlen_np,
            # overflow payloads materialize into the export in row order
            # (this heap's offsets are meaningless to the importing
            # engine); import_rows re-homes them into its own heap by
            # walking the same order
            "blob": np.frombuffer(b"".join(
                self._resolve_value(vals_np[i], int(vlen_np[i]))
                for i in np.nonzero(vlen_np > self.val_width)[0]
            ), dtype=np.uint8),
        }
        from ..flow import memory as flowmem

        # the snapshot payload lives until the transport drops it —
        # charge its residency for that lifetime (anchored on the key
        # array: dicts take no weakrefs, and the arrays die together)
        flowmem.charge_object(
            "storage/export-staging", out["key"],
            int(sum(a.nbytes for a in out.values())))
        return out

    @_locked
    def import_rows(self, rows: dict) -> None:
        """Land exported versions as one sorted run (the snapshot-apply
        role). Rows keep their source-engine ts/seq/txn fields verbatim;
        this engine's sequence high-water mark is raised past the largest
        imported seq so future local writes always win same-(key, ts)
        ties. Committed rows refresh the tscache, intents restore their
        locks. WAL-logged via a side file (the ingest durability shape):
        acknowledged imports survive process crashes."""
        n = len(rows["ts"])
        if n == 0:
            return
        if rows["key"].shape[1] != self.key_width:
            raise ValueError("imported keys do not match engine key width")
        src_w = rows["value"].shape[1]
        if src_w > self.val_width:
            raise ValueError("imported values wider than engine val width")
        cap = _pad(n)

        def padrow(a, fill=0):
            out = np.full((cap,) + a.shape[1:], fill, a.dtype)
            out[:n] = a
            return out

        vb = np.zeros((cap, self.val_width), np.uint8)
        vb[:n, :src_w] = rows["value"]
        # re-home exported overflow payloads (vlen > SOURCE inline width)
        # — the exported pointer slots are meaningless here. A payload
        # that fits THIS engine's inline width lands inline (a narrower
        # source's overflow can be a wider target's inline row; storing a
        # pointer there would be read back as inline bytes); bigger ones
        # go to this engine's heap. The side file below persists the
        # original rows + blob, so crash replay re-runs this re-homing.
        vlen_in = np.asarray(rows["vlen"], np.int64)
        if (vlen_in > src_w).any():
            blob_b = bytes(np.asarray(rows["blob"], np.uint8).tobytes())
            off = 0
            for i in np.nonzero(vlen_in > src_w)[0]:
                ln = int(vlen_in[i])
                payload = blob_b[off:off + ln]
                off += ln
                vb[i] = 0
                if ln <= self.val_width:
                    vb[i, :ln] = np.frombuffer(payload, np.uint8)
                else:
                    ptr = len(self._blob)
                    self._blob += payload
                    vb[i, :8] = np.frombuffer(ptr.to_bytes(8, "little"),
                                              np.uint8)
        seq = rows["seq"].astype(np.int64)
        self._seq = max(self._seq, int(seq.max()))
        if self._wal is not None and not self._replaying:
            # durable-before-visible, the ingest() discipline: side file
            # first (fsynced under wal_fsync), then the WAL record naming
            # it. The marker seq is allocated ABOVE the current high-water
            # mark (and raises it) so the replay gate `seq > self._seq` is
            # strictly satisfied when earlier records have been re-applied.
            marker = self._seq + 1
            self._seq = marker
            side = f"{self.wal_path}.import{int(marker):012d}.npz"
            with open(side, "wb") as f:
                np.savez(f, key=rows["key"], ts=rows["ts"], seq=seq,
                         txn=rows["txn"], tomb=rows["tomb"],
                         value=rows["value"], vlen=rows["vlen"],
                         blob=np.asarray(rows.get(
                             "blob", np.zeros(0, np.uint8)), np.uint8))
                f.flush()
                if self.wal_fsync:
                    os.fsync(f.fileno())
            if self.wal_fsync:
                dfd = os.open(os.path.dirname(side) or ".", os.O_RDONLY)
                try:
                    os.fsync(dfd)
                finally:
                    os.close(dfd)
            self._wal_record(_REC_IMPORT, os.path.basename(side).encode(),
                             b"", 0, int(marker), 0, False)
        blk = mvcc.KVBlock(
            key=jnp.asarray(padrow(rows["key"])),
            ts=jnp.asarray(padrow(rows["ts"])),
            seq=jnp.asarray(padrow(seq)),
            txn=jnp.asarray(padrow(rows["txn"])),
            tomb=jnp.asarray(padrow(rows["tomb"])),
            value=jnp.asarray(vb),
            vlen=jnp.asarray(padrow(rows["vlen"])),
            mask=jnp.asarray(np.arange(cap) < n),
        )
        run = mvcc.sort_block_host(blk)
        _charge_run(run)
        self.runs.insert(0, run)
        self._runs_changed()
        self.stats.runs = len(self.runs)
        self._register_run(run)
        self._note_run_intents(run, np.unique(rows["txn"]))
        committed = rows["txn"] == 0
        if committed.any():
            self._newest_committed.bulk(
                rows["key"][committed], rows["ts"][committed]
            )
        for i in np.nonzero(~committed)[0]:
            k = bytes(rows["key"][i]).rstrip(b"\x00")
            self._locks[k] = int(rows["txn"][i])
        self._maybe_compact()

    @_locked
    def clear_span(self, start: bytes | None, end: bytes | None) -> None:
        """Physically drop every version in [start, end) from the memtable
        and all runs — replica removal after a range moves away. NOT an
        MVCC delete: no tombstones, no history retained. WAL-logged (clear
        records replay in log order, like intent resolutions) so a crash
        cannot resurrect a departed range's data."""
        if self._wal is not None and not self._replaying:
            self._wal_record(_REC_CLEAR, start or b"", end or b"", 0, 0, 0,
                             end is not None)
        sw = K.encode_bound(start, self.key_width)
        ew = K.encode_bound(end, self.key_width)
        self.flush_mem_only()
        swj = None if sw is None else jnp.asarray(sw)
        ewj = None if ew is None else jnp.asarray(ew)
        new_runs = []
        for r in self.runs:
            m, cnt = _range_mask(r, swj, ewj)
            if int(np.asarray(cnt)) == 0:
                new_runs.append(r)
                continue
            # this run is rewritten or dropped: retire its read metadata
            # and block-cache entries (untouched runs keep theirs)
            self._drop_run_meta(r)
            held = self._take_run_intents(r)
            keep = r.mask & ~m
            kept = int(np.asarray(jnp.sum(keep)))
            if kept == 0:
                continue
            r2 = mvcc.KVBlock(
                key=r.key, ts=r.ts, seq=r.seq, txn=r.txn, tomb=r.tomb,
                value=r.value, vlen=r.vlen, mask=keep,
            )
            new_runs.append(mvcc.sort_block_host(r2, _pad))
            # a superset: intents inside the cleared span went with it
            self._note_run_intents(new_runs[-1], held)
        self.runs = new_runs
        # drop lock-table entries for the departed span
        def _in(k: bytes) -> bool:
            if start is not None and k < start:
                return False
            return end is None or k < end
        self._locks = {k: t for k, t in self._locks.items() if not _in(k)}
        self._runs_changed()
        self.stats.runs = len(self.runs)

    # -- stats / checkpoint -------------------------------------------------

    @_locked
    def compute_stats(self) -> MVCCStats:
        view = self._merged_view()
        s = self.stats
        if view is None:
            s.live_count = s.key_count = s.val_count = s.intent_count = 0
            return s
        mask = np.asarray(view.mask)
        s.val_count = int(mask.sum())
        s.intent_count = int((mask & (np.asarray(view.txn) != 0)).sum())
        words = np.asarray(K.key_words(view.key))[mask]
        s.key_count = len(np.unique(words, axis=0)) if len(words) else 0
        sel, _ = mvcc.mvcc_scan_filter(
            view, jnp.int64(np.iinfo(np.int64).max), jnp.int64(0)
        )
        s.live_count = int(np.asarray(sel).sum())
        return s

    @_locked
    def checkpoint(self, path: str):
        """Persist the engine state (CreateCheckpoint analog); the WAL
        truncates afterwards — everything below the checkpoint is durable
        in the .npz runs."""
        self.flush_mem_only()
        os.makedirs(path, exist_ok=True)
        for i, r in enumerate(self.runs):
            with open(os.path.join(path, f"run{i:04d}.npz"), "wb") as f:
                np.savez(
                    f,
                    key=np.asarray(r.key), ts=np.asarray(r.ts),
                    seq=np.asarray(r.seq),
                    txn=np.asarray(r.txn), tomb=np.asarray(r.tomb),
                    value=np.asarray(r.value), vlen=np.asarray(r.vlen),
                    mask=np.asarray(r.mask),
                )
                f.flush()
                os.fsync(f.fileno())
        if self._blob:
            # runs reference the overflow heap by offset; a checkpoint
            # without it would dangle every var-width value
            with open(os.path.join(path, "blob.bin"), "wb") as f:
                f.write(bytes(self._blob))
                f.flush()
                os.fsync(f.fileno())
        if self._replay_cache:
            # checkpoint truncates the WAL, which held the only durable
            # copy of the dedup entries — persist them alongside the runs
            # or a post-restore retry would double-apply
            with open(os.path.join(path, "replay_cache.json"), "w") as f:
                json.dump({cid: [s, r] for cid, (s, r)
                           in self._replay_cache.items()}, f)
                f.flush()
                os.fsync(f.fileno())
        with open(os.path.join(path, "MANIFEST"), "w") as f:
            f.write(f"{len(self.runs)} {self.key_width} {self.val_width}\n")
            f.flush()
            os.fsync(f.fileno())
        # the checkpoint must be durable BEFORE the WAL truncates, or a
        # crash in between loses acknowledged writes
        dfd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        self._truncate_wal()
        if self.wal_path is not None:
            # ingest/import side-files were only reachable through the
            # truncated WAL; their rows are in the checkpoint runs now
            import glob

            for pat in ("ingest", "import"):
                for side in glob.glob(f"{self.wal_path}.{pat}*.npz"):
                    try:
                        os.unlink(side)
                    except OSError:  # pragma: no cover - best-effort
                        pass

    @classmethod
    def open_checkpoint(cls, path: str, **kwargs) -> "Engine":
        with open(os.path.join(path, "MANIFEST")) as f:
            nruns, kw, vw = (int(x) for x in f.read().split())
        wal_path = kwargs.pop("wal_path", None)
        eng = cls(key_width=kw, val_width=vw, **kwargs)
        assert eng._wal is None, "pass wal_path to open_checkpoint, not cls"
        blob_path = os.path.join(path, "blob.bin")
        if os.path.exists(blob_path):
            with open(blob_path, "rb") as f:
                eng._blob = bytearray(f.read())
        rc_path = os.path.join(path, "replay_cache.json")
        if os.path.exists(rc_path):
            with open(rc_path) as f:
                eng._replay_cache = {
                    cid: (int(s), r) for cid, (s, r) in json.load(f).items()}
        for i in range(nruns):
            z = np.load(os.path.join(path, f"run{i:04d}.npz"))
            eng.runs.append(
                mvcc.KVBlock(
                    key=jnp.asarray(z["key"]), ts=jnp.asarray(z["ts"]),
                    seq=jnp.asarray(z["seq"]),
                    txn=jnp.asarray(z["txn"]), tomb=jnp.asarray(z["tomb"]),
                    value=jnp.asarray(z["value"]), vlen=jnp.asarray(z["vlen"]),
                    mask=jnp.asarray(z["mask"]),
                )
            )
        eng.stats.runs = len(eng.runs)
        eng._runs_changed()
        # restore the write-sequence high-water mark so post-restore writes
        # keep winning same-(key, ts) tie-breaks over persisted rows, and
        # rebuild the host lock table from persisted intents
        for r in eng.runs:
            m = np.asarray(r.mask)
            if m.any():
                eng._seq = max(eng._seq, int(np.asarray(r.seq)[m].max()))
                cm = m & (np.asarray(r.txn) == 0)
                if cm.any():
                    # rebuild the per-key newest-committed index exactly —
                    # a global floor would block writers on EVERY key until
                    # the clock passed the restored max timestamp
                    idx = np.nonzero(cm)[0]
                    eng._newest_committed.bulk(
                        np.asarray(r.key)[idx], np.asarray(r.ts)[idx]
                    )
            im = m & (np.asarray(r.txn) != 0)
            if im.any():
                ks = K.decode_keys(np.asarray(r.key)[np.nonzero(im)[0]])
                ts = np.asarray(r.txn)[np.nonzero(im)[0]]
                for kk, tt in zip(ks, ts):
                    eng._locks[kk] = int(tt)
                eng._note_run_intents(r, np.unique(ts))
        if wal_path is not None:
            # replay records that postdate the checkpoint, then arm the WAL
            eng._arm_wal(wal_path)
        return eng
