"""Block cache and split-block bloom filters — the Pebble read-path stack.

Reference: CockroachDB's storage engine puts two structures between the
iterator stack and disk (pebble/sstable): per-SST **bloom filters** so point
lookups skip tables that can't contain the key, and a node-wide **block
cache** so hot decoded blocks aren't re-read and re-decoded per lookup.
Here the analogues sit between `lsm.Engine`'s read paths and kernel
dispatch: a run that fails its bloom probe costs ~nothing instead of a
`pallas_scan`, and a seek window served from cache skips the
`_slice_window` device slice entirely.

Three pieces:

- ``SplitBloom``: split-block bloom filter in the RocksDB full-filter
  shape — every key maps to ONE 512-bit block, probes stay inside it
  (cache-line locality in the reference; here it keeps the probe loop a
  handful of scalar reads). A CRC taken at build time is verified lazily
  on the FIRST negative answer: a corrupt filter disables itself and
  answers "maybe" forever after, so false negatives are structurally
  impossible even under bit corruption (chaos site
  ``storage.bloom.build``).
- ``RunMeta``: per-run read-path metadata (sorted key column for seek
  binary search, live-row count, bloom), carrying a process-unique
  ``token`` that namespaces the run's block-cache entries — unlike
  ``id(run)``, tokens are never reused, so a dead run's cached windows
  can never be served for a new run that landed at the same address.
- ``BlockCache``: node-wide clock (second-chance) cache of decoded
  ``KVBlock`` windows keyed ``(run token, window position, window
  size)``. Runs are immutable, so entries never go stale — they are only
  *invalidated* when their run dies (compaction, intent resolution) or
  *evicted* by the clock sweep under budget pressure. The budget is
  ``storage.block_cache.size_bytes``, accounted as a ``cache``-level
  child of the root memory monitor tree (flow/memory.py) so cache
  residency and query scratch compete for the same node budget.
"""

from __future__ import annotations

import itertools
import threading
import zlib
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from ..utils import faults, locks, metric

BLOOM_BITS_PER_KEY = 10
BLOOM_K = 6  # near-optimal probe count at 10 bits/key (ln2 * 10 ≈ 6.9)
_BLOCK_BITS = 512  # one cache line in the reference full-filter layout

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)
_H2_OFFSET = np.uint64(0x9E3779B97F4A7C15)
_H2_MULT = np.uint64(0xC2B2AE3D27D4EB4F)
_MASK64 = 0xFFFFFFFFFFFFFFFF


def bloom_hashes(void_keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized double hash over a void-dtype key column: FNV-1a as h1
    plus an independent mix as h2 (forced odd so the probe sequence
    ``h1 + i*h2`` walks every residue). One pass per key byte, all keys
    at once — building a filter for a whole run is a few numpy sweeps."""
    raw = void_keys.view(np.uint8).reshape(len(void_keys), -1)
    h1 = np.full(len(void_keys), _FNV_OFFSET, dtype=np.uint64)
    h2 = np.full(len(void_keys), _H2_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for j in range(raw.shape[1]):
            col = raw[:, j].astype(np.uint64)
            h1 = (h1 ^ col) * _FNV_PRIME
            h2 = (h2 + col) * _H2_MULT ^ (h2 >> np.uint64(29))
    return h1, h2 | np.uint64(1)


class SplitBloom:
    """Split-block bloom filter over one run's live keys.

    The block index comes from the HIGH half of h1 and the probe bits
    from the low halves of h1/h2, so block choice and in-block probes are
    decorrelated — reusing the same bits for both collapses the filter's
    effective k. At 10 bits/key the theoretical false-positive rate is
    ~1.2%; the property test holds the line at <3%.
    """

    __slots__ = ("bits", "nblocks", "crc", "disabled", "_verified",
                 "__weakref__")

    def __init__(self, bits: np.ndarray, nblocks: int, crc: int):
        self.bits = bits
        self.nblocks = nblocks
        self.crc = crc
        self.disabled = False
        self._verified = False

    @classmethod
    def build(cls, void_keys: np.ndarray) -> "SplitBloom":
        faults.fire("storage.bloom.build")
        n = len(void_keys)
        nblocks = max(1, -(-n * BLOOM_BITS_PER_KEY // _BLOCK_BITS))
        bits = np.zeros(nblocks * _BLOCK_BITS, dtype=bool)
        if n:
            h1, h2 = bloom_hashes(void_keys)
            base = ((h1 >> np.uint64(32)) % np.uint64(nblocks)).astype(
                np.int64) * _BLOCK_BITS
            with np.errstate(over="ignore"):
                for i in range(BLOOM_K):
                    bit = ((h1 + np.uint64(i) * h2)
                           % np.uint64(_BLOCK_BITS)).astype(np.int64)
                    bits[base + bit] = True
        crc = zlib.crc32(np.packbits(bits).tobytes())
        filt = cls(bits, nblocks, crc)
        from ..flow import memory as flowmem

        # filter residency (~BLOOM_BITS_PER_KEY bytes/key as host bools)
        # charges the node budget until compaction drops the run's meta
        flowmem.charge_object("storage/bloom-residency", filt,
                              int(bits.nbytes))
        frac = faults.partial_fraction("storage.bloom.build")
        if frac is not None:
            # chaos: silent bit corruption AFTER the checksum was taken —
            # the lazy CRC verify must catch it on the first negative
            bits[:: max(1, int(round(1 / frac)))] ^= True
        return filt

    def might_contain(self, h1: int, h2: int) -> bool:
        """Probe with a precomputed (h1, h2) pair. True means "maybe
        present"; False is a proof of absence (CRC-checked)."""
        if self.disabled:
            return True
        base = ((h1 >> 32) % self.nblocks) * _BLOCK_BITS
        for i in range(BLOOM_K):
            if not self.bits[base + ((h1 + i * h2) & _MASK64) % _BLOCK_BITS]:
                # a negative is only trustworthy from an intact filter:
                # _verify is True exactly when corruption was detected
                # (the filter then answers maybe, here and forever)
                return self._verify()
        return True

    def _verify(self) -> bool:
        """First-negative CRC check. Positives never need verification
        (a flipped-ON bit only costs a wasted scan); a negative from a
        corrupt filter would LOSE a row, so the first one pays one CRC
        pass. Returns True when the filter is corrupt (and disables it)."""
        if self._verified:
            return False
        if zlib.crc32(np.packbits(self.bits).tobytes()) != self.crc:
            self.disabled = True
            metric.BLOOM_CORRUPTIONS.inc()
            return True
        self._verified = True
        return False


# Tokens are process-global and monotonic: a compacted-away run's cache
# entries can never alias a newly built run's.
_TOKENS = itertools.count(1)


@dataclass
class RunMeta:
    """Read-path metadata for one immutable sorted run."""

    token: int
    void_keys: np.ndarray  # full sorted key column, void dtype (memcmp order)
    n_live: int
    _bloom: SplitBloom | None = None
    _bloom_built: bool = False

    def bloom(self) -> SplitBloom | None:
        """The run's filter, built on first demand. Engine's run
        constructors (ingest/flush/compaction) force the build eagerly;
        rewrite paths (intent resolution, span clears) leave it lazy so
        commit-heavy workloads don't pay filter builds per txn. None
        means "no filter" — every point read scans the run (correct,
        just slower)."""
        if not self._bloom_built:
            self._bloom_built = True
            try:
                self._bloom = SplitBloom.build(self.void_keys[: self.n_live])
            except faults.InjectedFault:
                self._bloom = None
        return self._bloom


def build_meta(void_keys: np.ndarray, n_live: int) -> RunMeta:
    return RunMeta(next(_TOKENS), void_keys, int(n_live))


def block_nbytes(block) -> int:
    """Resident size of a cached window: the sum of its leaf buffers."""
    import jax

    return int(sum(int(np.asarray(x).nbytes)
                   for x in jax.tree_util.tree_leaves(block)))


class BlockCache:
    """Node-wide clock cache of decoded KVBlock windows.

    Lock order: ``storage.engine`` before ``storage.blockcache``, never
    the other way round: the cache never calls back into the engine, so
    the reverse edge cannot form. The engine's scans, and its metadata
    pruning and ``invalidate_run``, enter with ``storage.engine`` held; a
    point read (``Engine.get``) enters with no engine lock held, on a
    snapshot's immutable runs. A window such a reader puts under the
    token of a run that was dropped meanwhile is unreachable (tokens are
    never reused) and leaves by the clock sweep.
    """

    def __init__(self, name: str = "storage/block-cache"):
        self._mu = locks.rlock("storage.blockcache")
        self._name = name
        # key -> [block, nbytes, ref_bit]; dict order is clock order
        self._entries: OrderedDict[tuple, list] = OrderedDict()
        self._mon = None
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _monitor(self):
        from ..flow import memory as flowmem

        if self._mon is None:
            # long-lived "cache"-level child of the root tree — NOT a
            # query-level monitor, so the per-query drain census ignores
            # it while cache residency still charges the node budget
            self._mon = flowmem.ROOT.child(self._name, level="cache")
        return self._mon

    def _budget(self) -> int:
        from ..utils import settings

        return int(settings.get("storage.block_cache.size_bytes"))

    def get(self, token: int, pos: int, size: int):
        with self._mu:
            e = self._entries.get((token, pos, size))
            if e is None:
                self.misses += 1
                metric.BLOCKCACHE_MISSES.inc()
                return None
            e[2] = True  # second chance
            self.hits += 1
            metric.BLOCKCACHE_HITS.inc()
            return e[0]

    def put(self, token: int, pos: int, size: int, block) -> None:
        budget = self._budget()
        if budget <= 0:
            return  # cache disabled
        nbytes = block_nbytes(block)
        if nbytes > budget:
            return  # a window larger than the whole budget never caches
        from ..flow import memory as flowmem

        with self._mu:
            key = (token, pos, size)
            if key in self._entries:
                return
            mon = self._monitor()
            mon.budget = budget  # track the live setting value
            # clock sweep: referenced entries get a second chance (ref
            # cleared, rotated to the back), unreferenced ones evict
            while mon.used + nbytes > budget and self._entries:
                k, e = next(iter(self._entries.items()))
                if e[2]:
                    e[2] = False
                    self._entries.move_to_end(k)
                else:
                    del self._entries[k]
                    mon.release(e[1])
                    self.evictions += 1
                    metric.BLOCKCACHE_EVICTIONS.inc()
            try:
                mon.reserve(nbytes)
            except flowmem.BudgetExceededError:
                return  # an ancestor refused: serve uncached
            self._entries[key] = [block, nbytes, False]
            metric.BLOCKCACHE_BYTES.set(mon.used)

    def invalidate_run(self, token: int) -> None:
        """Drop every cached window of one run — and ONLY that run's:
        compaction output must not flush innocent neighbours."""
        with self._mu:
            dead = [k for k in self._entries if k[0] == token]
            for k in dead:
                e = self._entries.pop(k)
                self._mon.release(e[1])
            if dead:
                metric.BLOCKCACHE_BYTES.set(self._mon.used)

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            if self._mon is not None:
                self._mon.release()
                metric.BLOCKCACHE_BYTES.set(0)

    def used_bytes(self) -> int:
        with self._mu:
            return int(self._mon.used) if self._mon is not None else 0

    def close(self) -> None:
        with self._mu:
            self._entries.clear()
            if self._mon is not None:
                self._mon.release()
                self._mon.close()
                self._mon = None

    def stats(self) -> dict:
        with self._mu:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "bytes": int(self._mon.used) if self._mon is not None else 0,
                "entries": len(self._entries),
            }

    def describe(self) -> str:
        """One-line summary for EXPLAIN ANALYZE."""
        s = self.stats()
        total = s["hits"] + s["misses"]
        if total == 0:
            return "cold (no lookups)"
        return (f"{100.0 * s['hits'] / total:.1f}% hit rate "
                f"({s['hits']}/{total} lookups), {s['entries']} windows, "
                f"{s['bytes']} bytes")


_NODE_CACHE: BlockCache | None = None
_NODE_LOCK = threading.Lock()


def node_cache() -> BlockCache:
    """The node-wide cache every Engine on this node shares (the
    reference's cache is likewise per-store-node, not per-SST)."""
    global _NODE_CACHE
    c = _NODE_CACHE
    if c is None:
        with _NODE_LOCK:
            if _NODE_CACHE is None:
                _NODE_CACHE = BlockCache()
            c = _NODE_CACHE
    return c


def refresh_gauges() -> None:
    c = _NODE_CACHE
    if c is not None:
        metric.BLOCKCACHE_BYTES.set(c.used_bytes())
