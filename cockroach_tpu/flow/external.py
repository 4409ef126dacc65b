"""External (partitioned) operators — the colexecdisk analog.

Reference: pkg/sql/colexec/colexecdisk swaps an in-memory operator for an
external variant when it exceeds its memory budget (disk_spiller.go:103):
external hash join/agg partition recursively by key hash (Grace —
hash_based_partitioner.go), external sort merges sorted runs
(external_sort.go) staged in colcontainer disk queues.

TPU redesign: the budget is the device tile ceiling. Oversized inputs stage
on the HOST as compacted numpy partitions (the host-RAM tier standing in for
colcontainer's disk queues — an optional spill_dir persists partitions as
.npz, diskqueue.go:177 analog), partitioned ON DEVICE:

- Grace hash join: both sides bucket by the SAME key hash (ops.hashing), so
  partition i of the probe joins only partition i of the build; each
  partition joins in-memory with the existing kernels.
- External sort: rows bucket by range of an order-preserving uint64 of the
  primary sort key (quantile boundaries from the staged data); bucket i's
  rows all precede bucket j's (i<j), ties stay within one bucket, so
  sorting each bucket with the full key list and emitting buckets in order
  is a total order — the k-way merge becomes embarrassingly bucket-parallel
  (the same trick the shuffle plane uses for distributed sort).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, from_host
from ..coldata.types import Family, Schema
from ..ops import join as join_ops
from ..ops import sort as sort_ops
from ..ops.hashing import hash_columns
from ..utils import faults
from . import dispatch
from .operator import OneInputOperator, Operator


def _pow2(n: int) -> int:
    # partition reload / join output capacities are data-dependent: snap
    # them to the canonical shape ladder so a repeat run with different
    # literals (≈ different partition sizes) reuses the spill kernels
    from .operators import _canonical_cap

    return _canonical_cap(max(1, n))


class HostPartitions:
    """Host-staged row partitions (colcontainer partitioned queue analog).
    Each partition accumulates compacted numpy columns; reload() returns a
    device Batch per partition."""

    def __init__(self, schema: Schema, nparts: int, spill_dir: str | None = None):
        from . import memory as flowmem

        self.schema = schema
        self.nparts = nparts
        self.parts: list[list[dict]] = [[] for _ in range(nparts)]
        self.rows = [0] * nparts
        # staged host rows charge the node-level spill-staging account
        # (NOT the query monitor: partitions outlive operator accounts,
        # and the drain census ignores cache-level children). A finalizer
        # releases whatever free() was never called for; the holder dict
        # keeps the finalizer from retaining self.
        self._mon = flowmem.staging_monitor("flow/spill-staging")
        self._charged = [0] * nparts
        hold, mon = {"n": 0}, self._mon
        self._hold = hold
        import weakref

        weakref.finalize(self, lambda: mon.release(hold["n"]))

    def append_host(self, pid: int, arrays: dict, valids: dict, n: int):
        if n == 0:
            return
        # chaos hook: a failed host partition write (the colcontainer disk
        # queue's enqueue erroring) fires BEFORE the reservation so the
        # staging account never holds bytes for rows that were never staged
        faults.fire("flow.spill.partition_write")
        nb = int(sum(a.nbytes for a in arrays.values())
                 + sum(v.nbytes for v in valids.values()))
        self._mon.reserve(nb, force=True)
        self._charged[pid] += nb
        self._hold["n"] += nb
        self.parts[pid].append({"arrays": arrays, "valids": valids, "n": n})
        self.rows[pid] += n

    def free(self, pid: int) -> None:
        """Drop a partition's staged rows and release their reservation —
        callers free as they consume so peak staging tracks the live set."""
        self._mon.release(self._charged[pid])
        self._hold["n"] -= self._charged[pid]
        self._charged[pid] = 0
        self.parts[pid] = []
        self.rows[pid] = 0

    def charged(self, pid: int) -> int:
        """Staged bytes for one partition — host bytes, but a faithful
        estimate of the device bytes a full reload would pin (from_host
        pads only up to the next capacity rung)."""
        return self._charged[pid]

    def _host_columns(self, pid: int):
        """The partition's rows as contiguous host columns. Compacts the
        chunk list in place on first use (same bytes, one chunk) so
        repeated run/chunk iteration doesn't re-concatenate."""
        chunks = self.parts[pid]
        if len(chunks) > 1:
            arrays = {
                name: np.concatenate([c["arrays"][name] for c in chunks])
                for name in self.schema.names
            }
            valids = {
                name: np.concatenate([c["valids"][name] for c in chunks])
                for name in self.schema.names
            }
            self.parts[pid] = [
                {"arrays": arrays, "valids": valids, "n": self.rows[pid]}
            ]
        c = self.parts[pid][0]
        return c["arrays"], c["valids"]

    def reload(self, pid: int) -> Batch | None:
        chunks = self.parts[pid]
        if not chunks:
            return None
        n = self.rows[pid]
        arrays, valids = self._host_columns(pid)
        return from_host(self.schema, arrays, valids, capacity=_pow2(n))

    def reload_runs(self, pid: int, rows_per: int):
        """Yield the partition's rows as device batches of at most
        ``rows_per`` rows — the bounded-reload primitive behind the hybrid
        join's sorted runs and probe chunks. Capacities snap to the shape
        ladder so per-run kernels are shared across partitions (and
        queries); iteration order is deterministic, so a second pass sees
        the same chunk boundaries."""
        n = self.rows[pid]
        if n == 0:
            return
        if rows_per >= n:
            yield self.reload(pid)
            return
        arrays, valids = self._host_columns(pid)
        cap = _pow2(rows_per)
        for s in range(0, n, rows_per):
            e = min(n, s + rows_per)
            yield from_host(
                self.schema,
                {k: v[s:e] for k, v in arrays.items()},
                {k: v[s:e] for k, v in valids.items()},
                capacity=cap,
            )

    def extract(self, pid: int, sels) -> list[dict]:
        """Remove selected rows from a partition's staged chunks (``sels``:
        one bool array per chunk, parallel to the staging order) and return
        them as chunk dicts. The staging charge is re-measured so the
        accounting follows the surviving rows."""
        chunks = self.parts[pid]
        removed, kept = [], []
        for c, sel in zip(chunks, sels):
            nr = int(sel.sum())
            if nr == 0:
                kept.append(c)
                continue
            keep = ~sel
            removed.append({
                "arrays": {k: v[sel] for k, v in c["arrays"].items()},
                "valids": {k: v[sel] for k, v in c["valids"].items()},
                "n": nr,
            })
            nk = int(keep.sum())
            if nk:
                kept.append({
                    "arrays": {k: v[keep] for k, v in c["arrays"].items()},
                    "valids": {k: v[keep] for k, v in c["valids"].items()},
                    "n": nk,
                })
        if removed:
            self.parts[pid] = kept
            freed = self._charged[pid]
            nb = int(sum(
                sum(a.nbytes for a in c["arrays"].values())
                + sum(v.nbytes for v in c["valids"].values())
                for c in kept))
            self.rows[pid] = sum(c["n"] for c in kept)
            self._mon.release(freed - nb)
            self._hold["n"] -= freed - nb
            self._charged[pid] = nb
        return removed


def stage_batch(batch: Batch, schema: Schema, pids: np.ndarray | None,
                parts: HostPartitions):
    """Move a device batch's live rows to host partitions. `pids` is the
    per-row partition id (host numpy, dead rows ignored)."""
    mask = np.asarray(batch.mask)
    for pid in range(parts.nparts):
        sel = mask if pids is None else (mask & (pids == pid))
        n = int(sel.sum())
        if n == 0:
            continue
        arrays = {}
        valids = {}
        for name, col in zip(schema.names, batch.cols):
            arrays[name] = np.asarray(col.data)[sel]
            valids[name] = np.asarray(col.valid)[sel]
        parts.append_host(pid, arrays, valids, n)


class ReplayOp(Operator):
    """Re-emits already-spooled device tiles — glue that lets an in-memory
    operator hand its buffered input to the external variant it spills into
    (the disk_spiller handoff, disk_spiller.go:103)."""

    def __init__(self, tiles, schema: Schema, dictionaries):
        super().__init__()
        self.tiles = list(tiles)
        self.output_schema = schema
        self.dictionaries = dict(dictionaries)
        self._i = 0

    def init(self):
        super().init()
        self._i = 0

    def _next(self):
        if self._i >= len(self.tiles):
            return None
        b = self.tiles[self._i]
        self._i += 1
        return b


class ChainOp(ReplayOp):
    """Replays spooled tiles, then continues pulling from the live input —
    the handoff when an operator spills mid-stream. Does NOT re-init the
    live input (it is mid-stream by construction)."""

    def __init__(self, tiles, schema: Schema, dictionaries, rest: Operator):
        super().__init__(tiles, schema, dictionaries)
        self.rest = rest

    def _next(self):
        b = super()._next()
        return self.rest.next_batch() if b is None else b



def _array_key(a):
    """Content key for a small baked-in table (dictionary ranks/hashes) so
    spill kernels can share through the process-global kernel cache. Spill
    operators are constructed at RUNTIME (SortOp/AggregateOp/HashJoinOp
    hand off mid-query), so without a content key every spilling run of a
    cached plan would re-trace identical kernels."""
    if a is None:
        return None
    a = np.asarray(a)
    return (str(a.dtype), a.shape, a.tobytes())


def make_bucket_fn(schema: Schema, keys, tables, nparts: int,
                   with_hash: bool = False):
    """Jitted per-row partition id from the key columns' 64-bit hash —
    THE Grace partition function, shared by the external join and
    aggregation so their partitioning can never diverge. With
    ``with_hash`` the full hash rides along (one dispatch), for skew
    sampling and heavy-hitter routing keyed on the same value."""
    def fn(b: Batch):
        cols = [b.cols[i] for i in keys]
        types = [schema.types[i] for i in keys]
        h = hash_columns(cols, types, tables or None)
        pid = (h % np.uint64(nparts)).astype(jnp.int32)
        return (pid, h) if with_hash else pid

    key = dispatch.kernel_key(
        "grace_bucket", schema, tuple(keys), nparts, with_hash,
        tuple(sorted((i, _array_key(t)) for i, t in (tables or {}).items())),
    )
    return dispatch.jit(fn, key=key, name="grace_bucket")


# ---------------------------------------------------------------------------
# Grace hash join


class GraceHashJoinOp(OneInputOperator):
    """External hash join: both sides hash-partition into P buckets staged
    on the host; partition pairs join in-memory (hash_based_partitioner.go
    semantics), with two escape hatches where the reference would recurse:

    - Heavy-hitter routing: build-side key hashes are reservoir-sampled
      while staging (the kv/loadstats request-reservoir idiom). Keys
      owning more than ``sql.distsql.grace_skew_frac`` of the sample keep
      their build rows RESIDENT on device, and probe rows carrying those
      hashes route to a dedicated hot lane that streams against the
      resident table — instead of the whole hot key piling into one
      partition. Routing is hash-consistent on both sides, so every join
      type stays exact: a probe row's complete match set lives wherever
      its hash was routed (collisions route together; the join kernel
      applies the exact key predicate).
    - Hybrid degrade: a partition whose build side alone exceeds workmem
      (the budget says so up front — no device OOM retry involved)
      reloads its build as budget-sized sorted runs and merge-probes each
      run (ops.merge_join's exact-key order); resident partitions keep
      the one-shot hash path. Probe sides reload in budget-sized chunks
      either way, so device footprint is bounded by the budget, not by
      the largest partition."""

    def __init__(self, probe: Operator, build: Operator,
                 probe_keys, build_keys, spec, nparts: int = 8):
        super().__init__(probe)
        self.build = build
        self.probe_keys = tuple(probe_keys)
        self.build_keys = tuple(build_keys)
        self.spec = spec
        self.nparts = nparts
        self.output_schema = join_ops.join_output_schema(
            probe.output_schema, build.output_schema, spec
        )
        self.dictionaries = dict(probe.dictionaries)
        if spec.join_type not in ("semi", "anti"):
            off = len(probe.output_schema)
            for i, d in build.dictionaries.items():
                self.dictionaries[off + i] = d
        # host-side string bridges (same as HashJoinOp)
        self.probe_hash_tables = {}
        self.build_hash_tables = {}
        self.build_code_remaps = {}
        for pos, (pk, bk) in enumerate(zip(self.probe_keys, self.build_keys)):
            pt = probe.output_schema.types[pk]
            if pt.family is Family.STRING:
                pd_ = probe.dictionaries[pk]
                bd = build.dictionaries[bk]
                self.probe_hash_tables[pk] = pd_.hashes
                self.build_hash_tables[bk] = bd.hashes
                # crlint: allow-mem-accounting(dictionary code remap: one int32 per distinct build-side string, bounded by dictionary size)
                self.build_code_remaps[pos] = np.array(
                    [pd_.code_of(str(v)) for v in bd.values], dtype=np.int32
                )

    def children(self):
        return [self.child, self.build]

    def init(self):
        self.build.init()
        super().init()
        self._partitioned = False
        self._gen = None
        self._alloc = None
        self._hot_build = None
        self._hot_index = None
        self._hot_bytes = 0
        if hasattr(self, "_bucket_probe"):
            return
        self._bucket_probe = make_bucket_fn(
            self.child.output_schema, self.probe_keys,
            self.probe_hash_tables, self.nparts, with_hash=True,
        )
        self._bucket_build = make_bucket_fn(
            self.build.output_schema, self.build_keys,
            self.build_hash_tables, self.nparts, with_hash=True,
        )
        import dataclasses

        from ..ops import merge_join as mj

        pschema = self.child.output_schema
        bschema = self.build.output_schema
        pkeys, bkeys, spec = self.probe_keys, self.build_keys, self.spec
        pht = self.probe_hash_tables or None
        bht = self.build_hash_tables or None
        remaps = self.build_code_remaps or None
        tkey = (
            tuple(sorted((i, _array_key(t))
                         for i, t in self.probe_hash_tables.items())),
            tuple(sorted((i, _array_key(t))
                         for i, t in self.build_hash_tables.items())),
            tuple(sorted((i, _array_key(t))
                         for i, t in self.build_code_remaps.items())),
        )

        def hj_raw(p, build, index, out_cap, jt):
            sp = dataclasses.replace(spec, join_type=jt)
            return join_ops.hash_join_general(
                p, pschema, pkeys, build, bschema, bkeys, sp, out_cap,
                pht, bht, remaps, index=index,
            )

        self._hj_fn = dispatch.jit(
            hj_raw, static_argnames=("out_cap", "jt"),
            key=dispatch.kernel_key(
                "grace_hashprobe", pschema, bschema, pkeys, bkeys, spec,
                tkey),
            name="gracejoin_hashprobe",
        )

        def hindex_raw(b):
            return join_ops.build_index(b, bschema, bkeys, bht)

        self._hindex_fn = dispatch.jit(
            hindex_raw,
            key=dispatch.kernel_key("grace_hashindex", bschema, bkeys,
                                    tkey),
            name="gracejoin_hashindex",
        )

        # oversized partitions degrade to sorted-run merge probing: the
        # run index orders each reloaded build run by the EXACT composite
        # key (ops.merge_join), probe chunks binary-search it
        pranks, branks = mj.rank_tables_for(
            pschema, pkeys, self.child.dictionaries,
            bkeys, self.build.dictionaries,
        )
        rkey = (tuple(_array_key(r) for r in pranks),
                tuple(_array_key(r) for r in branks))

        def mindex_raw(b):
            return mj.build_merge_index(b, bschema, bkeys, branks)

        self._mindex_fn = dispatch.jit(
            mindex_raw,
            key=dispatch.kernel_key("grace_mergeindex", bschema, bkeys,
                                    rkey),
            name="gracejoin_mergeindex",
        )

        def mj_raw(p, b, index, out_cap, jt):
            sp = dataclasses.replace(spec, join_type=jt)
            return mj.merge_join(
                p, pschema, pkeys, b, bschema, bkeys, sp, out_cap,
                pranks, branks, build_index=index,
            )

        self._mj_fn = dispatch.jit(
            mj_raw, static_argnames=("out_cap", "jt"),
            key=dispatch.kernel_key(
                "grace_mergeprobe", pschema, bschema, pkeys, bkeys, spec,
                rkey),
            name="gracejoin_mergeprobe",
        )

    def _partition_all(self):
        import random as _random

        from ..utils import metric, settings

        pschema = self.child.output_schema
        bschema = self.build.output_schema
        # the probe side gets one extra lane (index nparts): rows carrying
        # a heavy-hitter hash detected from the build sample
        pparts = HostPartitions(pschema, self.nparts + 1)
        bparts = HostPartitions(bschema, self.nparts)
        size = int(settings.get("sql.distsql.grace_skew_sample"))
        frac = float(settings.get("sql.distsql.grace_skew_frac"))
        # fixed seed: a re-run of the same query samples identically
        rng = _random.Random(0x5CE7A11)
        samples: list[int] = []
        seen = 0
        bhashes: list[list[np.ndarray]] = [[] for _ in range(self.nparts)]
        while True:
            b = self.build.next_batch()
            if b is None:
                break
            pids_d, h_d = self._bucket_build(b)
            pids, h = np.asarray(pids_d), np.asarray(h_d)
            mask = np.asarray(b.mask)
            if size > 0 and frac > 0:
                # reservoir-sample live build key hashes (loadstats'
                # algorithm-R request reservoir, applied to join keys)
                for hv in h[mask]:
                    seen += 1
                    if len(samples) < size:
                        samples.append(int(hv))
                    else:
                        j = rng.randrange(seen)
                        if j < size:
                            samples[j] = int(hv)
            for pid in range(self.nparts):
                sel = mask & (pids == pid)
                n = int(sel.sum())
                if n == 0:
                    continue
                arrays = {name: np.asarray(col.data)[sel]
                          for name, col in zip(bschema.names, b.cols)}
                valids = {name: np.asarray(col.valid)[sel]
                          for name, col in zip(bschema.names, b.cols)}
                bparts.append_host(pid, arrays, valids, n)
                bhashes[pid].append(h[sel])
        hot = self._detect_hot(samples, frac, bparts, bhashes)
        while True:
            p = self.child.next_batch()
            if p is None:
                break
            pids_d, h_d = self._bucket_probe(p)
            pids = np.asarray(pids_d)
            if hot is not None:
                routed = np.isin(np.asarray(h_d), hot)
                n_hot = int((routed & np.asarray(p.mask)).sum())
                if n_hot:
                    metric.GRACE_JOIN_SKEW_ROUTED.inc(n_hot)
                pids = np.where(routed, self.nparts, pids)
            stage_batch(p, pschema, pids, pparts)
        self._pparts = pparts
        self._bparts = bparts
        self._partitioned = True

    def _detect_hot(self, samples, frac, bparts, bhashes):
        """Heavy-hitter hashes from the build-side reservoir -> resident
        device build table (extracted out of the staged partitions).
        Returns the sorted hot hash array for probe routing, or None."""
        from ..utils import log, settings

        from .memory import batch_bytes

        if not samples or frac <= 0:
            return None
        thr = max(2, int(frac * len(samples)))
        counts: dict[int, int] = {}
        for hv in samples:
            counts[hv] = counts.get(hv, 0) + 1
        hot_list = sorted(h for h, c in counts.items() if c >= thr)
        if not hot_list:
            return None
        hot = np.array(hot_list, dtype=np.uint64)
        sels = {pid: [np.isin(ch, hot) for ch in bhashes[pid]]
                for pid in range(self.nparts)}
        hot_rows = sum(int(s.sum()) for ss in sels.values() for s in ss)
        if hot_rows == 0:
            return None
        # residency check BEFORE extraction: the hot table must fit well
        # inside workmem, or routing would just move the oversize on-device
        budget = int(settings.get("sql.distsql.workmem_bytes"))
        total_rows = sum(bparts.rows) or 1
        total_bytes = sum(bparts.charged(pid)
                          for pid in range(self.nparts))
        est = int(total_bytes * hot_rows / total_rows)
        if est > budget // 4:
            log.info(log.SQL_EXEC,
                     "grace join skew: hot build side too large to pin",
                     hot_keys=len(hot_list), est_bytes=est)
            return None
        chunks = []
        for pid in range(self.nparts):
            chunks.extend(bparts.extract(pid, sels[pid]))
        bschema = self.build.output_schema
        arrays = {name: np.concatenate([c["arrays"][name] for c in chunks])
                  for name in bschema.names}
        valids = {name: np.concatenate([c["valids"][name] for c in chunks])
                  for name in bschema.names}
        n = sum(c["n"] for c in chunks)
        self._hot_build = from_host(bschema, arrays, valids,
                                    capacity=_pow2(n))
        self._hot_index = self._hindex_fn(self._hot_build)
        self._hot_bytes = batch_bytes(self._hot_build)
        self._alloc.reserve(self._hot_bytes, force=True)
        log.info(log.SQL_EXEC, "grace join skew: heavy hitters pinned",
                 hot_keys=len(hot_list), rows=n)
        return hot

    @staticmethod
    def _rows_per(nbytes: int, rows: int, budget: int) -> int:
        """Rows per bounded reload so one run/chunk stays inside the
        budget (floored: tiny budgets still make progress tile-at-a-time)."""
        if rows == 0:
            return 1
        per_row = max(1, nbytes // rows)
        return max(1024, int(budget // per_row))

    def _probe_stream(self, pid, rows_per, build, index):
        """Probe one partition in bounded chunks against a COMPLETE build
        (resident partition or the pinned hot table): every chunk's match
        set is fully present, so all join types are exact per chunk."""
        from .memory import batch_bytes

        jt = self.spec.join_type
        out_cap = 0
        for chunk in self._pparts.reload_runs(pid, rows_per):
            nb = batch_bytes(chunk)
            self._alloc.reserve(nb, force=True)
            try:
                out_cap = max(out_cap, _pow2(chunk.capacity))
                while True:
                    out, total = self._hj_fn(chunk, build, index,
                                             out_cap=out_cap, jt=jt)
                    if int(total) <= out_cap:
                        break
                    out_cap = _pow2(int(total) + 1)
                yield out
            finally:
                self._alloc.release(nb)

    def _probe_hot(self, budget):
        hot_pid = self.nparts
        try:
            rows_per = self._rows_per(self._pparts.charged(hot_pid),
                                      self._pparts.rows[hot_pid], budget)
            yield from self._probe_stream(hot_pid, rows_per,
                                          self._hot_build, self._hot_index)
        finally:
            self._pparts.free(hot_pid)
            self._alloc.release(self._hot_bytes)
            self._hot_bytes = 0
            self._hot_build = self._hot_index = None

    def _probe_resident(self, pid, budget):
        from ..coldata.batch import empty_batch

        from .memory import batch_bytes

        build = self._bparts.reload(pid)
        if build is None:
            build = empty_batch(self.build.output_schema, 1024)
        nb = batch_bytes(build)
        self._alloc.reserve(nb, force=True)
        try:
            index = self._hindex_fn(build)
            rows_per = self._rows_per(self._pparts.charged(pid),
                                      self._pparts.rows[pid], budget)
            yield from self._probe_stream(pid, rows_per, build, index)
        finally:
            self._alloc.release(nb)

    def _probe_runs(self, pid, budget):
        """Oversized partition: the budget (not an OOM retry) says the
        build side can't be resident, so it reloads as budget-sized sorted
        runs and each probe chunk binary-searches every run. Inner/left
        matches emit per run (runs are disjoint build rows — no dedup);
        probe-aligned verdicts (semi/anti/left-unmatched) OR-accumulate a
        per-chunk found mask across runs and resolve in a final pass."""
        from ..utils import log, metric

        from .memory import batch_bytes

        metric.GRACE_JOIN_MERGE_PARTS.inc()
        jt = self.spec.join_type
        rows_run = self._rows_per(self._bparts.charged(pid),
                                  self._bparts.rows[pid], budget)
        rows_chunk = self._rows_per(self._pparts.charged(pid),
                                    self._pparts.rows[pid], budget)
        log.info(log.SQL_EXEC,
                 "grace join partition exceeds workmem; merge-probing runs",
                 partition=pid, build_rows=self._bparts.rows[pid],
                 run_rows=rows_run)
        found: dict[int, jax.Array] = {}
        out_cap = 0
        for run in self._bparts.reload_runs(pid, rows_run):
            faults.fire("flow.spill.merge_probe")
            rb = batch_bytes(run)
            self._alloc.reserve(rb, force=True)
            try:
                index = self._mindex_fn(run)
                for ci, chunk in enumerate(
                        self._pparts.reload_runs(pid, rows_chunk)):
                    cb = batch_bytes(chunk)
                    self._alloc.reserve(cb, force=True)
                    try:
                        if jt in ("inner", "left"):
                            out_cap = max(out_cap, _pow2(chunk.capacity))
                            while True:
                                out, total = self._mj_fn(
                                    chunk, run, index, out_cap=out_cap,
                                    jt="inner")
                                if int(total) <= out_cap:
                                    break
                                out_cap = _pow2(int(total) + 1)
                            yield out
                        if jt != "inner":
                            m, _ = self._mj_fn(chunk, run, index,
                                               out_cap=chunk.capacity,
                                               jt="semi")
                            f = m.mask
                            found[ci] = (f if ci not in found
                                         else found[ci] | f)
                    finally:
                        self._alloc.release(cb)
            finally:
                self._alloc.release(rb)
        if jt == "inner":
            return
        # final probe-aligned pass over the same (deterministic) chunking
        from ..coldata.batch import empty_batch

        for ci, chunk in enumerate(
                self._pparts.reload_runs(pid, rows_chunk)):
            cb = batch_bytes(chunk)
            self._alloc.reserve(cb, force=True)
            try:
                f = found.get(ci)
                if f is None:
                    f = jnp.zeros((chunk.capacity,), jnp.bool_)
                if jt == "semi":
                    yield chunk.with_mask(f)
                elif jt == "anti":
                    yield chunk.with_mask(chunk.mask & ~f)
                else:  # left: unmatched rows null-extend via an empty run
                    unm = chunk.mask & ~f
                    empty = empty_batch(self.build.output_schema, 1024)
                    eidx = self._mindex_fn(empty)
                    out, _ = self._mj_fn(chunk.with_mask(unm), empty, eidx,
                                         out_cap=_pow2(chunk.capacity),
                                         jt="left")
                    yield out
            finally:
                self._alloc.release(cb)

    def _emit(self):
        from ..utils import settings

        from . import memory as flowmem

        if self._alloc is not None:
            self._alloc.release()
            self._alloc.close()
        self._alloc = flowmem.Allocator("grace join partition",
                                        stats=self.stats)
        self._partition_all()
        budget = int(settings.get("sql.distsql.workmem_bytes"))
        if self._hot_build is not None:
            yield from self._probe_hot(budget)
        for pid in range(self.nparts):
            try:
                if self._pparts.rows[pid] == 0:
                    continue
                if self._bparts.charged(pid) <= budget:
                    yield from self._probe_resident(pid, budget)
                else:
                    yield from self._probe_runs(pid, budget)
            finally:
                # free as we go: peak staging tracks the live partitions
                self._pparts.free(pid)
                self._bparts.free(pid)

    def _next(self):
        if self._gen is None:
            self._gen = self._emit()
        return next(self._gen, None)

    def close(self):
        super().close()
        self.build.close()
        self._gen = None
        self._hot_build = self._hot_index = None
        if getattr(self, "_alloc", None) is not None:
            self._alloc.release()
            self._alloc.close()
            self._alloc = None


# ---------------------------------------------------------------------------
# External sort


# crlint: allow-mem-accounting(tile-width device temp for order-preserving key packing; the owning batch is charged by its operator account)
def _primary_u64(batch: Batch, schema: Schema, key: sort_ops.SortKey,
                 rank_table=None) -> jax.Array:
    """Order-preserving uint64 of the primary sort key (NULL ordering
    folded in: null_key gets the top bit band)."""
    c = batch.cols[key.col]
    ops = sort_ops.order_keys(c.data, c.valid, key, schema.types[key.col],
                              rank_table)
    # order_keys returns leading 1-bit bool bands (null ordering, NaN
    # ordering) followed by the payload word(s). Fold the bands into the top
    # bits and range-partition on the FIRST payload word only — for
    # multi-word keys (BYTES wider than 8) this is order-preserving at
    # partition granularity: rows equal in the leading word stay in one
    # bucket, and the within-bucket sort uses the full key list.
    bands, payload = [], None
    for op in ops:
        if op.dtype == jnp.bool_:
            bands.append(op)
        else:
            payload = op
            break
    if payload is None:  # BOOL key: its one bool band IS the payload —
        # promote the bit to the top so the band right-shift below keeps it
        payload = bands.pop().astype(jnp.uint64) << np.uint64(63)
    u = jnp.zeros((batch.capacity,), jnp.uint64)
    shift = np.uint64(62)
    for op in bands:
        u = u | (op.astype(jnp.uint64) << shift)
        shift -= np.uint64(1)
    if payload.dtype in (jnp.float64, jnp.float32):
        f = payload.astype(jnp.float64)
        parts = jax.lax.bitcast_convert_type(f, jnp.uint32)
        p = (parts[..., 1].astype(jnp.uint64) << np.uint64(32)) | parts[
            ..., 0
        ].astype(jnp.uint64)
        neg = (p >> np.uint64(63)) != 0
        p = jnp.where(neg, ~p, p | np.uint64(1 << 63))
    elif payload.dtype == jnp.uint64:
        p = payload
    else:
        p = payload.astype(jnp.int64).astype(jnp.uint64) ^ np.uint64(1 << 63)
    # drop low bits to make room for the null/nan bands (ordering within
    # equal top bands preserved; only boundary granularity is affected)
    return u | (p >> np.uint64(64 - int(shift) - 1))


class ExternalSortOp(OneInputOperator):
    """External sort: range-partition rows by a uint64 of the primary key
    (quantile boundaries over staged samples), then sort each bucket with
    the full key list and emit buckets in order (external_sort.go role; the
    merge phase is bucket-ordered emission instead of a loser tree)."""

    def __init__(self, child: Operator, keys, budget_rows: int = 1 << 20,
                 nparts: int = 8):
        super().__init__(child)
        self.output_schema = child.output_schema
        self.keys = tuple(keys)
        self.budget_rows = budget_rows
        self.nparts = nparts
        self._staged = False

    def init(self):
        super().init()
        self._staged = False
        self._pid = 0
        if hasattr(self, "_u64_fn"):
            return
        schema = self.output_schema
        key = self.keys[0]
        rank_table = None
        if key.col in self.child.dictionaries:
            rank_table = self.child.dictionaries[key.col].ranks
        self._u64_fn = dispatch.jit(
            lambda b: _primary_u64(b, schema, key, rank_table),
            key=dispatch.kernel_key("extsort_u64", schema, key,
                                    _array_key(rank_table)),
            name="extsort_u64",
        )
        rank_tables = {
            k.col: self.child.dictionaries[k.col].ranks
            for k in self.keys
            if k.col in self.child.dictionaries
        }
        keys = self.keys

        def sort_fn(b):
            return sort_ops.sort_batch(b, schema, keys, rank_tables)

        self._sort_fn = dispatch.jit(sort_fn, key=dispatch.kernel_key(
            "extsort_sort", schema, keys,
            tuple(sorted((c, _array_key(t))
                         for c, t in rank_tables.items())),
        ), name="extsort_sort")

    def _stage_all(self):
        # pass 1: stage all rows + their primary u64 on the host
        chunks = []
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            u = np.asarray(self._u64_fn(b))
            mask = np.asarray(b.mask)
            arrays = {
                name: np.asarray(c.data)[mask]
                for name, c in zip(self.output_schema.names, b.cols)
            }
            valids = {
                name: np.asarray(c.valid)[mask]
                for name, c in zip(self.output_schema.names, b.cols)
            }
            chunks.append((arrays, valids, u[mask]))
        total = sum(len(c[2]) for c in chunks)
        if total == 0:
            self._parts = None
            self._staged = True
            return
        from . import memory as flowmem

        # quantile boundaries over the staged u64s: the transient key
        # vector is 8 B/row over the whole staged input — charge it for
        # the split computation's lifetime
        with flowmem.staged("flow/spill-staging", 8 * total):
            allu = np.concatenate([c[2] for c in chunks])
            P = min(self.nparts, max(1, (total + self.budget_rows - 1)
                                     // self.budget_rows * 2))
            qs = np.quantile(allu, np.linspace(0, 1, P + 1)[1:-1])
            bounds = np.unique(qs.astype(np.uint64))
        parts = HostPartitions(self.output_schema, len(bounds) + 1)
        for arrays, valids, u in chunks:
            pids = np.searchsorted(bounds, u, side="right")
            for pid in range(parts.nparts):
                sel = pids == pid
                n = int(sel.sum())
                if n:
                    parts.append_host(
                        pid,
                        {k: v[sel] for k, v in arrays.items()},
                        {k: v[sel] for k, v in valids.items()},
                        n,
                    )
        self._parts = parts
        self._staged = True

    def _next(self):
        if not self._staged:
            self._stage_all()
        if self._parts is None:
            return None
        while self._pid < self._parts.nparts:
            b = self._parts.reload(self._pid)
            self._pid += 1
            if b is not None:
                return self._sort_fn(b)
        return None


# ---------------------------------------------------------------------------
# Grace external aggregation (external_hash_aggregator.go role) — also the
# external DISTINCT, which is aggregation with no aggregate functions


class GraceAggregateOp(Operator):
    """External aggregation over partial-STATE tiles: rows partition by
    group-key hash, so partitions are GROUP-DISJOINT — each merges and
    finalizes independently and streams out one batch at a time, bounding
    memory by the largest partition instead of the full group count
    (hash_based_partitioner.go recursion is unnecessary here because the
    merge stage re-aggregates: a skewed partition still shrinks to its
    distinct groups).

    Built by AggregateOp's spill handoff: `child` replays the spooled
    state tiles then continues the live partial stream (ChainOp)."""

    def __init__(self, child: Operator, agg_op, nparts: int = 8):
        super().__init__()
        # zero group keys never reach here (no-GROUP-BY plans use
        # ScalarAggregateOp); partitioning without keys would duplicate
        # every row into all partitions
        assert agg_op.num_keys > 0, "Grace aggregation needs group keys"
        self.child = child
        self.agg = agg_op  # the spilling AggregateOp (owns merge/finalize)
        self.nparts = nparts
        self.output_schema = agg_op.output_schema
        self.dictionaries = dict(agg_op.dictionaries)
        self.col_stats = dict(agg_op.col_stats)

    def children(self):
        return [self.child]

    def init(self):
        self._parts = None
        self._pid = 0
        self._initialized = True
        if hasattr(self, "_bucket"):
            return
        schema = self.agg.state_schema
        keys = tuple(range(self.agg.num_keys))
        tables = {
            pos: d.hashes
            for pos, d in self.agg.dictionaries.items()
            if pos < self.agg.num_keys
        }
        self._bucket = make_bucket_fn(schema, keys, tables, self.nparts)

    def _stage_all(self):
        from ..utils import log, metric

        parts = HostPartitions(self.agg.state_schema, self.nparts)
        n_tiles = 0
        while True:
            b = self.child.next_batch()
            if b is None:
                break
            n_tiles += 1
            pids = np.asarray(self._bucket(b))
            stage_batch(b, self.agg.state_schema, pids, parts)
        metric.EXTERNAL_AGG_SPILLS.inc()
        log.info(log.SQL_EXEC, "aggregation spilled to Grace partitions",
                 tiles=n_tiles, partitions=self.nparts,
                 rows=sum(parts.rows))
        self._parts = parts

    def _next(self):
        if self._parts is None:
            self._stage_all()
        while self._pid < self.nparts:
            pid = self._pid
            self._pid += 1
            batch = self._parts.reload(pid)
            self._parts.free(pid)  # free as we go (releases the staging charge)
            if batch is None:
                continue
            cap = batch.capacity
            merged, ng = self.agg._merge_fn((batch,), cap=cap)
            while int(ng) > cap:
                cap = _pow2(int(ng) + 1)
                merged, ng = self.agg._merge_fn((batch,), cap=cap)
            if self.agg.mode == "partial":
                return merged
            return self.agg._finalize_fn(merged)
        return None

    def close(self):
        self.child.close()
        self._parts = None
