"""Cluster settings registry — the pkg/settings analog.

Reference: pkg/settings/registry.go holds typed, documented, SQL-updatable
settings (RegisterBoolSetting bool.go:138 etc.); test builds randomize
"metamorphic constants" (pkg/util/metamorphic/constants.go:82) such as
coldata-batch-size so unit tests sweep the tuning space. Here settings are
process-local (single-process framework; gossip distribution is the control
plane's job when multi-host arrives), typed, validated, resettable, and
metamorphically randomizable for tests.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any


@dataclass
class Setting:
    name: str
    default: Any
    kind: str  # bool | int | float | string | enum
    desc: str
    choices: tuple | None = None
    lo: float | None = None
    hi: float | None = None
    # metamorphic: (lo, hi) or choices to randomize within for test builds
    metamorphic: bool = False
    value: Any = None

    def get(self):
        return self.default if self.value is None else self.value


_REGISTRY: dict[str, Setting] = {}


def _register(s: Setting) -> Setting:
    if s.name in _REGISTRY:
        raise ValueError(f"duplicate setting {s.name}")
    # crlint: allow-shared-state(registration happens at import time, before any worker thread exists; runtime mutation goes through Setting.value) # crlint: allow-race-coverage(dict inserts happen only at import time, before any worker thread exists; runtime SET rebinds Setting.value — a GIL-atomic rebind read via Setting.get — and never touches the dict, so there is no post-startup write for a lock or racesan to witness)
    _REGISTRY[s.name] = s
    return s


def register_bool(name: str, default: bool, desc: str,
                  metamorphic: bool = False) -> Setting:
    return _register(Setting(name, default, "bool", desc,
                             metamorphic=metamorphic))


def register_int(name: str, default: int, desc: str, lo: int | None = None,
                 hi: int | None = None, metamorphic: bool = False) -> Setting:
    return _register(Setting(name, default, "int", desc, lo=lo, hi=hi,
                             metamorphic=metamorphic))


def register_float(name: str, default: float, desc: str,
                   lo: float | None = None, hi: float | None = None) -> Setting:
    return _register(Setting(name, default, "float", desc, lo=lo, hi=hi))


def register_enum(name: str, default: str, desc: str,
                  choices: tuple[str, ...],
                  metamorphic: bool = False) -> Setting:
    return _register(Setting(name, default, "enum", desc, choices=choices,
                             metamorphic=metamorphic))


def register_string(name: str, default: str, desc: str) -> Setting:
    return _register(Setting(name, default, "string", desc))


def get(name: str):
    return _REGISTRY[name].get()


def set(name: str, value) -> None:  # noqa: A001 - SQL SET semantics
    s = _REGISTRY[name]
    if s.kind == "bool":
        if not isinstance(value, bool):
            raise TypeError(f"{name} wants bool, got {value!r}")
    elif s.kind == "int":
        value = int(value)
        if s.lo is not None and value < s.lo:
            raise ValueError(f"{name}: {value} < min {s.lo}")
        if s.hi is not None and value > s.hi:
            raise ValueError(f"{name}: {value} > max {s.hi}")
    elif s.kind == "float":
        value = float(value)
        if s.lo is not None and value < s.lo:
            raise ValueError(f"{name}: {value} < min {s.lo}")
        if s.hi is not None and value > s.hi:
            raise ValueError(f"{name}: {value} > max {s.hi}")
    elif s.kind == "enum":
        if value not in s.choices:
            raise ValueError(f"{name}: {value!r} not in {s.choices}")
    s.value = value
    _notify(name, value)


_CHANGE_LISTENERS: list = []
# bare threading.Lock, not utils.locks: locks.py reads its settings from
# this module, so the ordered-lock machinery can't be imported here
_LISTENERS_MU = threading.Lock()


def on_change(cb) -> None:
    """Subscribe cb(name, value) to every settings.set — the gossip bridge
    (the reference gossips updated cluster settings to every node,
    settings/updater.go); Node wires this to publish into its infostore."""
    with _LISTENERS_MU:
        _CHANGE_LISTENERS.append(cb)


def remove_on_change(cb) -> None:
    with _LISTENERS_MU:
        if cb in _CHANGE_LISTENERS:
            _CHANGE_LISTENERS.remove(cb)


def _notify(name: str, value) -> None:
    with _LISTENERS_MU:
        snapshot = list(_CHANGE_LISTENERS)
    for cb in snapshot:
        cb(name, value)


def reset(name: str | None = None) -> None:
    # a RESET is a value change like any SET: listeners (the gossip bridge)
    # must see it, or peers keep the overridden value forever
    if name is None:
        for s in _REGISTRY.values():
            if s.value is not None:
                s.value = None
                _notify(s.name, s.get())
    else:
        s = _REGISTRY[name]
        s.value = None
        _notify(name, s.get())


def all_settings() -> dict[str, Setting]:
    return dict(_REGISTRY)


def randomize_metamorphic(rng) -> dict[str, Any]:
    """Randomize metamorphic settings (test builds only) — the
    metamorphic-constants analog. Returns what was chosen."""
    chosen = {}
    for s in _REGISTRY.values():
        if not s.metamorphic:
            continue
        if s.kind == "int":
            lo = int(s.lo if s.lo is not None else 1)
            hi = int(s.hi if s.hi is not None else 4096)
            # bias to powers of two (tile sizes)
            pows = [p for p in (256, 512, 1024, 2048, 4096) if lo <= p <= hi]
            v = int(rng.choice(pows)) if pows else int(rng.integers(lo, hi + 1))
        elif s.kind == "bool":
            v = bool(rng.integers(0, 2))
        elif s.kind == "enum":
            v = s.choices[int(rng.integers(len(s.choices)))]
        else:
            continue
        set(s.name, v)
        chosen[s.name] = v
    return chosen


# ---------------------------------------------------------------------------
# The framework's own settings (the ~700-setting registry's seed)

TILE_SIZE = register_int(
    "sql.distsql.tile_size", 1 << 20,
    "static tile capacity for scan batches (coldata batch size analog). "
    "Large tiles amortize XLA dispatch latency (per-round cost not "
    "measured on an attached chip) and keep sorts/gathers wide; resident "
    "tables pad to a tile "
    "multiple so no kernel ever compiles at full-table shape",
    lo=128, hi=1 << 24, metamorphic=True,
)
L0_COMPACTION = register_int(
    "storage.l0_compaction_threshold", 4,
    "number of L0 runs that triggers a compaction "
    "(DefaultPebbleOptions L0CompactionThreshold analog)",
    lo=1, hi=64,
)
WORKMEM_ROWS = register_int(
    "sql.distsql.workmem_rows", 1 << 21,
    "device-tile row budget for buffering operators; exceeding it swaps in "
    "the external (host-partitioned) variant — the workmem/disk-spill "
    "threshold (disk_spiller.go:103 analog)",
    lo=1024,
)
WORKMEM_BYTES = register_int(
    "sql.distsql.workmem_bytes", 2 << 30,
    "per-operator device-byte budget for buffering spools (colmem.Allocator "
    "against mon.BytesMonitor analog); exceeding it swaps in the external "
    "operator variant (disk_spiller.go:103)",
    lo=1 << 16,
)
GRACE_SKEW_SAMPLE = register_int(
    "sql.distsql.grace_skew_sample", 1024,
    "reservoir size for build-side key-hash sampling while a Grace hash "
    "join partitions its input; heavy hitters detected in the sample keep "
    "their build rows resident on device and their probe rows route "
    "through a dedicated hot lane instead of one oversized partition "
    "(0 disables sampling)",
    lo=0, hi=1 << 20,
)
GRACE_SKEW_FRAC = register_float(
    "sql.distsql.grace_skew_frac", 0.05,
    "fraction of the build-side key sample one key hash must own to count "
    "as a heavy hitter for Grace-join skew routing (0 disables routing)",
    lo=0.0, hi=1.0,
)
PALLAS_FILTER = register_enum(
    "storage.pallas_filter", "auto",
    "MVCC window scan-filter implementation: 'auto' uses the fused Pallas "
    "kernel on TPU and the jnp composition everywhere else (the kernel's "
    "tiling targets Mosaic; the GPU/Triton lowering is unexercised); 'on' "
    "forces Pallas, compiled for the backend in use — where that "
    "backend's compiler refuses the kernel its error surfaces (interpret "
    "mode is a test-only handle, never inferred); 'off' forces jnp",
    choices=("auto", "on", "off"),
)
PALLAS_MERGE = register_enum(
    "storage.pallas_merge", "auto",
    "LSM compaction merge implementation: 'auto' uses the bitonic-merge "
    "Pallas kernel on TPU for VMEM-sized merges (log2(N) compare-exchange "
    "stages exploiting run pre-sortedness) and the concat+lax.sort "
    "composition everywhere else; 'on' forces the kernel, compiled for "
    "the backend in use (interpret mode is a test-only handle, never "
    "inferred); 'off' forces concat+sort",
    choices=("auto", "on", "off"),
)
SQL_ADMISSION = register_bool(
    "admission.sql.enabled", True,
    "SQL admission control: every session statement takes a slot from the "
    "shared WorkQueue before executing (work_queue.go role); queue depth "
    "and wait land in admission_sql_queue_depth / admission_wait_seconds",
)
SQL_ADMISSION_SLOTS = register_int(
    "admission.sql.slots", 64,
    "concurrency slots of the SQL admission WorkQueue (the slot-based "
    "GrantCoordinator's size); statements past this run in (priority, "
    "arrival) order as slots free up",
    lo=1,
)
SQL_ADMISSION_MAX_QUEUE_DEPTH = register_int(
    "admission.sql.max_queue_depth", 512,
    "bound on the SQL admission wait queue: past this many queued "
    "statements, admit fails fast with AdmissionRejectedError (SQLSTATE "
    "53300 'server busy' at pgwire) instead of queuing toward collapse. "
    "0 = unbounded",
    lo=0,
)
SQL_ADMISSION_QUEUE_TIMEOUT = register_float(
    "admission.sql.queue_timeout_s", 30.0,
    "backstop deadline on SQL admission queue-wait for statements with "
    "no statement_timeout: past it the wait converts to a typed 53300 "
    "rejection with a retry-after hint (statements WITH a timeout count "
    "queue-wait against it instead). 0 = wait forever",
    lo=0.0,
)
TENANT_RATE = register_float(
    "admission.tenant.rate", 0.0,
    "per-tenant admission token refill rate (statements/s): each tenant "
    "id consumes one token per admitted statement from a bucket "
    "refilling at this rate; an empty bucket rejects with SQLSTATE "
    "53300 + retry-after = refill time. 0 = unlimited (no per-tenant "
    "rate limiting; the fair-share scheduler still applies)",
    lo=0.0,
)
TENANT_BURST = register_int(
    "admission.tenant.burst", 64,
    "per-tenant admission token bucket capacity: an idle tenant banks "
    "up to this many statements' worth of tokens before "
    "admission.tenant.rate throttles it",
    lo=1,
)
SHED_MEM_LOW = register_float(
    "admission.shed.mem_low", 0.90,
    "memory-pressure fraction (flow/memory.py mem_pressure) past which "
    "admission sheds the analytical lane: LOW-priority statements are "
    "rejected with 53300 while interactive traffic still lands",
    lo=0.0, hi=1.0,
)
SHED_MEM_HIGH = register_float(
    "admission.shed.mem_high", 0.97,
    "memory-pressure fraction past which admission sheds NORMAL "
    "priority too — only HIGH (txn control: COMMIT/ROLLBACK) is still "
    "admitted, so in-flight transactions can wind down",
    lo=0.0, hi=1.0,
)
SQL_MEM_ROOT_BUDGET = register_int(
    "sql.mem.root_budget_bytes", 0,
    "node-level logical-byte budget for the root memory monitor "
    "(--max-sql-memory role). 0 = unlimited: the tree still tracks "
    "usage/peaks, and mem_pressure() (read by the IOGovernor) reports 0",
    lo=0,
)
IO_PACING = register_bool(
    "admission.io_pacing.enabled", True,
    "write admission control: engine writes pay a delay proportional to "
    "L0 overload (io_load_listener role) so compaction catches up before "
    "read amplification inverts",
)
BULK_INGEST = register_bool(
    "storage.bulk_ingest.enabled", True,
    "route bulk loads (IMPORT, index backfill, bench loaders) through "
    "the AddSSTable-style run builder (storage/ingest.py): column "
    "batches sort and dedup device-side and link into the LSM as whole "
    "runs — one WAL link record per run instead of per-key WAL appends. "
    "Off falls back to the per-row write path",
)
BLOCK_CACHE_BYTES = register_int(
    "storage.block_cache.size_bytes", 256 << 20,
    "budget for the node-wide block cache of decoded KVBlock windows "
    "(storage/blockcache.py), accounted as a cache-level child of the "
    "root memory monitor tree. 0 disables caching entirely",
    lo=0,
)
COMPACTION_PACING = register_bool(
    "storage.compaction.pacing.enabled", True,
    "schedule size-tiered compactions through the IOGovernor's pacing "
    "loop instead of compacting inline the instant the L0 trigger "
    "trips: small-debt compactions may be deferred (min_interval_ms) so "
    "back-to-back merges can't starve foreground reads",
)
COMPACTION_PACING_INTERVAL = register_int(
    "storage.compaction.pacing.min_interval_ms", 0,
    "minimum milliseconds between paced size-tiered compactions while "
    "debt stays at or under storage.compaction.pacing.max_debt_runs; "
    "0 compacts as eagerly as the unpaced engine",
    lo=0, hi=60_000,
)
COMPACTION_PACING_MAX_DEBT = register_int(
    "storage.compaction.pacing.max_debt_runs", 8,
    "compaction debt (runs past the L0 trigger) above which pacing is "
    "bypassed and compaction runs immediately — read amplification past "
    "this point starves foreground reads worse than the compaction "
    "pause would",
    lo=1, hi=256,
)
DENSE_LUT_BITS = register_int(
    "sql.distsql.dense_lut_bits", 24,
    "max packed-key bits for the dense direct-addressing join index "
    "(ops/join.py): probes become one gather instead of a log2(n) binary "
    "search. 24 bits = a 64MiB int32 position table, far cheaper than the "
    "probe gathers it saves on any TPC-H-scale join",
    lo=0, hi=30,
)
SCAN_STREAM_ROWS = register_int(
    "sql.distsql.scan_stream_rows", 1 << 23,
    "tables larger than this stream host->device tile by tile with "
    "double-buffered async transfers instead of materializing wholly in "
    "HBM (the host half of SURVEY §7's pipelining hard part)",
    lo=1024,
)
MAX_FUSED_JOINS = register_int(
    "sql.distsql.max_fused_joins", 4,
    "maximum join probes composed into one fused streaming segment; deeper "
    "pipelines split into separate jits to bound XLA program size",
    lo=0, hi=64,
)
DENSE_AGG = register_bool(
    "sql.distsql.dense_agg.enabled", True,
    "allow the dense-code small-group aggregation specialization "
    "(falls back to the general sort-groupby path when off)",
    metamorphic=True,
)
FUSION_GENERAL_PROBE = register_bool(
    "sql.distsql.fusion.general_probe", True,
    "fuse duplicate-key inner/left join probes as speculative streaming "
    "emitters (static learned capacity, totals validated once per query) "
    "instead of per-tile host-synced capacity retries",
    metamorphic=True,
)
DENSE_AGG_STATES = register_int(
    "sql.distsql.dense_agg_states", 1 << 23,
    "maximum dense group-code space (product of per-key bounds) for the "
    "scatter-based dense aggregation path; larger key spaces use the "
    "general sort-groupby path",
    lo=64, hi=1 << 28,
)
DENSE_AGG_ACCEL_STATES = register_int(
    "sql.distsql.dense_agg.accel_max_states", 1 << 19,
    "tighter dense-state budget on accelerator backends: XLA:TPU scatters "
    "serialize on the VPU (~100ms per 1M-row segment op, measured), so "
    "big-G dense aggregation loses to the sort+segmented-scan path there "
    "while staying the right choice on CPU (cheap serial scatters)",
    lo=64, hi=1 << 28,
)
DCN_IO_TIMEOUT = register_float(
    "flow.dcn.io_timeout_s", 30.0,
    "deadline on cross-host control-plane socket I/O: flow/gossip/"
    "rangefeed dials, stream handshakes, and per-read waits on "
    "established DCN streams. Generous by design — it is a liveness "
    "backstop against silent peers and half-open TCP, not a latency "
    "SLO; chaos-injected stalls shorter than this must not become "
    "typed failures",
    lo=0.1, hi=600.0,
)
COLLECT_STATS = register_bool(
    "sql.stats.collect_execution_stats", False,
    "collect per-operator ComponentStats on every query; stats are recorded "
    "on the active tracing span (EXPLAIN ANALYZE always collects)",
)
JOIN_ORDER = register_enum(
    "sql.opt.join_order", "heuristic",
    "multi-way join ordering: 'heuristic' starts at the largest estimated "
    "source and greedily joins the smallest connected build side; 'cost' "
    "runs a Selinger-style left-deep DP over the equi-join graph for 2..6 "
    "sources (reorder_joins_limit analog), falling back to the heuristic "
    "when the DP declines",
    choices=("heuristic", "cost"),
)
FAULT_INJECTION = register_bool(
    "fault.injection.enabled", False,
    "arm the chaos fault-injection registry (utils/faults.py); test builds "
    "only — the testing-knobs analog, never enabled in production",
)
RPC_DEADLINE_S = register_float(
    "rpc.batch.deadline_s", 5.0,
    "per-RPC deadline for KV Batch calls (DeadlineExceeded analog); a "
    "timed-out RPC re-dials and retries under rpc.batch.max_retries",
    lo=0.05, hi=300.0,
)
RPC_MAX_RETRIES = register_int(
    "rpc.batch.max_retries", 4,
    "attempts per KV Batch RPC against transient errors (drops, timeouts) "
    "before the failure surfaces (util/retry MaxRetries analog)",
    lo=1, hi=64,
)
BREAKER_TRIP = register_int(
    "rpc.breaker.trip_threshold", 3,
    "consecutive reported RPC failures that open a peer's circuit breaker "
    "(rpc/peer.go reduction)",
    lo=1, hi=100,
)
BREAKER_COOLDOWN_S = register_float(
    "rpc.breaker.cooldown_s", 5.0,
    "open-breaker cooldown before the half-open probe is admitted",
    lo=0.01, hi=600.0,
)
FLOW_DEADLINE_S = register_float(
    "sql.distsql.flow_deadline_s", 30.0,
    "end-to-end deadline for a cross-host distributed query (setup + "
    "stream drain); on expiry remote flows are cancelled and the gateway "
    "degrades or errors (flowinfra timeout discipline)",
    lo=0.1, hi=3600.0,
)
SPLIT_QPS_THRESHOLD = register_float(
    "kv.range.split_qps_threshold", 2500.0,
    "decayed per-range QPS above which the split queue cuts the range at "
    "a sampled mid-load key (kv.range_split.load_qps_threshold analog)",
    lo=0.001, hi=1e9,
)
RANGE_MAX_BYTES = register_int(
    "kv.range.max_bytes", 64 << 20,
    "authoritative logical size above which the split queue cuts a range "
    "regardless of load (zone-config range_max_bytes analog); ranges whose "
    "combined size stays under half of this are merge candidates",
    lo=256,
)
RANGE_MERGE_ENABLED = register_bool(
    "kv.range.merge_enabled", True,
    "let the merge queue absorb a cold range into its cold left neighbor "
    "(kv.range_merge.queue_enabled analog); disable to freeze boundaries",
)
ALLOCATOR_ENABLED = register_bool(
    "kv.allocator.enabled", True,
    "run the range-lifecycle queues (split/merge/rebalance) on node start; "
    "the queues are also constructible standalone for deterministic tests",
)
FUSION_ENABLED = register_bool(
    "sql.distsql.fusion.enabled", True,
    "collapse contiguous stateless per-tile operator chains (filter / "
    "project / hash-bucket / fusable join probes) into single-kernel "
    "FusedPipeline segments at plan build (flow/fuse.py), so XLA fuses "
    "each chain into one dispatch and intermediate padded tiles never "
    "materialize; off runs the classic one-jit-per-operator pull path",
    metamorphic=True,
)
LOCK_ORDER_CHECKS = register_bool(
    "debug.lock_order.enabled", False,
    "make every utils/locks.OrderedLock acquisition verify the global "
    "lock-acquisition order (deadlock_detection analog): acquiring B "
    "while holding A records edge A->B, and an acquisition that would "
    "close a cycle raises LockOrderError instead of deadlocking; off "
    "(default) the wrappers are plain locks with no per-acquire overhead",
)
RACE_DETECTOR = register_bool(
    "debug.race_detector.enabled", False,
    "arm the runtime data-race sanitizer (utils/racesan.py): tracked "
    "control-plane fields run the Eraser lockset algorithm — a "
    "lockset-disjoint write/write or write/read across threads raises "
    "DataRaceError at the access instead of corrupting state; also keeps "
    "the per-thread held-lock stack live. Off (default) every "
    "note_read/note_write is a single settings check",
)
SHAPE_BUCKETS_ENABLED = register_bool(
    "sql.distsql.shape_buckets.enabled", True,
    "pad sub-tile resident tables up the canonical pow2 shape ladder "
    "(catalog.SHAPE_BUCKETS: 1k/8k/64k/512k/2M) instead of to their own "
    "1024-aligned cardinality, so kernels over small tables compile at a "
    "handful of process-shared shapes; masks keep padded rows dead, so "
    "results are bit-identical either way (tested)",
    metamorphic=True,
)
PLAN_CACHE_ENABLED = register_bool(
    "sql.plan_cache.enabled", True,
    "serve repeat statements (same structure, any numeric literals) from "
    "the prepared-plan LRU (sql/plancache.py): the cached operator tree "
    "rebinds literals as jit arguments, so the second execution performs "
    "zero new XLA compiles — the pgwire extended-protocol fast path",
)
PLAN_CACHE_SIZE = register_int(
    "sql.plan_cache.size", 128,
    "maximum prepared plans held by the per-catalog plan cache before "
    "LRU eviction (each entry pins a built operator tree and its "
    "compiled kernels)",
    lo=1, hi=1 << 16,
)
COMPILE_CACHE_ENABLED = register_bool(
    "sql.compile_cache.enabled", True,
    "persist XLA compilations to disk (jax compilation cache, L3 of the "
    "cache hierarchy) so process restarts reuse executables instead of "
    "recompiling the fleet; the directory is JAX_COMPILATION_CACHE_DIR "
    "when set, else <checkout>/.jax_cache (utils/backend.py)",
)
SLOW_QUERY_THRESHOLD = register_float(
    "sql.log.slow_query.latency_threshold", 0.0,
    "when > 0, any statement slower than this many seconds is logged to "
    "the SQL_EXEC channel and a statement diagnostics bundle (trace, "
    "plan, counters — sql/diagnostics.py) is captured to the bounded "
    "on-disk ring; 0 disables",
    lo=0.0,
)
XLA_PROFILE = register_bool(
    "sql.trace.xla_profile", False,
    "annotate query execution with jax.profiler.TraceAnnotation so "
    "device timelines captured by an external profiler carry query "
    "boundaries; off by default — the profiler is optional and queries "
    "must run without it",
)
DIAG_RING_SIZE = register_int(
    "sql.diagnostics.ring_size", 16,
    "maximum statement diagnostics bundles retained on disk before the "
    "oldest is evicted (sql/diagnostics.py ring); each bundle is a JSON "
    "file with the trace, plan, and counter snapshot",
    lo=1, hi=1 << 12,
)
DIAG_DIR = register_string(
    "sql.diagnostics.dir", "",
    "directory for statement diagnostics bundles; empty uses a "
    "per-process temporary directory cleaned up on interpreter exit",
)
TS_RETENTION_SECONDS = register_float(
    "ts.retention_seconds", 600.0,
    "timeseries retention horizon: the background metrics scraper "
    "(server/node.py) prunes kv/tsdb.py samples older than this after "
    "each scrape tick; 0 disables pruning",
    lo=0.0,
)
CHANGEFEED_FANOUT_BUFFER_BYTES = register_int(
    "changefeed.fanout.buffer_bytes", 1 << 20,
    "per-subscriber fan-out buffer budget (bytes), charged to the "
    "node's changefeed staging account; the backpressure ladder "
    "(coalesce -> shed -> evict) engages against this bound",
    lo=4096,
)
CHANGEFEED_FANOUT_HIGHWATER_FRAC = register_float(
    "changefeed.fanout.highwater_frac", 0.5,
    "fraction of the per-subscriber buffer budget at which duplicate-key "
    "events start coalescing to newest-version-per-key",
    lo=0.05, hi=1.0,
)
CHANGEFEED_FANOUT_SEND_DEADLINE_S = register_float(
    "changefeed.fanout.send_deadline_s", 5.0,
    "liveness bound on a subscriber connection: a send that blocks "
    "longer than this, or a subscriber with pending work and no "
    "successful send within it, is evicted (SlowConsumerError) and its "
    "sender thread reaped",
    lo=0.05,
)
CHANGEFEED_FANOUT_HEARTBEAT_S = register_float(
    "changefeed.fanout.heartbeat_s", 1.0,
    "idle-connection heartbeat: a subscriber with no new events still "
    "receives a resolved-timestamp checkpoint this often, so a dead "
    "socket is detected within heartbeat + send deadline",
    lo=0.05,
)
CHANGEFEED_FANOUT_MAX_SUBSCRIBERS = register_int(
    "changefeed.fanout.max_subscribers", 4096,
    "bound on concurrently registered fan-out subscribers per hub; "
    "past it new subscriptions are refused with a typed error frame "
    "instead of degrading everyone",
    lo=1,
)
MATVIEW_ENABLED = register_bool(
    "sql.matview.enabled", True,
    "master switch for the materialized-view subsystem: CREATE "
    "MATERIALIZED VIEW is refused when off (existing views keep "
    "serving their last refreshed state)",
)
MATVIEW_REWRITE_ENABLED = register_bool(
    "sql.matview.rewrite.enabled", True,
    "planner rewrite: a SELECT whose parameterized plan matches a "
    "registered materialized view's defining query is served from the "
    "standing state (AS OF the view's resolved frontier) instead of "
    "rescanning the base table",
)
MATVIEW_REFRESH_ON_READ = register_bool(
    "sql.matview.refresh_on_read.enabled", True,
    "drain pending changefeed deltas into a view's standing state "
    "before a statement that reads it; off = reads serve the state as "
    "of the last flush (the AS OF freshness bound is the frontier)",
)
MATVIEW_STAGING_BYTES = register_int(
    "sql.matview.staging_bytes", 4 << 20,
    "budget for a view maintainer's delta-tile staging account (the "
    "columnar insert/retract tiles built per flush are charged here "
    "before the fused maintenance dispatch)",
    lo=4096,
)
CHANGEFEED_FANOUT_MAX_SHEDS = register_int(
    "changefeed.fanout.max_consecutive_sheds", 3,
    "a subscriber whose buffer is shed to catch-up-scan this many times "
    "in a row without ever draining is evicted (the terminal rung of "
    "the backpressure ladder)",
    lo=1,
)
TS_SCRAPE_INTERVAL = register_float(
    "ts.scrape_interval_seconds", 10.0,
    "seconds between background metrics-scraper ticks on a server node "
    "(each tick records every registry counter/gauge into the "
    "timeseries store under cr.node.*)",
    lo=0.1,
)
