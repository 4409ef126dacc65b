"""Metrics registry — the pkg/util/metric analog.

Reference: metric.go:326 defines prometheus-backed Gauge/Counter/Histogram
types collected into a Registry and exported at /_status/vars; subsystems
register their metrics at construction. Here the registry is process-local
(the HTTP exporter arrives with the server layer) with the same three
types, a prometheus-text dump for scraping/tests, and the engine + flow
wired in as the first producers.
"""

from __future__ import annotations

import bisect
import threading
from dataclasses import dataclass, field


@dataclass
class Counter:
    """Monotonically increasing value (metric.Counter)."""

    name: str
    help: str = ""
    _value: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def inc(self, delta: float = 1.0) -> None:
        with self._lock:
            self._value += delta

    @property
    def value(self) -> float:
        return self._value


@dataclass
class Gauge:
    """Set-to-current value (metric.Gauge)."""

    name: str
    help: str = ""
    _value: float = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram (metric.Histogram reduced: no windowing)."""

    def __init__(self, name: str, help: str = "",
                 buckets: tuple[float, ...] = (
                     0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60)):
        self.name = name
        self.help = help
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.n = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            i = bisect.bisect_left(self.buckets, v)
            self.counts[i] += 1
            self.sum += v
            self.n += 1


class LabeledCounter:
    """Counter family keyed by one label (metric.Counter vector reduced).

    Mirrors the reference's per-range metric families: one logical name,
    one label dimension (e.g. range), a child Counter per observed label
    value. scrape() renders ``name{label="v"} n`` lines."""

    def __init__(self, name: str, label: str, help: str = ""):
        self.name = name
        self.label = label
        self.help = help
        self._children: dict[str, Counter] = {}
        self._lock = threading.Lock()

    def child(self, label_value) -> Counter:
        key = str(label_value)
        with self._lock:
            c = self._children.get(key)
            if c is None:
                c = self._children[key] = Counter(self.name)
            return c

    def inc(self, label_value, delta: float = 1.0) -> None:
        self.child(label_value).inc(delta)

    def value(self, label_value) -> float:
        return self.child(label_value).value

    def total(self) -> float:
        with self._lock:
            return sum(c.value for c in self._children.values())

    def items(self) -> list[tuple[str, float]]:
        with self._lock:
            return sorted((k, c.value) for k, c in self._children.items())


class LabeledGauge:
    """Gauge family keyed by one label (the gauge half of the labeled
    families: per-tenant admission tokens, per-lane queue depth). One
    logical name, one label dimension, a child Gauge per observed label
    value. scrape() renders ``name{label="v"} n`` lines."""

    def __init__(self, name: str, label: str, help: str = ""):
        self.name = name
        self.label = label
        self.help = help
        self._children: dict[str, Gauge] = {}
        self._lock = threading.Lock()

    def child(self, label_value) -> Gauge:
        key = str(label_value)
        with self._lock:
            g = self._children.get(key)
            if g is None:
                g = self._children[key] = Gauge(self.name)
            return g

    def set(self, label_value, v: float) -> None:
        self.child(label_value).set(v)

    def value(self, label_value) -> float:
        return self.child(label_value).value

    def items(self) -> list[tuple[str, float]]:
        with self._lock:
            return sorted((k, g.value) for k, g in self._children.items())


class Registry:
    """Named metric collection (metric.Registry). Subsystems register at
    construction; scrape() renders prometheus text exposition."""

    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_add(name, lambda: Counter(name, help))

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_add(name, lambda: Gauge(name, help))

    def histogram(self, name: str, help: str = "", **kw) -> Histogram:
        return self._get_or_add(name, lambda: Histogram(name, help, **kw))

    def labeled_counter(self, name: str, label: str,
                        help: str = "") -> LabeledCounter:
        return self._get_or_add(
            name, lambda: LabeledCounter(name, label, help))

    def labeled_gauge(self, name: str, label: str,
                      help: str = "") -> LabeledGauge:
        return self._get_or_add(
            name, lambda: LabeledGauge(name, label, help))

    def _get_or_add(self, name: str, make):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = make()
            return m

    def scrape(self) -> str:
        """Prometheus text exposition (the /_status/vars shape)."""
        out: list[str] = []
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if isinstance(m, Counter):
                out.append(f"# TYPE {name} counter")
                out.append(f"{name} {m.value:g}")
            elif isinstance(m, Gauge):
                out.append(f"# TYPE {name} gauge")
                out.append(f"{name} {m.value:g}")
            elif isinstance(m, LabeledCounter):
                out.append(f"# TYPE {name} counter")
                for k, v in m.items():
                    out.append(f'{name}{{{m.label}="{k}"}} {v:g}')
            elif isinstance(m, LabeledGauge):
                out.append(f"# TYPE {name} gauge")
                for k, v in m.items():
                    out.append(f'{name}{{{m.label}="{k}"}} {v:g}')
            elif isinstance(m, Histogram):
                out.append(f"# TYPE {name} histogram")
                cum = 0
                for b, c in zip(m.buckets, m.counts):
                    cum += c
                    out.append(f'{name}_bucket{{le="{b:g}"}} {cum}')
                cum += m.counts[-1]
                out.append(f'{name}_bucket{{le="+Inf"}} {cum}')
                out.append(f"{name}_sum {m.sum:g}")
                out.append(f"{name}_count {m.n}")
        return "\n".join(out) + "\n"


# the process-default registry (subsystems use this unless injected)
DEFAULT = Registry()

# engine + flow metrics (first producers; names mirror the reference's
# storage.*/sql.* metric families)
ENGINE_FLUSHES = DEFAULT.counter(
    "storage_flushes", "memtable flushes to sorted runs")
ENGINE_COMPACTIONS = DEFAULT.counter(
    "storage_compactions", "size-tiered compaction passes")
ENGINE_INGESTS = DEFAULT.counter(
    "storage_ingests", "bulk ingests (AddSSTable path)")
ENGINE_WRITES = DEFAULT.counter(
    "storage_writes", "KV write operations (put/delete)")
ENGINE_SCANS = DEFAULT.counter("storage_scans", "KV scan operations")
ENGINE_RUNS = DEFAULT.gauge("storage_runs", "sorted runs in the LSM")
QUERIES = DEFAULT.counter("sql_queries", "queries executed by run_operator")
PG_CONNS = DEFAULT.counter("pgwire_conns", "pgwire connections accepted")
QUERY_SECONDS = DEFAULT.histogram(
    "sql_query_seconds", "end-to-end query latency")
TXN_COMMITS = DEFAULT.counter("txn_commits", "committed transactions")
KV_POINT_READS = DEFAULT.counter(
    "sql_kv_point_reads",
    "primary keys read by the point-lookup plan route (KVTable.point_rows)")
ENGINE_SNAPSHOT_READS = DEFAULT.counter(
    "storage_engine_snapshot_reads",
    "point reads served off a snapshot (Engine.get): the store's mutex "
    "held while the snapshot is taken, released for the searches, the "
    "launches and the readback")
ENGINE_SNAPSHOT_BUILDS = DEFAULT.counter(
    "storage_engine_snapshot_builds",
    "read snapshots of the run set rebuilt because the run set changed "
    "(flush, ingest, compaction, a resolution that rewrote a run)")
KV_RANGE_READS = DEFAULT.counter(
    "sql_kv_range_reads",
    "pages read by the primary-key range plan route "
    "(KVTable.range_batches: one Engine.range_read each)")
KV_RANGE_ROWS = DEFAULT.counter(
    "sql_kv_range_rows",
    "rows the primary-key range route returned (newest visible version a "
    "key, inside the bounds)")
KV_RANGE_WINDOW_ROWS = DEFAULT.counter(
    "sql_kv_range_window_rows",
    "rows of run and memtable windows the primary-key range route sliced "
    "or took from the block cache: its read amplification")
KV_TABLE_DECODES = DEFAULT.counter(
    "sql_kv_table_decodes",
    "whole-table columnar decodes of a KV-backed table "
    "(KVTable.device_batch: the merged view of every run, filtered)")
ENGINE_COMMITS = DEFAULT.counter(
    "storage_intent_commits",
    "intent resolutions that committed (Engine.resolve_intents)")
ENGINE_RESOLVE_RUN_SORTS = DEFAULT.counter(
    "storage_resolve_run_sorts",
    "runs rewritten and re-sorted by an intent resolution: a run is "
    "touched only when it holds an intent of the resolved transaction (a "
    "flush came between the write and the commit)")
TXN_RETRIES = DEFAULT.counter("txn_retries", "transaction retries")
RANGE_SPLITS = DEFAULT.counter("range_splits", "admin range splits")
BLOOM_SKIPS = DEFAULT.counter(
    "storage_bloom_skips", "runs skipped by bloom filters on point reads")
BLOOM_CORRUPTIONS = DEFAULT.counter(
    "storage_bloom_corruptions",
    "bloom filters disabled after their lazy CRC verification failed on "
    "a first negative (the filter answers maybe forever after; reads "
    "stay correct, just unfiltered)")
BLOCKCACHE_HITS = DEFAULT.counter(
    "storage_blockcache_hits",
    "point/seek read windows served from the node block cache")
BLOCKCACHE_MISSES = DEFAULT.counter(
    "storage_blockcache_misses",
    "block-cache lookups that fell through to a device window slice")
BLOCKCACHE_EVICTIONS = DEFAULT.counter(
    "storage_blockcache_evictions",
    "cached windows evicted by the clock sweep under budget pressure")
BLOCKCACHE_BYTES = DEFAULT.gauge(
    "storage_blockcache_bytes",
    "bytes of decoded KVBlock windows resident in the node block cache")
INGEST_ROWS = DEFAULT.counter(
    "storage_ingest_rows",
    "rows landed as device-built runs through the bulk-ingest path")
INGEST_BYTES = DEFAULT.counter(
    "storage_ingest_bytes",
    "logical key+value bytes landed through the bulk-ingest path")
COMPACTION_PACING_DELAY = DEFAULT.histogram(
    "storage_compaction_pacing_delay_seconds",
    "how long the IOGovernor's pacing loop deferred a pending "
    "size-tiered compaction before it ran",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 60.0))
EXTERNAL_AGG_SPILLS = DEFAULT.counter(
    "sql_external_agg_spills", "aggregations spilled to Grace partitions")
RANGE_MOVES = DEFAULT.counter(
    "range_moves", "range relocations between stores")
RPC_RETRIES = DEFAULT.counter(
    "rpc_retries", "RPC attempts retried past transient errors")
RPC_TIMEOUTS = DEFAULT.counter(
    "rpc_timeouts", "RPCs that exceeded their per-call deadline")
FAULTS_INJECTED = DEFAULT.counter(
    "faults_injected", "chaos faults fired by utils/faults.py")
DIST_DEGRADED = DEFAULT.counter(
    "distsql_degraded_queries",
    "cross-host queries re-planned onto surviving hosts or run locally "
    "after a host became unreachable")
DIST_FLOWS_CANCELLED = DEFAULT.counter(
    "distsql_flows_cancelled",
    "remote flow registrations torn down by gateway cancellation")
BREAKER_TRIPS = DEFAULT.counter(
    "rpc_breaker_trips", "circuit breakers opened by failure reports")
RANGE_CACHE_EVICTIONS = DEFAULT.counter(
    "range_cache_evictions",
    "stale range-descriptor cache entries evicted after mismatches")
REPLAY_CACHE_HITS = DEFAULT.counter(
    "kv_replay_cache_hits",
    "retried mutation batches deduplicated by the server replay cache")
AMBIGUOUS_RESULTS = DEFAULT.counter(
    "kv_ambiguous_results",
    "mutation batches whose apply state was unknowable after retries")
RPC_RETRIES_BY_RANGE = DEFAULT.labeled_counter(
    "rpc_retries_by_range", "range",
    "RPC retries attributed to the range being addressed")
RPC_RETRY_BUDGET_EXHAUSTED = DEFAULT.counter(
    "rpc_retry_budget_exhausted",
    "RPCs abandoned because their range's retry budget ran dry")
LEASE_FAILOVERS = DEFAULT.counter(
    "kv_lease_failovers",
    "range leases transferred after epoch-fencing an expired holder")
GOSSIP_INFOS_EVICTED = DEFAULT.counter(
    "gossip_infos_evicted",
    "gossip infos dropped by the bound or by liveness-epoch expiry")
REPLICATION_RECONNECTS = DEFAULT.counter(
    "replication_stream_reconnects",
    "replication streams re-subscribed after a transport error")
KV_RANGE_SPLITS = DEFAULT.counter(
    "kv_range_splits",
    "load/size-driven range splits applied by the split queue "
    "(distinct from range_splits, which counts admin splits)")
KV_RANGE_MERGES = DEFAULT.counter(
    "kv_range_merges",
    "cold adjacent ranges absorbed by the merge queue")
KV_LEASE_TRANSFERS = DEFAULT.counter(
    "kv_lease_transfers",
    "range leases moved to underfull stores by the rebalancer")
RANGE_MERGES = DEFAULT.counter(
    "range_merges", "range boundary removals (meta merge_at applications)")
RANGE_CACHE_COALESCED = DEFAULT.counter(
    "range_cache_coalesced_lookups",
    "authoritative meta lookups answered by an in-flight peer lookup "
    "instead of stampeding the meta range (single-flight)")
CONTENTION_RECORD_ERRORS = DEFAULT.counter(
    "contention_record_errors",
    "failures recording a contention event into the registry (the "
    "conflict path continues; the event is lost to observability)")
KERNEL_DISPATCHES = DEFAULT.counter(
    "sql_kernel_dispatches",
    "XLA executable dispatches issued by the flow layer (each jitted "
    "kernel call is one accelerator round trip; flow/dispatch.py)")
FUSED_PIPELINE_LENGTHS = DEFAULT.histogram(
    "sql_fused_pipeline_lengths",
    "operators collapsed into each FusedPipeline segment by the "
    "plan-build fusion pass (flow/fuse.py)",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32))
KERNEL_COMPILES = DEFAULT.counter(
    "sql_kernel_compiles",
    "new XLA traces/compiles issued through flow/dispatch.jit (each is a "
    "fresh executable specialization; the zero-recompile serving path "
    "holds this flat on repeat queries)")
KERNEL_CACHE_HITS = DEFAULT.counter(
    "sql_kernel_cache_hits",
    "kernel constructions answered by the process-global dispatch.jit "
    "key= cache (structurally identical kernels share one wrapper)")
PLAN_CACHE_HITS = DEFAULT.counter(
    "sql_plan_cache_hits",
    "statements served by a cached prepared plan (build->fuse->compile "
    "skipped; literals rebound into the cached operator tree)")
PLAN_CACHE_MISSES = DEFAULT.counter(
    "sql_plan_cache_misses",
    "cacheable statements that built a fresh plan (first sight, schema "
    "change, or settings change)")
PLAN_CACHE_EVICTIONS = DEFAULT.counter(
    "sql_plan_cache_evictions",
    "prepared plans dropped by LRU capacity or catalog-version bumps "
    "(DDL invalidation)")
PLAN_CACHE_POOL_BUILDS = DEFAULT.counter(
    "sql_plan_cache_pool_builds",
    "operator trees built beyond a plan-cache entry's first, because a "
    "session found every tree of a plan that keeps nothing between runs "
    "out with another session (sql/plancache.py)")
PLAN_CACHE_POOL_RUNS = DEFAULT.counter(
    "sql_plan_cache_pool_runs",
    "statements that ran on a tree other than their plan-cache entry's "
    "first")
PLAN_CACHE_MESH_RUNS = DEFAULT.counter(
    "sql_plan_cache_mesh_runs",
    "runs of a plan-cache entry's mesh program (parallel/planner.py "
    "MeshOp): a statement of a multi-device node that ran across its "
    "devices; an overflow's re-run counts again")
SQL_MEM_CURRENT = DEFAULT.gauge(
    "sql_mem_current",
    "logical SQL bytes currently reserved against the node's root memory "
    "monitor (flow/memory.py BytesMonitor tree)")
SQL_MEM_MAX = DEFAULT.gauge(
    "sql_mem_max",
    "high water of sql_mem_current since process start (the root "
    "monitor's peak reservation)")
SQL_MEM_QUERY_PEAK = DEFAULT.histogram(
    "sql_mem_query_peak_bytes",
    "per-query peak logical memory at query-monitor close (bytes)",
    buckets=(1 << 12, 1 << 16, 1 << 20, 1 << 22, 1 << 24, 1 << 26,
             1 << 28, 1 << 30, 1 << 32, 1 << 34))
SQL_MEM_QUERY_LEAKS = DEFAULT.counter(
    "sql_mem_query_leaks",
    "query memory monitors that closed with bytes still reserved (an "
    "operator failed to release its account — always a bug; "
    "scripts/check_no_leaks.py asserts this stays flat)")
EXTERNAL_SORT_SPILLS = DEFAULT.counter(
    "sql_external_sort_spills",
    "sorts that exceeded workmem and spilled to the external "
    "range-partitioned sort")
GRACE_JOIN_SPILLS = DEFAULT.counter(
    "sql_grace_join_spills",
    "hash joins whose build side exceeded workmem and spilled to the "
    "Grace hash join")
GRACE_JOIN_MERGE_PARTS = DEFAULT.counter(
    "sql_grace_join_merge_parts",
    "Grace join partitions whose build side alone exceeded workmem and "
    "degraded to chunked sorted-run merge probing instead of one "
    "in-memory hash table")
GRACE_JOIN_SKEW_ROUTED = DEFAULT.counter(
    "sql_grace_join_skew_rows",
    "probe rows routed through the resident heavy-hitter build table "
    "instead of host partitions during a Grace hash join")
ADMISSION_SQL_SLOTS = DEFAULT.gauge(
    "admission_sql_slots",
    "configured concurrency slots of the SQL admission WorkQueue "
    "(admission.sql.slots)")
ADMISSION_SQL_SLOTS_IN_USE = DEFAULT.gauge(
    "admission_sql_slots_in_use",
    "SQL admission slots currently granted to executing statements")
ADMISSION_SQL_QUEUE_DEPTH = DEFAULT.gauge(
    "admission_sql_queue_depth",
    "statements waiting in the SQL admission queue for a slot")
ADMISSION_WAIT_SECONDS = DEFAULT.histogram(
    "admission_wait_seconds",
    "time statements spent queued in SQL admission before their slot "
    "was granted",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5,
             10, 60))
ADMISSION_SQL_TIMEOUTS = DEFAULT.counter(
    "admission_sql_timeouts",
    "admission waits that hit their timeout and withdrew (any "
    "concurrently granted slot is handed back, never leaked)")
ADMISSION_LANE_QUEUE_DEPTH = DEFAULT.labeled_gauge(
    "admission_lane_queue_depth", "lane",
    "statements waiting in the SQL admission queue by priority lane "
    "(interactive = point/DML at NORMAL/HIGH, analytical = LOW — the "
    "lane shed first under overload)")
ADMISSION_TENANT_TOKENS = DEFAULT.labeled_gauge(
    "admission_tenant_tokens", "tenant",
    "admission token-bucket level by tenant id (admission.tenant.rate/"
    "burst); -1 when the tenant is not rate-limited")
CHANGEFEED_SUBSCRIBERS = DEFAULT.gauge(
    "changefeed_subscribers",
    "rangefeed fan-out subscribers currently registered across all hubs "
    "on this node (live + in catch-up)")
CHANGEFEED_EVENTS_EMITTED = DEFAULT.counter(
    "changefeed_events_emitted",
    "event frames delivered to fan-out subscribers (catch-up scan "
    "events included; checkpoints excluded)")
CHANGEFEED_EVENTS_COALESCED = DEFAULT.counter(
    "changefeed_events_coalesced",
    "buffered events dropped by duplicate-key coalescing — rung one of "
    "the slow-consumer backpressure ladder (the subscriber still sees "
    "the newest version of every key)")
CHANGEFEED_SHEDS = DEFAULT.counter(
    "changefeed_sheds",
    "subscriber buffers shed to catch-up-scan — rung two of the ladder: "
    "the buffer is dropped and the subscriber is re-fed by an engine "
    "scan from its frontier instead of from memory")
CHANGEFEED_EVICTIONS = DEFAULT.counter(
    "changefeed_evictions",
    "subscribers evicted with SlowConsumerError (send deadline "
    "exceeded, dead socket, or repeated sheds without draining)")
CHANGEFEED_BUFFER_BYTES = DEFAULT.gauge(
    "changefeed_buffer_bytes",
    "bytes currently buffered across all fan-out subscribers (the "
    "changefeed staging account under the node monitor root)")
CHANGEFEED_SEND_LAG_SECONDS = DEFAULT.histogram(
    "changefeed_send_lag_seconds",
    "per-event delay from hub enqueue to subscriber socket send — the "
    "fan-out plane's delivery-lag distribution")
MATVIEW_VIEWS = DEFAULT.gauge(
    "matview_views",
    "materialized views currently registered on this node")
MATVIEW_FLUSHES = DEFAULT.counter(
    "matview_flushes",
    "view-maintenance flushes: each drains a base table's buffered "
    "changefeed delta into every standing view in a handful of fused "
    "dispatches and advances the shared resolved frontier")
MATVIEW_DELTA_EVENTS = DEFAULT.counter(
    "matview_delta_events",
    "changefeed events (inserts, updates, tombstones) applied to "
    "standing view state incrementally — the work a full rescan never "
    "has to do")
MATVIEW_FULL_RESCANS = DEFAULT.counter(
    "matview_full_rescans",
    "views rebuilt by a base-table rescan instead of delta work: "
    "initial population at CREATE, restart recovery, and the "
    "out-of-bounds group-key fallback (a group key outside the dense "
    "layout minted since CREATE)")
MATVIEW_MINMAX_RESCANS = DEFAULT.counter(
    "matview_minmax_rescans",
    "per-view re-scan fallbacks after a retraction hit a group's "
    "current min/max extremum — the one aggregate family that cannot "
    "retract natively")
MATVIEW_REWRITE_HITS = DEFAULT.counter(
    "matview_rewrite_hits",
    "SELECTs whose plan matched a registered view's defining query and "
    "were served from standing state by the settings-gated planner "
    "rewrite (sql.matview.rewrite.enabled)")
MATVIEW_REFRESH_LAG_SECONDS = DEFAULT.histogram(
    "matview_refresh_lag_seconds",
    "per-flush staleness closed by view maintenance: wall-clock age of "
    "the oldest buffered event when its flush lands")
ADMISSION_REJECTIONS = DEFAULT.labeled_counter(
    "admission_rejections", "tenant",
    "statements refused admission by tenant id (queue full, rate "
    "limit, overload shed, or queue-wait deadline) — surfaced to "
    "clients as SQLSTATE 53300 'server busy' with a retry-after hint")
