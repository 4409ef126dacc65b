"""Fault-injection registry — settings-gated, deterministic chaos hooks.

Reference mapping (each named site's CockroachDB analogue):

- ``kv.rpc.client.batch``   — DistSender send errors (kvcoord/
  dist_sender.go's sendError paths): the request is dropped/delayed on
  the wire before the server evaluates it.
- ``kv.rpc.server.eval``    — replica-side evaluation failure
  (kvserver's TestingEvalFilter knobs): the server errors/hangs before
  touching the store, the client sees a severed stream.
- ``flow.host.setup``       — SetupFlow RPC failure (distsql/server.go
  SetupFlow returning an error to the gateway).
- ``flow.host.stream``      — FlowStream attach/stream failure
  (flowinfra's ConnectInboundStream timeout/error discipline).
- ``kv.dialer.dial``        — nodedialer connect failures (rpc/
  nodedialer's breaker-tracked dials).
- ``storage.wal.append``    — pebble WAL write errors (vfs error
  injection, pebble's errorfs): delay models a stalling disk, `partial`
  models a torn append (half a record hits the platter before the
  crash), error models EIO.
- ``storage.wal.fsync``     — fsync stall/failure (pebble's
  WALFailover trigger condition).
- ``kv.rpc.server.respond`` — the server applied the batch but the
  response never reached the client (the classic ambiguous-result
  window: kvcoord's sendError after a successful proposal). `drop`
  severs the stream post-apply.
- ``liveness.heartbeat``    — node-liveness heartbeat failures
  (liveness.go's heartbeat RPC timing out / losing the disk). Sites
  also fire a node-scoped variant ``liveness.heartbeat.n<id>`` so a
  test can blackhole ONE node's heartbeats while others stay live.
- ``liveness.epoch_bump``   — the IncrementEpoch CPut failing
  (liveness.go's IncrementEpoch contention path). Node-scoped variant
  ``liveness.epoch_bump.n<id>`` keyed by the node DOING the bump.
- ``gossip.broadcast``      — gossip exchange failures (gossip.go's
  client connect/send errors). Node-scoped ``gossip.broadcast.n<id>``.
- ``kv.rangefeed.subscribe`` — rangefeed (re)subscription failures
  (kvclient/rangefeed's restart-on-error discipline).
- ``ranger.split.apply``     — split-queue crash AFTER the meta write
  but BEFORE bookkeeping (lease carry / cache repair / load handoff) —
  the splitTrigger's partial-application window. Queue purgatory
  retries must converge.
- ``ranger.merge.apply``     — merge-queue crash after the boundary is
  removed from meta but before bookkeeping (mergeTrigger window).
- ``ranger.lease.transfer``  — the range's data moved but the lease
  transfer write was lost (AdminTransferLease's in-flight window);
  retry must be a no-op move + lease stamp.
- ``storage.ingest.link``    — AddSSTable crash window: the bulk-ingest
  run's side file is durable but the WAL link record never lands
  (cmd_add_sstable's link-don't-copy torn-link case). The run must stay
  invisible — replay sees no record — and a retry must land it cleanly;
  the orphaned side file is cleaned at the next checkpoint.
- ``storage.compaction.swap`` — crash between a compaction's run-set
  swap and its cache/bloom bookkeeping: block-cache invalidation for the
  replaced runs must still happen or readers could be served stale
  cached windows.
- ``flow.spill.partition_write`` — a host spill-partition write failing
  mid-stage (colcontainer's disk queue enqueue erroring,
  diskqueue.go's write path): the spilling operator's query fails but
  the staging account must not retain bytes for rows never staged,
  and monitors must still drain to zero.
- ``flow.spill.merge_probe`` — an oversized Grace-join partition's
  sorted-run merge-probe failing between runs (the external joiner's
  partition-processing window): partial join output may already have
  streamed downstream; the query must surface the error and a clean
  re-run must produce complete, correct output.
- ``storage.bloom.build``    — bloom filter construction failure.
  `error` models an allocation/build crash (the run serves reads
  filterless — correct, just unpruned); `partial` models silent bit
  corruption after the build checksum was taken — the lazy CRC verify
  must disable the filter on its first negative answer, preserving the
  zero-false-negative guarantee.

- ``changefeed.fanout.enqueue`` — fan-out buffer enqueue failure
  (kvserver/rangefeed's BufferedSender overflow path): the batch never
  reaches the subscriber's buffer; the subscriber sheds to a
  catch-up-scan from its frontier, so nothing is lost and no bytes leak.
- ``changefeed.subscriber.send`` — subscriber stream send failure
  mid-event (the MuxRangeFeed per-stream error discipline): the
  subscriber is evicted and resumes by reconnecting from its frontier.
- ``changefeed.frontier.checkpoint`` — resolved-timestamp checkpoint
  write/send failure (changefeedccl's frontier persistence): the
  frontier stays stale, so a resume re-delivers (idempotent by (ts,
  key)) rather than ever skipping events.
- ``matview.flush`` / ``matview.delta.apply`` /
  ``matview.frontier.checkpoint`` — materialized-view maintenance
  failures at flush start, inside a delta-kernel apply, and between
  compute and the frontier/state swap. All three leave the buffered
  delta in place and the standing state untouched, so the retrying
  flush re-applies the identical delta from the old frontier —
  bit-identical to a fresh full scan, nothing lost or duplicated.

Discipline: everything is OFF unless ``fault.injection.enabled`` is set
AND the test armed specs via :func:`arm`. Firing decisions come from ONE
seeded ``random.Random`` so a chaos run replays exactly given its seed.
Sites call :func:`fire` which is a cheap no-op (one module-bool check)
when disarmed — production paths pay nothing.
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass, field


# Machine-readable site registry — the docstring above is the prose; THIS
# is what tooling consumes. The fault-coverage lint pass parses this dict
# literal (site -> one-line description) and enforces that (a) every
# fire()/fire_scoped()/partial_fraction() call in product code names a
# registered site, (b) every registered site has a product fire call, and
# (c) every registered site is exercised by at least one chaos test —
# scripts/run_chaos_matrix.py fails on uncovered sites. Keep this a pure
# literal: the linter reads it with ast.literal_eval, never by import.
SITES: dict[str, str] = {
    "kv.rpc.client.batch": "DistSender send error before evaluation",
    "kv.rpc.server.eval": "replica-side evaluation failure",
    "kv.rpc.server.respond": "response lost after apply (ambiguous result)",
    "flow.host.setup": "SetupFlow RPC failure at the gateway",
    "flow.host.stream": "FlowStream attach/stream failure",
    "kv.dialer.dial": "nodedialer connect failure (breaker-tracked)",
    "storage.wal.append": "WAL write error/stall/torn append",
    "storage.wal.fsync": "fsync stall or failure",
    "liveness.heartbeat": "node-liveness heartbeat failure (node-scoped)",
    "liveness.epoch_bump": "IncrementEpoch CPut failure (node-scoped)",
    "gossip.broadcast": "gossip exchange failure (node-scoped)",
    "kv.rangefeed.subscribe": "rangefeed (re)subscription failure",
    "ranger.split.apply": "split partially applied before bookkeeping",
    "ranger.merge.apply": "merge partially applied before bookkeeping",
    "ranger.lease.transfer": "lease transfer write lost in flight",
    "storage.ingest.link": "bulk-ingest side file durable, link lost",
    "flow.spill.partition_write": "host spill-partition write failure",
    "flow.spill.merge_probe": "oversized-partition merge-probe run failure",
    "storage.compaction.swap": "crash between run swap and bookkeeping",
    "storage.bloom.build": "bloom build crash or silent bit corruption",
    "admission.grant.stall": "queued admission grant stalls (delay) or is "
                             "lost (error: waiter withdraws, typed busy)",
    "admission.bucket.refill": "tenant token-bucket refill failure "
                               "(typed busy with retry-after hint)",
    "changefeed.fanout.enqueue": "fan-out buffer enqueue failure: the "
                                 "batch is not buffered, the subscriber "
                                 "sheds to catch-up-scan (no gap, no "
                                 "leaked bytes)",
    "changefeed.subscriber.send": "subscriber socket send failure "
                                  "mid-stream: the consumer is evicted "
                                  "and must reconnect from its frontier",
    "changefeed.frontier.checkpoint": "resolved-frontier checkpoint "
                                      "failure (job progress write or "
                                      "subscriber checkpoint frame): "
                                      "resume re-delivers past the stale "
                                      "frontier, never skips",
    "matview.delta.apply": "materialized-view delta kernel failure "
                           "mid-flush: no state swapped, buffered delta "
                           "retained, retry from frontier is bit-exact",
    "matview.flush": "materialized-view flush failure before any "
                     "apply: events stay buffered at the subscription, "
                     "next flush resumes from the frontier",
    "matview.frontier.checkpoint": "materialized-view frontier "
                                   "checkpoint failure after compute, "
                                   "before swap: retry re-applies the "
                                   "same delta, nothing lost or doubled",
}


class InjectedFault(ConnectionError):
    """Raised by `error`/`drop` faults. Subclasses ConnectionError so the
    retry layer classifies an injected drop exactly like a real one."""

    def __init__(self, site: str, kind: str):
        super().__init__(f"injected {kind} at {site}")
        self.site = site
        self.kind = kind


@dataclass
class FaultSpec:
    """What can happen at one site.

    kind: 'error' | 'drop' | 'delay' | 'partial'
      - error/drop raise InjectedFault (drop = the wire died; error = the
        peer answered with a failure) — sites may translate further.
      - delay sleeps `delay_s` then proceeds (slow disk / slow peer).
      - partial is site-interpreted (WAL: append a torn half-record).
    p:         firing probability per pass through the site.
    max_fires: stop firing after this many hits (so a retrying caller
               eventually succeeds — the chaos harness's "transient"
               knob). None = unlimited (a dead-forever peer).
    """

    kind: str = "error"
    p: float = 1.0
    delay_s: float = 0.01
    max_fires: int | None = None
    fires: int = field(default=0, compare=False)


_lock = threading.Lock()
_armed = False
_rng = random.Random(0)
_specs: dict[str, FaultSpec] = {}
_log: list[tuple[str, str]] = []  # (site, kind) of every fired fault


def arm(seed: int, specs: dict[str, FaultSpec]) -> None:
    """Enable injection with a fixed seed (also flips the gating setting
    so `fire` sites are live). Tests pair this with `disarm` in finally."""
    from . import settings

    global _armed, _rng
    # The chaos matrix runner (scripts/run_chaos_matrix.py) perturbs every
    # in-test seed through the environment so one pytest invocation can be
    # replayed across N distinct seeds without editing the tests.
    seed += int(os.environ.get("CHAOS_SEED_OFFSET", "0"))
    with _lock:
        _rng = random.Random(seed)
        _specs.clear()
        _specs.update(specs)
        _log.clear()
        _armed = True
    settings.set("fault.injection.enabled", True)


def disarm() -> None:
    from . import settings

    global _armed
    with _lock:
        _armed = False
        _specs.clear()
        _log.clear()
    settings.set("fault.injection.enabled", False)


def fired() -> list[tuple[str, str]]:
    """(site, kind) of every fault that actually fired, in order."""
    with _lock:
        return list(_log)


def fire(site: str) -> None:
    """Called at an instrumented site. Raises InjectedFault for error/drop
    faults, sleeps for delay faults, no-ops when disarmed or the die-roll
    misses. `partial` never fires here — sites with a partial-capable
    action consult :func:`partial_fraction` instead."""
    if not _armed:
        return
    spec = _roll(site)
    if spec is None or spec.kind == "partial":
        return
    if spec.kind == "delay":
        time.sleep(spec.delay_s)
        return
    raise InjectedFault(site, spec.kind)


def fire_scoped(site: str, node_id: int) -> None:
    """Fire a site that exists per-node: checks the generic name AND the
    node-scoped ``<site>.n<id>`` variant. Tests arm whichever granularity
    they need — the generic name hits every node, the scoped name
    blackholes exactly one (the registry is process-global, so without
    scoping a heartbeat fault would kill every node in a multi-node
    test)."""
    fire(site)
    fire(f"{site}.n{node_id}")


def partial_fraction(site: str) -> float | None:
    """For sites that can tear a write: returns the fraction of the write
    to persist (then the site raises as if the disk died mid-append), or
    None when no partial fault fires."""
    if not _armed:
        return None
    spec = _roll(site, kinds=("partial",))
    if spec is None:
        return None
    return 0.5


def _roll(site: str, kinds: tuple[str, ...] | None = None):
    from . import metric

    with _lock:
        if not _armed:
            return None
        spec = _specs.get(site)
        if spec is None:
            return None
        if kinds is not None and spec.kind not in kinds:
            return None
        if kinds is None and spec.kind == "partial":
            return None
        if spec.max_fires is not None and spec.fires >= spec.max_fires:
            return None
        if _rng.random() >= spec.p:
            return None
        spec.fires += 1
        _log.append((site, spec.kind))
    metric.FAULTS_INJECTED.inc()
    return spec
