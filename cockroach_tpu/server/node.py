"""Node lifecycle — the server.Server / node startup reduction.

Reference: pkg/server/server.go assembles the engine, liveness heartbeats,
gossip, the jobs registry and the timeseries poller around one stopper;
pkg/server/node.go is the per-node identity. This Node composes the same
subsystems over one Engine/DB so they run AS A SYSTEM instead of as
libraries:

- liveness:   a background heartbeat keeps this node's epoch-stamped record
  fresh (kv/liveness.py); the jobs registry fences stale claimants with it.
- jobs:       Registry(liveness=...) adopts orphaned jobs of dead nodes on a
  ticker (jobs/adopt.go's claim-expired loop).
- tsdb:       a metrics poller snapshots the default registry into the
  timeseries keyspace on a ticker (ts/db.go PollSource role).
- gossip:     optional; serves an infostore endpoint, exchanges with peers,
  and bridges CLUSTER SETTINGS both ways — a SET here publishes
  `setting/<name>`, a fresher remote info applies locally (the
  settings/updater.go <- gossip path).
- admission:  the engine's IOGovernor paces writes by L0 health; the Node
  exposes it for observability.

start()/stop() bound every thread (the stopper discipline); everything is
single-process-scoped, multi-host rides the DCN socket plane (flow/dcn.py).
"""

from __future__ import annotations

import threading

from ..kv import DB, Clock
from ..kv.jobs import Registry, register_builtin_jobs
from ..kv.liveness import LeaseManager, NodeLiveness
from ..kv.tsdb import TimeSeriesDB
from ..storage.lsm import Engine
from ..utils import admission, log, metric, settings, tracing


class Node:
    def __init__(
        self,
        node_id: int = 1,
        db: DB | None = None,
        engine: Engine | None = None,
        heartbeat_interval_s: float = 0.2,
        ttl_ms: int = 1000,
        metrics_interval_s: float | None = 0.5,
        adopt_interval_s: float = 0.5,
        gossip_peers: list | None = None,
        lease_ranges: list[int] | None = None,
        devices: int | None = None,
    ):
        self.node_id = int(node_id)
        # the devices this node's SQL stores live on: None is ONE device,
        # jax's first, whatever else the machine shows; n > 1 spans the
        # first n (parallel/mesh.make_mesh), base tables row-sharded over
        # them (catalog.Table.mesh_batch) and statements placed by
        # sql/distsql.py. A deployment fact like --store, not a setting.
        self.mesh = None
        if devices is not None and int(devices) > 1:
            import jax

            from ..parallel.mesh import make_mesh

            if len(jax.devices()) < int(devices):
                raise ValueError(
                    f"Node(devices={devices}): jax shows "
                    f"{len(jax.devices())} device(s)")
            self.mesh = make_mesh(int(devices))
        self.db = db if db is not None else DB(
            # key budget: tsdb keys are "\x01ts<metric>|<13-digit ms>" —
            # metric names run ~30 bytes, so the node store uses wide keys
            engine if engine is not None else Engine(key_width=64,
                                                     val_width=128),
            Clock(),
        )
        self.liveness = NodeLiveness(
            self.db, self.node_id,
            heartbeat_interval_ms=int(heartbeat_interval_s * 1000),
            ttl_ms=ttl_ms,
        )
        # epoch leases: the node competes for every range in lease_ranges
        # (replica_range_lease acquisition loop); a vacant or dead-holder
        # lease is taken after fencing the holder's liveness epoch
        self.leases = LeaseManager(self.liveness)
        self._lease_ranges = list(lease_ranges or [])
        self._advertised_leases: dict[int, tuple[int, int]] = {}
        self.jobs = Registry(self.db, node_id=self.node_id,
                             liveness=self.liveness)
        register_builtin_jobs(self.jobs)
        self.tsdb = TimeSeriesDB(self.db)
        self.gossip = None
        self._gossip_peers = list(gossip_peers or [])
        self._hb_interval = heartbeat_interval_s
        self._metrics_interval = metrics_interval_s
        self._adopt_interval = adopt_interval_s
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._settings_cb = None
        self._applying_remote = False
        # range lifecycle (kv/allocator.py): wired in start() when the DB
        # is DistSender-backed and kv.allocator.enabled
        self.ranger = None
        self._wired_sender = None
        self._lease_guard_local = threading.local()

    # -- lifecycle -----------------------------------------------------------

    def start(self, gossip_port: int = 0,
              pg_port: int | None = None,
              http_port: int | None = None,
              kv_port: int | None = None) -> "Node":
        self._stop.clear()
        from ..sql import plancache

        plancache.maybe_enable_compile_cache()
        self.liveness.heartbeat()  # own record exists before anything reads

        # disk health: WAL-backed engines get a monitor fed by their own
        # WAL appends plus a periodic probe (storage/disk.py)
        self.disk = None
        eng = self.db.engine
        if getattr(eng, "wal_path", None):
            import os

            from ..storage.disk import DiskMonitor

            self.disk = DiskMonitor(
                os.path.dirname(eng.wal_path) or "."
            ).start()
            eng.disk_monitor = self.disk

        # run pending upgrade migrations before serving (upgrademanager
        # role: the store's persisted version catches up to the binary's)
        from ..kv.upgrade import run_upgrades

        ran = run_upgrades(self.db)
        for name in ran:
            log.info(log.OPS, "upgrade migration complete", name=name)

        # the serving engine's L0 health feeds the admission shed ladder:
        # a badly-behind LSM sheds analytical statements before the write
        # path inverts (io_load_listener -> GrantCoordinator shape)
        if getattr(eng, "governor", None) is not None:
            admission.set_io_health_provider(eng.governor.l0_overload)

        self._spawn(self._heartbeat_loop, "liveness-heartbeat")
        self._spawn(self._metrics_loop, "tsdb-poller")
        self._spawn(self._adopt_loop, "jobs-adopt")

        self.admin = None
        if http_port is not None:
            from .http import AdminServer

            self.admin = AdminServer(self, port=http_port).serve_background()

        self.kv_rpc = None
        if kv_port is not None:
            from ..kv.rpc import BatchServer

            # the Internal.Batch endpoint (server/node.go Node.Batch role).
            # Range-addressed mutation batches are guarded by the lease
            # check: a fenced node answers EpochFencedError instead of
            # serving writes under an epoch it no longer owns.
            self.kv_rpc = BatchServer(self.db, port=kv_port,
                                      lease_check=self._lease_check)
        if self._lease_ranges:
            self._spawn(self._lease_loop, "lease-acquire")

        self.dialer = None

        self.pg = None
        if pg_port is not None:
            from .pgwire import PgServer

            # every pgwire connection gets its own Session over this
            # node's shared catalog/DB (conn-executor-per-session)
            from ..catalog import Catalog

            self._sql_catalog = Catalog()
            self._sql_catalog.mesh = self.mesh
            self.pg = PgServer(catalog=self._sql_catalog, db=self.db,
                               port=pg_port).serve_background()

        if gossip_port is not None and (self._gossip_peers
                                        or gossip_port >= 0):
            from ..flow.gossip import Gossip

            self.gossip = Gossip(self.node_id)
            self._gossip_addr = self.gossip.serve(port=gossip_port)
            if self._gossip_peers:
                self.gossip.run_background(self._gossip_peers,
                                           interval_s=0.1)
            self._settings_cb = self._publish_setting
            settings.on_change(self._settings_cb)
            self._spawn(self._settings_apply_loop, "gossip-settings")
            # advertise the KV endpoint + hand out a dialer (nodedialer
            # role: peers resolve node ids through gossip, never addresses)
            from ..kv.dialer import NodeDialer, advertise

            if self.kv_rpc is not None:
                advertise(self.gossip, self.node_id, self.kv_rpc.addr)
            self.dialer = NodeDialer(self.gossip)

        # range lifecycle: a DistSender-backed node runs the split/merge/
        # rebalance queues and carries the (holder, epoch) guard onto
        # EVERY routed piece — range-addressed stamping survives an
        # auto-split mid-batch (the DistSender split-path open item)
        from ..kv.dist import DistSender

        sender = self.db.engine
        if isinstance(sender, DistSender):
            if sender.lease_check is None:
                sender.lease_check = self._dist_lease_check
                self._wired_sender = sender
            if settings.get("kv.allocator.enabled"):
                from ..kv.allocator import RangeLifecycle
                from ..kv.loadstats import RangeLoadStats

                if sender.load is None:
                    sender.load = RangeLoadStats()
                self.ranger = RangeLifecycle(
                    sender, load=sender.load, leases=self.leases,
                    gossip=self.gossip, node_id=self.node_id,
                    store_nodes={sid: self.node_id
                                 for sid in sender.stores},
                    # scans walk span_stats over every range — pace them
                    # well below the heartbeat cadence or the scanner's
                    # engine passes starve foreground traffic
                    interval_s=max(self._hb_interval * 5, 0.25),
                )
                self.ranger.start()
        log.info(log.OPS, "node started", node=self.node_id)
        return self

    def stop(self) -> None:
        self._stop.set()
        admission.set_io_health_provider(None)
        if self.ranger is not None:
            self.ranger.stop()
            self.ranger = None
        if self._wired_sender is not None:
            self._wired_sender.lease_check = None
            self._wired_sender = None
        if self._settings_cb is not None:
            settings.remove_on_change(self._settings_cb)
            self._settings_cb = None
        # stop() may run ON a node thread (the fenced heartbeat path):
        # joining yourself deadlocks, so skip the calling thread
        for t in self._threads:
            if t is not threading.current_thread():
                t.join(timeout=5)
        self._threads.clear()
        if self.gossip is not None:
            self.gossip.close()
            self.gossip = None
        if getattr(self, "pg", None) is not None:
            self.pg.close()
            self.pg = None
        if getattr(self, "admin", None) is not None:
            self.admin.close()
            self.admin = None
        if getattr(self, "disk", None) is not None:
            self.disk.stop()
            self.disk = None
        if getattr(self, "kv_rpc", None) is not None:
            self.kv_rpc.close()
            self.kv_rpc = None
        if getattr(self, "dialer", None) is not None:
            self.dialer.close()
            self.dialer = None
        log.info(log.OPS, "node stopped", node=self.node_id)

    # stopper discipline: close() is the public teardown name (the
    # reference's stopper.Stop); every queue/scanner thread is joined
    close = stop

    def _dist_lease_check(self, range_id: int) -> None:
        """DistSender routing guard: when THIS node believes it holds the
        range's lease, verify the (holder, epoch) pair is still valid —
        so a fenced node fails every piece of a multi-range batch,
        including children minted by an auto-split mid-batch. Vacant or
        foreign leases pass through (the server-side guard owns those).
        Reentrancy: the guard's own lease/liveness reads route through
        this same sender; the thread-local skips the nested check.

        An intent on the lease record means a transfer/carry txn is
        mid-commit — and that txn's commit may be waiting on the sender
        lock THIS request holds, so waiting the intent out would
        deadlock until the retry budget expires. Serve under the
        current terms instead: the fencing property lives in the epoch
        equality check, which a committed transfer re-asserts on the
        very next request."""
        from ..kv.txn import TransactionRetryError
        from ..storage.lsm import WriteIntentError

        if getattr(self._lease_guard_local, "busy", False):
            return
        self._lease_guard_local.busy = True
        try:
            rec = self.leases.holder(range_id)
            if rec is not None and rec.node_id == self.node_id:
                self.leases.check(range_id)
        except (WriteIntentError, TransactionRetryError):
            pass
        finally:
            self._lease_guard_local.busy = False

    def _spawn(self, fn, name: str) -> None:
        t = threading.Thread(target=fn, name=f"{name}-n{self.node_id}",
                             daemon=True)
        t.start()
        self._threads.append(t)

    # -- loops ---------------------------------------------------------------

    def _heartbeat_loop(self) -> None:
        from ..kv.liveness import EpochFencedError
        from ..kv.txn import TransactionRetryError

        while not self._stop.wait(self._hb_interval):
            try:
                with tracing.timed("node.heartbeat"):
                    self.liveness.heartbeat()
            except EpochFencedError:
                # declared dead by a peer: the WHOLE node must stop taking
                # work (a fenced node that keeps adopting jobs runs them in
                # parallel with its fencer). Stop every loop; claims made
                # under the old believed epoch keep failing their fence
                # check. The reference's node exits on this signal too.
                log.warning(log.OPS, "heartbeat fenced; stopping node",
                            node=self.node_id)
                self._stop.set()
                return
            except TransactionRetryError:
                continue  # contended heartbeat key; next tick retries
            except (ConnectionError, OSError):
                # blackholed heartbeat (liveness.heartbeat fault or a real
                # partition): the record silently ages toward expiry while
                # the node keeps trying — exactly the reference's behavior
                # when a node loses the liveness range
                continue

    # -- leases ---------------------------------------------------------------

    def _lease_check(self, req: dict) -> None:
        """BatchServer guard for range-addressed mutation batches: raises
        EpochFencedError / NotLeaseHolderError when this node may not
        serve the range. Batches without a range address (plain
        BatchClient traffic) bypass the guard — single-node topologies
        have no lease protocol to honor."""
        from ..kv.liveness import NotLeaseHolderError
        from ..storage.lsm import WriteIntentError

        rid = req.get("range")
        if rid is not None:
            try:
                self.leases.check(int(rid))
            except WriteIntentError as e:
                # lease/liveness record mid-commit (a heartbeat or a
                # failover's fencing write): lease state is UNRESOLVED,
                # and the only safe answer is "don't serve" — typed so
                # the router re-resolves and retries instead of
                # surfacing a storage-level error to the application
                raise NotLeaseHolderError(
                    f"r{rid} lease state unresolved (record mid-commit); "
                    f"retry") from e

    def _lease_loop(self) -> None:
        while not self._stop.wait(self._hb_interval):
            with tracing.timed("node.lease"):
                self._lease_pass()

    def _lease_pass(self) -> None:
        from ..kv.liveness import NotLeaseHolderError, StillLiveError
        from ..kv.txn import TransactionRetryError
        from ..storage.lsm import WriteIntentError

        for rid in self._lease_ranges:
            try:
                prev = self.leases.holder(rid)
                rec = self.leases.acquire(rid)
            except NotLeaseHolderError:
                continue  # a live peer holds it; that's healthy
            except (StillLiveError, TransactionRetryError):
                continue  # lost a failover race; next tick re-reads
            except WriteIntentError:
                continue  # a peer's lease write mid-commit; next tick
            except (ConnectionError, OSError):
                continue  # injected epoch_bump/transport fault
            except Exception as e:  # noqa: BLE001 - loop must survive  # crlint: allow-broad-except(lease loop must survive; logged)
                log.warning(log.OPS, "lease acquire failed",
                            range=rid, error=str(e))
                continue
            if (prev is not None and prev.node_id != self.node_id
                    and self.gossip is not None):
                # we just fenced the old holder: its gossiped state
                # is stale under the bumped epoch — expire it
                self.gossip.note_epoch(prev.node_id, prev.epoch + 1)
            ad = (rec.node_id, rec.epoch)
            if (self._advertised_leases.get(rid) != ad
                    and self.gossip is not None):
                self.gossip.add_info(f"lease/{rid}",
                                     f"{rec.node_id}:{rec.epoch}")
                self._advertised_leases[rid] = ad

    def _metrics_loop(self) -> None:
        import time as _time

        from ..kv import hlc

        last_prune = _time.monotonic()
        while True:
            # constructor interval wins when given; otherwise the live
            # cluster setting paces the scraper (SET takes effect next tick)
            iv = (self._metrics_interval if self._metrics_interval is not None
                  else settings.get("ts.scrape_interval_seconds"))
            if self._stop.wait(iv):
                return
            try:
                # re-publish the pull-style gauges (memory monitors,
                # admission queue) so each scrape records live values even
                # when nothing ran since the last tick
                from ..flow import memory as flowmem
                from ..kv import fanout
                from ..storage import blockcache

                with tracing.timed("node.tsdb_scrape"):
                    flowmem.refresh_gauges()
                    admission.refresh_gauges()
                    blockcache.refresh_gauges()
                    fanout.refresh_gauges()
                    self.tsdb.record(metric.DEFAULT)
                retention = settings.get("ts.retention_seconds")
                # prune at ~1/10 the scrape cadence: a retention trim scans
                # the whole ts keyspace, too heavy for per-tick work
                if retention and _time.monotonic() - last_prune >= iv * 10:
                    with tracing.timed("node.tsdb_prune"):
                        wall, _ = hlc.unpack(self.db.clock.now())
                        self.tsdb.prune_all(wall - int(retention * 1e3))
                    last_prune = _time.monotonic()
            except Exception as e:  # metric write must never kill the node  # crlint: allow-broad-except(metric write must never kill the node; logged)
                log.warning(log.OPS, "tsdb poll failed", error=str(e))

    def _adopt_loop(self) -> None:
        while not self._stop.wait(self._adopt_interval):
            try:
                with tracing.timed("node.adopt"):
                    adopted = self.jobs.adopt_orphans()
                for j in adopted:
                    log.info(log.OPS, "re-adopted orphaned job",
                             job=j.job_id, state=j.state)
            except Exception as e:  # crlint: allow-broad-except(adoption pass failure is logged, loop continues)
                log.warning(log.OPS, "adoption pass failed", error=str(e))

    # -- gossip <-> settings bridge ------------------------------------------

    _SETTING_PREFIX = "setting/"

    def _publish_setting(self, name: str, value) -> None:
        if self.gossip is None or self._applying_remote:
            return
        self.gossip.add_info(self._SETTING_PREFIX + name, value)

    def _settings_apply_loop(self) -> None:
        applied: dict[str, object] = {}
        while not self._stop.wait(0.1):
            if self.gossip is None:
                return
            with tracing.timed("node.settings_apply"):
                self._apply_gossiped_settings(applied)

    def _apply_gossiped_settings(self, applied: dict[str, object]) -> None:
        for key in self.gossip.keys():
            if not key.startswith(self._SETTING_PREFIX):
                continue
            name = key[len(self._SETTING_PREFIX):]
            info = self.gossip.get_info(key)
            if info is None or applied.get(name) == info:
                continue
            try:
                self._applying_remote = True
                settings.set(name, info)
                applied[name] = info
            except Exception as e:  # crlint: allow-broad-except(bad gossiped value is logged and pinned to avoid a retry storm)
                log.warning(log.OPS, "gossiped setting rejected",
                            setting=name, error=str(e))
                applied[name] = info  # don't retry a bad value forever
            finally:
                self._applying_remote = False

    def gossip_addr(self):
        return getattr(self, "_gossip_addr", None)
