"""Changefeed fan-out plane: subscriber tree, backpressure ladder,
reconnect-from-frontier, liveness reaping and introspection surfaces.

The chaos-side counterparts (injected faults at the three
changefeed.* sites, racesan schedule) live in test_chaos.py; this file
covers the deterministic contracts:

- demux: each subscriber sees exactly its span's events, bit-identical
  (after (ts, key) dedup) to a direct changes_between scan;
- reconnect: a client killed mid-stream resumes with since=<last
  checkpoint> and the deduped union equals the full-history oracle;
- the ladder: coalesce → shed → typed SlowConsumerError eviction, with
  the staging monitor draining to zero;
- liveness: a dead socket is reaped within heartbeat + deadline and the
  no-leak census stays clean;
- bounded tree: past max_subscribers the newcomer gets a typed
  subscriber_limit frame, existing registrations keep streaming;
- vtable + admin endpoint snapshots.
"""

import socket
import time

import pytest

from scripts.check_no_leaks import assert_no_leaks, snapshot

from cockroach_tpu.kv import DB
from cockroach_tpu.kv import fanout
from cockroach_tpu.kv.changefeed import (
    RangefeedServer,
    changes_between,
    subscribe_rangefeed,
)
from cockroach_tpu.kv.hlc import ManualClock
from cockroach_tpu.storage.lsm import Engine
from cockroach_tpu.utils import settings
from cockroach_tpu.utils.errors import SlowConsumerError
from cockroach_tpu.flow import memory as flowmem


def _db():
    return DB(Engine(key_width=16, val_width=64, memtable_size=64),
              ManualClock())


def _oracle(db, start=None, end=None):
    """(ts, key) -> value map from a direct catch-up scan — the
    bit-identity reference every stream must dedup to."""
    events, _resolved = changes_between(db, 0, db.clock.now(), start, end)
    return {(e["ts"], e["key"]): e["value"] for e in events}


def _drain(sock, frames, until_resolved, deadline_s=15):
    """Collect event frames (deduped by (ts, key)) until the resolved
    frontier reaches `until_resolved`, an error frame arrives, or the
    stream ends. Returns (events, resolved, error_frame)."""
    sock.settimeout(deadline_s)
    events, resolved = {}, 0
    deadline = time.time() + deadline_s
    for f in frames:
        if "error" in f:
            return events, resolved, f
        if "resolved" in f:
            resolved = max(resolved, f["resolved"])
            if resolved >= until_resolved:
                break
        else:
            events[(f["ts"], f["key"])] = f["value"]
        if time.time() > deadline:
            break
    return events, resolved, None


@pytest.fixture
def _fast_knobs():
    """Tight liveness knobs so reap/eviction paths run in test time."""
    prev = {k: settings.get(k) for k in (
        "changefeed.fanout.heartbeat_s",
        "changefeed.fanout.send_deadline_s")}
    settings.set("changefeed.fanout.heartbeat_s", 0.05)
    settings.set("changefeed.fanout.send_deadline_s", 1.0)
    yield
    for k, v in prev.items():
        settings.set(k, v)


# -- demux ------------------------------------------------------------------


def test_fanout_demux_spans_bit_identity():
    """Two span subscribers + one full subscriber on the same hub: each
    receives exactly its span's committed versions — equal, after
    (ts, key) dedup, to a direct changes_between scan."""
    db = _db()
    db.txn(lambda t: (t.put(b"a1", b"v1"), t.put(b"b1", b"v2")))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        subs = [subscribe_rangefeed(srv.addr, start=b"a", end=b"b"),
                subscribe_rangefeed(srv.addr, start=b"b", end=b"c"),
                subscribe_rangefeed(srv.addr)]
        db.txn(lambda t: (t.put(b"a2", b"v3"), t.delete(b"b1")))
        hi = db.clock.now()
        got = [_drain(s, fr, hi) for s, fr in subs]
        for s, _fr in subs:
            s.close()
        spans = [(b"a", b"b"), (b"b", b"c"), (None, None)]
        for (events, resolved, err), (lo, hi_k) in zip(got, spans):
            assert err is None
            assert resolved >= hi
            assert events == _oracle(db, lo, hi_k)
    finally:
        srv.close()


# -- reconnect-from-frontier ------------------------------------------------


def test_reconnect_from_frontier_bit_identity():
    """Kill the client mid-stream, reconnect with since=<last observed
    checkpoint>: the deduped union of both connections equals the full
    changes_between history — no loss, duplicates collapse."""
    db = _db()
    for i in range(5):
        db.txn(lambda t, i=i: t.put(b"k%d" % i, b"v%d" % i))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        mid = db.clock.now()
        sock, frames = subscribe_rangefeed(srv.addr)
        first, ckpt, err = _drain(sock, frames, mid)
        assert err is None and ckpt >= mid
        # torn disconnect: no goodbye, no unsubscribe
        sock.close()
        for i in range(5, 10):
            db.txn(lambda t, i=i: t.put(b"k%d" % i, b"v%d" % i))
        hi = db.clock.now()
        sock2, frames2 = subscribe_rangefeed(srv.addr, since=ckpt)
        second, ckpt2, err2 = _drain(sock2, frames2, hi)
        sock2.close()
        assert err2 is None and ckpt2 >= hi
        merged = dict(first)
        merged.update(second)
        assert merged == _oracle(db), \
            "reconnect-from-frontier lost or duplicated a version"
        # the frontier contract: nothing below the checkpoint re-streams
        assert all(ts > ckpt for ts, _k in second), \
            "second connection re-sent versions below its since frontier"
    finally:
        srv.close()


# -- the backpressure ladder ------------------------------------------------


def _ladder_hub(db):
    """Hub with an undrained registration (test seam: no sender thread)
    forced LIVE so _enqueue_locked exercises the ladder deterministically.
    The poller is parked (huge interval) — the test drives every rung."""
    hub = fanout.FanoutHub(db, poll_interval_s=3600)
    a, b = socket.socketpair()
    sub = hub.add_subscriber(a, start_sender=False)
    with hub._mu:
        sub.state = fanout.LIVE
    return hub, sub, a, b


def _batch(n_keys, nbytes, versions=1, key_prefix=b"lad"):
    out = []
    ts = 1
    for v in range(versions):
        for i in range(n_keys):
            out.append((ts, b"%s%04d" % (key_prefix, i), b"x" * nbytes,
                        nbytes, time.monotonic()))
            ts += 1
    return out


def test_ladder_rung_one_coalesces_duplicate_keys():
    db = _db()
    prev = {k: settings.get(k) for k in (
        "changefeed.fanout.buffer_bytes",
        "changefeed.fanout.highwater_frac")}
    settings.set("changefeed.fanout.buffer_bytes", 4096)
    settings.set("changefeed.fanout.highwater_frac", 0.1)
    hub, sub, a, b = _ladder_hub(db)
    try:
        # 3 versions of 2 keys, 100 B each = 600 B > high water (409 B):
        # the queue coalesces to newest-version-per-key
        with hub._mu:
            hub._enqueue_locked(sub, _batch(2, 100, versions=3))
        assert sub.state == fanout.LIVE
        assert sub.coalesced == 4 and len(sub.buf) == 2
        assert sub.queued_bytes == 200
        # the survivors are the NEWEST version of each key
        assert sorted(e[0] for e in sub.buf) == [5, 6]
        assert sub.mon.used == 200, "coalesce must rebase the reservation"
    finally:
        hub.close()
        a.close()
        b.close()
        for k, v in prev.items():
            settings.set(k, v)


def test_ladder_rung_two_sheds_to_catchup():
    db = _db()
    prev = {k: settings.get(k) for k in (
        "changefeed.fanout.buffer_bytes",
        "changefeed.fanout.highwater_frac")}
    settings.set("changefeed.fanout.buffer_bytes", 4096)
    settings.set("changefeed.fanout.highwater_frac", 0.1)
    hub, sub, a, b = _ladder_hub(db)
    try:
        # 60 DISTINCT keys x 100 B: coalescing drops nothing, the queue
        # blows the 4096 B budget, the ladder sheds to catch-up
        with hub._mu:
            hub._enqueue_locked(sub, _batch(60, 100))
        assert sub.state == fanout.CATCHUP
        assert sub.sheds == 1 and sub.sheds_run == 1
        assert sub.buf == [] and sub.queued_bytes == 0
        assert sub.mon.used == 0, "shed must release every buffered byte"
    finally:
        hub.close()
        a.close()
        b.close()
        for k, v in prev.items():
            settings.set(k, v)


def test_ladder_terminal_rung_typed_eviction():
    db = _db()
    prev = {k: settings.get(k) for k in (
        "changefeed.fanout.buffer_bytes",
        "changefeed.fanout.highwater_frac",
        "changefeed.fanout.max_consecutive_sheds")}
    settings.set("changefeed.fanout.buffer_bytes", 4096)
    settings.set("changefeed.fanout.highwater_frac", 0.1)
    settings.set("changefeed.fanout.max_consecutive_sheds", 2)
    hub, sub, a, b = _ladder_hub(db)
    try:
        for _round in range(2):  # two sheds without ever draining
            with hub._mu:
                hub._enqueue_locked(sub, _batch(60, 100))
                sub.state = fanout.LIVE  # simulate the rescan completing
        assert sub.sheds_run == 2
        with hub._mu:
            hub._enqueue_locked(sub, _batch(60, 100))
        assert sub.state == fanout.EVICTED
        err = sub.evict_error
        assert isinstance(err, SlowConsumerError)
        assert err.subscriber_id == sub.id
        assert err.frontier == sub.frontier, \
            "the typed error must carry the exact reconnect point"
        assert "shed" in err.reason
        assert sub.mon.used == 0
    finally:
        hub.close()
        a.close()
        b.close()
        for k, v in prev.items():
            settings.set(k, v)
    assert flowmem.staging_monitor("changefeed").used == 0, \
        "fan-out staging account retained bytes after hub close"


def test_eviction_never_blocks_peers():
    """The ladder runs entirely under the hub lock without touching the
    evicted subscriber's socket: a sibling registration keeps streaming
    while one member of the tree is being evicted."""
    db = _db()
    db.txn(lambda t: t.put(b"p1", b"v1"))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        hub = srv.hub
        # healthy real client alongside a doomed seam registration
        sock, frames = subscribe_rangefeed(srv.addr)
        x, y = socket.socketpair()
        doomed = hub.add_subscriber(x, start_sender=False)
        with hub._mu:
            hub._evict_locked(doomed, "test: forced eviction")
        assert doomed.state == fanout.EVICTED
        db.txn(lambda t: t.put(b"p2", b"v2"))
        hi = db.clock.now()
        events, resolved, err = _drain(sock, frames, hi)
        sock.close()
        assert err is None and resolved >= hi
        assert events == _oracle(db), "peer stream degraded by eviction"
        x.close()
        y.close()
    finally:
        srv.close()


# -- liveness (the old per-connection _tail had no send bound) --------------


def test_dead_socket_reaped_and_census_clean(_fast_knobs):
    """A client that vanishes without a goodbye: the heartbeat checkpoint
    hits the dead socket (or the reaper's send deadline trips) and the
    registration + its sender thread are reaped while the server keeps
    running — then the full census (threads, socket fds, monitor drains)
    returns to the pre-server baseline."""
    before = snapshot()
    db = _db()
    db.txn(lambda t: t.put(b"d1", b"v1"))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        sock, frames = subscribe_rangefeed(srv.addr)
        sock.settimeout(10)
        assert next(frames) is not None  # established and streaming
        sock.close()  # torn: no unsubscribe, no FIN handshake with server
        deadline = time.time() + 10
        while time.time() < deadline:
            with srv.hub._mu:
                if not srv.hub._subs:
                    break
            time.sleep(0.02)
        with srv.hub._mu:
            assert not srv.hub._subs, \
                "dead subscriber not reaped within heartbeat + deadline"
    finally:
        srv.close()
    assert flowmem.staging_monitor("changefeed").used == 0
    assert_no_leaks(before)


# -- bounded subscriber tree ------------------------------------------------


def test_subscriber_limit_typed_refusal():
    db = _db()
    db.txn(lambda t: t.put(b"l1", b"v1"))
    prev = settings.get("changefeed.fanout.max_subscribers")
    settings.set("changefeed.fanout.max_subscribers", 1)
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        sock1, frames1 = subscribe_rangefeed(srv.addr)
        sock1.settimeout(10)
        assert next(frames1) is not None  # first registration streams
        sock2, frames2 = subscribe_rangefeed(srv.addr)
        sock2.settimeout(10)
        refusal = next(frames2)
        assert refusal == {"error": "subscriber_limit"}
        assert next(frames2, None) is None, "refused conn must close"
        sock2.close()
        # the tree itself is unaffected: the first stream still resolves
        hi = db.clock.now()
        events, resolved, err = _drain(sock1, frames1, hi)
        assert err is None and resolved >= hi
        sock1.close()
    finally:
        srv.close()
        settings.set("changefeed.fanout.max_subscribers", prev)


# -- introspection ----------------------------------------------------------


def test_vtable_and_status_endpoint_snapshot():
    from cockroach_tpu.server.http import AdminServer
    from cockroach_tpu.sql import crdb_internal

    # the vtable spans every live hub of the process, and hubs sit in a
    # WeakSet until collected: one that an earlier test of this worker
    # left unclosed (seen under six workers in PR 30: a second hub with a
    # subscriber 1 of its own) would be counted below
    for leaked in fanout.hubs():
        leaked.close()
    db = _db()
    db.txn(lambda t: t.put(b"s1", b"v1"))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        sock, frames = subscribe_rangefeed(srv.addr, start=b"s", end=b"t")
        hi = db.clock.now()
        _events, resolved, err = _drain(sock, frames, hi)
        assert err is None and resolved >= hi
        # the sender writes a resolved frame to the wire BEFORE it records
        # the frontier (Subscriber._maybe_checkpoint), so the frame this
        # test just drained can be ahead of the registry for an instant
        deadline = time.time() + 10
        while True:
            tab = crdb_internal.build(
                object(), "crdb_internal.node_changefeed_subscribers")
            rows = {name: tab.columns[name] for name in tab.schema.names}
            if (len(rows["frontier"]) and int(rows["frontier"][0]) >= hi
                    or time.time() > deadline):
                break
            time.sleep(0.01)

        def col_str(name):  # STRING columns are dictionary-encoded
            return str(tab.dictionaries[name].values[int(rows[name][0])])

        assert len(rows["subscriber_id"]) == 1
        assert col_str("state") == fanout.LIVE
        assert col_str("span_start") == "s"
        assert col_str("span_end") == "t"
        assert int(rows["frontier"][0]) >= hi
        assert int(rows["sent_events"][0]) >= 1
        # the admin payload method wraps the same snapshot (self unused:
        # payload methods need no listener)
        payload = AdminServer.changefeeds(None)
        assert len(payload["subscribers"]) == 1
        assert payload["subscribers"][0]["state"] == fanout.LIVE
        assert payload["buffer_bytes"] >= 0
        sock.close()
    finally:
        srv.close()


def test_hub_close_idempotent_and_registry_drops():
    db = _db()
    hub = fanout.FanoutHub(db, poll_interval_s=3600)
    assert hub in fanout.hubs()
    hub.close()
    hub.close()  # second close is a no-op, not a crash
    assert hub not in fanout.hubs()
    assert fanout.subscriber_rows() == [] or all(
        r["hub"] != hub.name for r in fanout.subscriber_rows())


# -- deadline discipline ------------------------------------------------------


def test_subscribe_dial_arms_read_deadline():
    """subscribe_rangefeed's connect timeout persists as the per-frame
    read deadline (the untimed-wait regression: a silent server used to
    park the consumer in recv forever — now it reads as end-of-feed and
    the consumer re-subscribes from its last checkpoint)."""
    db = _db()
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        sock, frames = subscribe_rangefeed(srv.addr)
        assert sock.gettimeout() == settings.get("flow.dcn.io_timeout_s")
        sock.close()
    finally:
        srv.close()


def test_silent_subscription_ends_feed_not_hangs():
    """Against a peer that accepts and never answers, the frame iterator
    terminates within the io deadline instead of blocking forever."""
    import socket

    prev = settings.get("flow.dcn.io_timeout_s")
    settings.set("flow.dcn.io_timeout_s", 0.3)
    lsn = socket.create_server(("127.0.0.1", 0))  # accepts, never serves
    try:
        sock, frames = subscribe_rangefeed(lsn.getsockname())
        t0 = time.time()
        assert list(frames) == []  # timeout reads as end-of-feed
        assert time.time() - t0 < 5.0
        sock.close()
    finally:
        settings.set("flow.dcn.io_timeout_s", prev)
        lsn.close()
