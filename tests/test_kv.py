"""KV txn layer tests — isolation, conflicts, retries, and a kvnemesis-style
randomized serializability check (reference: pkg/kv tests + kvnemesis)."""

import threading

import numpy as np
import pytest

from cockroach_tpu.kv import (
    DB, ManualClock, TransactionAbortedError, TransactionRetryError,
)
from cockroach_tpu.storage import Engine


def mkdb():
    return DB(Engine(val_width=16), ManualClock())


def test_hlc_monotone():
    from cockroach_tpu.kv import hlc

    c = ManualClock()
    a, b = c.now(), c.now()
    assert b > a  # same wall time -> logical bump
    c.advance(10)
    d = c.now()
    assert d > b
    wall, logical = hlc.unpack(d)
    assert wall == 11 and logical == 0
    e = c.update(hlc.pack(99, 5))
    assert e > hlc.pack(99, 5)


def test_db_basic():
    db = mkdb()
    ts1 = db.put(b"a", b"1")
    db.put(b"a", b"2")
    assert db.get(b"a") == b"2"
    assert db.get(b"a", ts=ts1) == b"1"
    db.delete(b"a")
    assert db.get(b"a") is None
    db.put(b"b", b"x")
    db.put(b"c", b"y")
    assert db.scan(b"a", b"z") == [(b"b", b"x"), (b"c", b"y")]


def test_txn_commit_visibility():
    db = mkdb()
    db.put(b"k", b"base")
    t = db.new_txn()
    t.put(b"k", b"txn")
    # uncommitted write invisible to non-transactional reads below intent ts,
    # and a conflict at-or-above it
    assert db.get(b"k", ts=t.read_ts - 1) == b"base"
    assert t.get(b"k") == b"txn"  # own write visible
    t.commit()
    assert db.get(b"k") == b"txn"


def test_txn_rollback():
    db = mkdb()
    db.put(b"k", b"base")
    t = db.new_txn()
    t.put(b"k", b"gone")
    t.rollback()
    assert db.get(b"k") == b"base"
    with pytest.raises(TransactionAbortedError):
        t.put(b"k", b"zombie")


def test_txn_write_write_conflict():
    db = mkdb()
    t1 = db.new_txn()
    t2 = db.new_txn()
    t1.put(b"k", b"one")
    with pytest.raises(TransactionRetryError):
        t2.put(b"k", b"two")
    t1.commit()


def test_txn_write_too_old():
    """A write under a newer committed version restarts the transaction
    only if a read it made no longer holds at a later timestamp; a blind
    write moves its timestamp up instead (the reference pushes a
    WriteTooOld write and refreshes: since PR 41, kv.Txn._refresh_past)."""
    db = mkdb()
    t1 = db.new_txn()
    t1.get(b"k")  # the read that the newer version invalidates
    db.put(b"k", b"newer")  # commits above t1.read_ts
    with pytest.raises(TransactionRetryError):
        t1.put(b"k", b"stale")
    t1.rollback()
    # a read of ANOTHER key still holds: the timestamp moves, the write lands
    t2 = db.new_txn()
    assert t2.get(b"other") is None
    ts0 = t2.read_ts
    db.put(b"k", b"newer still")
    t2.put(b"k", b"mine")
    assert t2.read_ts > ts0
    t2.commit()
    assert db.get(b"k") == b"mine"
    # a blind write has no read to refresh
    t3 = db.new_txn()
    db.put(b"k", b"newest")
    t3.put(b"k", b"blind")
    t3.commit()
    assert db.get(b"k") == b"blind"


def test_txn_read_refresh_invalidation():
    db = mkdb()
    db.put(b"k", b"v0")
    t = db.new_txn()
    assert t.get(b"k") == b"v0"
    db.put(b"k", b"v1")  # invalidates t's read before commit
    t.put(b"other", b"x")
    with pytest.raises(TransactionRetryError):
        t.commit()
    assert db.get(b"other") is None  # rolled back


def test_txn_closure_retries():
    db = mkdb()
    db.put(b"counter", b"0")
    calls = {"n": 0}

    def incr(t):
        calls["n"] += 1
        v = int(t.get(b"counter") or b"0")
        if calls["n"] == 1:
            # sneak in a conflicting commit mid-txn on first attempt
            db.put(b"counter", str(v + 10).encode())
        t.put(b"counter", str(v + 1).encode())

    db.txn(incr)
    # first attempt fails refresh (or write-too-old) and retries cleanly
    assert calls["n"] >= 2
    assert db.get(b"counter") == b"11"


def test_txn_rewrite_last_write_wins():
    """A txn rewriting its own key sees and commits the latest write —
    intent sequence numbers (enginepb.TxnSeq analog)."""
    db = mkdb()

    def rw(t):
        t.put(b"rw", b"first")
        assert t.get(b"rw") == b"first"
        t.put(b"rw", b"second")
        assert t.get(b"rw") == b"second"
        t.delete(b"rw")
        assert t.get(b"rw") is None
        t.put(b"rw", b"final")

    db.txn(rw)
    assert db.get(b"rw") == b"final"


@pytest.mark.slow
def test_bank_transfer_invariant():
    """Total balance is conserved across random transfer txns."""
    db = mkdb()
    rng = np.random.default_rng(3)
    n = 10
    for i in range(n):
        db.put(f"acct{i}".encode(), b"100")
    for _ in range(60):
        a, b = rng.integers(0, n, 2)
        if a == b:
            continue
        amt = int(rng.integers(1, 20))

        def xfer(t, a=a, b=b, amt=amt):
            va = int(t.get(f"acct{a}".encode()))
            vb = int(t.get(f"acct{b}".encode()))
            t.put(f"acct{a}".encode(), str(va - amt).encode())
            t.put(f"acct{b}".encode(), str(vb + amt).encode())

        db.txn(xfer)
    total = sum(int(v) for _, v in db.scan(None, None))
    assert total == n * 100


@pytest.mark.slow
def test_kvnemesis_lite():
    """Randomized serial-equivalence: run sequential txns doing random
    read-modify-writes over a small keyspace against a python dict model."""
    db = mkdb()
    rng = np.random.default_rng(5)
    model: dict[bytes, bytes] = {}
    ctr_keys = [f"c{i}".encode() for i in range(6)]  # int-valued RMW keys
    str_keys = [f"k{i}".encode() for i in range(6)]  # blind put/del keys
    for step in range(120):
        kind = rng.random()
        if kind < 0.5:
            k1 = ctr_keys[rng.integers(len(ctr_keys))]
            k2 = ctr_keys[rng.integers(len(ctr_keys))]
        else:
            k1 = str_keys[rng.integers(len(str_keys))]
            k2 = k1

        def op(t, k1=k1, k2=k2, kind=kind, step=step):
            if kind < 0.5:  # transfer-style RMW over two keys
                a = int(t.get(k1) or b"0")
                b = int(t.get(k2) or b"0")
                t.put(k1, str(a + 1).encode())
                t.put(k2, str(b + 2).encode())
                return ("rmw",)
            if kind < 0.75:
                t.put(k1, f"s{step}".encode())
                return ("put",)
            t.delete(k1)
            return ("del",)

        res = db.txn(op)
        # apply the same op to the model (sequentially — txns are serial here)
        if res[0] == "rmw":
            a = int(model.get(k1, b"0"))
            b = int(model.get(k2, b"0"))
            model[k1] = str(a + 1).encode()
            model[k2] = str(b + 2).encode()
        elif res[0] == "put":
            model[k1] = f"s{step}".encode()
        else:
            model.pop(k1, None)
    got = dict(db.scan(None, None))
    assert got == model


def test_interleaved_serializability():
    """Two interleaved txns cannot both commit if they cross-read/write the
    same keys (write skew prevented by the refresh check)."""
    db = mkdb()
    db.put(b"x", b"0")
    db.put(b"y", b"0")
    t1 = db.new_txn()
    t2 = db.new_txn()
    # t1 reads x writes y; t2 reads y writes x — classic write skew
    assert t1.get(b"x") == b"0"
    assert t2.get(b"y") == b"0"
    t1.put(b"y", b"1")
    t2.put(b"x", b"1")  # allowed: x carries no intent and no newer commit
    t1.commit()         # commits y=1
    with pytest.raises(TransactionRetryError):
        t2.commit()     # must fail: its read of y was invalidated
    assert db.get(b"y") == b"1"
    assert db.get(b"x") == b"0"  # t2 rolled back


def test_non_txn_write_respects_intents():
    """Non-txn DB.put/delete sequence through the lock check: writing under
    another txn's intent raises WriteIntentError instead of silently laying
    a committed version beneath the intent."""
    from cockroach_tpu.kv import DB, WriteIntentError

    db = DB()
    t = db.new_txn()
    t.put("k", "txnval")
    with pytest.raises(WriteIntentError):
        db.put("k", "sneaky")
    with pytest.raises(WriteIntentError):
        db.delete("k")
    t.commit()
    assert db.get("k") == b"txnval"
    db.put("k", "after")  # lock released by commit
    assert db.get("k") == b"after"


def test_node_liveness_epochs():
    """liveness.go analog: heartbeats extend expiration under an epoch;
    expired records can be fenced by an epoch increment; live ones can't."""
    from cockroach_tpu.kv import DB, ManualClock
    from cockroach_tpu.kv.liveness import NodeLiveness, StillLiveError
    from cockroach_tpu.storage.lsm import Engine

    clock = ManualClock(start=1)
    db = DB(Engine(key_width=16, val_width=32, memtable_size=256), clock)
    n1 = NodeLiveness(db, 1, ttl_ms=1000)
    n2 = NodeLiveness(db, 2, ttl_ms=1000)

    r1 = n1.heartbeat()
    n2.heartbeat()
    assert r1.epoch == 1
    assert n2.is_live(1) and n1.is_live(2)
    assert {r.node_id for r in n1.livenesses()} == {1, 2}

    # node 1 keeps heartbeating: epoch stays, expiration extends
    clock.advance(500)
    r1b = n1.heartbeat()
    assert r1b.epoch == 1 and r1b.expiration > r1.expiration

    # fencing a LIVE node is refused
    with pytest.raises(StillLiveError):
        n2.increment_epoch(1)

    # after expiry, node 2 declares node 1 dead by bumping its epoch
    clock.advance(5000)
    assert not n2.is_live(1)
    fenced = n2.increment_epoch(1)
    assert fenced.epoch == 2

    # node 1's next heartbeat detects the fence (its old epoch is gone)
    from cockroach_tpu.kv.liveness import EpochFencedError

    with pytest.raises(EpochFencedError):
        n1.heartbeat()


def test_jobs_resume_from_checkpoint():
    """pkg/jobs analog: a job killed mid-run re-adopts and RESUMES from its
    persisted progress instead of restarting (the backup-checkpoint
    discipline, manifest_handling.go:1401)."""
    from cockroach_tpu.kv import DB, ManualClock
    from cockroach_tpu.kv.jobs import Registry
    from cockroach_tpu.storage.lsm import Engine

    db = DB(Engine(key_width=16, val_width=256, memtable_size=256),
            ManualClock())
    reg = Registry(db)
    work_log: list[int] = []
    crash_at = {"n": 3}

    def resume(registry, job):
        done = job.progress.get("done", 0)
        total = job.payload["total"]
        for i in range(done, total):
            if i == crash_at["n"]:
                crash_at["n"] = -1  # only crash once
                raise RuntimeError("simulated crash")
            work_log.append(i)
            job.progress["done"] = i + 1
            registry.checkpoint(job)
        return {"rows": total}

    reg.register("backfill", resume)
    job = reg.create("backfill", {"total": 6})
    assert reg.load(job.job_id).state == "pending"

    with pytest.raises(RuntimeError):
        reg.adopt_and_resume(job.job_id)
    assert reg.load(job.job_id).state == "failed"
    assert work_log == [0, 1, 2], "crashed at unit 3"

    # "restart": a fresh registry over the same engine re-adopts; the
    # failed record still holds progress, so work resumes at unit 3
    reg2 = Registry(db)
    reg2.register("backfill", resume)
    j = reg2.load(job.job_id)
    j.state = "pending"  # operator-retry (RESUME JOB)
    reg2.checkpoint(j)
    out = reg2.adopt_and_resume(job.job_id)
    assert out.state == "succeeded" and out.progress["rows"] == 6
    assert work_log == [0, 1, 2, 3, 4, 5], "no unit re-ran"


def test_backup_as_a_job(tmp_path):
    """BACKUP rides the jobs frame: durable record, engine checkpoint,
    restore from the produced artifact."""
    from cockroach_tpu.kv import DB, ManualClock
    from cockroach_tpu.kv.jobs import Registry, register_builtin_jobs
    from cockroach_tpu.storage.lsm import Engine

    db = DB(Engine(key_width=16, val_width=256, memtable_size=64),
            ManualClock())
    db.txn(lambda t: [t.put(b"k%03d" % i, b"v%03d" % i) for i in range(50)])
    reg = Registry(db)
    register_builtin_jobs(reg)
    path = str(tmp_path / "bk")
    job = reg.create("backup", {"path": path})
    done = reg.adopt_and_resume(job.job_id)
    assert done.state == "succeeded" and done.progress["path"] == path

    restored = Engine.open_checkpoint(path)
    got = restored.scan(b"k", b"l", ts=db.clock.now())
    assert len(got) == 50 and got[0] == (b"k000", b"v000")


def test_changefeed_exactly_once_resume(tmp_path):
    """CDC reduction: the feed emits each committed version once, resumes
    from the checkpointed resolved frontier after a crash, and surfaces
    deletes as NULL values (the changefeedccl envelope)."""
    import json as _json

    from cockroach_tpu.kv import DB, ManualClock
    from cockroach_tpu.kv.changefeed import register_changefeed_job
    from cockroach_tpu.kv.jobs import Registry
    from cockroach_tpu.storage.lsm import Engine

    db = DB(Engine(key_width=16, val_width=256, memtable_size=64),
            ManualClock())
    reg = Registry(db)
    register_changefeed_job(reg)
    sink = str(tmp_path / "feed.ndjson")

    db.txn(lambda t: [t.put(b"u001", b"alice"), t.put(b"u002", b"bob")])
    job = reg.create("changefeed", {"sink": sink, "start": "u",
                                    "end": "v", "polls": 1})
    reg.adopt_and_resume(job.job_id)
    lines = [_json.loads(x) for x in open(sink).read().splitlines()]
    assert [(e["key"], e["value"]) for e in lines] == [
        ("u001", "alice"), ("u002", "bob")]

    # more writes + a delete; resume the feed (operator RESUME after crash)
    db.txn(lambda t: (t.put(b"u001", b"alice2"), t.delete(b"u002")))
    j = reg.load(job.job_id)
    j.state = "pending"
    reg.checkpoint(j)
    reg.adopt_and_resume(job.job_id)
    lines = [_json.loads(x) for x in open(sink).read().splitlines()]
    assert len(lines) == 4, "exactly once per version, no re-emission"
    assert (lines[2]["key"], lines[2]["value"]) == ("u001", "alice2")
    assert (lines[3]["key"], lines[3]["value"]) == ("u002", None)


def test_kvnemesis_with_ingest_and_limited_scans():
    """kvnemesis extension over the round-3 paths: bulk INGEST runs
    interleave with transactional RMWs and LIMITED scans (iterator seeks +
    pagination boundaries); every read must match a sequential dict model."""

    db = DB(Engine(key_width=16, val_width=16, memtable_size=32),
            ManualClock())
    rng = np.random.default_rng(11)
    model: dict[bytes, bytes] = {}

    def key(i: int) -> bytes:
        return b"n%05d" % i

    for step in range(80):
        kind = rng.random()
        if kind < 0.25:
            # bulk ingest a contiguous strip (AddSSTable path)
            lo = int(rng.integers(0, 400))
            width = int(rng.integers(1, 40))
            idx = np.arange(lo, lo + width)
            keys = np.zeros((width, 16), dtype=np.uint8)
            for j, i in enumerate(idx):
                kb = key(int(i))
                keys[j, :len(kb)] = np.frombuffer(kb, dtype=np.uint8)
            vals = np.zeros((width, 16), dtype=np.uint8)
            payload = b"g%03d" % step
            vals[:, :len(payload)] = np.frombuffer(payload, dtype=np.uint8)
            db.engine.ingest(keys, vals, ts=db.clock.now(),
                             vlens=np.full(width, len(payload)))
            for i in idx:
                model[key(int(i))] = payload
        elif kind < 0.6:
            # transactional RMW
            k = key(int(rng.integers(0, 400)))

            def op(t, k=k, step=step):
                cur = t.get(k) or b""
                t.put(k, b"t%03d" % step)
                return cur

            db.txn(op)
            model[k] = b"t%03d" % step
        elif kind < 0.75:
            k = key(int(rng.integers(0, 400)))
            db.delete(k)
            model.pop(k, None)
        else:
            # limited scan from a random start: must equal the model's
            # first `limit` keys at/after start (pagination correctness)
            start = key(int(rng.integers(0, 400)))
            limit = int(rng.integers(1, 25))
            got = db.scan(start, None, max_keys=limit)
            want = sorted(
                (k, v) for k, v in model.items() if k >= start
            )[:limit]
            assert got == want, f"step {step}: scan from {start!r}"
    got = dict(db.scan(None, None))
    assert got == model


def test_rangefeed_push_subscription():
    """MuxRangeFeed reduction: a subscriber receives committed versions as
    events plus resolved checkpoints, across writes made AFTER subscribing
    (push, not poll-from-client)."""
    from cockroach_tpu.kv.changefeed import (
        RangefeedServer, subscribe_rangefeed,
    )

    db = DB(Engine(key_width=16, val_width=64, memtable_size=64),
            ManualClock())
    db.txn(lambda t: t.put(b"w1", b"before"))
    srv = RangefeedServer(db, poll_interval_s=0.02)
    try:
        sock, frames = subscribe_rangefeed(srv.addr, start=b"w", end=b"x")
        sock.settimeout(15)  # a stalled server fails the test, not hangs it
        got = []
        resolved = 0
        import time as _time

        deadline = _time.time() + 10
        wrote = False
        for f in frames:
            if "resolved" in f:
                resolved = f["resolved"]
                if not wrote:
                    db.txn(lambda t: (t.put(b"w2", b"after"),
                                      t.delete(b"w1")))
                    wrote = True
            else:
                got.append((f["key"], f["value"]))
            if len(got) >= 3 or _time.time() > deadline:
                break
        sock.close()
        assert ("w1", "before") in got, "catch-up scan event"
        assert ("w2", "after") in got, "post-subscribe write pushed"
        assert ("w1", None) in got, "delete surfaces as NULL"
        assert resolved > 0
    finally:
        srv.close()


def test_commit_heavy_intent_resolution_bounds_runs():
    """resolve_intents rewrites every run AND mints a new one per commit
    (the per-commit memtable flush); its end-of-resolution compaction
    hook must keep the run count bounded under a commit-heavy loop —
    without it, N commits leave ~N runs and every cold merged-view
    rebuild pays for all of them."""
    from cockroach_tpu.utils import settings

    db = mkdb()
    prev = settings.get("storage.compaction.pacing.enabled")
    settings.set("storage.compaction.pacing.enabled", False)
    try:
        n = 40
        for i in range(n):
            t = db.new_txn()
            t.put(b"k%d" % (i % 8), b"v%d" % i)
            t.commit()
        eng = db.engine
        assert len(eng.runs) <= eng.l0_trigger + 1, (
            f"{len(eng.runs)} runs after {n} commits "
            f"(trigger {eng.l0_trigger})")
        for j in range(8):
            assert db.get(b"k%d" % j) is not None
    finally:
        settings.set("storage.compaction.pacing.enabled", prev)


# -- concurrent sessions on the non-transactional surface (DB.put / get /
# delete): what the guarantees are under threads, whatever serves them ------


def _session_tape(tid: int, n: int):
    """Deterministic mixed-DML tape over keys private to one thread, so
    the interleaving cannot change what any thread reads."""
    ops = []
    for i in range(n):
        k = f"t{tid}-k{i % 8}"
        if i % 5 == 4:
            ops.append(("delete", k, None))
        elif i % 3 == 2:
            ops.append(("get", k, None))
        else:
            ops.append(("put", k, f"v{tid}.{i}"))
    return ops


def _play(db, ops, out):
    for kind, k, v in ops:
        if kind == "put":
            out.append((kind, k, db.put(k, v)))
        elif kind == "delete":
            out.append((kind, k, db.delete(k)))
        else:
            out.append((kind, k, db.get(k)))


def _in_threads(n, fn):
    errs = []

    def run(i):
        try:
            fn(i)
        except Exception as e:  # noqa: BLE001 - reported by the assert
            errs.append(e)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not any(t.is_alive() for t in ts)
    return errs


def test_concurrent_sessions_leave_the_sequential_replays_state():
    """Six threads' tapes of DB.put / get / delete leave the store in the
    state the per-key sequential replay gives, and every read returns
    what that replay reads."""
    n = 6
    tapes = [_session_tape(t, 60) for t in range(n)]
    db = DB(Engine())
    outs = [[] for _ in range(n)]
    assert not _in_threads(n, lambda t: _play(db, tapes[t], outs[t]))

    replay = DB(Engine())
    want = [[] for _ in range(n)]
    for t in range(n):
        _play(replay, tapes[t], want[t])
    assert dict(db.scan(None, None)) == dict(replay.scan(None, None))
    for t in range(n):
        for (kind, k, got), (kind2, k2, exp) in zip(outs[t], want[t]):
            assert (kind, k) == (kind2, k2)
            if kind == "get":
                assert got == exp, (k, got, exp)
            else:  # a write answers with its timestamp
                assert isinstance(got, int) and isinstance(exp, int)


def test_write_intent_error_reaches_the_conflicting_thread_only():
    """A write under a foreign intent raises WriteIntentError in the
    thread that sent it and in no other: the innocent write commits."""
    from cockroach_tpu.storage.lsm import WriteIntentError

    db = DB(Engine())
    with db.engine.mu:  # a live transaction's intent, as the lock table has it
        db.engine.put(b"locked", b"i", ts=db.clock.now(), txn=42)
    results = {}
    barrier = threading.Barrier(2)

    def session(i):
        barrier.wait()
        if i == 0:
            try:
                db.put("locked", "v")
                results["conflict"] = "committed"
            except WriteIntentError:
                results["conflict"] = "typed"
        else:
            results["innocent"] = db.put("innocent", "v")

    assert not _in_threads(2, session)
    assert results["conflict"] == "typed"
    assert isinstance(results["innocent"], int)
    assert db.get("innocent") == b"v"


def test_every_acknowledged_concurrent_put_survives_a_reopen(tmp_path):
    """Concurrent DB.put writers on an engine with wal_fsync=True,
    reopened from the WAL: every acknowledged write is there."""
    wal = str(tmp_path / "wal.log")
    db = DB(Engine(wal_path=wal, wal_fsync=True))
    n, per = 6, 15
    barrier = threading.Barrier(n)
    acked, mu = [], threading.Lock()

    def writer(i):
        barrier.wait()
        got = []
        for j in range(per):
            db.put(f"w{i}-{j}", f"{i}.{j}")
            got.append((f"w{i}-{j}".encode(), f"{i}.{j}".encode()))
        with mu:
            acked.extend(got)

    assert not _in_threads(n, writer)
    assert len(acked) == n * per
    db.engine.close()

    reopened = DB(Engine(wal_path=wal, wal_fsync=True))
    state = dict(reopened.scan(None, None))
    for k, v in acked:
        assert state.get(k) == v, k
    reopened.engine.close()
