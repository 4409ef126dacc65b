"""Transactional KV — the pkg/kv surface (kv.DB / kv.Txn) over the LSM
engine's MVCC intents.

Reference mapping:
- ``DB.txn(fn)``   <- kv.DB.Txn closure-with-retries (pkg/kv/db.go); retries
  on retryable errors with a bumped timestamp, like TxnCoordSender's retry
  loop around serializability failures.
- intents          <- provisional values owned by a txn id; reads of other
  txns' visible intents fail (WriteIntentError), writes check the lock
  before laying an intent (concurrency manager's lock table role).
- commit           <- read-span refresh validation (span refresher
  interceptor semantics) then intent resolution at the commit timestamp
  (MVCCResolveWriteIntent); abort drops the intents.
- WriteTooOld      <- a newer committed version above the txn's read_ts
  forces a retry, as in the reference's WriteTooOldError.

Single-process scope: latching is the GIL (flows are single-threaded);
distribution of this layer rides the same control plane as DistSQL when
multi-host lands.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field

from ..storage.lsm import Engine, WriteIntentError
from . import hlc


class TransactionRetryError(Exception):
    """Retryable: the txn must restart at a higher timestamp."""


class TransactionAbortedError(Exception):
    """Non-retryable inside the closure: the txn was aborted."""

# txn control-flow errors cross the query error boundary unwrapped (the
# colexecerror.ExpectedError discipline)
from ..utils.errors import register_passthrough as _rp  # noqa: E402

_rp(TransactionRetryError)
_rp(TransactionAbortedError)



_txn_ids = itertools.count(1)

# tries a server-side retry loop makes before the conflict goes to the
# client (kv.DB.Txn's and the autocommit point read's)
MAX_RETRIES = 16
# how long a write stands in line for a key another transaction holds
# before it gives up as a retryable conflict
LOCK_WAIT_S = 0.5


def _backoff(attempt: int) -> None:
    """Wait out the holder of a conflicting intent before the next try:
    1 ms doubling to 64 ms (the reference queues the waiter in the lock
    table; a retry that comes back at once only meets the same intent, and
    takes the engine's mutex from the transaction that would resolve it)."""
    time.sleep(min(0.064, 0.001 * (1 << attempt)))


@dataclass
class Txn:
    db: "DB"
    txn_id: int
    read_ts: int
    _finished: bool = False
    # read spans for commit-time refresh validation: (start, end, is_point);
    # point spans cover exactly their key, end=None means unbounded
    _read_spans: list[tuple[bytes, bytes | None, bool]] = field(
        default_factory=list)
    _write_keys: list[bytes] = field(default_factory=list)
    # callbacks fired once after a SUCCESSFUL commit (discarded on
    # rollback/retry): side effects that must be atomic with the txn
    # (e.g. KVTable's in-memory dictionary additions)
    _commit_hooks: list = field(default_factory=list)

    def on_commit(self, cb) -> None:
        self._commit_hooks.append(cb)

    def note_read_span(self, start: bytes, end: bytes | None,
                       point: bool = False) -> None:
        """Record an externally-performed read (e.g. a columnar table scan
        executed at this txn's snapshot) so commit-time refresh validation
        covers it — the span-refresher contract for reads that bypass
        Txn.get/scan."""
        self._check_open()
        self._read_spans.append((start, end, point))

    # -- reads --------------------------------------------------------------

    def get(self, key: bytes | str) -> bytes | None:
        self._check_open()
        k = _b(key)
        self._read_spans.append((k, None, True))
        try:
            return self.db.engine.get(k, ts=self.read_ts, txn=self.txn_id)
        except WriteIntentError as e:
            _record_contention(e, self.txn_id)
            raise TransactionRetryError(
                f"conflicting intent on {e.keys}"
            ) from e

    def scan(self, start: bytes | str | None, end: bytes | str | None,
             max_keys: int | None = None) -> list[tuple[bytes, bytes]]:
        self._check_open()
        s = _b(start) if start is not None else None
        e = _b(end) if end is not None else None
        self._read_spans.append((s or b"", e, False))
        try:
            return self.db.engine.scan(
                s, e, ts=self.read_ts, txn=self.txn_id, max_keys=max_keys
            )
        except WriteIntentError as err:
            _record_contention(err, self.txn_id)
            raise TransactionRetryError(
                f"conflicting intent on {err.keys}"
            ) from err

    def range_read(self, start: bytes, end: bytes, **page):
        """One page of a device-resident range read (Engine.range_read)
        at this transaction's snapshot: its own intents are visible, a
        foreign intent is its retryable conflict. The caller notes the
        whole span once (`note_read_span`) for the commit's refresh."""
        self._check_open()
        try:
            return self.db.engine.range_read(
                start, end, ts=self.read_ts, txn=self.txn_id, **page)
        except WriteIntentError as err:
            _record_contention(err, self.txn_id)
            raise TransactionRetryError(
                f"conflicting intent on {err.keys}"
            ) from err

    # -- writes -------------------------------------------------------------

    def put(self, key: bytes | str, value: bytes | str) -> None:
        self._write(_b(key), value, tomb=False)

    def delete(self, key: bytes | str) -> None:
        self._write(_b(key), b"", tomb=True)

    def _write(self, key: bytes, value, tomb: bool) -> None:
        self._check_open()
        eng = self.db.engine
        deadline = time.monotonic() + LOCK_WAIT_S
        nap = 0.001
        while True:
            # the lock-check + write pair holds the engine mutex so a
            # concurrent txn can't interleave between the check and the
            # intent landing (latch-acquisition atomicity,
            # concurrency_manager.SequenceReq)
            with eng.mu:
                other = eng.other_intent(key, self.txn_id)
                if other is None:
                    if eng.newest_committed_ts(key) > self.read_ts:
                        self._refresh_past(key)
                    if tomb:
                        eng.delete(key, ts=self.read_ts, txn=self.txn_id)
                    else:
                        eng.put(key, value, ts=self.read_ts,
                                txn=self.txn_id)
                    break
            # another transaction holds the key: stand in line for it, the
            # mutex released (the lock table's wait queue, reduced to a
            # poll), and give up as a retryable conflict only at the
            # deadline, which is also what breaks a wait cycle
            if time.monotonic() >= deadline:
                _record_contention(WriteIntentError([key], [other]),
                                   self.txn_id)
                raise TransactionRetryError(
                    f"key {key!r} locked by txn {other}")
            time.sleep(nap)
            nap = min(0.016, 2 * nap)
        self._write_keys.append(key)

    def _refresh_past(self, key: bytes) -> None:
        """WriteTooOld on `key`: someone committed above this transaction's
        timestamp. Move the timestamp up to now if every read made so far
        still holds there (the span refresher; a blind write has no read
        and always may), else the transaction must restart. Called with
        the engine mutex held."""
        now = self.db.clock.now()
        for s, e, is_point in self._read_spans:
            if self.db.engine.has_committed_writes_in(
                    s, e, self.read_ts, now, point=is_point):
                raise TransactionRetryError(f"write too old on {key!r}")
        self.read_ts = now

    # -- lifecycle ----------------------------------------------------------

    def commit(self) -> int:
        self._check_open()
        commit_ts = self.db.clock.now()
        # refresh + resolve are one atomic section under the engine mutex:
        # a write landing between a validated refresh and the intent
        # resolution would invalidate the just-checked read spans
        with self.db.engine.mu:
            # refresh: reads must still be valid at commit_ts
            for s, e, is_point in self._read_spans:
                if self.db.engine.has_committed_writes_in(
                    s, e, self.read_ts, commit_ts, point=is_point
                ):
                    self.rollback()
                    raise TransactionRetryError(
                        f"read span {s!r} invalidated before commit"
                    )
            self.db.engine.resolve_intents(
                self.txn_id, commit_ts, commit=True
            )
        self._finished = True
        from ..utils import metric

        metric.TXN_COMMITS.inc()
        for cb in self._commit_hooks:
            cb()
        return commit_ts

    def rollback(self) -> None:
        if self._finished:
            return
        self.db.engine.resolve_intents(self.txn_id, 0, commit=False)
        self._finished = True

    def _check_open(self):
        if self._finished:
            raise TransactionAbortedError("txn already finished")


def _b(x: bytes | str) -> bytes:
    return x.encode() if isinstance(x, str) else bytes(x)


def _record_contention(e: WriteIntentError, waiting_txn: int) -> None:
    """Feed the contention registry (pkg/sql/contention role); never let
    observability break the conflict path."""
    try:
        from .contention import DEFAULT

        DEFAULT.record(e.keys, e.txns, waiting_txn)
    # crlint: allow-broad-except(conflict path must not fail on observability; logged + counted)
    except Exception as rec_err:  # pragma: no cover - registry must not mask errors
        from ..utils import log, metric

        metric.CONTENTION_RECORD_ERRORS.inc()
        log.warning(log.OPS, "contention record failed",
                    error=f"{type(rec_err).__name__}: {rec_err}")


class DB:
    """kv.DB analog: non-transactional ops commit immediately; ``txn`` runs
    a closure with automatic retries."""

    def __init__(self, engine: Engine | None = None,
                 clock: hlc.Clock | None = None):
        self.engine = engine or Engine()
        self.clock = clock or hlc.Clock()

    # non-transactional (auto-committed) ops. Like the reference, non-txn
    # requests still sequence through concurrency control: a write under
    # another txn's intent conflicts (WriteIntentError) instead of silently
    # laying a committed version beneath the intent; non-txn reads surface
    # the same WriteIntentError (callers retry after the owner resolves).
    def put(self, key, value) -> int:
        k = _b(key)
        with self.engine.mu:
            self._check_lock(k)
            ts = self.clock.now()
            self.engine.put(k, value, ts=ts)
        return ts

    def delete(self, key) -> int:
        k = _b(key)
        with self.engine.mu:
            self._check_lock(k)
            ts = self.clock.now()
            self.engine.delete(k, ts=ts)
        return ts

    def _check_lock(self, key: bytes) -> None:
        other = self.engine.other_intent(key, 0)
        if other is not None:
            raise WriteIntentError([key], [other])

    def get(self, key, ts: int | None = None) -> bytes | None:
        return self.engine.get(_b(key), ts=ts if ts is not None
                               else self.clock.now())

    def get_committed(self, key, max_retries: int = MAX_RETRIES
                      ) -> bytes | None:
        """Autocommit point read that never reads through an intent and
        hands one to the caller only as a last resort: a foreign intent at
        or below the read timestamp is waited out (its transaction is a
        single statement about to commit or abort) and the read is made
        again at a fresh timestamp, ``max_retries`` times; then the
        conflict surfaces as TransactionRetryError (SQLSTATE 40001)."""
        from ..utils import metric

        k = _b(key)
        for attempt in range(max_retries):
            try:
                return self.get(k)
            except WriteIntentError as e:
                _record_contention(e, 0)
                metric.TXN_RETRIES.inc()
                _backoff(attempt)
        raise TransactionRetryError(
            f"read of {k!r} gave up after {max_retries} retries")

    def range_committed(self, start: bytes, end: bytes, ts: int,
                        max_retries: int = MAX_RETRIES, **page):
        """`get_committed` for one page of a range read
        (Engine.range_read): a foreign intent inside the span is waited
        out and the page read again, ``max_retries`` times, then the
        conflict surfaces as TransactionRetryError (SQLSTATE 40001); never
        read through. Every try reads at ``ts``, the statement's one
        timestamp: the intent it waited for commits above it (a commit
        takes its timestamp from the clock after this read took its own)
        or aborts, so the snapshot at ``ts`` is what it was."""
        from ..utils import metric

        for attempt in range(max_retries):
            try:
                return self.engine.range_read(start, end, ts=ts, **page)
            except WriteIntentError as e:
                _record_contention(e, 0)
                metric.TXN_RETRIES.inc()
                _backoff(attempt)
        raise TransactionRetryError(
            f"read of [{start!r}, {end!r}) gave up after {max_retries} "
            f"retries")

    def scan(self, start, end, ts: int | None = None, max_keys=None):
        return self.engine.scan(
            _b(start) if start is not None else None,
            _b(end) if end is not None else None,
            ts=ts if ts is not None else self.clock.now(),
            max_keys=max_keys,
        )

    def new_txn(self) -> Txn:
        return Txn(self, next(_txn_ids), self.clock.now())

    def txn(self, fn, max_retries: int = MAX_RETRIES):
        """Run fn(txn) with commit; retry on TransactionRetryError with a
        fresh timestamp (the kv.DB.Txn closure contract: fn must be
        idempotent across retries).

        AmbiguousResultError (kv/rpc.py) is deliberately NOT retried:
        when a remote mutation's apply state is unknowable, re-running
        the closure could commit it twice. It rolls back local intents
        and surfaces — the application decides whether to read-verify
        and resume (TxnCoordSender surfaces ambiguity the same way)."""
        for attempt in range(max_retries):
            t = self.new_txn()
            try:
                out = fn(t)
                t.commit()
                return out
            except TransactionRetryError:
                from ..utils import metric

                metric.TXN_RETRIES.inc()
                t.rollback()
                _backoff(attempt)
                continue
            except BaseException:
                # any other error: roll back so the intents don't wedge the
                # keys forever, then surface the error (kv.DB.Txn contract)
                t.rollback()
                raise
        raise TransactionRetryError(f"txn gave up after {max_retries} retries")
