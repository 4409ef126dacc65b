"""Order-checked lock wrappers — the runtime half of crlint's lock-order pass.

Reference: CockroachDB wires syncutil.Mutex with a deadlock-detection build
tag (sasha-s/go-deadlock) that records the global lock-acquisition order and
crashes on an inversion instead of deadlocking in production. Here the same
discipline is a pair of checks:

  * static  — ``cockroach_tpu/lint/lockorder.py`` walks every module's
    with-stacks and the lock-held call graph and fails CI on a cycle;
  * runtime — this module's ``OrderedLock`` family records, under
    ``debug.lock_order.enabled``, the edge "held A, acquired B" into one
    process-wide graph and raises :class:`LockOrderError` the moment an
    acquisition would close a cycle (any length, across threads), turning
    a would-be deadlock hang in the chaos suite into a stack trace.

The wrappers are drop-in for ``threading.Lock`` / ``RLock`` / ``Condition``
(context manager, ``acquire``/``release``/``wait``/``notify``). With the
setting off (the default) the only cost over a bare lock is one settings
read per acquire; control-plane locks use these wrappers, per-dispatch hot
locks (flow/dispatch, utils/metric, utils/log) deliberately stay bare.

Checking is edge-recording, not lock-holding: the graph accumulates every
ordering ever observed, so an A->B in one thread and B->A in another is
caught even when the two never race — exactly what a chaos run wants.
"""

from __future__ import annotations

import threading

from . import settings

__all__ = [
    "LockOrderError", "OrderedLock", "OrderedRLock", "OrderedCondition",
    "WaitTracedRLock",
    "lock", "rlock", "condition", "reset",
]


class LockOrderError(RuntimeError):
    """An acquisition would invert the observed global lock order."""


# process-wide order graph: _edges[a] = {b: (a_site, b_site)} meaning some
# thread acquired b while holding a. Guarded by _graph_mu (itself never
# held while user locks are taken, so it cannot participate in a cycle).
_graph_mu = threading.Lock()
_edges: dict[str, dict[str, str]] = {}
_tls = threading.local()


def reset() -> None:
    """Forget every recorded ordering (test isolation)."""
    with _graph_mu:
        _edges.clear()


def _held_stack() -> list[str]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _reachable(src: str, dst: str) -> list[str] | None:
    """Path src -> ... -> dst in the edge graph, or None. Caller holds
    _graph_mu."""
    seen = {src}
    frontier = [(src, [src])]
    while frontier:
        node, path = frontier.pop()
        for nxt in _edges.get(node, ()):
            if nxt == dst:
                return path + [nxt]
            if nxt not in seen:
                seen.add(nxt)
                frontier.append((nxt, path + [nxt]))
    return None


def _note_acquire(name: str) -> None:
    # the held stack serves two debug consumers: the order graph below
    # (debug.lock_order) and the race sanitizer's locksets
    # (utils/racesan.py reads _held_stack under debug.race_detector) —
    # graph edges and cycle checks stay gated on lock_order alone
    st = _held_stack()
    if st and st[-1] != name \
            and settings.get("debug.lock_order.enabled"):
        prev = st[-1]
        with _graph_mu:
            back = _reachable(name, prev)
            if back is not None:
                raise LockOrderError(
                    f"lock order inversion: acquiring {name!r} while "
                    f"holding {prev!r}, but the opposite order "
                    f"{' -> '.join(back)} -> {prev!r} was already observed; "
                    "two threads interleaving these paths deadlock"
                )
            _edges.setdefault(prev, {}).setdefault(name, "")
    st.append(name)


def _note_release(name: str) -> None:
    st = _held_stack()
    # release order need not be LIFO (lock handoff patterns); drop the
    # most recent matching entry
    for i in range(len(st) - 1, -1, -1):
        if st[i] == name:
            del st[i]
            return


class OrderedLock:
    """``threading.Lock`` with order checking under debug.lock_order."""

    _factory = staticmethod(threading.Lock)

    def __init__(self, name: str):
        self.name = name
        self._lk = self._factory()

    def _checking(self) -> bool:
        # either debug mode needs the per-thread held stack maintained
        return bool(settings.get("debug.lock_order.enabled")
                    or settings.get("debug.race_detector.enabled"))

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        check = self._checking()
        if check:
            _note_acquire(self.name)
        got = self._lk.acquire(blocking, timeout)
        if check and not got:
            _note_release(self.name)
        return got

    def release(self) -> None:
        self._lk.release()
        if self._checking():
            _note_release(self.name)

    def locked(self) -> bool:
        return self._lk.locked()

    def __enter__(self) -> "OrderedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


class OrderedRLock(OrderedLock):
    """``threading.RLock`` variant; re-entry is not an inversion because
    _note_acquire skips a self-edge when the same name tops the stack."""

    _factory = staticmethod(threading.RLock)

    def locked(self) -> bool:  # RLock has no .locked() before 3.12
        if self._lk.acquire(blocking=False):
            self._lk.release()
            return False
        return True


class WaitTracedRLock(OrderedRLock):
    """An OrderedRLock whose contended waits show in a statement's trace: a
    blocking acquire first tries without blocking (the owner's re-entry and
    a free lock end there, at no cost), and only a wait opens a leaf span
    named ``wait_span`` around it (utils/tracing: nothing outside a traced
    statement), so the span's total says how long statements stood in line
    for this lock."""

    def __init__(self, name: str, wait_span: str):
        super().__init__(name)
        self.wait_span = wait_span

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if not blocking or timeout != -1:
            return super().acquire(blocking, timeout)
        if super().acquire(False):
            return True
        from . import tracing

        with tracing.leaf_span(self.wait_span):
            return super().acquire()


class OrderedCondition:
    """``threading.Condition`` over an OrderedRLock. ``wait`` releases the
    underlying lock, so the held-stack entry is dropped for the duration —
    re-acquisition on wakeup is a fresh ordered acquire."""

    def __init__(self, name: str):
        self.name = name
        self._lock = OrderedRLock(name)
        self._cond = threading.Condition(self._lock._lk)

    def acquire(self, *a, **kw) -> bool:
        return self._lock.acquire(*a, **kw)

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> "OrderedCondition":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def wait(self, timeout: float | None = None) -> bool:
        checking = self._lock._checking()
        if checking:
            _note_release(self.name)
        try:
            return self._cond.wait(timeout)
        finally:
            if checking:
                _note_acquire(self.name)

    def wait_for(self, predicate, timeout: float | None = None):
        # reimplemented over self.wait so the held-stack bookkeeping above
        # applies to every sleep, not just the first
        import time

        result = predicate()
        if result:
            return result
        end = None if timeout is None else time.monotonic() + timeout
        while not result:
            if end is not None:
                remaining = end - time.monotonic()
                if remaining <= 0:
                    break
                self.wait(remaining)
            else:
                self.wait()
            result = predicate()
        return result

    def notify(self, n: int = 1) -> None:
        self._cond.notify(n)

    def notify_all(self) -> None:
        self._cond.notify_all()

    def __repr__(self) -> str:
        return f"<OrderedCondition {self.name!r}>"


# factories mirroring threading's callables — these are what the static
# pass (lint/lockorder.py _LOCK_CTORS) recognizes as lock definitions
def lock(name: str) -> OrderedLock:
    return OrderedLock(name)


def rlock(name: str, wait_span: str | None = None) -> OrderedRLock:
    if wait_span is not None:
        return WaitTracedRLock(name, wait_span)
    return OrderedRLock(name)


def condition(name: str) -> OrderedCondition:
    return OrderedCondition(name)
