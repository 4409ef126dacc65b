"""Tier-1 wiring for the crlint static-analysis suite (cockroach_tpu/lint/
+ scripts/check_lint.py) and the runtime lock-order detector
(cockroach_tpu/utils/locks.py).

Two halves:

- each lint pass is proven LIVE against a fixture tree that trips it
  (a gate that silently stopped finding anything is worse than no gate),
  plus pragma-suppression semantics;
- the real tree is held at zero findings and an acyclic static lock
  graph, and OrderedLock turns an A->B/B->A inversion into an immediate
  LockOrderError instead of a deadlock.
"""

import threading

import pytest

from cockroach_tpu.lint import run_lint
from cockroach_tpu.lint.core import load_files
from cockroach_tpu.lint import lockorder
from cockroach_tpu.utils import locks, settings
from scripts.check_lint import check


# ---------------------------------------------------------------- fixtures

def _tree(tmp_path, files: dict[str, str]):
    """Materialize {relpath: source} under tmp_path and return the root.
    Paths start with cockroach_tpu/... so the passes scope exactly like
    the real tree (core._canonical_rel anchors on that component)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return tmp_path / "cockroach_tpu"


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- real tree

def test_real_tree_is_clean():
    problems = check()
    assert not problems, "\n".join(problems)


def test_real_tree_lock_graph_acyclic():
    files = load_files(["cockroach_tpu"])
    lock_names, edges = lockorder.build_lock_graph(files)
    assert lock_names, "lock indexing broke: no locks found in the tree"
    assert not lockorder.check(files)


# ------------------------------------------------------------- host-sync

_HOT = "cockroach_tpu/flow/runtime.py"

def test_host_sync_flags_implicit_transfers(tmp_path):
    root = _tree(tmp_path, {_HOT: (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    n = int(jnp.sum(x))\n"          # int() on traced value
        "    v = x.item()\n"                 # .item()
        "    h = np.asarray(jnp.abs(x))\n"   # device -> host copy
        "    if jnp.any(x):\n"               # truth test forces sync
        "        pass\n"
        "    return n, v, h\n")})
    found = run_lint([root], rules=("host-sync",))
    assert len(found) == 4, [f.render() for f in found]
    assert _rules(found) == ["host-sync"]


def test_host_sync_scoped_to_hot_modules(tmp_path):
    # same code outside the hot path (and in the allowlisted wire module)
    # is not a finding
    src = "import jax.numpy as jnp\ndef f(x):\n    return int(jnp.sum(x))\n"
    root = _tree(tmp_path, {
        "cockroach_tpu/bench/tpcds.py": src,
        "cockroach_tpu/flow/wire.py": src,
    })
    assert not run_lint([root], rules=("host-sync",))


def test_host_sync_pragma_and_host_literals(tmp_path):
    root = _tree(tmp_path, {_HOT: (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(x, rows):\n"
        "    # crlint: allow-host-sync(one sync per query by design)\n"
        "    n = int(jnp.sum(x))\n"
        "    a = np.asarray([1, 2, 3])\n"     # host literal: no readback
        "    if jnp.issubdtype(x.dtype, jnp.integer):\n"  # host predicate
        "        pass\n"
        "    return n, a\n")})
    assert not run_lint([root], rules=("host-sync",))


# --------------------------------------------------------------- raw-jit

def test_raw_jit_flagged_outside_dispatch(tmp_path):
    root = _tree(tmp_path, {
        "cockroach_tpu/ops/thing.py": (
            "import jax\n"
            "import functools\n"
            "f = jax.jit(lambda x: x)\n"
            "g = functools.partial(jax.pmap, axis_name='d')\n"),
        "cockroach_tpu/flow/dispatch.py": (
            "import jax\n"
            "def jit(fn, **kw):\n"
            "    return jax.jit(fn, **kw)\n"),
    })
    found = run_lint([root], rules=("raw-jit",))
    # both sites in ops/thing.py (incl. the partial arg), none in dispatch
    assert len(found) == 2, [f.render() for f in found]
    assert all(f.path == "cockroach_tpu/ops/thing.py" for f in found)


# ----------------------------------------------------------- broad-except

def test_silent_swallow_is_unsuppressible(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/kv/thing.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # crlint: allow-broad-except(pragma must NOT mute this)\n"
        "    except Exception:\n"
        "        pass\n")})
    found = run_lint([root], rules=("broad-except",))
    assert len(found) == 1
    assert not found[0].suppressible


def test_broad_except_pragma_and_reraise(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/flow/thing.py": (
        "def ok_reraise():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        raise\n"
        "def ok_pragma():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:  # crlint: allow-broad-except(logged)\n"
        "        log(e)\n"
        "def bad():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        log(e)\n")})
    found = run_lint([root], rules=("broad-except",))
    assert len(found) == 1
    assert found[0].line > 10  # the finding is in bad(), not the first two


def test_broad_except_scoped_outside_kv_flow_server(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/bench/thing.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n")})
    assert not run_lint([root], rules=("broad-except",))


# ------------------------------------------------------------ tracing-api

def test_tracing_api_flags_direct_span_construction(tmp_path):
    root = _tree(tmp_path, {
        "cockroach_tpu/flow/thing.py": (
            "from ..utils import tracing\n"
            "from ..utils.tracing import Span\n"
            "def f(tr):\n"
            "    a = Span('x')\n"             # imported-name construction
            "    b = tracing.Span('y')\n"     # attribute construction
            "    tr._current.set(a)\n"        # tracer internals
            "    return a, b\n"),
        "cockroach_tpu/utils/tracing.py": (
            "class Span:\n"
            "    pass\n"
            "def span(name):\n"
            "    return Span(name)\n"),       # the API itself is exempt
    })
    found = run_lint([root], rules=("tracing-api",))
    assert len(found) == 3, [f.render() for f in found]
    assert all(f.path == "cockroach_tpu/flow/thing.py" for f in found)


def test_tracing_api_pragma_suppresses(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/plan/thing.py": (
        "from ..utils import tracing\n"
        "def f():\n"
        "    # crlint: allow-tracing-api(test fixture builds a detached tree)\n"
        "    return tracing.Span('x')\n")})
    assert not run_lint([root], rules=("tracing-api",))


def test_tracing_api_ignores_entered_spans(tmp_path):
    # the sanctioned forms produce no findings
    root = _tree(tmp_path, {"cockroach_tpu/kv/thing.py": (
        "from ..utils import tracing\n"
        "def f():\n"
        "    with tracing.span('a') as sp:\n"
        "        with tracing.leaf_span('b'):\n"
        "            pass\n"
        "    return tracing.synthetic_span(sp, 'c', 0.1)\n")})
    assert not run_lint([root], rules=("tracing-api",))


# ---------------------------------------------------------- unused-import

def test_unused_import_flagged_and_pragma(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/util.py": (
        "import os\n"
        "import sys  # crlint: allow-unused-import(re-export shim)\n"
        "import json\n"
        "print(json.dumps({}))\n")})
    found = run_lint([root], rules=("unused-import",))
    assert len(found) == 1
    assert "'os'" in found[0].message


def test_empty_pragma_reason_does_not_suppress(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/util.py": (
        "import os  # crlint: allow-unused-import()\n")})
    assert len(run_lint([root], rules=("unused-import",))) == 1


# ------------------------------------------------------------- lock-order

def test_lock_order_cycle_through_call_graph(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/mod.py": (
        "import threading\n"
        "LOCK_A = threading.Lock()\n"
        "LOCK_B = threading.Lock()\n"
        "def path_one():\n"
        "    with LOCK_A:\n"
        "        with LOCK_B:\n"
        "            pass\n"
        "def path_two():\n"
        "    with LOCK_B:\n"
        "        helper()\n"       # inversion is one call deep
        "def helper():\n"
        "    with LOCK_A:\n"
        "        pass\n")})
    found = run_lint([root], rules=("lock-order",))
    assert len(found) == 1
    assert "cycle" in found[0].message


def test_lock_order_consistent_nesting_is_clean(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/mod.py": (
        "import threading\n"
        "LOCK_A = threading.Lock()\n"
        "LOCK_B = threading.Lock()\n"
        "def one():\n"
        "    with LOCK_A:\n"
        "        with LOCK_B:\n"
        "            pass\n"
        "def two():\n"
        "    with LOCK_A:\n"
        "        with LOCK_B:\n"
        "            pass\n")})
    assert not run_lint([root], rules=("lock-order",))


# ------------------------------------------------ runtime OrderedLock

@pytest.fixture
def lock_order_on():
    locks.reset()
    prev = settings.get("debug.lock_order.enabled")
    settings.set("debug.lock_order.enabled", True)
    yield
    settings.set("debug.lock_order.enabled", prev)
    locks.reset()


def test_ordered_lock_inversion_raises(lock_order_on):
    a, b = locks.lock("t.A"), locks.lock("t.B")
    with a:
        with b:
            pass
    with b:
        with pytest.raises(locks.LockOrderError):
            a.acquire()


def test_ordered_lock_transitive_cycle(lock_order_on):
    x, y, z = locks.lock("t.X"), locks.lock("t.Y"), locks.lock("t.Z")
    with x:
        with y:
            pass
    with y:
        with z:
            pass
    with z:
        with pytest.raises(locks.LockOrderError):
            x.acquire()


def test_ordered_lock_cross_thread(lock_order_on):
    # the order graph is global: thread 1 records A->B, thread 2's B->A
    # trips even though the two never contend
    a, b = locks.lock("t2.A"), locks.lock("t2.B")
    def t1():
        with a:
            with b:
                pass
    th = threading.Thread(target=t1)
    th.start()
    th.join()
    with b:
        with pytest.raises(locks.LockOrderError):
            a.acquire()


def test_ordered_lock_disabled_is_noop():
    locks.reset()
    assert settings.get("debug.lock_order.enabled") is False
    a, b = locks.lock("t3.A"), locks.lock("t3.B")
    with a:
        with b:
            pass
    with b:
        with a:  # inverted, but checking is off
            pass


def test_ordered_rlock_reentry_ok(lock_order_on):
    r = locks.rlock("t.R")
    with r:
        with r:
            pass
    assert not r.locked()


def test_ordered_condition_wait_notify(lock_order_on):
    c = locks.condition("t.C")
    hits = []
    def waiter():
        with c:
            c.wait_for(lambda: hits, timeout=5)
            hits.append("woke")
    th = threading.Thread(target=waiter)
    th.start()
    import time
    time.sleep(0.05)
    with c:
        hits.append("set")
        c.notify_all()
    th.join(timeout=5)
    assert hits == ["set", "woke"]


# ----------------------------------------------------------- shared-state

_RACY = (
    "import threading\n"
    "class W:\n"
    "    def __init__(self):\n"
    "        self.counter = 0\n"
    "        self._t = threading.Thread(target=self._loop, daemon=True)\n"
    "        self._t.start()\n"
    "    def _loop(self):\n"
    "        for _ in range(10):\n"
    "            self.counter += 1\n"
    "    def bump(self):\n"
    "        self.counter += 1\n"
)


def test_shared_state_flags_multi_entry_unlocked_rmw(tmp_path):
    """Live trip: a field RMW-mutated from both a spawned thread and the
    main entry with no lock anywhere is exactly the race the pass hunts."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": _RACY})
    found = run_lint([root], rules=("shared-state",))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "shared-state"
    assert "counter" in found[0].message
    assert "no common lock" in found[0].message


def test_shared_state_lock_guard_is_quiet(tmp_path):
    """The fix the finding demands, verified quiet: both sites under one
    OrderedLock."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": (
        "import threading\n"
        "from ..utils import locks\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._mu = locks.lock('kv.widget')\n"
        "        self.counter = 0\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        with self._mu:\n"
        "            self.counter += 1\n"
        "    def bump(self):\n"
        "        with self._mu:\n"
        "            self.counter += 1\n")})
    assert not run_lint([root], rules=("shared-state",))


def test_shared_state_inline_pragma_suppresses(tmp_path):
    src = _RACY.replace(
        "    def bump(self):\n",
        "    def bump(self):\n"
        "        # crlint: allow-shared-state(single writer by protocol)\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("shared-state",))


def test_shared_state_def_line_waiver_covers_body(tmp_path):
    src = _RACY.replace(
        "    def bump(self):\n",
        "    # crlint: allow-shared-state(test-only mutator, documented)\n"
        "    def bump(self):\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("shared-state",))


# --------------------------------------------------------- mem-accounting

_HOT_ALLOC = (
    "import numpy as np\n"
    "def f(n):\n"
    "    return np.zeros((n, 1024))\n"
)


def test_mem_accounting_flags_uncharged_hot_path_alloc(tmp_path):
    """Live trip: a data-sized materialization on a flow hot path with no
    accounting evidence anywhere in the function."""
    root = _tree(tmp_path, {_HOT: _HOT_ALLOC})
    found = run_lint([root], rules=("mem-accounting",))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "mem-accounting"
    assert "np.zeros" in found[0].message


def test_mem_accounting_evidence_and_scope(tmp_path):
    # reserve() in the function is evidence; the same alloc in a
    # non-hot-path module is out of scope entirely
    root = _tree(tmp_path, {
        _HOT: ("import numpy as np\n"
               "def g(mon, n):\n"
               "    mon.reserve(n * 8192)\n"
               "    return np.zeros((n, 1024))\n"),
        "cockroach_tpu/bench/gen.py": _HOT_ALLOC,
    })
    assert not run_lint([root], rules=("mem-accounting",))


def test_mem_accounting_small_literal_shape_is_quiet(tmp_path):
    root = _tree(tmp_path, {_HOT: (
        "import numpy as np\n"
        "def f():\n"
        "    return np.zeros((4, 8))\n")})
    assert not run_lint([root], rules=("mem-accounting",))


def test_mem_accounting_inline_pragma_suppresses(tmp_path):
    root = _tree(tmp_path, {_HOT: (
        "import numpy as np\n"
        "def f(n):\n"
        "    # crlint: allow-mem-accounting(bounded by tile count)\n"
        "    return np.zeros((n, 1024))\n")})
    assert not run_lint([root], rules=("mem-accounting",))


# --------------------------------------------------------- fault-coverage

_FAULTS_FIXTURE = {
    "cockroach_tpu/utils/faults.py": (
        "SITES: dict[str, str] = {\n"
        "    'a.b': 'site one',\n"
        "    'c.d': 'site two',\n"
        "}\n"
        "def fire(site):\n"
        "    pass\n"),
    "cockroach_tpu/kv/thing.py": (
        "from ..utils import faults\n"
        "def f(name):\n"
        "    faults.fire('a.b')\n"),
    "tests/test_foo.py": (
        "import pytest\n"
        "pytestmark = pytest.mark.chaos\n"
        "def test_x():\n"
        "    assert 'a.b'\n"),
}


def _fault_tree(tmp_path, files):
    _tree(tmp_path, files)
    return [tmp_path / "cockroach_tpu", tmp_path / "tests"]


def test_fault_coverage_flags_all_three_gaps(tmp_path):
    """Live trip of every finding class: a computed site name, a dead
    registration, and a registered site no chaos test exercises."""
    files = dict(_FAULTS_FIXTURE)
    files["cockroach_tpu/kv/thing.py"] = (
        "from ..utils import faults\n"
        "def f(name):\n"
        "    faults.fire('a.b')\n"
        "    faults.fire(name)\n")
    found = run_lint(_fault_tree(tmp_path, files),
                     rules=("fault-coverage",))
    msgs = [f.message for f in found]
    assert len(found) == 3, [f.render() for f in found]
    assert any("not a string literal" in m for m in msgs)
    assert any("no fire call in product code" in m for m in msgs)
    assert any("not exercised by any chaos-marked test" in m for m in msgs)


def test_fault_coverage_closed_loop_is_quiet(tmp_path):
    files = dict(_FAULTS_FIXTURE)
    files["cockroach_tpu/utils/faults.py"] = (
        "SITES: dict[str, str] = {\n"
        "    'a.b': 'site one',\n"
        "}\n"
        "def fire(site):\n"
        "    pass\n")
    assert not run_lint(_fault_tree(tmp_path, files),
                        rules=("fault-coverage",))


def test_fault_coverage_scoped_site_names_count(tmp_path):
    """A test naming the node-scoped '<site>.n<id>' variant covers the
    base registration (fire_scoped's contract)."""
    files = dict(_FAULTS_FIXTURE)
    files["cockroach_tpu/utils/faults.py"] = (
        "SITES: dict[str, str] = {\n"
        "    'a.b': 'site one',\n"
        "}\n"
        "def fire(site):\n"
        "    pass\n")
    files["tests/test_foo.py"] = (
        "import pytest\n"
        "pytestmark = pytest.mark.chaos\n"
        "def test_x():\n"
        "    assert 'a.b.n3'\n")
    assert not run_lint(_fault_tree(tmp_path, files),
                        rules=("fault-coverage",))


def test_fault_coverage_registry_pragma_suppresses(tmp_path):
    files = dict(_FAULTS_FIXTURE)
    files["cockroach_tpu/utils/faults.py"] = (
        "SITES: dict[str, str] = {\n"
        "    'a.b': 'site one',\n"
        "    # crlint: allow-fault-coverage(planned site, test in flight)\n"
        "    'c.d': 'site two',\n"
        "}\n"
        "def fire(site):\n"
        "    pass\n")
    files["cockroach_tpu/kv/thing.py"] = (
        "from ..utils import faults\n"
        "def f():\n"
        "    faults.fire('a.b')\n"
        "    faults.fire('c.d')\n")
    assert not run_lint(_fault_tree(tmp_path, files),
                        rules=("fault-coverage",))


# --------------------------------------------------------- unknown-pragma

def test_unknown_rule_pragma_is_a_finding(tmp_path):
    """A typo'd pragma suppresses nothing — and saying so is itself a
    finding, so the near-miss can't silently convince anyone a waiver is
    in force."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": (
        "def f():\n"
        "    # crlint: allow-mem-acounting(typo never suppresses)\n"
        "    return 1\n")})
    found = run_lint([root])
    assert [f.rule for f in found] == ["unknown-pragma"]
    assert "mem-acounting" in found[0].message


# ------------------------------------------------------------------- CLI

def test_cli_exit_codes_clean_findings_internal(tmp_path):
    from cockroach_tpu.lint.__main__ import main

    clean = tmp_path / "cockroach_tpu" / "ok.py"
    clean.parent.mkdir(parents=True, exist_ok=True)
    clean.write_text("X = 1\n")
    assert main([str(clean)]) == 0

    dirty = tmp_path / "cockroach_tpu" / "dirty.py"
    dirty.write_text("import jax\nf = jax.jit(lambda x: x)\n")
    assert main([str(dirty)]) == 1

    broken = tmp_path / "cockroach_tpu" / "broken.py"
    broken.write_text("def f(:\n")
    assert main([str(broken)]) == 2  # linter failure, not a finding


def test_cli_changed_only_filters_report(tmp_path):
    from cockroach_tpu.lint.__main__ import main

    root = _tree(tmp_path, {
        "cockroach_tpu/kv/a.py": "import jax\nf = jax.jit(lambda x: x)\n",
        "cockroach_tpu/kv/b.py": "import jax\ng = jax.jit(lambda x: x)\n",
    })
    lst = tmp_path / "changed.txt"
    lst.write_text("cockroach_tpu/kv/a.py\n")
    # both files dirty, but only a.py is in the changed list
    assert main([str(root), "--changed-only", str(lst)]) == 1
    lst.write_text("cockroach_tpu/kv/other.py\n")
    assert main([str(root), "--changed-only", str(lst)]) == 0


def test_cli_json_is_stable_and_location_sorted(tmp_path):
    import json as _json

    from cockroach_tpu.lint.__main__ import main

    root = _tree(tmp_path, {
        "cockroach_tpu/kv/b.py": "import jax\ng = jax.jit(lambda x: x)\n",
        "cockroach_tpu/kv/a.py": "import jax\nf = jax.jit(lambda x: x)\n",
    })
    import io
    import contextlib

    bufs = []
    for _ in range(2):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert main([str(root), "--json"]) == 1
        bufs.append(buf.getvalue())
    assert bufs[0] == bufs[1]  # byte-stable across runs
    recs = _json.loads(bufs[0])
    locs = [(r["path"], r["line"]) for r in recs]
    assert locs == sorted(locs)
    assert locs[0][0].endswith("a.py")


# ----------------------------------------------------------- untimed-wait

_UNTIMED = (
    "import queue\n"
    "import threading\n"
    "class W:\n"
    "    def __init__(self):\n"
    "        self.ev = threading.Event()\n"
    "        self.q = queue.Queue()\n"
    "        self._t = threading.Thread(target=self._loop, daemon=True)\n"
    "        self._t.start()\n"
    "    def _loop(self):\n"
    "        self.ev.wait()\n"
    "        return self.q.get()\n"
)


def test_untimed_wait_flags_thread_reachable_waits(tmp_path):
    """Live trip: an Event.wait() and a Queue.get() with no timeout on a
    spawned thread's path are exactly the wedge the pass hunts."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": _UNTIMED})
    found = run_lint([root], rules=("untimed-wait",))
    assert len(found) == 2, [f.render() for f in found]
    assert all(f.rule == "untimed-wait" for f in found)
    msgs = " ".join(f.message for f in found)
    assert ".wait()" in msgs and ".get()" in msgs


def test_untimed_wait_bounded_is_quiet(tmp_path):
    """The fix the finding demands, verified quiet: explicit timeouts."""
    src = _UNTIMED.replace("self.ev.wait()", "self.ev.wait(1.0)") \
                  .replace("self.q.get()", "self.q.get(timeout=1.0)")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("untimed-wait",))


def test_untimed_wait_unreachable_helper_is_quiet(tmp_path):
    """The pass walks the thread-entry graph: a wait in a helper no
    thread entry reaches is not control-plane blocking."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": (
        "import threading\n"
        "def helper(ev):\n"
        "    ev.wait()\n")})
    assert not run_lint([root], rules=("untimed-wait",))


def test_untimed_wait_inline_pragma_suppresses(tmp_path):
    src = _UNTIMED.replace(
        "        self.ev.wait()\n",
        "        # crlint: allow-untimed-wait(shutdown path, reaped by "
        "close)\n"
        "        self.ev.wait()\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    found = run_lint([root], rules=("untimed-wait",))
    assert len(found) == 1  # the queue.get() is still a finding
    assert ".get()" in found[0].message


def test_untimed_wait_def_line_waiver_covers_body(tmp_path):
    src = _UNTIMED.replace(
        "    def _loop(self):\n",
        "    # crlint: allow-untimed-wait(owner arms deadlines before "
        "start)\n"
        "    def _loop(self):\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("untimed-wait",))


def test_untimed_wait_empty_reason_does_not_suppress(tmp_path):
    src = _UNTIMED.replace(
        "        self.ev.wait()\n",
        "        self.ev.wait()  # crlint: allow-untimed-wait()\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert len(run_lint([root], rules=("untimed-wait",))) == 2


# ------------------------------------------------------- recompile-hazard

_SHAPE_HOT_FIXTURE = "cockroach_tpu/flow/operators.py"


def test_recompile_hazard_flags_unbucketed_cap(tmp_path):
    """Live trip: a cap derived straight from len() in a shape-hot
    module mints one executable per cardinality."""
    root = _tree(tmp_path, {_SHAPE_HOT_FIXTURE: (
        "def plan(rows):\n"
        "    cap = len(rows)\n"
        "    return cap\n")})
    found = run_lint([root], rules=("recompile-hazard",))
    assert len(found) == 1, [f.render() for f in found]
    assert "canonical-bucketing" in found[0].message


def test_recompile_hazard_bucketed_cap_is_quiet(tmp_path):
    root = _tree(tmp_path, {_SHAPE_HOT_FIXTURE: (
        "from .fuse import _canonical_cap\n"
        "def plan(rows):\n"
        "    cap = _canonical_cap(len(rows))\n"
        "    return cap\n")})
    assert not run_lint([root], rules=("recompile-hazard",))


def test_recompile_hazard_flags_impure_kernel_key(tmp_path):
    """f-strings and repr() in a kernel key make two equal kernels key
    differently — a guaranteed cache miss and retrace."""
    root = _tree(tmp_path, {"cockroach_tpu/ops/thing.py": (
        "from ..flow import dispatch\n"
        "def f(schema, n):\n"
        "    return dispatch.kernel_key('agg', f'{schema}', repr(n))\n")})
    found = run_lint([root], rules=("recompile-hazard",))
    assert len(found) == 2, [f.render() for f in found]
    msgs = " ".join(f.message for f in found)
    assert "f-string" in msgs and "repr()" in msgs


def test_recompile_hazard_flags_keyless_closure_jit(tmp_path):
    """dispatch.jit on a fresh closure outside construction re-traces on
    every call; key= or construction-time hoisting is the fix."""
    root = _tree(tmp_path, {"cockroach_tpu/ops/thing.py": (
        "from ..flow import dispatch\n"
        "def f(x):\n"
        "    g = dispatch.jit(lambda v: v + 1)\n"
        "    return g(x)\n")})
    found = run_lint([root], rules=("recompile-hazard",))
    assert len(found) == 1
    assert "fresh wrapper" in found[0].message


def test_recompile_hazard_construction_and_keyed_are_quiet(tmp_path):
    """init() runs once per operator instance (instances are reused
    across queries), and key= rides the process-global kernel cache —
    neither is a per-call retrace."""
    root = _tree(tmp_path, {"cockroach_tpu/ops/thing.py": (
        "from ..flow import dispatch\n"
        "class Op:\n"
        "    def init(self):\n"
        "        self.g = dispatch.jit(lambda v: v + 1)\n"
        "def f(x):\n"
        "    h = dispatch.jit(lambda v: v - 1, key=('dec', 'i64'))\n"
        "    return h(x)\n")})
    assert not run_lint([root], rules=("recompile-hazard",))


def test_recompile_hazard_def_line_waiver_covers_body(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/ops/thing.py": (
        "from ..flow import dispatch\n"
        "# crlint: allow-recompile-hazard(cold path, traced once by "
        "contract)\n"
        "def f(x):\n"
        "    g = dispatch.jit(lambda v: v + 1)\n"
        "    return g(x)\n")})
    assert not run_lint([root], rules=("recompile-hazard",))


# --------------------------------------------------------- race-coverage

def test_race_coverage_flags_uninstrumented_shared_field(tmp_path):
    """Live trip: multi-entry unlocked writes the sanitizer never sees —
    the gap between the escape analysis and racesan's hand-placed
    instrumentation."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": _RACY})
    found = run_lint([root], rules=("race-coverage",))
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].rule == "race-coverage"
    assert "note_read/note_write" in found[0].message


def test_race_coverage_instrumented_is_quiet(tmp_path):
    """racesan note_* calls naming the field in its module count as
    coverage: the runtime detector now sees every access."""
    src = _RACY.replace(
        "import threading\n",
        "import threading\n"
        "from ..utils import racesan\n"
    ).replace(
        "            self.counter += 1\n",
        "            racesan.note_write(self, 'counter')\n"
        "            self.counter += 1\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("race-coverage",))


def test_race_coverage_lock_guarded_is_quiet(tmp_path):
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": (
        "import threading\n"
        "from ..utils import locks\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._mu = locks.lock('kv.widget')\n"
        "        self.counter = 0\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        with self._mu:\n"
        "            self.counter += 1\n"
        "    def bump(self):\n"
        "        with self._mu:\n"
        "            self.counter += 1\n")})
    assert not run_lint([root], rules=("race-coverage",))


def test_race_coverage_init_site_pragma_waives_state_wide(tmp_path):
    """A reasoned pragma on the __init__ assignment (the ergonomic spot)
    waives the whole state, like shared-state's state-wide waiver."""
    src = _RACY.replace(
        "        self.counter = 0\n",
        "        # crlint: allow-race-coverage(single-writer by "
        "protocol; instrumenting would false-positive under racesan)\n"
        "        self.counter = 0\n")
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    assert not run_lint([root], rules=("race-coverage",))


def test_race_coverage_map_statuses(tmp_path):
    """coverage_map labels every analyzed state; the waived row keeps
    its sites visible (the CLI's --race-map contract)."""
    from cockroach_tpu.lint.core import TreeCache
    from cockroach_tpu.lint.racecoverage import coverage_map, render_map

    src = _RACY.replace(
        "        self.counter = 0\n",
        "        # crlint: allow-race-coverage(documented lock-free "
        "single-writer)\n"
        "        self.counter = 0\n")
    _tree(tmp_path, {"cockroach_tpu/kv/widget.py": src})
    files = load_files([tmp_path / "cockroach_tpu"])
    rows = coverage_map(files, TreeCache(files))
    by_state = {r["state"].rsplit(".", 1)[-1]: r for r in rows}
    assert by_state["counter"]["status"] == "waived"
    assert by_state["counter"]["sites"]
    text = render_map(rows)
    assert "counter: waived" in text


def test_unknown_pragma_covers_new_rules(tmp_path):
    """Typo'd waivers of the three new passes are themselves findings."""
    root = _tree(tmp_path, {"cockroach_tpu/kv/widget.py": (
        "def f():\n"
        "    # crlint: allow-untimed-waits(typo)\n"
        "    # crlint: allow-recompile-hazzard(typo)\n"
        "    # crlint: allow-race-coverge(typo)\n"
        "    return 1\n")})
    found = run_lint([root])
    assert sorted(f.rule for f in found) == ["unknown-pragma"] * 3


# ------------------------------------------------- real tree: new passes

def test_real_tree_new_passes_are_clean_individually():
    """Each PR-20 pass holds zero findings at HEAD on its own (the tree
    gate runs them all; this pins the per-rule contract)."""
    found = run_lint(
        ["cockroach_tpu", "scripts", "tests", "__graft_entry__.py",
         "chip_smoke.py"],
        rules=("untimed-wait", "recompile-hazard", "race-coverage"))
    assert not found, [f.render() for f in found]


def test_run_lint_fills_per_pass_timings():
    """run_lint exposes per-pass wall seconds plus the shared load/parse
    cost — the budget the TreeCache defends."""
    from cockroach_tpu.lint.core import ALL_RULES

    timings = {}
    found = run_lint(["cockroach_tpu/lint"], timings=timings)
    assert "load/parse" in timings
    for rule in ALL_RULES:
        assert rule in timings, rule
        assert timings[rule] >= 0.0
    assert not found


def test_cli_changed_only_git_mode(tmp_path, monkeypatch):
    """--changed-only --git takes the changed set straight from git:
    untracked/modified files are reported, committed-clean ones are
    filtered out."""
    import subprocess

    from cockroach_tpu.lint.__main__ import main

    root = _tree(tmp_path, {
        "cockroach_tpu/kv/a.py": "import jax\nf = jax.jit(lambda x: x)\n",
    })
    monkeypatch.chdir(tmp_path)
    env = {"GIT_CONFIG_GLOBAL": "/dev/null", "GIT_CONFIG_SYSTEM": "/dev/null"}
    subprocess.run(["git", "init", "-q"], check=True, env={**__import__("os").environ, **env})
    # untracked: the dirty file is in the changed set
    assert main([str(root), "--changed-only", "--git"]) == 1
    subprocess.run(["git", "add", "-A"], check=True,
                   env={**__import__("os").environ, **env})
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-qm", "x"], check=True,
        env={**__import__("os").environ, **env})
    # committed and unmodified: filtered out of the report
    assert main([str(root), "--changed-only", "--git"]) == 0
