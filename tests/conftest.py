"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the reference's analog is `fakedist`
— pkg/sql/physicalplan/fake_span_resolver.go — which fakes multi-node
distribution inside one process). The chip is reached only through
benchmarks/run.py and chip_smoke.py; tests/test_tpu_compile.py asks the
chip's compiler about a described (not attached) v5e from inside its own
fixture.
"""

from cockroach_tpu.utils.backend import force_cpu_backend

force_cpu_backend(8)

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu"
assert len(jax.devices()) == 8, "virtual 8-device CPU mesh required"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic seeded fault-injection tests (fast seeds "
        "run in tier-1; exclude with -m 'not chaos')")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _memory_drain_census():
    """leaktest analog for the memory-monitor tree: every query-level
    monitor must drain to zero by the time its query scope closes. The
    drain-failure counter (flow/memory.py) is monotonic, so any increase
    across a test means that test leaked reserved bytes — fail it, with
    the offending monitors named (scripts/check_no_leaks.py carries the
    same census for standalone harnesses)."""
    from scripts.check_no_leaks import _drain_failure_count

    before = _drain_failure_count()
    yield
    after = _drain_failure_count()
    # the node-wide block cache (storage/blockcache.py) outlives any one
    # engine: drain it between tests so cached windows from a dead test's
    # runs can't pin root-monitor bytes or leak hit-rate state across
    # tests (every test starts cold, like a fresh node)
    from cockroach_tpu.storage import blockcache

    blockcache.node_cache().clear()
    if after > before:
        from cockroach_tpu.flow import memory

        raise AssertionError(
            f"query memory monitors closed non-drained ({before} -> "
            f"{after}): {memory.drain_failures(last=after - before)}")
