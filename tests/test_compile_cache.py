"""The persistent compile cache has one home: JAX_COMPILATION_CACHE_DIR when
it is set, else the fixed <checkout>/.jax_cache — and one function decides
(utils/backend.enable_compile_cache), which every entry point goes through."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from cockroach_tpu.utils import backend

_CHECKOUT = pathlib.Path(__file__).resolve().parent.parent

# a Session serving one statement, with the size/time thresholds dropped so
# that even this small compile is persisted
_CHILD = """
import json, os, sys
from cockroach_tpu.utils.backend import force_cpu_backend
force_cpu_backend()
import jax
from cockroach_tpu.sql import Session
s = Session()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
s.execute("create table t (a int primary key, b int)")
s.execute("insert into t values (1, 2), (3, 4)")
assert int(s.execute("select sum(b) as t from t")["t"][0]) == 6
s.close()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir}))
"""


def _run_child(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         cwd=_CHECKOUT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])["dir"]


def test_session_writes_where_the_environment_says(tmp_path):
    env_dir = tmp_path / "cache_from_env"
    assert _run_child(env_dir) == str(env_dir)
    assert any(env_dir.iterdir()), "nothing was cached in the env's directory"


def test_session_defaults_to_the_checkout(tmp_path):
    default = _CHECKOUT / ".jax_cache"
    assert _run_child(None) == str(default)
    assert any(default.iterdir())


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_one_function_decides(monkeypatch, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = (str(_CHECKOUT / ".jax_cache"), False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = (env_dir, True)
    assert backend.compile_cache_dir() == want


def test_every_entry_point_goes_through_it(monkeypatch, capsys):
    """Session, Node and chip_smoke.py all call the one function, and none
    of them names a directory of its own."""
    import chip_smoke
    from cockroach_tpu.server.node import Node
    from cockroach_tpu.sql import Session, plancache

    calls = []

    def record():
        calls.append(1)
        return backend.compile_cache_dir()[0]

    monkeypatch.setattr(backend, "enable_compile_cache", record)
    monkeypatch.setattr(plancache, "_compile_cache_on", False)
    Session().close()
    assert len(calls) == 1
    monkeypatch.setattr(plancache, "_compile_cache_on", False)
    node = Node().start(gossip_port=None)
    node.stop()
    assert len(calls) == 2
    chip_smoke.phase_device()
    assert len(calls) == 3
    capsys.readouterr()
    for path in ("chip_smoke.py", "cockroach_tpu/cli.py",
                 "cockroach_tpu/sql/plancache.py",
                 "cockroach_tpu/sql/session.py",
                 "cockroach_tpu/server/node.py"):
        assert "jax_compilation_cache_dir" not in (
            _CHECKOUT / path).read_text(), path
