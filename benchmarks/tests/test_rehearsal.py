"""Each cell's command end to end at tiny size on the CPU: counts and
`correct`, no timing metric, `platform: cpu`; the control comes out as not
correct; a run on the wrong platform, or from a bare copy, fails."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from helpers import BENCH, ROOT, run_cell

CELLS = ["tpch_sf001.q1", "tpch_sf001.q3"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    rc, lines, err = run_cell(cell, seed=2**31 + 12345, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0
    assert last["metrics"], "a rehearsal still prints its counts"
    assert all(m["unit"] == "count" for m in last["metrics"].values())
    compares = [ln for ln in lines if ln.get("step") == "compare"]
    assert compares and all("limit" in c and "value" in c for c in compares)
    controls = [c for c in compares if c.get("control")]
    assert controls and all(c["control_failed_as_it_must"] for c in controls)


def test_real_cells_refuse_the_cpu():
    """The committed cells name `platform: tpu`: without a chip, exit 2 and
    no result line."""
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in json.load(open(manifest))["workloads"]]
    rc, lines, err = run_cell(cells[0], manifest=manifest)
    assert rc == 2
    assert not any("correct" in ln for ln in lines)
    assert "jax found 'cpu'" in err


def test_bare_copy_fails(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: non-zero, no result."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tpch_sf1.q1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
