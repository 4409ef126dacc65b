"""The rest of a run with the timed path broken underneath: an answer
altered where the server produces it has to read `correct: false`. The
platform check is passed by a CPU configuration; everything after it (load,
warm-up, child, window, oracle, result line) is the real run."""

import os

import pytest

from helpers import HERE, run_cell


@pytest.mark.parametrize("workload", ["tpch_sf001.q1", "tpch_sf001.q3"])
def test_altered_answer_reads_not_correct(workload):
    rc, lines, err = run_cell(workload, seed=31337, seconds=2,
                              run_py=os.path.join(HERE, "broken_run.py"))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["attempted"] > 0
    assert last["correct"] is False
    failed = [c["name"] for c in lines
              if c.get("step") == "compare" and not c["ok"]]
    assert failed, "a comparison has to name what went wrong"
