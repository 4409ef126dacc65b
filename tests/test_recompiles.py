"""Tier-1 wiring for the zero-recompile serving-path guard
(scripts/check_recompiles.py): one case a served TPC-H text through one
Session. Cold compiles stay within the recorded budget, adaptation settles
in one run, and the text with another substitution parameter hits the plan
cache and triggers zero new XLA traces."""

import pytest

from scripts import check_recompiles as gate


@pytest.fixture(scope="module")
def sess():
    s = gate.open_session()
    yield s
    s.close()


@pytest.mark.parametrize("name", [
    pytest.param(q, marks=pytest.mark.xfail(strict=True, reason=gate.KNOWN[q]))
    if q in gate.KNOWN else q
    for q in gate._REBIND
])
def test_recompiles(sess, name):
    problems = gate.case(sess, name)
    assert not problems, "\n".join(problems)
