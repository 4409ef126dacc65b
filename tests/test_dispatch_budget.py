"""Tier-1 wiring for the kernel-dispatch budget guard
(scripts/check_dispatch_budget.py): one case a served text. Each runs the
text through a Session until it has settled and holds the dispatches of
the next execution to the recorded budget; the marginal cost of an extra
input tile must stay one fused kernel."""

import pytest

from scripts import check_dispatch_budget as gate


@pytest.fixture(scope="module")
def cat():
    return gate.catalog()


@pytest.mark.parametrize("name", gate.CASES)
def test_dispatch_budget(cat, name):
    problems = gate.case(name, cat)
    assert not problems, "\n".join(problems)
