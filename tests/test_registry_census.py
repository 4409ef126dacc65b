"""Census of the registries a deletion can leave entries behind in: every
registered cluster setting is read by name somewhere in the program, every
registered fault site is fired by the program and armed by a test, and
every registered metric is moved by the program. Text passes over the
sources, one case an item, so the item left behind is the test that fails."""

import pathlib
import re

import pytest

from cockroach_tpu.utils import faults, settings

ROOT = pathlib.Path(__file__).resolve().parent.parent
SETTINGS_PY = "cockroach_tpu/utils/settings.py"


def _sources(sub: str) -> dict[str, str]:
    return {p.relative_to(ROOT).as_posix(): p.read_text()
            for p in sorted((ROOT / sub).rglob("*.py"))}


@pytest.fixture(scope="module")
def program():
    return _sources("cockroach_tpu")


@pytest.fixture(scope="module")
def tests():
    return _sources("tests")


@pytest.mark.parametrize("name", sorted(settings.all_settings()))
def test_setting_is_read_by_name(program, name):
    readers = [p for p, s in program.items()
               if p != SETTINGS_PY and f'"{name}"' in s]
    assert readers, f"setting {name!r} is registered and no module names it"


@pytest.mark.parametrize("site", sorted(faults.SITES))
def test_fault_site_is_fired_and_armed(program, tests, site):
    fire = re.compile(r'\bfire(?:_scoped)?\(\s*"%s"' % re.escape(site))
    firing = [p for p, s in program.items() if fire.search(s)]
    assert firing, f"fault site {site!r} is registered and never fired"
    # a node-scoped site is armed as "<site>.n<id>"
    arm = re.compile(r'"%s(?:\.n\d+)?"' % re.escape(site))
    arming = [p for p, s in tests.items() if arm.search(s)]
    assert arming, f"fault site {site!r} is armed by no test"


def test_every_registered_metric_is_moved(program):
    moves = {"counter": r"inc", "gauge": r"(?:set|inc|dec)",
             "histogram": r"observe"}
    everything = "\n".join(program.values())
    registered = re.findall(
        r'^([A-Z][A-Z0-9_]*) = (?:metric\.)?DEFAULT\.'
        r'(counter|gauge|histogram)\(', everything, re.M)
    assert len(registered) > 50  # the pass still finds the registry
    idle = [var for var, kind in registered
            if not re.search(r"\b%s\.%s\(" % (var, moves[kind]), everything)]
    assert not idle, f"registered and never moved: {idle}"

