"""The one general traffic generator: a mix file + a seed -> statements.

A mix (`benchmarks/traffic/<mix>.json`) is data: clients, statement
templates with weights, and for each `{name}` in a template a parameter
generator from the fixed vocabulary below. A new mix is a new file; it never
needs code here. numpy only: the load generator's process imports this.

Every draw comes from `numpy.random.default_rng([seed, stream])`, so the
same seed gives the same statements whatever the timing. A run draws
`param_sets` parameter sets per template up front and each client cycles
through them, so every seed does the same amount of work.
"""

from __future__ import annotations

import datetime
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_M64 = (1 << 64) - 1


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    total = float(sum(t["weight"] for t in mix["templates"]))
    mix["_cum"] = np.cumsum([t["weight"] / total for t in mix["templates"]])
    return mix


def _date(days: int) -> str:
    return (datetime.date(1970, 1, 1)
            + datetime.timedelta(days=int(days))).isoformat()


def _days(s: str) -> int:
    return (datetime.date.fromisoformat(s) - datetime.date(1970, 1, 1)).days


class Params:
    """Draws one template's parameters."""

    def __init__(self, spec: dict, rng: np.random.Generator):
        self.spec, self.rng = spec, rng

    def draw(self) -> dict:
        out = {}
        for name, g in self.spec.items():
            kind = g["gen"]
            if kind == "uniform_int":
                out[name] = int(self.rng.integers(g["lo"], g["hi"] + 1))
            elif kind == "date_range":
                out[name] = _date(self.rng.integers(_days(g["lo"]),
                                                    _days(g["hi"]) + 1))
            elif kind == "choice":
                out[name] = g["values"][int(self.rng.integers(
                    len(g["values"])))]
            else:
                raise ValueError(f"unknown parameter generator {kind!r}")
        return out

    def extremes(self) -> list[dict]:
        """The lowest and the highest value of every ranged parameter (the
        warm-up sends both, so data-dependent capacities are learned)."""
        outs = []
        for end in ("lo", "hi"):
            o = self.draw()
            for name, g in self.spec.items():
                if g["gen"] == "uniform_int":
                    o[name] = int(g[end])
                elif g["gen"] == "date_range":
                    o[name] = g[end]
            outs.append(o)
        return outs


class Stream:
    """One client's statements: (template index, params, sql), endlessly."""

    def __init__(self, mix: dict, seed: int, client: int):
        self.mix = mix
        self.rng = np.random.default_rng([seed & _M64, 1 + client])
        self.params = [Params(t.get("params", {}),
                              np.random.default_rng([seed & _M64, 0, j]))
                       for j, t in enumerate(mix["templates"])]
        # the run's parameter sets: the same for every client
        self.sets = [[p.draw() for _ in range(int(mix["param_sets"]))]
                     for p in self.params]
        self.n = client  # clients start at different sets

    def render(self, j: int, p: dict) -> str:
        return self.mix["templates"][j]["sql"].format(**p)

    def next(self):
        j = int(np.searchsorted(self.mix["_cum"], self.rng.random(),
                                side="right"))
        j = min(j, len(self.params) - 1)
        p = self.sets[j][self.n % len(self.sets[j])]
        self.n += 1
        return j, p, self.render(j, p)

    def warmup(self) -> list[tuple[int, dict, str]]:
        """Every statement shape, ranged parameters at both ends."""
        return [(j, o, self.render(j, o))
                for j, p in enumerate(self.params) for o in p.extremes()]
