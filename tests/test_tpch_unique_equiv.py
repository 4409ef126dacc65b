"""The unique-build proof changes which join kernels run, never an answer:
all 22 TPC-H texts answer the same with the proof on as with the binder's
helper patched to say "unproven" everywhere, and the benchmark's own Q3
text runs both its joins on the unique route."""

import json
import os

import numpy as np
import pytest

from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.sql import Session, binder as binder_mod, sql
from cockroach_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.002, seed=11)


@pytest.mark.parametrize("qname", sorted(TPCH_SQL, key=lambda q: int(q[1:])))
def test_tpch_answers_do_not_depend_on_the_proof(cat, qname, monkeypatch):
    proven = sql(cat, TPCH_SQL[qname])
    with monkeypatch.context() as m:
        m.setattr(binder_mod.Binder, "_build_unique",
                  lambda self, build, on, table: False)
        general = sql(cat, TPCH_SQL[qname])
    with_proof, without = proven.explain(), general.explain()
    # the joins of grouped subqueries (q2, q17, q20, q21) were unique before
    assert (with_proof.count("(unique build)")
            >= without.count("(unique build)"))
    if qname == "q3":
        assert with_proof.count("(unique build)") == 2
    if with_proof == without:
        return  # the proof moved no join of this statement: one plan
    got, want = proven.run(), general.run()
    assert list(got) == list(want)
    for col in want:
        g, w = got[col], want[col]
        assert len(g) == len(w), f"{col}: {len(g)} vs {len(w)} rows"
        if w.dtype.kind == "f":
            np.testing.assert_allclose(g, w, rtol=1e-9, err_msg=col)
        else:
            np.testing.assert_array_equal(g, w, err_msg=col)


def test_the_cells_q3_takes_the_unique_route(cat, monkeypatch):
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "q3_stream.json")) as f:
        text = json.load(f)["templates"][0]["sql"]
    kernels = []
    real = tracing.annotation

    def spy(name, **args):
        if name == "flow.dispatch":
            kernels.append(args["kernel"])
        return real(name, **args)

    monkeypatch.setattr(tracing, "annotation", spy)

    def tags():
        rec = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
        return (rec.get("join_unique_tiles", 0),
                rec.get("join_general_tiles", 0))

    from cockroach_tpu.sql import sqlstats

    s = Session(cat)
    u0, g0 = tags()
    try:
        for day in (1, 31, 15):  # learn, re-specialize, steady
            s.execute(text.format(date=f"1995-03-{day:02d}"))
    finally:
        # the registry is the process's: tests elsewhere look their own
        # statement up by a piece of its text (`group by l_orderkey`)
        sqlstats.DEFAULT.clear()
    u1, g1 = tags()
    assert u1 - u0 == 6 and g1 == g0  # two joins, one lineitem tile, 3 runs
    assert "hashjoin_emit" in kernels
    assert "hashjoin_build" not in kernels
