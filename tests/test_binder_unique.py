"""The binder's unique-build proof (sql/binder.py _build_unique over catalog
Table.unique_key): a join built from SQL text is planned `(unique build)`
exactly where the build key is proven unique over the rows that can reach
the join, and keeps the duplicate-key probe everywhere else. One case a
claim of the proof; every answer against a host nested-loop oracle."""

import numpy as np
import pytest

import cockroach_tpu.catalog as catalog_mod
from cockroach_tpu.coldata.types import INT64, Schema
from cockroach_tpu.sql import Session, binder as binder_mod, explain
from cockroach_tpu.utils import tracing

N_DIM, N_FACT = 40, 300


def _table(name, valids=None, **cols):
    return catalog_mod.Table.from_strings(
        name, Schema.of(**{c: INT64 for c in cols}),
        {c: np.asarray(v, np.int64) for c, v in cols.items()}, valids=valids)


def _tables():
    rng = np.random.default_rng(5)
    k = rng.permutation(np.arange(0, 3 * N_DIM, 3))  # unique, not dense
    dup_k = k.copy()
    dup_k[7] = dup_k[3]  # one duplicate
    fk = rng.choice(np.concatenate([k, [1, 2]]), N_FACT)  # 1, 2 match nothing
    nul_valid = np.ones(N_DIM, bool)
    nul_valid[5] = False
    a, b = np.divmod(np.arange(N_DIM), 4)  # partsupp's shape: 4 rows an `a`
    return [
        _table("fact", fk=fk, x=np.arange(N_FACT),
               a=rng.integers(0, 10, N_FACT), b=rng.integers(0, 4, N_FACT)),
        _table("dim", k=k, v=np.arange(N_DIM) * 10),
        _table("dense", k=np.arange(1, N_DIM + 1), v=np.arange(N_DIM)),
        _table("dup", k=dup_k, v=np.arange(N_DIM) * 10),
        _table("nul", valids={"k": nul_valid}, k=k, v=np.arange(N_DIM) * 10),
        _table("pair", a=a, b=b, v=np.arange(N_DIM)),
    ]


@pytest.fixture
def sess():
    s = Session(val_width=64)
    for t in _tables():
        s.catalog.add(t)
    s.execute("create table kvd (k int primary key, v int)")
    dim = s.catalog.get("dim")
    for k, v in zip(dim.columns["k"][:12], dim.columns["v"][:12]):
        s.execute(f"insert into kvd values ({k}, {v})")
    return s


def _col(s, table, col):
    t = s.catalog.get(table)
    a = np.asarray(t.columns[col])
    if col in t.valids:  # NULL never equals anything
        a = np.where(np.asarray(t.valids[col]), a, -10**9)
    return a


def _oracle(s, build, on=(("fk", "k"),), pred=None, how="inner", rows=None):
    """sum(x), sum(v), count over fact JOIN build's first `rows` rows on
    the (fact, build) column pairs `on`, by nested loops."""
    x, bv = _col(s, "fact", "x"), _col(s, build, "v")[:rows]
    keep = np.ones(len(bv), bool) if pred is None else pred(bv)
    pairs = [(_col(s, "fact", p), _col(s, build, b)[:rows]) for p, b in on]
    sx = sv = n = 0
    for i, xx in enumerate(x):
        hit = keep.copy()
        for pk, bk in pairs:
            hit &= bk == pk[i]
        m = int(hit.sum())
        sx, sv, n = sx + xx * m, sv + bv[hit].sum(), n + m
        if how == "left" and not m:
            sx, n = sx + xx, n + 1
    return int(sx), int(sv), int(n)


def _run(s, text):
    r = s.execute(text)
    return tuple(int(r[c][0] if r[c][0] is not None else 0)
                 for c in ("sx", "sv", "n"))


SEL = "select sum(x) as sx, sum(v) as sv, count(*) as n from "

# (case, FROM and WHERE of the statement, planned unique?, oracle arguments)
CASES = [
    ("filtered_unique_build", "fact, dim where fk = k and v > 50", True,
     dict(build="dim", pred=lambda v: v > 50)),
    ("dense_surrogate_key", "fact, dense where fk = k", True,
     dict(build="dense")),
    ("one_duplicate", "fact, dup where fk = k and v >= 0", False,
     dict(build="dup")),
    ("one_null", "fact, nul where fk = k", False, dict(build="nul")),
    ("derived_table",
     "fact, (select k, v from dim where v > 50) d where fk = d.k", False,
     dict(build="dim", pred=lambda v: v > 50)),
    # kvd holds dim's first 12 rows: its primary key is unique, unproven
    ("kv_table", "fact, kvd where fk = k", False, dict(build="dim", rows=12)),
    ("pair_key_both_columns",
     "fact, pair where fact.a = pair.a and fact.b = pair.b", True,
     dict(build="pair", on=(("a", "a"), ("b", "b")))),
    ("pair_key_one_column", "fact, pair where fact.a = pair.a", False,
     dict(build="pair", on=(("a", "a"),))),
    ("left_join_unique", "fact left join dim on fk = k", True,
     dict(build="dim", how="left")),
    ("left_join_duplicate", "fact left join dup on fk = k", False,
     dict(build="dup", how="left")),
]


@pytest.mark.parametrize("case,tail,unique,oracle",
                         CASES, ids=[c[0] for c in CASES])
def test_join_is_planned_unique_only_where_proven(sess, case, tail, unique,
                                                  oracle):
    text = SEL + tail
    plan = explain(sess.catalog, text)
    assert ("(unique build)" in plan) == unique, plan
    want = _oracle(sess, **oracle)
    assert _run(sess, text) == want
    # the statement again, now from the plan cache: the same answer
    assert _run(sess, text) == want


def test_join_output_as_build_side_stays_general(sess):
    """A bound LEFT JOIN joined in as a build side is a join output: its
    key is unique here, but nothing proves it."""
    text = ("select sum(x) as sx, sum(dim.v) as sv, count(*) as n from fact, "
            "dim left join dense on dim.v = dense.v where fk = dim.k")
    plan = explain(sess.catalog, text)
    outer = next(ln for ln in plan.splitlines() if "hash-join (inner)" in ln)
    assert "(unique build)" not in outer, plan
    assert _run(sess, text) == _oracle(sess, "dim")


@pytest.mark.parametrize("text,proven", [
    # EXISTS over a filtered base table: the inner source's own table
    ("select count(*) as n from fact where exists "
     "(select * from dim where dim.k = fact.fk and dim.v > 50)", True),
    ("select count(*) as n from fact where exists "
     "(select * from dup where dup.k = fact.fk)", False),
    # IN (SELECT): a derived table, proven only where it is grouped on
    # exactly the key it is joined on
    ("select count(*) as n from fact where fk in "
     "(select k from dup group by k)", True),
    ("select count(*) as n from fact where fk in (select k from dim)",
     False),
], ids=["exists_base_table", "exists_duplicate", "in_grouped",
        "in_derived"])
def test_subquery_join_sites_take_the_helper(sess, monkeypatch, text,
                                             proven):
    calls = []
    real = binder_mod.Binder._build_unique

    def spy(self, build, on, table):
        calls.append(real(self, build, on, table))
        return calls[-1]

    monkeypatch.setattr(binder_mod.Binder, "_build_unique", spy)
    plan = explain(sess.catalog, text)
    assert calls == [proven]
    assert ("(unique build)" in plan) == proven
    build = "dim" if "dim" in text else "dup"
    bk, bv = _col(sess, build, "k"), _col(sess, build, "v")
    keep = set(bk[bv > 50] if "v > 50" in text else bk)
    want = sum(1 for f in _col(sess, "fact", "fk") if f in keep)
    assert int(sess.execute(text)["n"][0]) == want


@pytest.mark.parametrize("where,semi_below,proven", [
    # the build side behind its own IN-subquery's semi-join: still the
    # scan of `dim` with rows removed, so still proven (PR 32)
    ("fk = dim.k and dim.k in (select k from dup group by k)", True, True),
    # the probe side filtered the same way; `dim` is untouched
    ("fk = dim.k and fk in (select k from dup group by k)", True, True),
    # NOT IN stays an anti join on top of the joined rows
    ("fk = dim.k and dim.k not in (select k from dup group by k)", False,
     True),
], ids=["in_on_the_build", "in_on_the_probe", "not_in_on_top"])
def test_an_in_subquery_filters_its_source_below_the_join(sess, where,
                                                          semi_below, proven):
    text = SEL + "fact, dim where " + where
    lines = [ln.strip() for ln in explain(sess.catalog, text).splitlines()]
    inner = next(i for i, ln in enumerate(lines) if "hash-join (inner)" in ln)
    sub = next(i for i, ln in enumerate(lines)
               if "hash-join (semi)" in ln or "hash-join (anti)" in ln)
    assert (sub > inner) == semi_below, lines
    assert ("(unique build)" in lines[inner]) == proven
    in_dup = np.isin(_col(sess, "dim", "k"), _col(sess, "dup", "k"))
    keep = ~in_dup if "not in" in where else in_dup
    assert _run(sess, text) == _oracle(sess, "dim", pred=lambda v: keep)


def test_rehosted_table_with_a_duplicate_is_not_served_the_unique_plan(sess):
    """A plan built on the proof does not outlive it: re-hosting the build
    table under its name bumps the catalog version the plan cache keys on,
    and the new Table object carries no cached proof."""
    text = SEL + "fact, dim where fk = k"
    assert "(unique build)" in explain(sess.catalog, text)
    assert _run(sess, text) == _oracle(sess, "dim")
    assert _run(sess, text) == _oracle(sess, "dim")  # cached, unique
    dup = sess.catalog.get("dup")
    sess.catalog.add(catalog_mod.Table(
        "dim", dup.schema, dict(dup.columns)))
    assert "(unique build)" not in explain(sess.catalog, text)
    want = _oracle(sess, "dup")
    assert want[2] > _oracle(sess, "dense")[2]  # the duplicate matches
    assert _run(sess, text) == want


def test_matview_rehost_drops_the_cached_proof():
    """sql/matview.py swaps a view table's columns in place: the proof's
    cache goes where _dense_keys goes (and the catalog version moves), so
    a key that stops being one is not answered from the old rows."""
    s = Session(val_width=160)
    s.execute("create table t (k int primary key, flag string, "
              "qty decimal(12,2))")
    s.execute("insert into t values (1, 'A', 1.00)")
    s.execute("insert into t values (2, 'B', 2.00)")
    s.execute("create materialized view mv as "
              "select flag, sum(qty) as sq from t group by flag")
    s.execute("select * from mv")
    tbl = s.catalog.get("mv")
    assert tbl.unique_key(("sq",)) is True  # sums 1.00 and 2.00
    version = s.catalog.version
    s.execute("insert into t values (3, 'A', 1.00)")
    s.execute("select * from mv")  # the read re-hosts the view's table
    assert s.catalog.get("mv") is tbl
    assert s.catalog.version > version
    assert tbl.unique_key(("sq",)) is False  # both 2.00 now


def _count_unique_calls(monkeypatch):
    calls = []
    real = np.unique

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(catalog_mod.np, "unique", counting)
    return calls


def test_unique_key_verifies_once_a_column_set(monkeypatch):
    dim, dup, pair = (t for t in _tables() if t.name in ("dim", "dup",
                                                         "pair"))
    calls = _count_unique_calls(monkeypatch)
    assert dim.unique_key(("k",)) is True
    assert len(calls) == 1
    assert dim.unique_key(("k",)) is True  # cached
    assert dim.unique_key(["k"]) is True
    assert len(calls) == 1
    assert dup.unique_key(("k",)) is False
    assert dup.unique_key(("k",)) is False
    assert len(calls) == 2
    # a pair is proven as the pair, in either order, once
    assert pair.unique_key(("a", "b")) and pair.unique_key(("b", "a"))
    assert len(calls) == 3
    assert not pair.unique_key(("a",)) and not pair.unique_key(("b",))
    assert len(calls) == 5


def test_unique_key_short_cuts_and_refusals(monkeypatch):
    from cockroach_tpu.coldata.types import FLOAT64
    from cockroach_tpu.sql import stats

    tables = {t.name: t for t in _tables()}
    calls = _count_unique_calls(monkeypatch)
    # a surrogate key is answered by dense_key_info: no sort of the key
    assert tables["dense"].unique_key(("k",)) is True
    # any superset of a key is a key
    assert tables["dense"].unique_key(("k", "v")) is True
    assert calls == []
    # a NULL in the key, a float key: never proven
    assert tables["nul"].unique_key(("k",)) is False
    flt = catalog_mod.Table.from_strings(
        "flt", Schema.of(k=FLOAT64), {"k": np.arange(4.0)})
    assert flt.unique_key(("k",)) is False
    assert calls == []
    # ANALYZE statistics only ever say no, and early
    dup = tables["dup"]
    dup.set_stats(stats.analyze_table(dup))
    calls.clear()
    assert dup.unique_key(("k",)) is False
    assert calls == []
    dim = tables["dim"]
    dim.set_stats(stats.analyze_table(dim))
    calls.clear()
    assert dim.unique_key(("k",)) is True  # ndv == rows proves nothing
    assert len(calls) == 1
    # ranges too wide to pack into one word fall back to rows
    big = np.array([0, 2**62, -2**62, 5], np.int64)
    wide = _table("wide", a=big, b=big[::-1].copy())
    assert wide.unique_key(("a", "b")) is True
    wide2 = _table("wide2", a=np.array([0, 2**62, -2**62, 0]),
                   b=np.array([7, 2**62, -2**62, 7]))
    assert wide2.unique_key(("a", "b")) is False
    # an empty table has no duplicate
    assert _table("none", k=np.zeros(0)).unique_key(("k",)) is True


def test_probe_tile_tags_land_on_the_pull_span(sess):
    def tags():
        rec = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
        return (rec.get("join_unique_tiles", 0),
                rec.get("join_general_tiles", 0))

    u0, g0 = tags()
    sess.execute(SEL + "fact, dim where fk = k")
    u1, g1 = tags()
    assert u1 > u0 and g1 == g0
    sess.execute(SEL + "fact, dup where fk = k")
    u2, g2 = tags()
    assert u2 == u1 and g2 > g1
    # EXPLAIN ANALYZE renders the tag with the others of flow/pull
    out = explain(sess.catalog,
                  "explain analyze (debug) " + SEL + "fact, dim where fk = k")
    assert "join_unique_tiles" in out, out
