"""SQL front-end tests: parse + bind TPC-H SQL and diff against the
hand-built Rel plans (the reference's logictest analog — behavior parity
between the SQL surface and the engine; pkg/sql/parser + optbuilder roles)."""

import numpy as np
import pytest

from cockroach_tpu.bench import queries as Q
from cockroach_tpu.bench import tpch
from cockroach_tpu.bench.tpch_sql import TPCH_SQL
from cockroach_tpu.sql import sql
from cockroach_tpu.sql.parser import parse


@pytest.fixture(scope="module")
def cat():
    return tpch.gen_tpch(sf=0.005, seed=7)


# ---------------------------------------------------------------------------
# parser unit tests


def test_parse_simple():
    s = parse("select a, b as bb from t where a > 3 order by bb desc limit 5")
    assert len(s.items) == 2
    assert s.items[1].alias == "bb"
    assert s.limit == 5
    assert s.order_by[0].desc


def test_parse_join_group():
    s = parse("""
        select x, count(*) from t1 join t2 on t1.a = t2.b
        where c between 1 and 2 group by x having count(*) > 1
    """)
    assert s.group_by and s.having is not None


def test_parse_case_extract():
    s = parse("""
        select case when a = 1 then 2 else 3 end,
               extract(year from d) from t
    """)
    assert len(s.items) == 2


def test_parse_date_interval():
    s = parse("select a from t where d < date '1995-03-15' + interval '3' month")
    assert s.where is not None


def test_parse_errors():
    with pytest.raises(SyntaxError):
        parse("select from t")
    with pytest.raises(SyntaxError):
        parse("select a t where")


# ---------------------------------------------------------------------------
# end-to-end: TPC-H SQL == hand-built plans



# the compile-heaviest sweeps (multi-join Q2/Q5/Q7/Q8... plans take
# 20-50s of XLA compile each on this host) run in the slow tier; tier-1
# keeps a representative spread of the parser/planner surface under its
# wall-clock cap, `-m slow` covers the full 22
_COMPILE_HEAVY = {"q2", "q3", "q5", "q7", "q8", "q9", "q10", "q11",
                  "q16", "q18", "q20", "q21"}


@pytest.mark.parametrize("qname", [
    pytest.param(q, marks=pytest.mark.slow) if q in _COMPILE_HEAVY else q
    for q in sorted(TPCH_SQL)
])
def test_tpch_sql_matches_handbuilt(cat, qname):
    got = sql(cat, TPCH_SQL[qname]).run()
    want = Q.QUERIES[qname](cat).run()
    assert set(got) >= set(want), f"missing columns: {set(want) - set(got)}"
    for col in want:
        w = want[col]
        g = got[col]
        assert len(g) == len(w), f"{col}: {len(g)} vs {len(w)} rows"
        if w.dtype.kind == "f" or g.dtype.kind == "f":
            np.testing.assert_allclose(
                g.astype(np.float64), w.astype(np.float64), rtol=1e-9,
                err_msg=col,
            )
        else:
            np.testing.assert_array_equal(g, w, err_msg=col)


def test_sql_scalar_subquery(cat):
    got = sql(cat, """
        select count(*) as n from lineitem
        where l_extendedprice > (select avg(l_extendedprice) from lineitem)
    """).run()
    df = tpch.to_pandas(cat, "lineitem")
    want = int((df.l_extendedprice > df.l_extendedprice.mean()).sum())
    assert int(got["n"][0]) == want


def test_sql_in_select_semi(cat):
    got = sql(cat, """
        select count(*) as n from orders
        where o_orderkey in (select l_orderkey from lineitem
                             where l_quantity > 49)
    """).run()
    li = tpch.to_pandas(cat, "lineitem")
    o = tpch.to_pandas(cat, "orders")
    big = li[li.l_quantity > 49].l_orderkey.unique()
    want = int(o.o_orderkey.isin(big).sum())
    assert int(got["n"][0]) == want


def test_sql_not_in_select_anti(cat):
    got = sql(cat, """
        select count(*) as n from customer
        where c_custkey not in (select o_custkey from orders)
    """).run()
    c = tpch.to_pandas(cat, "customer")
    o = tpch.to_pandas(cat, "orders")
    want = int((~c.c_custkey.isin(o.o_custkey)).sum())
    assert int(got["n"][0]) == want


def test_sql_distinct_and_like(cat):
    got = sql(cat, """
        select distinct p_mfgr from part where p_name like '%green%'
        order by p_mfgr
    """).run()
    p = tpch.to_pandas(cat, "part")
    want = np.sort(p[p.p_name.str.contains("green")].p_mfgr.unique())
    np.testing.assert_array_equal(got["p_mfgr"], want)


def test_sql_duplicate_agg_names_and_order(cat):
    got = sql(cat, """
        select l_returnflag, sum(l_quantity), sum(l_extendedprice)
        from lineitem group by l_returnflag
        order by sum(l_extendedprice) desc
    """).run()
    li = tpch.to_pandas(cat, "lineitem")
    w = (li.groupby("l_returnflag")
         .agg(q=("l_quantity", "sum"), e=("l_extendedprice", "sum"))
         .reset_index().sort_values("e", ascending=False))
    assert "sum" in got and "sum_1" in got  # both aggregates survive
    np.testing.assert_array_equal(got["l_returnflag"], w.l_returnflag)
    np.testing.assert_allclose(got["sum"].astype(np.float64), w.q, rtol=1e-9)
    np.testing.assert_allclose(got["sum_1"].astype(np.float64), w.e, rtol=1e-9)


def test_sql_double_negated_in(cat):
    got = sql(cat, """
        select count(*) as n from customer
        where not (c_custkey not in (select o_custkey from orders))
    """).run()
    c = tpch.to_pandas(cat, "customer")
    o = tpch.to_pandas(cat, "orders")
    want = int(c.c_custkey.isin(o.o_custkey).sum())
    assert int(got["n"][0]) == want


def test_sql_offset_without_limit(cat):
    got = sql(cat, """
        select n_nationkey from nation order by n_nationkey offset 5
    """).run()
    np.testing.assert_array_equal(got["n_nationkey"], np.arange(5, 25))


def test_sql_correlated_nonequality_exists(cat):
    """EXISTS with an extra <> correlation (TPC-H q21's shape) rewrites to a
    min/max-per-key grouped join; oracle is pandas."""
    got = sql(cat, """
        select count(*) as n from lineitem l1
        where exists (
          select * from lineitem l2
          where l2.l_orderkey = l1.l_orderkey
            and l2.l_suppkey <> l1.l_suppkey
        )
    """).run()
    li = tpch.to_pandas(cat, "lineitem")
    per = li.groupby("l_orderkey").l_suppkey.agg(["min", "max"])
    j = li.merge(per, left_on="l_orderkey", right_index=True)
    want = int(((j["min"] != j.l_suppkey) | (j["max"] != j.l_suppkey)).sum())
    assert int(got["n"][0]) == want


def test_sql_correlated_nonequality_not_exists(cat):
    got = sql(cat, """
        select count(*) as n from lineitem l1
        where not exists (
          select * from lineitem l2
          where l2.l_orderkey = l1.l_orderkey
            and l2.l_suppkey <> l1.l_suppkey
        )
    """).run()
    li = tpch.to_pandas(cat, "lineitem")
    per = li.groupby("l_orderkey").l_suppkey.agg(["min", "max"])
    j = li.merge(per, left_on="l_orderkey", right_index=True)
    want = int(((j["min"] == j.l_suppkey) & (j["max"] == j.l_suppkey)).sum())
    assert int(got["n"][0]) == want


def test_sql_subquery_in_from(cat):
    got = sql(cat, """
        select n_name, total from (
            select n_name, sum(s_acctbal) as total
            from supplier, nation
            where s_nationkey = n_nationkey
            group by n_name
        ) as t
        where total > 0
        order by total desc
    """).run()
    s = tpch.to_pandas(cat, "supplier")
    n = tpch.to_pandas(cat, "nation")
    j = s.merge(n, left_on="s_nationkey", right_on="n_nationkey")
    w = j.groupby("n_name").s_acctbal.sum().reset_index()
    w = w[w.s_acctbal > 0].sort_values("s_acctbal", ascending=False)
    np.testing.assert_array_equal(got["n_name"], w.n_name)
    np.testing.assert_allclose(
        got["total"].astype(np.float64), w.s_acctbal, rtol=1e-9
    )


def test_sql_not_in_three_valued():
    """NOT IN follows three-valued logic even over nullable columns: a NULL
    in the subquery empties the result; NULL probe keys are dropped; an
    empty subquery keeps every row (x NOT IN () is TRUE)."""
    import cockroach_tpu.catalog as catalog_mod
    from cockroach_tpu.coldata.types import INT64, Schema

    c2 = catalog_mod.Catalog()
    c2.add(catalog_mod.Table.from_strings(
        "t", Schema.of(a=INT64), {"a": np.arange(5)}))
    c2.add(catalog_mod.Table.from_strings(
        "u", Schema.of(b=INT64, c=INT64),
        {"b": np.arange(3), "c": np.arange(100, 103)},
        valids={"b": np.array([True, False, True])}))
    # NULL in the subquery result: NOT IN is never true -> empty
    got = sql(c2, "select count(*) as n from t "
                  "where a not in (select b from u)").run()
    assert int(got["n"][0]) == 0
    # nullable OUTER argument: NULL probe keys dropped, others anti-join
    got = sql(c2, "select count(*) as n from u "
                  "where b not in (select a from t)").run()
    assert int(got["n"][0]) == 0  # b values {0, 2} are all in t; NULL dropped
    got = sql(c2, "select count(*) as n from u "
                  "where b not in (select c from u)").run()
    assert int(got["n"][0]) == 2  # {0, 2} not in {100..102}; NULL dropped
    # empty subquery: every row passes, even the NULL-key one
    got = sql(c2, "select count(*) as n from u "
                  "where b not in (select a from t where a > 100)").run()
    assert int(got["n"][0]) == 3
    # IN (not negated) over the same nullable column is fine
    got = sql(c2, "select count(*) as n from t "
                  "where a in (select b from u)").run()
    assert int(got["n"][0]) >= 1
    # and NOT IN over provably non-null columns still binds (no execution
    # of the subquery at bind time on this fast path)
    got = sql(c2, "select count(*) as n from t "
                  "where a not in (select c from u)").run()
    assert int(got["n"][0]) == 5