"""Expression eval tests — selection/projection semantics incl. 3-valued logic
(reference analog: colexecsel/colexecproj generated kernel behavior)."""

import numpy as np

from cockroach_tpu import coldata as cd
from cockroach_tpu.ops import expr as ex


def setup_batch():
    schema = cd.Schema.of(
        a=cd.INT64, b=cd.FLOAT64, d=cd.DECIMAL(10, 2), dt=cd.DATE
    )
    arrays = {
        "a": np.array([1, 2, 3, 4, 5]),
        "b": np.array([0.5, 1.5, 2.5, 3.5, 4.5]),
        "d": np.array([100, 250, 399, 1000, 5]),  # 1.00 2.50 3.99 10.00 0.05
        "dt": np.array([0, 365, 10956, 10957, 19000], dtype=np.int32),
    }
    valids = {"a": np.array([True, True, False, True, True])}
    return schema, cd.from_host(schema, arrays, valids=valids, capacity=8)


def test_filter_cmp_with_nulls():
    schema, b = setup_batch()
    # a > 1 : rows 1,3,4 true; row 2 NULL (excluded); row 0 false
    m = ex.filter_mask(b, schema, ex.Cmp("gt", ex.ColRef(0), ex.lit(1)))
    np.testing.assert_array_equal(np.asarray(m)[:5], [False, True, False, True, True])


def test_decimal_compare_and_arith():
    schema, b = setup_batch()
    # d <= 3.99 -> rows 0,1,2,4
    pred = ex.Cmp("le", ex.ColRef(2), ex.Const(3.99, cd.DECIMAL(10, 2)))
    m = ex.filter_mask(b, schema, pred)
    np.testing.assert_array_equal(np.asarray(m)[:5], [True, True, True, False, True])
    # d * d has scale 4
    t = ex.expr_type(ex.BinOp("*", ex.ColRef(2), ex.ColRef(2)), schema)
    assert t.scale == 4
    d, v = ex.eval_expr(ex.BinOp("*", ex.ColRef(2), ex.ColRef(2)), b.cols, schema)
    assert int(np.asarray(d)[1]) == 62500  # 2.50^2 = 6.25 at scale 4


def test_kleene_and_or():
    schema = cd.Schema.of(x=cd.BOOL, y=cd.BOOL)
    xv = np.array([True, True, True, False, False, False, True, False, True])
    xn = np.array([True, True, True, True, True, True, False, False, False])
    yv = np.array([True, False, False, True, False, True, True, False, False])
    yn = np.array([True, True, False, True, True, False, True, True, False])
    b = cd.from_host(
        schema, {"x": xv, "y": yv}, valids={"x": xn, "y": yn}, capacity=16
    )
    d, v = ex.eval_expr(ex.and_(ex.ColRef(0), ex.ColRef(1)), b.cols, schema)
    d, v = np.asarray(d)[:9], np.asarray(v)[:9]
    # NULL AND false = false (known); NULL AND true = NULL
    assert v[7] and not d[7]  # x NULL, y false -> false
    assert not v[6]  # x NULL, y true -> NULL
    assert not v[8]  # NULL AND NULL -> NULL
    assert v[0] and d[0]
    assert v[1] and not d[1]
    do, vo = ex.eval_expr(ex.or_(ex.ColRef(0), ex.ColRef(1)), b.cols, schema)
    do, vo = np.asarray(do)[:9], np.asarray(vo)[:9]
    assert vo[6] and do[6]  # NULL OR true -> true
    assert not vo[7]  # NULL OR false -> NULL
    assert vo[0] and do[0]


def test_case_and_cast():
    schema, b = setup_batch()
    e = ex.Case(
        whens=((ex.Cmp("gt", ex.ColRef(0), ex.lit(3)), ex.lit(100)),),
        otherwise=ex.lit(0),
    )
    d, v = ex.eval_expr(e, b.cols, schema)
    np.testing.assert_array_equal(np.asarray(d)[:5], [0, 0, 0, 100, 100])
    c = ex.Cast(ex.ColRef(2), cd.FLOAT64)
    d, v = ex.eval_expr(c, b.cols, schema)
    np.testing.assert_allclose(np.asarray(d)[:5], [1.0, 2.5, 3.99, 10.0, 0.05])


def test_extract_year():
    schema, b = setup_batch()
    d, v = ex.eval_expr(ex.ExtractYear(ex.ColRef(3)), b.cols, schema)
    np.testing.assert_array_equal(np.asarray(d)[:5], [1970, 1971, 1999, 2000, 2022])
    # day 10956 = 1999-12-31, day 10957 = 2000-01-01 (7 leap days in 1970-1999)


def test_year_in_32_bits_agrees_with_the_calendar_at_its_edges():
    """_year_from_days computes in int32 (PR 28: the chip's compiler takes
    24 s for its nine divisions on int64): every year boundary a DATE can
    hold, a TIMESTAMP's furthest days, and the dtype it hands on."""
    import datetime

    epoch = datetime.date(1970, 1, 1)
    days, want = [], []
    for y in list(range(1, 9999, 37)) + [1, 1900, 1970, 2000, 2100, 9999]:
        for m, d in ((1, 1), (2, 28), (3, 1), (12, 31)):
            days.append((datetime.date(y, m, d) - epoch).days)
            want.append(y)
    got = ex._year_from_days(np.asarray(days, dtype=np.int32))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(np.asarray(got), want)
    # int64 days of a TIMESTAMP's range (+-2**63 us): 400-year eras repeat
    far = np.asarray([-106751991, 106751991, -719468, -719469], np.int64)
    got = np.asarray(ex._year_from_days(far))
    for d, y in zip(far, got):
        era_days = 146097
        k = (int(d) - 0) // era_days
        base = int(d) - k * era_days  # same calendar position, years +400k
        assert y == (epoch + datetime.timedelta(days=base)).year + 400 * k


def test_division_by_zero_is_null():
    schema = cd.Schema.of(x=cd.INT64, y=cd.INT64)
    b = cd.from_host(
        schema, {"x": np.array([10, 10]), "y": np.array([2, 0])}, capacity=4
    )
    d, v = ex.eval_expr(ex.BinOp("/", ex.ColRef(0), ex.ColRef(1)), b.cols, schema)
    assert np.asarray(d)[0] == 5.0
    assert not np.asarray(v)[1]


def test_code_lookup_string_predicate():
    # s LIKE '%an%' pre-evaluated per dictionary code on host
    dic = cd.Dictionary(np.array(["apple", "banana", "mango"], dtype=object))
    table = np.array(["an" in str(s) for s in dic.values])
    schema = cd.Schema.of(s=cd.STRING)
    b = cd.from_host(schema, {"s": np.array([0, 1, 2, 1], dtype=np.int32)}, capacity=8)
    m = ex.filter_mask(b, schema, ex.CodeLookup(col=0, table=table))
    np.testing.assert_array_equal(np.asarray(m)[:4], [False, True, True, True])


def test_scalar_builtins_sql():
    """sem/builtins surface: abs/ceil/floor/round/sign/sqrt/exp/ln,
    coalesce, length, upper/lower — oracle numpy/pandas."""
    import numpy as np

    from cockroach_tpu.bench import tpch
    from cockroach_tpu.sql import sql

    cat = tpch.gen_tpch(sf=0.002, seed=9)
    li = tpch.to_pandas(cat, "lineitem")

    got = sql(cat, """
        select abs(l_quantity - 25.0) as a, ceil(l_discount) as c,
               floor(l_tax) as f, round(l_extendedprice) as r,
               sqrt(l_quantity) as s,
               coalesce(l_quantity, 0) as co
        from lineitem order by l_orderkey, l_linenumber limit 50
    """).run()
    df = li.sort_values(["l_orderkey", "l_linenumber"]).head(50)
    np.testing.assert_allclose(np.asarray(got["a"], np.float64),
                               (df.l_quantity - 25.0).abs(), rtol=0, atol=1e-9)
    np.testing.assert_allclose(np.asarray(got["c"], np.float64),
                               np.ceil(df.l_discount), rtol=0)
    np.testing.assert_allclose(np.asarray(got["f"], np.float64),
                               np.floor(df.l_tax), rtol=0)
    np.testing.assert_allclose(np.asarray(got["r"], np.float64),
                               np.floor(df.l_extendedprice + 0.5), rtol=0)
    np.testing.assert_allclose(np.asarray(got["s"], np.float64),
                               np.sqrt(df.l_quantity), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(got["co"], np.float64),
                               df.l_quantity, rtol=0)

    got = sql(cat, """
        select length(l_shipmode) as n, upper(l_shipmode) as u,
               lower(l_shipmode) as lo
        from lineitem order by l_orderkey, l_linenumber limit 10
    """).run()
    df = li.sort_values(["l_orderkey", "l_linenumber"]).head(10)
    assert list(got["n"]) == [len(s) for s in df.l_shipmode]
    assert list(got["u"]) == [s.upper() for s in df.l_shipmode]
    assert list(got["lo"]) == [s.lower() for s in df.l_shipmode]


def test_random_expression_fuzz():
    """sqlsmith-lite: random arithmetic/comparison/boolean/CASE expressions
    over lineitem evaluated by the engine vs a numpy oracle interpreter —
    the vectorized-vs-row cross-check pattern of
    distsql/columnar_operators_test.go, aimed at expression lowering."""
    import numpy as np

    from cockroach_tpu.bench import tpch
    from cockroach_tpu.coldata.types import FLOAT64
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.ops import expr as ex
    from cockroach_tpu.plan import builder as plan_builder
    from cockroach_tpu.sql.rel import Rel

    cat = tpch.gen_tpch(sf=0.002, seed=21)
    base = Rel.scan(cat, "lineitem", (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_linenumber",
    ))
    df = tpch.to_pandas(cat, "lineitem")
    cols = {
        0: df.l_quantity.to_numpy(dtype=np.float64),
        1: df.l_extendedprice.to_numpy(dtype=np.float64),
        2: df.l_discount.to_numpy(dtype=np.float64),
        3: df.l_tax.to_numpy(dtype=np.float64),
        4: df.l_linenumber.to_numpy(dtype=np.float64),
    }
    rng = np.random.default_rng(99)

    def gen_num(depth):
        r = rng.random()
        if depth >= 3 or r < 0.35:
            if rng.random() < 0.5:
                return ("col", int(rng.integers(0, 5)))
            return ("lit", float(np.round(rng.uniform(-5, 5), 2)))
        if r < 0.8:
            op = rng.choice(["+", "-", "*"])
            return ("bin", str(op), gen_num(depth + 1), gen_num(depth + 1))
        if r < 0.9:
            return ("func", str(rng.choice(["abs", "floor", "ceil"])),
                    gen_num(depth + 1))
        return ("case", gen_bool(depth + 1), gen_num(depth + 1),
                gen_num(depth + 1))

    def gen_bool(depth):
        if depth >= 3 or rng.random() < 0.6:
            op = rng.choice(["lt", "le", "gt", "ge", "eq", "ne"])
            return ("cmp", str(op), gen_num(depth + 1), gen_num(depth + 1))
        op = rng.choice(["and", "or"])
        return ("bool", str(op), gen_bool(depth + 1), gen_bool(depth + 1))

    def to_engine(t):
        k = t[0]
        if k == "col":
            # engine sees typed columns (decimal etc.); cast to float so
            # engine and oracle share one numeric domain
            return ex.Cast(ex.ColRef(t[1]), FLOAT64)
        if k == "lit":
            return ex.Const(t[1], FLOAT64)
        if k == "bin":
            return ex.BinOp(t[1], to_engine(t[2]), to_engine(t[3]))
        if k == "func":
            return ex.Func1(t[1], to_engine(t[2]))
        if k == "case":
            return ex.Case(((to_engine(t[1]), to_engine(t[2])),),
                           to_engine(t[3]))
        if k == "cmp":
            return ex.Cmp(t[1], to_engine(t[2]), to_engine(t[3]))
        if k == "bool":
            return ex.BoolOp(t[1], (to_engine(t[2]), to_engine(t[3])))
        raise AssertionError(k)

    def oracle(t):
        k = t[0]
        if k == "col":
            return cols[t[1]]
        if k == "lit":
            return np.full(len(cols[0]), t[1])
        if k == "bin":
            a, b = oracle(t[2]), oracle(t[3])
            return {"+": a + b, "-": a - b, "*": a * b}[t[1]]
        if k == "func":
            f = {"abs": np.abs, "floor": np.floor, "ceil": np.ceil}[t[1]]
            return f(oracle(t[2]))
        if k == "case":
            return np.where(oracle(t[1]), oracle(t[2]), oracle(t[3]))
        if k == "cmp":
            a, b = oracle(t[2]), oracle(t[3])
            return {"lt": a < b, "le": a <= b, "gt": a > b,
                    "ge": a >= b, "eq": a == b, "ne": a != b}[t[1]]
        if k == "bool":
            a, b = oracle(t[2]), oracle(t[3])
            return a & b if t[1] == "and" else a | b
        raise AssertionError(k)

    for trial in range(25):
        tree = gen_num(0)
        rel = base.project([("out", to_engine(tree))])
        got = run_operator(plan_builder.build(rel.plan, cat))["out"]
        want = oracle(tree)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), want, rtol=1e-9, atol=1e-9,
            err_msg=f"trial {trial}: {tree}")


def test_cast_matrix():
    """colexecbase cast semantics: DECIMAL->INT rounds (Postgres), scale
    cuts round half away from zero, FLOAT->INT rounds, DATE<->TIMESTAMP,
    numeric->BOOL."""
    import numpy as np

    import cockroach_tpu.catalog as catalog_mod
    from cockroach_tpu import coldata as cd
    from cockroach_tpu.sql import sql

    cat = catalog_mod.Catalog()
    schema = cd.Schema.of(i=cd.INT64, d=cd.DECIMAL(12, 2), f=cd.FLOAT64,
                          day=cd.DATE)
    cat.add(catalog_mod.Table.from_strings("t", schema, {
        "i": np.array([-3, 0, 7], dtype=np.int64),
        "d": np.array([-155, 0, 155], dtype=np.int64),  # -1.55, 0, 1.55
        "f": np.array([-2.5, 0.5, 2.49]),
        "day": np.array([0, 1, 10957], dtype=np.int32),  # 2000-01-01
    }))

    res = sql(cat, """
        select cast(d as int) as di, cast(f as int) as fi,
               cast(i as decimal) as idec, cast(d as float) as df,
               cast(i as bool) as ib, cast(day as timestamp) as ts
        from t order by i
    """).run()
    assert list(res["di"]) == [-2, 0, 2], "numeric->int rounds half away"
    assert list(res["fi"]) == [-2, 0, 2], "float->int rounds (banker's at .5)"
    np.testing.assert_allclose(np.asarray(res["idec"], np.float64),
                               [-3.0, 0.0, 7.0])
    np.testing.assert_allclose(np.asarray(res["df"], np.float64),
                               [-1.55, 0.0, 1.55])
    assert list(res["ib"]) == [True, False, True]
    assert int(res["ts"][2]) == 10957 * 86400 * 1000000
