"""Join kernel tests vs numpy oracle (reference analog:
pkg/sql/colexec/hashjoiner_test.go + columnar_operators_test.go oracle)."""

import numpy as np
import pytest

from cockroach_tpu import coldata as cd
from cockroach_tpu.ops import join as jn


def make_tables(rng, np_build=40, np_probe=100, key_range=30, null_frac=0.1):
    bschema = cd.Schema.of(bk=cd.INT64, bv=cd.INT64)
    pschema = cd.Schema.of(pk=cd.INT64, pv=cd.INT64)
    bk = rng.integers(0, key_range, np_build)
    pk = rng.integers(0, key_range, np_probe)
    bkv = rng.random(np_build) > null_frac
    pkv = rng.random(np_probe) > null_frac
    b = cd.from_host(
        bschema,
        {"bk": bk, "bv": np.arange(np_build) * 10},
        valids={"bk": bkv},
        capacity=64,
    )
    p = cd.from_host(
        pschema,
        {"pk": pk, "pv": np.arange(np_probe)},
        valids={"pk": pkv},
        capacity=128,
    )
    return (bschema, b, bk, bkv), (pschema, p, pk, pkv)


def oracle_pairs(pk, pkv, bk, bkv):
    """list of (probe_i, build_j) inner matches."""
    out = []
    for i in range(len(pk)):
        if not pkv[i]:
            continue
        for j in range(len(bk)):
            if bkv[j] and bk[j] == pk[i]:
                out.append((i, j))
    return out


def test_unique_inner_left_semi_anti(rng):
    # unique build keys
    bschema = cd.Schema.of(bk=cd.INT64, bv=cd.INT64)
    pschema = cd.Schema.of(pk=cd.INT64, pv=cd.INT64)
    bk = np.array([1, 3, 5, 7, 9])
    pk = np.array([1, 2, 3, 9, 9, 4, 7])
    pkv = np.array([True, True, True, True, False, True, True])
    b = cd.from_host(bschema, {"bk": bk, "bv": bk * 100}, capacity=8)
    p = cd.from_host(pschema, {"pk": pk, "pv": np.arange(7)}, valids={"pk": pkv}, capacity=16)

    out = jn.hash_join_unique(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("inner", True)
    )
    res = cd.to_host(out, pschema.concat(bschema))
    order = np.argsort(res["pv"])
    np.testing.assert_array_equal(np.asarray(res["pv"])[order], [0, 2, 3, 6])
    np.testing.assert_array_equal(np.asarray(res["bv"])[order], [100, 300, 900, 700])

    out = jn.hash_join_unique(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("left", True)
    )
    res = cd.to_host(out, pschema.concat(bschema))
    assert len(res["pv"]) == 7
    bv_by_pv = dict(zip(res["pv"], res["bv"]))
    assert bv_by_pv[1] is None and bv_by_pv[4] is None  # no match, NULL key
    assert bv_by_pv[0] == 100

    out = jn.hash_join_unique(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("semi", True)
    )
    res = cd.to_host(out, pschema)
    np.testing.assert_array_equal(sorted(res["pv"]), [0, 2, 3, 6])

    out = jn.hash_join_unique(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("anti", True)
    )
    res = cd.to_host(out, pschema)
    # NULL-key probe row 4 is kept by anti join (NOT EXISTS semantics)
    np.testing.assert_array_equal(sorted(res["pv"]), [1, 4, 5])


@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_general_join_vs_oracle(rng, join_type):
    (bschema, b, bk, bkv), (pschema, p, pk, pkv) = make_tables(rng)
    out, total = jn.hash_join_general(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec(join_type, False), 1024
    )
    pairs = oracle_pairs(pk, pkv, bk, bkv)
    if join_type == "inner":
        res = cd.to_host(out, pschema.concat(bschema))
        got = sorted(zip(res["pv"], res["bv"]))
        want = sorted((pv, bj * 10) for (pv, bj) in pairs)
        assert got == want
        assert int(total) == len(pairs)
    elif join_type == "left":
        res = cd.to_host(out, pschema.concat(bschema))
        matched_p = {i for i, _ in pairs}
        want = sorted((i, j * 10) for i, j in pairs) + sorted(
            (i, None) for i in range(len(pk)) if i not in matched_p
        )
        got = sorted(
            zip(res["pv"], res["bv"]),
            key=lambda t: (t[0], -1 if t[1] is None else t[1]),
        )
        want = sorted(want, key=lambda t: (t[0], -1 if t[1] is None else t[1]))
        assert got == want
    elif join_type == "semi":
        res = cd.to_host(out, pschema)
        assert sorted(res["pv"]) == sorted({i for i, _ in pairs})
    else:
        res = cd.to_host(out, pschema)
        matched_p = {i for i, _ in pairs}
        assert sorted(res["pv"]) == [i for i in range(len(pk)) if i not in matched_p]


def test_general_join_overflow_reports_total(rng):
    bschema = cd.Schema.of(bk=cd.INT64)
    pschema = cd.Schema.of(pk=cd.INT64)
    b = cd.from_host(bschema, {"bk": np.zeros(50, dtype=np.int64)}, capacity=64)
    p = cd.from_host(pschema, {"pk": np.zeros(50, dtype=np.int64)}, capacity=64)
    out, total = jn.hash_join_general(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("inner", False), 128
    )
    assert int(total) == 2500  # caller must rerun with >= 2500 capacity
    out, total = jn.hash_join_general(
        p, pschema, (0,), b, bschema, (0,), jn.JoinSpec("inner", False), 4096
    )
    assert int(out.length()) == 2500


# ---------------------------------------------------------------------------
# The exact-key emission (expand_runs) against the loop it replaced
#
# hash_join_general lays a probe tile's output out by a prefix sum: the m-th
# match of probe row i at slot base[i] + m. Under an ExactKeyLayout it does
# so by a run-length expansion with no loop; without one (64-bit hashes)
# the count loop and the emit loop stay. The same tables through both have
# to give the same tile slot for slot, truncation included; merge_join,
# which emits through the same helper, is held to a numpy oracle.

_PSCHEMA = cd.Schema.of(pk=cd.INT64, pv=cd.INT64)
_BSCHEMA = cd.Schema.of(bk=cd.INT64, bv=cd.INT64)
_KEY_BITS = 6  # keys 0..63
_LAYOUT = jn.ExactKeyLayout((("int", 0, _KEY_BITS),), _KEY_BITS)


def _run_tables(scenario):
    """(probe keys, key valid, live), (build keys, key valid, live): 96
    probe rows in a 128-row tile, 200 build rows in a 256-row tile."""
    rng = np.random.default_rng(sum(map(ord, scenario)))
    n_p, n_b = 96, 200
    pk = rng.integers(0, 24, n_p)
    bk = rng.integers(0, 24, n_b)
    pkv = np.ones(n_p, bool)
    bkv = np.ones(n_b, bool)
    plive = np.ones(n_p, bool)
    blive = np.ones(n_b, bool)
    if scenario == "mixed":  # dead rows and NULL keys on both sides
        pkv = rng.random(n_p) > 0.15
        bkv = rng.random(n_b) > 0.15
        plive = rng.random(n_p) > 0.2
        blive = rng.random(n_b) > 0.2
        plive[0] = False  # the tile's first slot belongs to a later row
    elif scenario == "no_match":
        bk = bk + 32
    elif scenario == "long_run":  # one key owns more rows than any capacity
        bk[:] = 40 + rng.integers(0, 8, n_b)
        bk[20:170] = 7
        pk[3] = 7
        pk[50] = 7
        blive[60:70] = False  # a hole in the run
    elif scenario == "max_run_1":  # a build key never repeats
        bk = np.concatenate([rng.permutation(64), np.zeros(n_b - 64, np.int64)])
        blive[64:] = False
        pk = rng.integers(0, 64, n_p)
    elif scenario == "max_run_48":
        bk[:48] = 5
        bk[48:] = rng.integers(6, 24, n_b - 48)
        pkv = rng.random(n_p) > 0.1
    else:
        raise AssertionError(scenario)
    return (pk, pkv, plive), (bk, bkv, blive)


def _run_batches(scenario):
    (pk, pkv, plive), (bk, bkv, blive) = _run_tables(scenario)
    bvv = np.arange(len(bk)) % 7 != 3  # a NULL build VALUE is not a NULL key
    p = cd.from_host(_PSCHEMA, {"pk": pk, "pv": np.arange(len(pk)) + 1000},
                     valids={"pk": pkv}, capacity=128)
    b = cd.from_host(_BSCHEMA, {"bk": bk, "bv": np.arange(len(bk)) * 10},
                     valids={"bk": bkv, "bv": bvv}, capacity=256)
    p = p.with_mask(p.mask & np.pad(plive, (0, 128 - len(pk))))
    b = b.with_mask(b.mask & np.pad(blive, (0, 256 - len(bk))))
    return p, b


def _oracle_slots(scenario, join_type):
    """Every output row in slot order: (probe row, build row or None)."""
    (pk, pkv, plive), (bk, bkv, blive) = _run_tables(scenario)
    slots = []
    for i in np.flatnonzero(plive):
        js = (np.flatnonzero(blive & bkv & (bk == pk[i])) if pkv[i] else [])
        slots += [(i, j) for j in js]
        if join_type == "left" and len(js) == 0:
            slots.append((i, None))
    return slots


def _assert_semi_anti(out, p, slots, join_type):
    matched = {i for i, j in slots if j is not None}
    live = np.flatnonzero(np.asarray(p.mask))
    want = [i for i in live if (i in matched) == (join_type == "semi")]
    np.testing.assert_array_equal(np.flatnonzero(np.asarray(out.mask)), want)


def _capacity(kind, n_slots):
    return {"fits": 2048, "one": 1,
            "truncates": max(2, min(100, n_slots // 2))}[kind]


def _assert_same_tile(got, want):
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(want.mask))
    assert len(got.cols) == len(want.cols)
    for i, (g, w) in enumerate(zip(got.cols, want.cols)):
        gv, wv = np.asarray(g.valid), np.asarray(w.valid)
        np.testing.assert_array_equal(gv, wv, err_msg=f"valid of column {i}")
        np.testing.assert_array_equal(np.asarray(g.data)[gv],
                                      np.asarray(w.data)[wv],
                                      err_msg=f"data of column {i}")


_SCENARIOS = ["mixed", "no_match", "long_run", "max_run_1", "max_run_48"]


@pytest.mark.parametrize("scenario", _SCENARIOS)
@pytest.mark.parametrize("cap_kind", ["fits", "truncates", "one"])
@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_exact_key_emission_equals_the_hash_loop_slot_for_slot(
        join_type, cap_kind, scenario):
    p, b = _run_batches(scenario)
    slots = _oracle_slots(scenario, "left" if join_type == "anti"
                          else join_type)
    cap = _capacity(cap_kind, len(slots))
    spec = jn.JoinSpec(join_type, False)
    looped, total_l = jn.hash_join_general(
        p, _PSCHEMA, (0,), b, _BSCHEMA, (0,), spec, cap)
    expanded, total_e = jn.hash_join_general(
        p, _PSCHEMA, (0,), b, _BSCHEMA, (0,), spec, cap,
        exact_layout=_LAYOUT)
    assert int(total_e) == int(total_l)
    _assert_same_tile(expanded, looped)
    if join_type in ("inner", "left"):
        assert int(total_e) == len(slots)  # the true total, cut or not
        assert expanded.capacity == cap
        assert int(expanded.length()) == min(cap, len(slots))
        if cap_kind == "truncates":
            assert len(slots) > cap or not slots  # inner, no match
        if scenario == "long_run":
            run = sum(1 for i, j in slots if i == 3)
            assert run == 140 and (cap_kind == "fits" or run > cap)
    else:
        _assert_semi_anti(expanded, p, slots, join_type)


def test_the_run_scenarios_cover_what_they_name():
    def longest(scenario):
        by_probe = {}
        for i, j in _oracle_slots(scenario, "inner"):
            by_probe[i] = by_probe.get(i, 0) + 1
        return max(by_probe.values(), default=0)

    assert longest("no_match") == 0
    assert longest("max_run_1") == 1
    assert longest("max_run_48") == 48
    assert longest("long_run") == 140
    (_, pkv, plive), (_, bkv, blive) = _run_tables("mixed")
    assert not pkv.all() and not plive.all()
    assert not bkv.all() and not blive.all()


@pytest.mark.parametrize("scenario", _SCENARIOS)
@pytest.mark.parametrize("cap_kind", ["fits", "truncates", "one"])
@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_merge_join_emission_vs_oracle_slot_for_slot(
        join_type, cap_kind, scenario):
    from cockroach_tpu.ops import merge_join as mj

    p, b = _run_batches(scenario)
    slots = _oracle_slots(scenario, "left" if join_type == "anti"
                          else join_type)
    cap = _capacity(cap_kind, len(slots))
    out, total = mj.merge_join(p, _PSCHEMA, 0, b, _BSCHEMA, 0,
                               jn.JoinSpec(join_type, False), cap)
    if join_type in ("semi", "anti"):
        _assert_semi_anti(out, p, slots, join_type)
        return
    assert int(total) == len(slots)
    kept = slots[:cap]
    mask = np.asarray(out.mask)
    np.testing.assert_array_equal(mask, np.arange(cap) < len(kept))
    pk, pv, bk, bv = (np.asarray(c.data)[:len(kept)] for c in out.cols)
    pkv, pvv, bkv, bvv = (np.asarray(c.valid) for c in out.cols)
    (tpk, tpkv, _), (tbk, _, _) = _run_tables(scenario)
    pi = np.array([i for i, _ in kept], np.int64)
    found = np.array([j is not None for _, j in kept], bool)
    bj = np.array([j if j is not None else 0 for _, j in kept], np.int64)
    np.testing.assert_array_equal(pv, pi + 1000)
    np.testing.assert_array_equal(pvv, mask)
    np.testing.assert_array_equal(pkv[:len(kept)], tpkv[pi])
    np.testing.assert_array_equal(pk[tpkv[pi]], tpk[pi][tpkv[pi]])
    # a NULL-extended row: live, its build side NULL; past the cut: nothing
    np.testing.assert_array_equal(bkv[:len(kept)], found)
    np.testing.assert_array_equal(bvv[:len(kept)], found & (bj % 7 != 3))
    assert not bkv[len(kept):].any() and not bvv[len(kept):].any()
    np.testing.assert_array_equal(bk[found], tbk[bj[found]])
    np.testing.assert_array_equal(bv[bvv[:len(kept)]],
                                  (bj * 10)[bvv[:len(kept)]])


def test_string_key_join_cross_dictionary(rng):
    d1 = cd.Dictionary(np.array(["a", "b", "c"], dtype=object))
    d2 = cd.Dictionary(np.array(["c", "a"], dtype=object))
    pschema = cd.Schema.of(s=cd.STRING, pv=cd.INT64)
    bschema = cd.Schema.of(t=cd.STRING, bv=cd.INT64)
    p = cd.from_host(
        pschema,
        {"s": np.array([0, 1, 2], dtype=np.int32), "pv": np.arange(3)},
        capacity=8,
    )
    b = cd.from_host(
        bschema,
        {"t": np.array([0, 1], dtype=np.int32), "bv": np.array([100, 200])},
        capacity=8,
    )
    out = jn.hash_join_unique(
        p,
        pschema,
        (0,),
        b,
        bschema,
        (0,),
        jn.JoinSpec("inner", True),
        probe_hash_tables={0: d1.hashes},
        build_hash_tables={0: d2.hashes},
        # plan-time remap: build codes -> probe dictionary codes
        build_code_remaps={0: np.array([d1.code_of(str(v)) for v in d2.values])},
    )
    res = cd.to_host(out, pschema.concat(bschema), dictionaries={0: d1})
    got = sorted(zip(res["s"], res["bv"]))
    assert got == [("a", 200), ("c", 100)]


# ---------------------------------------------------------------------------
# round 2: right/full outer, cross join, UNION ALL


@pytest.fixture(scope="module")
def outer_cat():
    import cockroach_tpu.catalog as catalog_mod
    from cockroach_tpu.coldata.types import INT64, STRING, Schema

    c = catalog_mod.Catalog()
    c.add(catalog_mod.Table.from_strings(
        "l", Schema.of(lk=INT64, lv=INT64, ls=STRING),
        {"lk": np.array([1, 2, 2, 3, 5]),
         "lv": np.array([10, 20, 21, 30, 50]),
         "ls": np.array(["a", "b", "b", "c", "e"], dtype=object)},
    ))
    c.add(catalog_mod.Table.from_strings(
        "r", Schema.of(rk=INT64, rv=INT64, rs=STRING),
        {"rk": np.array([2, 3, 3, 4]),
         "rv": np.array([200, 300, 301, 400]),
         "rs": np.array(["x", "y", "y", "z"], dtype=object)},
    ))
    return c


def _pd(cat, name):
    from cockroach_tpu.coldata.batch import to_host
    import pandas as pd

    t = cat.get(name)
    b = t.device_batch()
    return pd.DataFrame(to_host(b, t.schema, t.dict_by_index()))


def test_right_outer_join(outer_cat):
    from cockroach_tpu.sql.rel import Rel

    l = Rel.scan(outer_cat, "l")
    r = Rel.scan(outer_cat, "r")
    res = l.join(r, on=[("lk", "rk")], how="right",
                 build_unique=False).run()
    want = _pd(outer_cat, "l").merge(
        _pd(outer_cat, "r"), left_on="lk", right_on="rk", how="right")
    assert len(res["rk"]) == len(want)
    got = sorted(zip(res["rv"], [x if x is not None else -1
                                 for x in res["lv"]]))
    exp = sorted(zip(want.rv, want.lv.fillna(-1).astype(int)))
    assert got == exp
    # null-extended probe STRING decodes to None
    nulls = [s for v, s in zip(res["lv"], res["ls"]) if v is None]
    assert nulls and all(s is None for s in nulls)


def test_full_outer_join(outer_cat):
    from cockroach_tpu.sql.rel import Rel

    l = Rel.scan(outer_cat, "l")
    r = Rel.scan(outer_cat, "r")
    res = l.join(r, on=[("lk", "rk")], how="full",
                 build_unique=False).run()
    want = _pd(outer_cat, "l").merge(
        _pd(outer_cat, "r"), left_on="lk", right_on="rk", how="outer")
    assert len(res["lk"]) == len(want)
    got = sorted(((-1 if a is None else a), (-1 if b is None else b))
                 for a, b in zip(res["lv"], res["rv"]))
    exp = sorted(zip(want.lv.fillna(-1).astype(int),
                     want.rv.fillna(-1).astype(int)))
    assert got == exp


def test_cross_join(outer_cat):
    from cockroach_tpu.sql.rel import Rel

    l = Rel.scan(outer_cat, "l", ("lk", "lv"))
    r = Rel.scan(outer_cat, "r", ("rk", "rs"))
    res = l.cross_join(r).run()
    assert len(res["lk"]) == 5 * 4
    got = sorted(zip(res["lv"], res["rk"]))
    exp = sorted((lv, rk) for lv in [10, 20, 21, 30, 50]
                 for rk in [2, 3, 3, 4])
    assert got == exp
    assert set(res["rs"]) == {"x", "y", "z"}  # dict decodes across product


def test_union_all(outer_cat):
    from cockroach_tpu.sql.rel import Rel

    from cockroach_tpu.ops import expr as ex

    l = Rel.scan(outer_cat, "l", ("lk", "lv"))
    u = l.union_all(l.filter(
        ex.Cmp("gt", l.c("lv"), l.c("lv"))))  # empty second arm
    res = u.run()
    assert sorted(res["lk"]) == [1, 2, 2, 3, 5]
    u2 = l.union_all(l)
    assert sorted(u2.run()["lv"]) == sorted([10, 20, 21, 30, 50] * 2)
    # arity mismatch rejected
    with pytest.raises(ValueError):
        l.union_all(Rel.scan(outer_cat, "r"))


def test_right_full_joins_distributed(outer_cat):
    from cockroach_tpu.parallel import mesh as mesh_mod
    from cockroach_tpu.sql.rel import Rel

    mesh = mesh_mod.make_mesh(8)
    l = Rel.scan(outer_cat, "l", ("lk", "lv"))
    r = Rel.scan(outer_cat, "r", ("rk", "rv"))
    for how in ("right", "full"):
        rel = l.join(r, on=[("lk", "rk")], how=how, build_unique=False)
        got = rel.run_distributed(mesh, broadcast_rows=0)
        want = rel.run()
        key = lambda d: sorted(
            ((-1 if a is None else a), (-1 if b is None else b))
            for a, b in zip(d["lv"], d["rv"]))
        assert key(got) == key(want), how


# ---------------------------------------------------------------------------
# dense direct-addressing paths


def _dense_catalog():
    """dim has an arange PK (dense analytic); child has fanout-2 clustering."""
    from cockroach_tpu.catalog import Catalog, Table

    cat = Catalog()
    n = 50
    cat.add(Table.from_strings(
        "dim", cd.Schema.of(dk=cd.INT64, dv=cd.INT64),
        {"dk": np.arange(1, n + 1), "dv": np.arange(1, n + 1) * 7},
    ))
    cat.add(Table.from_strings(
        "child", cd.Schema.of(ck=cd.INT64, sub=cd.INT64, cv=cd.INT64),
        {"ck": np.repeat(np.arange(1, n + 1), 2),
         "sub": np.tile(np.array([10, 20]), n),
         "cv": np.arange(2 * n)},
    ))
    return cat


def test_dense_key_info_detection():
    cat = _dense_catalog()
    assert cat.get("dim").dense_key_info()["dk"] == (1, 1)
    assert cat.get("child").dense_key_info()["ck"] == (1, 2)
    assert "dv" not in cat.get("dim").dense_key_info()
    assert "sub" not in cat.get("child").dense_key_info()


@pytest.mark.parametrize("jt", ["inner", "left", "semi", "anti"])
def test_analytic_join_vs_sorted(jt, rng):
    """HashJoinOp with an analytic dense build must equal the sorted-index
    fallback, including out-of-range probe keys and a filtered build."""
    from cockroach_tpu.catalog import Catalog, Table
    from cockroach_tpu.flow import operators as ops
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.ops import expr as ex
    from cockroach_tpu.ops.join import JoinSpec

    cat = _dense_catalog()
    # probe keys include 0 and n+5 (out of build range) and NULLs
    pk = rng.integers(-2, 58, 40)
    pschema = cd.Schema.of(fk=cd.INT64, pv=cd.INT64)
    pkv = rng.random(40) > 0.15
    cat.add(Table.from_strings(
        "probe", pschema,
        {"fk": pk, "pv": np.arange(40)}, valids={"fk": pkv},
    ))

    def build_tree():
        scan = ops.ScanOp(cat.get("dim"))
        # filter keeps dv < 200 — a mask-only chain over the table
        pred = ex.Cmp("lt", ex.ColRef(1), ex.lit(200))
        return ops.FilterOp(scan, pred)

    probe = ops.ScanOp(cat.get("probe"))
    j = ops.HashJoinOp(probe, build_tree(), (0,), (0,),
                       JoinSpec(join_type=jt, build_unique=True))
    j.init()
    assert j._analytic is not None, "analytic path must engage"
    got = run_operator(j)

    probe2 = ops.ScanOp(cat.get("probe"))
    j2 = ops.HashJoinOp(probe2, build_tree(), (0,), (0,),
                        JoinSpec(join_type=jt, build_unique=True))
    j2._plan_analytic = lambda: None  # force the sorted fallback
    j2.init()
    assert j2._analytic is None
    want = run_operator(j2)
    for c in want:
        np.testing.assert_array_equal(got[c], want[c]), c


def test_analytic_clustered_fanout(rng):
    """Composite-key join against the fanout-2 child table."""
    from cockroach_tpu.catalog import Catalog, Table
    from cockroach_tpu.flow import operators as ops
    from cockroach_tpu.flow.runtime import run_operator
    from cockroach_tpu.ops.join import JoinSpec

    cat = _dense_catalog()
    pk = rng.integers(0, 55, 64)
    sub = rng.choice(np.array([10, 20, 30]), 64)
    pschema = cd.Schema.of(fk=cd.INT64, fsub=cd.INT64, pv=cd.INT64)
    cat.add(Table.from_strings(
        "probe2", pschema,
        {"fk": pk, "fsub": sub, "pv": np.arange(64)},
    ))
    probe = ops.ScanOp(cat.get("probe2"))
    build = ops.ScanOp(cat.get("child"))
    j = ops.HashJoinOp(probe, build, (0, 1), (0, 1),
                       JoinSpec(join_type="inner", build_unique=True))
    j.init()
    assert j._analytic is not None and j._analytic.fanout == 2
    got = run_operator(j)
    # numpy oracle
    child_ck = np.repeat(np.arange(1, 51), 2)
    child_sub = np.tile(np.array([10, 20]), 50)
    child_cv = np.arange(100)
    rows = []
    for i in range(64):
        hit = np.nonzero((child_ck == pk[i]) & (child_sub == sub[i]))[0]
        for h in hit:
            rows.append((pk[i], sub[i], i, child_ck[h], child_sub[h],
                         child_cv[h]))
    want = np.array(sorted(rows))
    got_rows = np.array(sorted(zip(*[got[c] for c in
                                     ("fk", "fsub", "pv", "ck", "sub", "cv")])))
    np.testing.assert_array_equal(got_rows.astype(np.int64),
                                  want.astype(np.int64))
