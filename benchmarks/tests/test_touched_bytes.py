"""The touched-bytes function against the count by hand: q1 reads seven
lineitem columns, 44 B a row."""

import traffic
from touched_bytes import touched_bytes


def test_q1_is_44_bytes_a_row():
    from cockroach_tpu.bench import tpch
    from loaders.tpch import Loaded

    cat = tpch.gen_tpch(sf=0.001, seed=5)
    loaded = Loaded.__new__(Loaded)
    loaded.tables = dict(cat.tables)
    rows = cat.get("lineitem").num_rows
    assert touched_bytes(loaded, "tpch_q1") == 44 * rows
    # at SF1: 44 B x 6,002,051 rows = 0.264 GB, 0.32 ms at 819 GB/s
    assert 44 * 6_002_051 == 264_090_244


def test_same_seed_same_statements():
    """Any seed up to a little over 2**31 gives the same statements twice,
    and another seed gives others."""
    big = 2**31 + 99
    mix = traffic.load_mix("q1_stream")
    s1, s2 = traffic.Stream(mix, big, 0), traffic.Stream(mix, big, 0)
    drawn = [s1.next() for _ in range(8)]
    assert drawn == [s2.next() for _ in range(8)]
    assert len({sql for _j, _p, sql in drawn}) <= mix["param_sets"]
    other = traffic.Stream(mix, big + 1, 0)
    assert [other.next() for _ in range(8)] != drawn
    lo, hi = s1.warmup()
    assert lo[1] == {"delta": 60} and hi[1] == {"delta": 120}
