"""CHAR(n) stored raw in a KV-backed table (PR 45): a column declared with a
width is kept at n bytes plus its length in the row's value slot: no
dictionary entry, no write to the companion key space. On the device it is
coldata's BYTES(n) representation, ordered and de-duplicated by the sort keys
as they are; results give text. A STRING without a width stays
dictionary-coded, byte for byte what it was."""

import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.coldata import types as T
from cockroach_tpu.coldata.batch import to_host
from cockroach_tpu.sql import Session
from cockroach_tpu.storage import rowcodec

SCHEMA = T.Schema.of(id=T.INT64, k=T.INT64, c=T.CHAR(12), pad=T.CHAR(5),
                     f=T.BOOL)
TEXTS = ["", "a", "hello world!", "héllo", "\x7fÿ", "zz", None,
         "123456789012"]


def _row(i, c, pad="p"):
    return {"id": i, "k": -i, "c": c, "pad": pad, "f": i % 2 == 0}


def test_the_slot_layout_is_the_sources_widths():
    offs, width = rowcodec.slot_layout(SCHEMA)
    assert offs == (1, 9, 17, 17 + 4 + 12, 17 + 16 + 4 + 5)
    assert width == rowcodec.value_width(SCHEMA) == 1 + 8 + 8 + 16 + 9 + 8
    sb = T.Schema.of(id=T.INT64, k=T.INT64, c=T.CHAR(120), pad=T.CHAR(60))
    assert rowcodec.value_width(sb) == 1 + 8 + 8 + 124 + 64 == 205
    assert repr(T.CHAR(120)) == "CHAR(120)" and T.CHAR(120).text
    assert T.CHAR(120).family is T.Family.BYTES


@pytest.mark.parametrize("c", TEXTS)
def test_a_row_round_trips_on_the_host(c):
    v = rowcodec.encode_row(SCHEMA, _row(3, c))
    assert len(v) == rowcodec.value_width(SCHEMA)
    assert rowcodec.decode_row(SCHEMA, v) == _row(3, c)
    if c is not None:  # the stored length is the text's bytes
        off = rowcodec.slot_layout(SCHEMA)[0][2]
        assert int.from_bytes(v[off:off + 4], "little") == len(c.encode())
        assert v[off + 4:off + 16] == c.encode().ljust(12, b"\x00")


def test_the_vectorized_encoder_writes_the_same_bytes():
    rows = [_row(i, c, pad=None if i == 2 else f"p{i}")
            for i, c in enumerate(TEXTS)]
    cols = {n: np.array([("" if n in ("c", "pad") else 0)
                         if r[n] is None else r[n] for r in rows],
                        dtype=object if n in ("c", "pad") else None)
            for n in SCHEMA.names}
    valids = {n: np.array([r[n] is not None for r in rows])
              for n in ("c", "pad")}
    got = rowcodec.encode_rows(SCHEMA, cols, valids)
    for i, r in enumerate(rows):
        assert got[i].tobytes() == rowcodec.encode_row(SCHEMA, r), r
    # bytes in, as a bulk loader makes them: a narrower matrix is padded
    m = np.frombuffer(b"abc" + b"de\x00", dtype=np.uint8).reshape(2, 3)
    two = rowcodec.encode_rows(
        T.Schema.of(id=T.INT64, c=T.CHAR(12)),
        {"id": np.array([1, 2]), "c": m})
    assert [rowcodec.decode_row(T.Schema.of(id=T.INT64, c=T.CHAR(12)),
                                v.tobytes())["c"] for v in two] == [
        "abc", "de"]


@pytest.mark.parametrize("bad,why", [
    ("x" * 13, "too long"), ("é" * 7, "too long"), ("a\x00b", "NUL")])
def test_what_does_not_fit_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        rowcodec.encode_row(SCHEMA, _row(1, bad))
    with pytest.raises(ValueError, match=why):
        rowcodec.encode_rows(
            SCHEMA, {"id": np.array([1]), "k": np.array([1]),
                     "c": np.array([bad], dtype=object),
                     "pad": np.array(["p"], dtype=object),
                     "f": np.array([True])})


def test_the_device_decodes_the_stored_bytes():
    rows = [_row(i, c) for i, c in enumerate(TEXTS)]
    value = jnp.asarray(np.stack([
        np.frombuffer(rowcodec.encode_row(SCHEMA, r), dtype=np.uint8)
        for r in rows]))
    sel = jnp.asarray(np.array([True] * len(rows)))
    batch = rowcodec.decode_columns(value, sel, SCHEMA, (2, 0, 4))
    assert batch.cols[0].data.shape == (len(rows), 12)
    assert batch.cols[0].data.dtype == jnp.uint8
    host = to_host(batch, SCHEMA.select((2, 0, 4)))
    assert list(host["c"]) == TEXTS
    assert list(host["id"]) == list(range(len(rows)))
    assert list(host["f"]) == [i % 2 == 0 for i in range(len(rows))]
    # a row the selection leaves out decodes to zero bytes, not garbage
    none = rowcodec.decode_columns(value, ~sel, SCHEMA, (2,))
    assert not np.asarray(none.cols[0].data).any()


@pytest.fixture
def sess():
    s = Session(key_width=64, val_width=64)
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, c CHAR(12) NOT NULL, "
              "v VARCHAR(8), s STRING)")
    try:
        yield s
    finally:
        s.close()


def _dict_rows(s) -> int:
    t = s.catalog.tables["t"]
    start, end = rowcodec.table_span(t.dict_table_id)
    return len(s.db.scan(start, end))


def test_create_table_gives_char_columns_and_no_dictionary_rows(sess):
    t = sess.catalog.tables["t"]
    assert [repr(x) for x in t.schema.types] == [
        "INT64", "CHAR(12)", "CHAR(8)", "STRING"]
    assert set(t.dictionaries) == {"s"}  # the STRING beside them: coded
    for i, c in enumerate(["bb", "a", "", "héllo", "zz" * 6, "a"]):
        sess.execute(f"INSERT INTO t VALUES ({i}, '{c}', "
                     f"{'NULL' if i == 2 else repr('v' + str(i))}, 'same')")
    assert _dict_rows(sess) == 1  # one STRING value, however many CHARs
    got = sess.execute("SELECT id, c, v, s FROM t WHERE id = 3")
    assert [list(got[n]) for n in ("id", "c", "v", "s")] == [
        [3], ["héllo"], ["v3"], ["same"]]
    assert list(sess.execute("SELECT v FROM t WHERE id = 2")["v"]) == [None]
    assert t.get_row(4)["c"] == "zz" * 6
    with pytest.raises(Exception, match="too long"):
        sess.execute("INSERT INTO t VALUES (9, 'thirteen chars', 'v', 's')")


def test_order_by_and_distinct_are_bytewise(sess):
    rng = np.random.default_rng(5)
    texts = ["".join(chr(int(x)) for x in rng.integers(33, 127, size=n))
             for n in rng.integers(0, 13, size=200)] + ["é", "z", "z", ""]
    ids = np.arange(len(texts), dtype=np.int64)
    sess.catalog.tables["t"].bulk_load(
        {"id": ids, "c": np.array(texts, dtype=object),
         "v": np.array(["v"] * len(texts), dtype=object),
         "s": np.array([f"s{i % 4}" for i in ids], dtype=object)})
    assert _dict_rows(sess) == 4
    where = f"id BETWEEN 0 AND {len(texts)}"
    got = sess.execute(f"SELECT c FROM t WHERE {where} ORDER BY c")
    assert list(got["c"]) == sorted(texts, key=str.encode)
    got = sess.execute(f"SELECT c FROM t WHERE {where} ORDER BY c DESC")
    assert list(got["c"]) == sorted(texts, key=str.encode, reverse=True)
    got = sess.execute(f"SELECT DISTINCT c FROM t WHERE {where} ORDER BY c")
    assert list(got["c"]) == sorted(set(texts), key=str.encode)
    # the whole-table decode gives the same text as the range route
    got = sess.execute("SELECT id, c FROM t")
    assert dict(zip(map(int, got["id"]), got["c"])) == dict(
        zip(range(len(texts)), texts))
    got = sess.execute("SELECT s, count(*) AS n FROM t GROUP BY s "
                       "ORDER BY s")
    assert list(got["s"]) == ["s0", "s1", "s2", "s3"]
    assert [int(x) for x in got["n"]] == [51] * 4


def test_an_update_and_a_delete_keep_the_text(sess):
    for i in range(6):
        sess.execute(f"INSERT INTO t VALUES ({i}, 'c{i}', 'v{i}', 's')")
    sess.execute("UPDATE t SET v = 'new' WHERE id = 2")
    sess.execute("UPDATE t SET c = 'moved' WHERE id BETWEEN 4 AND 5")
    sess.execute("DELETE FROM t WHERE id = 0")
    got = sess.execute("SELECT id, c, v FROM t WHERE id BETWEEN 0 AND 9")
    assert [(int(i), c, v) for i, c, v in zip(got["id"], got["c"],
                                              got["v"])] == [
        (1, "c1", "v1"), (2, "c2", "new"), (3, "c3", "v3"),
        (4, "moved", "v4"), (5, "moved", "v5")]


PEOPLE = ["ann", "bob", "", "héllo", "Ann", "anna", "a_b", "b%", "zz" * 6,
          "ñu", "abc", "abd"]


@pytest.fixture
def people(sess):
    """t.c NOT NULL and t.v nullable hold PEOPLE (v NULL at id 2), in a run
    and in the memtable, so a predicate reads both."""
    for i, c in enumerate(PEOPLE):
        v = "NULL" if i == 2 else repr(c[:8].encode()[:8].decode(
            errors="ignore"))
        sess.execute(f"INSERT INTO t VALUES ({i}, '{c}', {v}, 's{i % 2}')")
        if i == 5:
            sess.db.engine.flush_mem_only()
    return sess


def _ids(sess, where):
    got = sess.execute(f"SELECT id FROM t WHERE {where} ORDER BY id")
    return [int(x) for x in got["id"]]


def _like(pattern, text, ci=False):
    import re
    rx = "".join(".*" if ch == "%" else "." if ch == "_" else re.escape(ch)
                 for ch in pattern)
    return re.fullmatch(rx, text, re.S | (re.I if ci else 0)) is not None


@pytest.mark.parametrize("where,want", [
    ("c = 'ann'", lambda c: c == "ann"),
    ("'ann' = c", lambda c: c == "ann"),
    ("c = ''", lambda c: c == ""),
    ("c = 'nobody'", lambda c: False),
    ("c = 'longer than twelve bytes'", lambda c: False),
    ("c <> 'ann'", lambda c: c != "ann"),
    ("c < 'b'", lambda c: c.encode() < b"b"),
    ("c <= 'ann'", lambda c: c.encode() <= b"ann"),
    ("c > 'ann'", lambda c: c.encode() > b"ann"),
    ("'h' <= c", lambda c: c.encode() >= b"h"),
    ("c >= 'zzzzzzzzzzzz'", lambda c: c.encode() >= b"z" * 12),
    ("c < 'zzzzzzzzzzzzz'", lambda c: c.encode() < b"z" * 13),
    ("c BETWEEN 'ann' AND 'bob'", lambda c: b"ann" <= c.encode() <= b"bob"),
    ("c IN ('bob', 'héllo', 'nobody')", lambda c: c in ("bob", "héllo")),
    ("c IN ('ann')", lambda c: c == "ann"),
    ("c NOT IN ('bob', 'ann')", lambda c: c not in ("bob", "ann")),
    ("c = 'ann' OR id = 1", lambda c: c in ("ann", "bob")),
    ("NOT (c = 'ann')", lambda c: c != "ann"),
    ("length(c) = 5", lambda c: len(c) == 5),
    ("char_length(c) < 3", lambda c: len(c) < 3),
    ("starts_with(c, 'an')", lambda c: c.startswith("an")),
])
def test_a_char_column_is_filtered_by_comparison(people, where, want):
    assert _ids(people, where) == [
        i for i, c in enumerate(PEOPLE) if want(c)]


@pytest.mark.parametrize("pattern", [
    "ann", "an%", "%n", "%n%", "a_n", "a__", "_", "%", "", "%%", "_%_",
    "h_llo", "_u", "%é%", "a\\_b", "b%", "%b", "a%a", "%zz", "zz%zz",
    "____________", "_____________", "ab_", "%a%b%"])
def test_like_over_a_char_column_matches_pythons_regex(people, pattern):
    for neg in ("", "NOT "):
        assert _ids(people, f"c {neg}LIKE '{pattern}'") == [
            i for i, c in enumerate(PEOPLE)
            if _like(pattern, c) != bool(neg)]
    if pattern.isascii():  # ILIKE folds ASCII letters, on both sides
        assert _ids(people, f"c ILIKE '{pattern.upper()}'") == [
            i for i, c in enumerate(PEOPLE) if _like(pattern, c, ci=True)]


def test_char_predicates_keep_null_and_the_plan(people):
    from cockroach_tpu.flow import dispatch

    # v is NULL at id 2: neither v = x nor v <> x holds there
    assert _ids(people, "v = 'ann'") == [0]
    assert 2 not in _ids(people, "v <> 'ann'")
    assert 2 not in _ids(people, "v NOT LIKE 'a%'")
    assert _ids(people, "v IS NULL") == [2]
    # two raw columns compare bytewise; widths differ (12 and 8)
    assert _ids(people, "c = v") == [
        i for i, c in enumerate(PEOPLE) if i != 2 and len(c.encode()) <= 8]
    assert _ids(people, "c > v") == [8]
    # another literal is the same plan: nothing compiles
    _ids(people, "c = 'warm' AND id BETWEEN 0 AND 99")
    c0 = dispatch.compiles()
    assert _ids(people, "c = 'bob' AND id BETWEEN 0 AND 99") == [1]
    assert _ids(people, "c = 'héllo' AND id BETWEEN 1 AND 98") == [3]
    assert dispatch.compiles() == c0
    # UPDATE and DELETE filter by the text too
    people.execute("UPDATE t SET v = 'hit' WHERE c = 'bob'")
    people.execute("DELETE FROM t WHERE c LIKE 'an%'")
    got = people.execute("SELECT id, v FROM t WHERE c < 'b' ORDER BY id")
    assert [(int(i), v) for i, v in zip(got["id"], got["v"])] == [
        (2, None), (4, "Ann"), (6, "a_b"), (10, "abc"), (11, "abd")]
    assert _ids(people, "v = 'hit'") == [1]
    # GROUP BY beside a filter, and the dictionary column still binds
    got = people.execute("SELECT s, count(*) AS n FROM t WHERE c >= 'a' "
                         "GROUP BY s ORDER BY s")
    assert [(s, int(n)) for s, n in zip(got["s"], got["n"])] == [
        ("s0", 3), ("s1", 5)]
    assert _ids(people, "s = 's1' AND c = 'bob'") == [1]


def test_char_columns_join_by_their_bytes_whatever_their_widths(people):
    # t.c CHAR(12) against u.name VARCHAR(6): equal text joins, NULL never
    people.execute("CREATE TABLE u (uid INT PRIMARY KEY, name VARCHAR(6))")
    for i, n in enumerate(["bob", "héllo", "ann", None, "ANN", "a_b"]):
        people.execute(f"INSERT INTO u VALUES ({i}, "
                       f"{'NULL' if n is None else repr(n)})")
    got = people.execute("SELECT t.id, u.uid FROM t JOIN u ON t.c = u.name "
                         "ORDER BY t.id")
    assert [(int(a), int(b)) for a, b in zip(got["id"], got["uid"])] == [
        (0, 2), (1, 0), (3, 1), (6, 5)]
    assert _ids(people, "c IN (SELECT name FROM u)") == [0, 1, 3, 6]
    assert _ids(people, "c NOT IN (SELECT name FROM u WHERE uid < 3)") == [
        2, 4, 5, 6, 7, 8, 9, 10, 11]
    assert _ids(people, "c = (SELECT name FROM u WHERE uid = 1)") == [3]
    got = people.execute("SELECT c, count(*) AS n FROM t GROUP BY c "
                         "ORDER BY c")
    assert list(got["c"]) == sorted(PEOPLE, key=str.encode)
    got = people.execute("SELECT count(DISTINCT c) AS n FROM t")
    assert int(got["n"][0]) == len(PEOPLE)
    # a column added with a width later is stored and filtered the same way
    people.execute("ALTER TABLE u ADD COLUMN w VARCHAR(5)")
    people.execute("UPDATE u SET w = 'hey' WHERE name = 'ann'")
    got = people.execute("SELECT uid, w FROM u WHERE w = 'hey'")
    assert [(int(a), b) for a, b in zip(got["uid"], got["w"])] == [
        (2, "hey")]


@pytest.mark.parametrize("where,why", [
    ("c = s", "cannot compare"),
    ("c = 5", "cannot compare"),
    ("c ILIKE 'É%'", "ASCII"),
    ("c IN ('a', 5)", "literals"),
    ("id = (SELECT max(c) FROM t)", "max over a CHAR"),
])
def test_what_a_char_column_cannot_be_compared_with_is_refused(sess, where,
                                                                why):
    with pytest.raises(Exception, match=why):
        sess.execute(f"SELECT id FROM t WHERE {where}")


def test_a_char_predicate_ships_to_another_node():
    import json

    from cockroach_tpu.flow import wire
    from cockroach_tpu.ops import expr as ex

    pred = ex.and_(
        ex.Cmp("le", ex.ColRef(1), ex.Const("h\u00e9".encode(), T.CHAR(12))),
        ex.Not(ex.BytesLike(ex.ColRef(1), b"a%_", True)),
        ex.Cmp("gt", ex.BytesLen(ex.ColRef(2)), ex.lit(3)))
    assert wire.dec_expr(json.loads(json.dumps(wire.enc_expr(pred)))) == pred


def test_a_descriptor_round_trips_the_width():
    s = Session(key_width=64, val_width=64)
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, c CHAR(12))")
    s.execute("INSERT INTO t VALUES (1, 'kept')")
    again = Session(db=s.db)  # rediscovers the table from its descriptor
    t = again.catalog.tables["t"]
    assert repr(t.schema.type_of("c")) == "CHAR(12)"
    assert list(again.execute("SELECT c FROM t WHERE id = 1")["c"]) == [
        "kept"]
    s.close()


def test_a_table_without_a_width_is_byte_for_byte_what_it_was():
    """`kv (k INT, v STRING)`: the value is one byte of null bits and two
    8-byte slots, the string a dictionary code, as before this PR."""
    s = Session()
    s.execute("CREATE TABLE kv (k INT PRIMARY KEY, v STRING)")
    t = s.catalog.tables["kv"]
    assert t.schema.types == (T.INT64, T.STRING)
    assert rowcodec.value_width(t.schema) == 17
    s.execute("UPSERT INTO kv (k, v) VALUES (7, 'A')")
    s.execute("UPSERT INTO kv (k, v) VALUES (8, 'B')")
    raw = s.db.get(rowcodec.encode_pk(t.table_id, 8))
    assert raw == (bytes([0b11]) + (8).to_bytes(8, "little")
                   + (1).to_bytes(8, "little"))  # 'B' is code 1
    assert rowcodec.encode_row(t.schema, {"k": 8, "v": 1}) == raw
    vec = rowcodec.encode_rows(t.schema, {"k": np.array([8]),
                                          "v": np.array([1])})
    assert vec[0].tobytes() == raw
    s.close()
