"""pgwire protocol tests — a minimal hand-rolled v3 client (no Postgres
driver ships in this image; the reference likewise tests conn.go at the
message level, pkg/sql/pgwire/conn_test.go). Covers startup, simple
queries with text results, NULLs, DML tags, transaction status in
ReadyForQuery, error/recovery, and two concurrent sessions."""

import socket
import struct

import pytest

from cockroach_tpu.server.pgwire import PgServer
from cockroach_tpu.sql import Session


class MiniPg:
    """Just enough of the v3 protocol to drive the server."""

    def __init__(self, addr):
        self.sock = socket.create_connection(addr, timeout=30)
        body = struct.pack("!I", 196608) + b"user\x00t\x00\x00"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self.txn_status = None
        self._drain_until_ready()

    def _recv_exact(self, n):
        buf = bytearray()
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            assert c, "server closed"
            buf.extend(c)
        return bytes(buf)

    def _msg(self):
        tag = self._recv_exact(1)
        n = struct.unpack("!I", self._recv_exact(4))[0]
        return tag, self._recv_exact(n - 4)

    def _drain_until_ready(self):
        msgs = []
        while True:
            tag, body = self._msg()
            msgs.append((tag, body))
            if tag == b"Z":
                self.txn_status = body
                return msgs

    def query(self, sql):
        """-> (rows as lists of str|None, command_tag, error|None)"""
        body = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        rows, names, tag_line, err = [], None, None, None
        for tag, body in self._drain_until_ready():
            if tag == b"T":
                ncols = struct.unpack("!H", body[:2])[0]
                names = []
                off = 2
                for _ in range(ncols):
                    end = body.index(b"\x00", off)
                    names.append(body[off:end].decode())
                    off = end + 1 + 18
            elif tag == b"D":
                ncols = struct.unpack("!H", body[:2])[0]
                off = 2
                row = []
                for _ in range(ncols):
                    ln = struct.unpack("!i", body[off:off + 4])[0]
                    off += 4
                    if ln == -1:
                        row.append(None)
                    else:
                        row.append(body[off:off + ln].decode())
                        off += ln
                rows.append(row)
            elif tag == b"C":
                tag_line = body.rstrip(b"\x00").decode()
            elif tag == b"E":
                err = body.decode(errors="replace")
        return rows, names, tag_line, err

    def close(self):
        self.sock.sendall(b"X" + struct.pack("!I", 4))
        self.sock.close()


@pytest.fixture
def server():
    sess = Session()
    srv = PgServer(catalog=sess.catalog, db=sess.db).serve_background()
    yield srv
    srv.close()


def test_pgwire_end_to_end(server):
    c = MiniPg(server.addr)
    assert c.txn_status == b"I"
    _, _, tag, err = c.query(
        "create table t (a int primary key, b int, s string)")
    assert err is None and tag == "CREATE TABLE"
    _, _, tag, err = c.query(
        "insert into t values (1, 10, 'x'), (2, null, 'y')")
    assert err is None and tag == "INSERT 0 2"
    rows, names, tag, err = c.query("select a, b, s from t order by a")
    assert err is None
    assert names == ["a", "b", "s"]
    assert rows == [["1", "10", "x"], ["2", None, "y"]]
    assert tag == "SELECT 2"
    c.close()


def test_pgwire_txn_status_and_errors(server):
    c = MiniPg(server.addr)
    c.query("create table u (a int primary key)")
    c.query("begin")
    assert c.txn_status == b"T"  # in a block
    c.query("insert into u values (1)")
    # an error aborts the block: status E, statements rejected
    _, _, _, err = c.query("select nope from u")
    assert err is not None
    assert c.txn_status == b"E"
    _, _, _, err = c.query("insert into u values (2)")
    assert err is not None and "aborted" in err
    c.query("rollback")
    assert c.txn_status == b"I"
    rows, _, _, err = c.query("select count(*) from u")
    assert err is None and rows == [["0"]]
    # errors outside a block recover to idle
    _, _, _, err = c.query("select broken syntax here")
    assert err is not None
    assert c.txn_status == b"I"
    c.close()


def test_pgwire_two_concurrent_sessions(server):
    a = MiniPg(server.addr)
    b = MiniPg(server.addr)
    a.query("create table shared (k int primary key, v int)")
    a.query("insert into shared values (1, 100)")
    # session A opens a txn and writes; B (its own session) stays idle
    a.query("begin")
    a.query("update shared set v = 200 where k = 1")
    assert a.txn_status == b"T"
    assert b.txn_status == b"I"
    # B's read hits A's intent -> serialization failure with SQLSTATE 40001
    _, _, _, err = b.query("select v from shared")
    assert err is not None and "40001" in err
    a.query("commit")
    rows, _, _, err = b.query("select v from shared")
    assert err is None and rows == [["200"]]
    a.close()
    b.close()


def test_pgwire_through_node_lifecycle():
    from cockroach_tpu.server.node import Node

    node = Node(node_id=4, heartbeat_interval_s=0.1)
    node.start(gossip_port=None, pg_port=0)
    try:
        c = MiniPg(node.pg.addr)
        c.query("create table nt (a int primary key)")
        c.query("insert into nt values (7)")
        rows, _, _, err = c.query("select a from nt")
        assert err is None and rows == [["7"]]
        c.close()
    finally:
        node.stop()


def test_pgwire_tpch_q1_equals_session():
    """The served TPC-H q1 text over the node's pgwire listener returns the
    Session's answer, column for column, and compiles nothing the Session's
    run had not (the wire is the plan cache's second reader)."""
    import numpy as np

    from cockroach_tpu.bench.tpch import gen_tpch
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.server.node import Node

    node = Node().start(gossip_port=None, pg_port=0)
    try:
        # the way cli.py's --demo-tpch loads them: generated tables adopted
        # by the serving catalog
        sess = Session(catalog=node._sql_catalog, db=node.db,
                       bootstrap=False)
        sess.catalog.tables.update(gen_tpch(sf=0.005, seed=3).tables)
        want = sess.execute(TPCH_SQL["q1"])
        c = MiniPg(node.pg.addr)
        c0 = dispatch.compiles()
        rows, names, _, err = c.query(TPCH_SQL["q1"])
        assert err is None and dispatch.compiles() == c0
        c.close()
        sess.close()
    finally:
        node.stop()
    assert names == list(want) and len(rows) == len(want[names[0]]) == 4
    for j, name in enumerate(names):
        col, got = np.asarray(want[name]), [r[j] for r in rows]
        if col.dtype.kind in "fiu":
            np.testing.assert_allclose(np.array(got, dtype=np.float64),
                                       col.astype(np.float64), rtol=1e-12,
                                       err_msg=name)
        else:
            assert got == [str(v) for v in col], name


class MiniPgExt(MiniPg):
    """Extended-protocol messages (Parse/Bind/Describe/Execute/Sync)."""

    def _send_msg(self, tag: bytes, body: bytes):
        self.sock.sendall(tag + struct.pack("!I", len(body) + 4) + body)

    def prepare(self, name: str, sql: str):
        self._send_msg(b"P", name.encode() + b"\x00" + sql.encode()
                       + b"\x00" + struct.pack("!H", 0))

    def bind(self, portal: str, stmt: str, params: list):
        body = portal.encode() + b"\x00" + stmt.encode() + b"\x00"
        body += struct.pack("!H", 1) + struct.pack("!H", 0)  # all text
        body += struct.pack("!H", len(params))
        for p in params:
            if p is None:
                body += struct.pack("!i", -1)
            else:
                pb = str(p).encode()
                body += struct.pack("!i", len(pb)) + pb
        body += struct.pack("!H", 0)  # result formats: default text
        self._send_msg(b"B", body)

    def describe_portal(self, portal: str):
        self._send_msg(b"D", b"P" + portal.encode() + b"\x00")

    def execute(self, portal: str):
        self._send_msg(b"E", portal.encode() + b"\x00"
                       + struct.pack("!i", 0))

    def sync(self):
        self._send_msg(b"S", b"")
        return self._drain_until_ready()


def test_pgwire_extended_protocol(server):
    c = MiniPgExt(server.addr)
    try:
        c.query("create table ep (id int primary key, v int, s string)")
        c.query("insert into ep values (1, 10, 'a'), (2, 20, 'b'),"
                " (3, 30, 'it''s')")
        # Parse/Bind/Describe/Execute with int + string parameters
        c.prepare("sel", "select id, v, s from ep where v > $1 and s <> $2"
                         " order by id")
        c.bind("", "sel", ["15", "zzz"])
        c.describe_portal("")
        c.execute("")
        msgs = c.sync()
        tags = [t for t, _ in msgs]
        assert b"1" in tags and b"2" in tags  # Parse/BindComplete
        assert b"T" in tags  # RowDescription from Describe
        drows = [b for t, b in msgs if t == b"D"]
        assert len(drows) == 2  # v in (20, 30)
        assert b"E" not in tags
        # RowDescription came ONLY from Describe, before the DataRows
        assert tags.index(b"T") < tags.index(b"D")

        # rebind same statement with different params (incl. quote escape)
        c.bind("", "sel", ["0", "it's"])
        c.execute("")
        msgs = c.sync()
        drows = [b for t, b in msgs if t == b"D"]
        assert len(drows) == 2  # id 1 and 2 (id 3's s matches $2)

        # NULL parameter: v > NULL matches nothing
        c.bind("", "sel", [None, "zzz"])
        c.execute("")
        msgs = c.sync()
        assert [b for t, b in msgs if t == b"D"] == []

        # DML through the extended path + NoData describe
        c.prepare("ins", "insert into ep values ($1, $2, $3)")
        c.bind("", "ins", ["4", "40", "d"])
        c.describe_portal("")
        c.execute("")
        msgs = c.sync()
        tags = [t for t, _ in msgs]
        assert b"n" in tags  # NoData
        assert any(t == b"C" and b"INSERT" in b for t, b in msgs)
        rows, _, _, _ = c.query("select count(*) as n from ep")
        assert rows == [["4"]]

        # error recovery: unknown portal fails ONCE, Sync recovers
        c.execute("nope")
        c.execute("nope")  # discarded (post-error, pre-Sync)
        msgs = c.sync()
        errs = [b for t, b in msgs if t == b"E"]
        assert len(errs) == 1
        rows, _, _, err = c.query(
            "select count(*) as one from ep where id = 1")
        assert err is None and rows == [["1"]]
    finally:
        c.close()


def test_pgwire_describe_statement_and_param_edge_cases(server):
    c = MiniPgExt(server.addr)
    try:
        c.query("create table dx (id int primary key, s string)")
        c.query("insert into dx values (1, 'a')")
        # Describe STATEMENT: ParameterDescription then RowDescription
        c.prepare("ds", "select id, s from dx where id = $1")
        c._send_msg(b"D", b"Sds\x00")
        msgs = c.sync()
        tags = [t for t, _ in msgs]
        assert b"t" in tags and b"T" in tags
        tbody = next(b for t, b in msgs if t == b"t")
        assert struct.unpack("!H", tbody[:2])[0] == 1  # one placeholder
        # a param VALUE containing '$1' must not be re-substituted
        c.prepare("p2", "select id from dx where s <> $1 and s <> $2")
        c.bind("", "p2", ["x", "$1"])
        c.execute("")
        msgs = c.sync()
        assert len([b for t, b in msgs if t == b"D"]) == 1
        assert not any(t == b"E" for t, _ in msgs)
        # binary result format is rejected, not silently mis-encoded
        body = (b"\x00" + b"p2\x00" + struct.pack("!H", 0)
                + struct.pack("!H", 2)
                + struct.pack("!i", 1) + b"x"
                + struct.pack("!i", 1) + b"y"
                + struct.pack("!HH", 1, 1))  # result format: binary
        c._send_msg(b"B", body)
        msgs = c.sync()
        assert any(t == b"E" and b"binary result" in b for t, b in msgs)
    finally:
        c.close()


def test_placeholder_inside_string_literal_is_text(server):
    c = MiniPgExt(server.addr)
    try:
        c.query("create table lt (id int primary key, s string)")
        c.query("insert into lt values (1, 'a$1b'), (2, 'x')")
        # '$1' inside the prepared SQL's literal is TEXT, not a param
        c.prepare("q", "select id from lt where s = 'a$1b' and id = $1")
        c.bind("", "q", ["1"])
        c.execute("")
        msgs = c.sync()
        assert len([b for t, b in msgs if t == b"D"]) == 1
        assert not any(t == b"E" for t, _ in msgs)
    finally:
        c.close()


def test_pgwire_extended_rebind_rides_plan_cache():
    """Parse-once/Bind-many through the extended protocol must hit the
    prepared-plan cache on every rebind: the inlined literals reach
    Session.execute, sql/plancache.py re-parameterizes them back out, and
    the repeat serves with zero new XLA compiles."""
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.sql import plancache

    sess = Session()
    srv = PgServer(catalog=sess.catalog, db=sess.db).serve_background()
    try:
        c = MiniPgExt(srv.addr)
        c.query("create table pc (id int primary key, v int)")
        c.query("insert into pc values (1, 10), (2, 20), (3, 30)")
        c.prepare("sel", "select v from pc where id = $1")
        c.bind("", "sel", ["1"])
        c.execute("")
        c.sync()
        cache = plancache.cache_for(sess.catalog)
        h0, c0 = cache.hits, dispatch.compiles()
        c.bind("", "sel", ["2"])
        c.execute("")
        msgs = c.sync()
        rows = [b for t, b in msgs if t == b"D"]
        assert len(rows) == 1 and rows[0].endswith(b"20")
        assert cache.hits == h0 + 1
        assert dispatch.compiles() == c0  # zero-recompile serving path
        c.close()
    finally:
        srv.close()


def test_pgwire_overload_sheds_typed_53300_not_hang_or_drop():
    """Overload at the wire: with one slot held and a depth-1 queue, a
    first client queues (not dropped) and a second is refused with
    SQLSTATE 53300 on an open, still-usable connection (never a hang,
    never a connection teardown). Once the slot frees, the queued
    statement completes and the refused client's retry succeeds."""
    import time

    from cockroach_tpu.utils import admission

    sess = Session()
    srv = PgServer(catalog=sess.catalog, db=sess.db).serve_background()
    saved = admission._SQL_QUEUE
    q = admission.WorkQueue(slots=1, max_queue_depth=1)
    admission._SQL_QUEUE = q
    c1 = c2 = None
    try:
        assert q.admit(tenant_id=1)  # the test parks the only slot
        c1 = MiniPg(srv.addr)
        c2 = MiniPg(srv.addr)
        # c1 issues a statement but we don't read the reply yet: its
        # server thread must be sitting in the admission queue
        body = b"select 1\x00"
        c1.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        deadline = time.time() + 10.0
        while q.queue_depth < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert q.queue_depth == 1, "first statement never queued"
        # queue at its bound: c2 gets the typed busy, 53300 on the wire
        _, _, _, err = c2.query("select 1")
        assert err is not None and "53300" in err
        assert "admission" in err or "busy" in err or "full" in err
        # the refusal did not tear down c2: protocol still in sync
        assert c2.txn_status == b"I"
        # free the slot: the queued c1 statement is granted and completes
        q.release()
        msgs = c1._drain_until_ready()
        assert any(t == b"D" for t, _ in msgs), "queued stmt lost"
        assert not any(t == b"E" for t, _ in msgs)
        # and c2's retry now admits normally
        rows, _, _, err = c2.query("select 1")
        assert err is None and rows == [["1"]]
        assert q.in_use == 0 and q.queue_depth == 0
    finally:
        if c1 is not None:
            c1.close()
        if c2 is not None:
            c2.close()
        admission._SQL_QUEUE = saved
        srv.close()
        sess.close()
