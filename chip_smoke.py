"""Chip smoke: the served SQL path and the storage plane on one attached TPU.

    python chip_smoke.py             # one chip: device, sql, kv
    python chip_smoke.py --full      # the same at bench.py's full sizes
    python chip_smoke.py --chips 4   # four chips: shuffle only (SF0.05)

The quickest proof that the system still starts on the chip. It drives the
main path once through the entry points a user calls, checks every answer
against an independent reference, and exits non-zero on the first phase that
fails. Data is generated from --seed inside the run. One process holds the
chip: everything runs here, and no child that needs JAX is started.

Sizes. A run must finish inside 1200 s on an empty compile cache, and on
this engine a cold run is almost all compile (PERF.md, PR 22: one MVCC
`lax.sort` instantiation takes minutes above 4,096 rows; q3's first two
runs at SF1 take 810 s). So the default run keeps TPC-H at SF1 — q1 three
times, q3 once, q1 again over pgwire — and the 1,000 acknowledged SQL
writes, and cuts two things: q3's second and third run, and YCSB-E's
keyspace (4,096 keys, where its sorts compile in seconds; its batched scans
still go through the Pallas scan filter on the chip, but nothing that small
is merged, so the merge gate is reached only by `--full`). `--full` restores
q3 three times and YCSB-E at bench.py's 1M keys: 2,364 s on an empty cache
when PR 22 ran it. The four-chip shuffle runs q3 at SF0.05 for the same
reason, four times over (a four-chip call is charged fourfold): its one SPMD
program holds 18 sorts and did not finish compiling for a described v5e 2x2
at SF1 in 263 CPU-minutes, against 243 s on 8 cores at SF0.05. `--full
--chips 4` asks for SF1.

There is no CPU mode. Without a TPU the script prints why and exits
non-zero before any phase runs; tests/test_chip_smoke.py rehearses the phase
functions at tiny size on the CPU mesh by calling them directly.

Every line on stdout is one JSON object; the last one is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
These are single readings of a smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import shutil
import socket
import struct
import sys
import tempfile
import time

import numpy as np


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, why="") -> None:
    """A result check that survives `python -O`, unlike assert."""
    if not cond:
        raise AssertionError(why)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# device


def phase_device() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from cockroach_tpu.utils import backend

    cache_dir, from_env = backend.compile_cache_dir()
    check(backend.enable_compile_cache() == cache_dir)
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    try:
        bitcast = backend.float_bitcast_ok()
    except Exception as e:  # a compile error is its own verdict, reported
        bitcast = f"{type(e).__name__}: {str(e)[:200]}"
    out = {**device_info(), "jax": jax.__version__,
           "jaxlib": jaxlib.__version__, "libtpu": libtpu,
           "compile_cache_dir": cache_dir,
           "compile_cache_from_env": from_env,
           "float_bitcast_ok": bitcast}
    emit(phase="device", **out)
    return out


# ---------------------------------------------------------------------------
# sql


class _SimpleQueryClient:
    """Just enough of pgwire v3 for one simple query with text results."""

    def __init__(self, addr):
        self.sock = socket.create_connection(addr, timeout=600)
        body = struct.pack("!I", 196608) + b"user\x00smoke\x00\x00"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self._until_ready()

    def _recv(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("pgwire server closed the connection")
            buf.extend(c)
        return bytes(buf)

    def _until_ready(self) -> list[tuple[bytes, bytes]]:
        msgs = []
        while True:
            tag = self._recv(1)
            body = self._recv(struct.unpack("!I", self._recv(4))[0] - 4)
            msgs.append((tag, body))
            if tag == b"Z":
                return msgs

    def query(self, sql: str) -> tuple[list[str], list[list[str | None]]]:
        body = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        names: list[str] = []
        rows: list[list[str | None]] = []
        for tag, body in self._until_ready():
            if tag == b"E":
                raise RuntimeError(
                    f"pgwire error: {body.decode(errors='replace')}")
            if tag not in (b"T", b"D"):
                continue
            off = 2
            row: list[str | None] = []
            for _ in range(struct.unpack("!H", body[:2])[0]):
                if tag == b"T":
                    end = body.index(b"\x00", off)
                    names.append(body[off:end].decode())
                    off = end + 1 + 18
                    continue
                ln = struct.unpack("!i", body[off:off + 4])[0]
                off += 4
                row.append(None if ln == -1
                           else body[off:off + ln].decode())
                off += max(ln, 0)
            if tag == b"D":
                rows.append(row)
        return names, rows

    def close(self) -> None:
        self.sock.sendall(b"X" + struct.pack("!I", 4))
        self.sock.close()


def _operators(root) -> list[str]:
    """Class names of the operator tree, fusion wrappers looked through."""
    from cockroach_tpu.flow.fuse import unwrap

    names, stack = [], [root]
    while stack:
        op = unwrap(stack.pop())
        names.append(type(op).__name__)
        stack.extend(op.children())
    return sorted(set(names))


# second runs of a join query may re-specialize once: join emission caps are
# learned from the first run (scripts/check_recompiles.py holds that to the
# same budget). From the run after, a statement compiles nothing.
_ADAPT_BUDGET = 16


def phase_sql(sf: float = 1.0, seed: int = 19920101,
              queries: tuple[str, ...] = ("q1", "q3"),
              once: tuple[str, ...] = ()) -> dict:
    """TPC-H at `sf` served as SQL text by an in-process Node: Session
    (parse, bind, plan cache, admission, flow, device), then q1 again over
    the node's pgwire listener. Every answer is held to bench.py's pandas
    oracle. A query runs three times unless it is named in `once`."""
    import bench  # the repo-root driver holds the pandas oracles
    from cockroach_tpu.bench import tpch
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.ops import segscan
    from cockroach_tpu.plan import builder as plan_builder
    from cockroach_tpu.server.node import Node
    from cockroach_tpu.sql import Session, sql

    t0 = time.time()
    cat = tpch.gen_tpch(sf=sf, seed=seed)
    node = Node().start(pg_port=0)
    try:
        # the way cli.py's --demo-tpch loads them: generated tables adopted
        # by the serving catalog; scans cache them on the device
        sess = Session(catalog=node._sql_catalog, db=node.db,
                       bootstrap=False)
        for name, table in cat.tables.items():
            sess.catalog.tables[name] = table
        nrows = sess.catalog.get("lineitem").num_rows
        emit(phase="sql", step="load", sf=sf, seed=seed,
             lineitem_rows=nrows, seconds=round(time.time() - t0, 2))

        out: dict = {"lineitem_rows": nrows, "queries": {}}
        results = {}
        for q in queries:
            runs = []
            for run in (("first",) if q in once
                        else ("first", "second", "third")):
                c0, d0 = dispatch.compiles(), dispatch.total()
                t0 = time.time()
                res = sess.execute(TPCH_SQL[q])
                runs.append({
                    "run": run, "seconds": round(time.time() - t0, 3),
                    "compiles": dispatch.compiles() - c0,
                    "dispatches": dispatch.total() - d0})
                emit(phase="sql", query=q, **runs[-1])
            pandas_s = bench._pandas_baseline(q, sess.catalog, res)
            if q not in once:
                check(runs[1]["compiles"] <= _ADAPT_BUDGET, (q, runs))
                check(runs[2]["compiles"] == 0, (
                    f"{q}: a repeated statement compiled again", runs))
            root = plan_builder.build(
                sql(sess.catalog, TPCH_SQL[q]).optimized_plan(),
                sess.catalog)
            ops = _operators(root)
            emit(phase="sql", query=q, oracle="pandas", equal=True,
                 pandas_seconds=round(pandas_s, 3), operators=ops,
                 segment_strategy=("scan" if segscan.use_scans()
                                   else "scatter"))
            results[q] = res
            out["queries"][q] = {"runs": runs, "operators": ops}

        # q1 once more, through the wire
        q = queries[0]
        client = _SimpleQueryClient(node.pg.addr)
        try:
            c0, t0 = dispatch.compiles(), time.time()
            names, rows = client.query(TPCH_SQL[q])
            wire_s, wire_c = time.time() - t0, dispatch.compiles() - c0
        finally:
            client.close()
        want = results[q]
        check(names == list(want), (names, list(want)))
        check(len(rows) == len(want[names[0]]))
        for j, name in enumerate(names):
            col = np.asarray(want[name])
            got = [r[j] for r in rows]
            if col.dtype.kind in "fiu":
                np.testing.assert_allclose(
                    np.array(got, dtype=np.float64),
                    col.astype(np.float64), rtol=1e-12, err_msg=name)
            else:
                check(got == [str(v) for v in col], name)
        emit(phase="sql", query=q, via="pgwire", rows=len(rows),
             equal_to_session=True, seconds=round(wire_s, 3),
             compiles=wire_c)
        out["pgwire"] = {"rows": len(rows), "compiles": wire_c}
        sess.close()
    finally:
        node.stop()
    return out


# ---------------------------------------------------------------------------
# kv


def phase_kv(n_keys: int = 1 << 20, ops: int = 512, concurrency: int = 128,
             n_rows: int = 1000) -> dict:
    """(a) YCSB-E over a WAL-backed 16-byte-key engine (the defaults are
    the sizes bench.py uses); (b) acknowledged SQL writes read back through
    a Session."""
    from cockroach_tpu.bench.ycsb import run_ycsb_e
    from cockroach_tpu.kv import DB, Clock
    from cockroach_tpu.sql import Session
    from cockroach_tpu.storage import mvcc
    from cockroach_tpu.storage.lsm import Engine

    before = dict(mvcc.KERNEL_CALLS)
    t0 = time.time()
    y = run_ycsb_e(n_keys=n_keys, ops=ops, scan_len=64,
                   concurrency=concurrency)
    calls = {k: mvcc.KERNEL_CALLS[k] - before.get(k, 0)
             for k in ("scan_filter.pallas", "scan_filter.jnp",
                       "merge.pallas", "merge.jnp")}
    served = {
        stage: "+".join(impl for impl in ("pallas", "jnp")
                        if calls[f"{stage}.{impl}"]) or "none"
        for stage in ("scan_filter", "merge")}
    emit(phase="kv", step="ycsb_e", seconds=round(time.time() - t0, 2),
         n_keys=y["n_keys"], bit_identical=y["bit_identical"],
         compactions=y["compactions"], runs=y["runs"], ops=y["ops"],
         rows_scanned=y["rows_scanned"], point_ops=y["point_ops"],
         kernel_calls=calls, **served)
    check(y["bit_identical"], "bulk ingest and the put path disagree")
    check(y["rows_scanned"] > 0 and y["point_ops"] > 0)

    # (b) acknowledged writes are read back
    tmp = tempfile.mkdtemp(prefix="chip_smoke_wal_")
    sess = Session(db=DB(Engine(key_width=24, val_width=128,
                                memtable_size=4096,
                                wal_path=f"{tmp}/kv.wal"), Clock()))
    try:
        t0 = time.time()
        sess.execute("create table smoke (k int primary key, v int, "
                     "s string)")
        want: dict[int, tuple[int, str]] = {}

        def row(k: int) -> tuple[int, str]:
            return (k * 7919) % 100003, f"r{k:05d}"

        single = n_rows // 2
        for k in range(single):  # autocommit, one row a statement
            v, s = row(k)
            res = sess.execute(f"insert into smoke values ({k}, {v}, '{s}')")
            check(res["rows_affected"] == 1, res)
            want[k] = (v, s)
        k = single
        while k < n_rows:  # explicit transactions, ten rows a statement
            hi = min(k + 10, n_rows)
            sess.execute("begin")
            vals = ", ".join("({}, {}, '{}')".format(i, *row(i))
                             for i in range(k, hi))
            res = sess.execute(f"insert into smoke values {vals}")
            check(res["rows_affected"] == hi - k, res)
            sess.execute("commit")
            want.update({i: row(i) for i in range(k, hi)})
            k = hi
        write_s = time.time() - t0

        t0 = time.time()
        lost = []
        for k, (v, s) in want.items():
            got = sess.execute(f"select v, s from smoke where k = {k}")
            if (len(got["v"]) != 1 or int(got["v"][0]) != v
                    or str(got["s"][0]) != s):
                lost.append(k)
        read_s = time.time() - t0
        lo, hi = n_rows // 4, 3 * n_rows // 4
        agg = sess.execute(f"select count(*) as n, sum(v) as t from smoke "
                           f"where k >= {lo} and k < {hi}")
        want_n = sum(1 for k in want if lo <= k < hi)
        want_t = sum(v for k, (v, _) in want.items() if lo <= k < hi)
        emit(phase="kv", step="sql_writes", rows=len(want),
             single_row_txns=single, multi_row_txns=(n_rows - single + 9) // 10,
             lost=len(lost), write_seconds=round(write_s, 2),
             read_seconds=round(read_s, 2),
             range_count=int(agg["n"][0]), range_sum=int(agg["t"][0]))
        check(not lost, f"acknowledged writes not read back: {lost[:10]}")
        check((int(agg["n"][0]), int(agg["t"][0])) == (want_n, want_t))
    finally:
        sess.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return {"ycsb": y, "kernel_calls": calls, **served, "rows": len(want)}


# ---------------------------------------------------------------------------
# shuffle (four chips)


def phase_shuffle(sf: float = 1.0, seed: int = 19920101, n_devices: int = 4
                  ) -> dict:
    """q3 through Rel.run_distributed on a mesh over `n_devices` devices,
    against the same plan on one device and the pandas oracle."""
    import jax

    import bench
    from cockroach_tpu.bench import queries as Q
    from cockroach_tpu.bench import tpch
    from cockroach_tpu.parallel import mesh as mesh_mod
    from cockroach_tpu.parallel.planner import DistributedQuery

    devices = jax.devices()[:n_devices]
    check(len(devices) == n_devices, (
        f"need {n_devices} devices, jax reports {len(jax.devices())}"))
    mesh = mesh_mod.make_mesh(n_devices)
    t0 = time.time()
    cat = tpch.gen_tpch(sf=sf, seed=seed)
    rel = Q.QUERIES["q3"](cat)
    nrows = cat.get("lineitem").num_rows
    emit(phase="shuffle", step="load", sf=sf, lineitem_rows=nrows,
         seconds=round(time.time() - t0, 2))

    # the program run_distributed builds, held here for inspection
    dq = DistributedQuery(rel.plan, cat, mesh)
    check(not dq._local_fallback, "q3 fell back to local execution")
    shard_rows = {}
    for (tname, _names, _cap), batch in dq._scan_cache.items():
        shard_rows[tname] = {
            sh.device.id: int(np.asarray(sh.data).sum())
            for sh in batch.mask.addressable_shards}
    li = shard_rows["lineitem"]
    check(len(li) == n_devices, f"lineitem shards sit on {sorted(li)}")
    check(sum(li.values()) == nrows, (li, nrows))
    # row-sharded in order, the per-device capacity padded to 1024 rows:
    # every device holds 1/n of the rows, the last one short by the padding
    check(max(li.values()) - min(li.values()) < n_devices * 1024, li)
    t0 = time.time()
    compiled = dq._fn._jitted.lower(*dq._scan_batches).compile()
    hlo = compiled.as_text()
    check("all-to-all" in hlo, "no all-to-all in the distributed program")
    emit(phase="shuffle", step="program", local_fallback=False,
         lineitem_rows_per_device=li, all_to_all=hlo.count("all-to-all"),
         compile_seconds=round(time.time() - t0, 2),
         memory=str(compiled.memory_analysis()))

    t0 = time.time()
    got = rel.run_distributed(mesh)
    dist_s = time.time() - t0
    bench._pandas_baseline("q3", cat, got)
    emit(phase="shuffle", step="distributed", rows=len(got["revenue"]),
         equals_pandas=True, seconds=round(dist_s, 2))
    # the same plan on one device, last: on an empty cache it compiles
    # for longer than everything above
    t0 = time.time()
    want = rel.run()
    for k in want:
        a, w = np.asarray(got[k]), np.asarray(want[k])
        check(len(a) == len(w), (k, len(a), len(w)))
        if w.dtype.kind == "f":
            np.testing.assert_allclose(a, w, rtol=1e-9, err_msg=k)
        else:
            check((a == w).all(), k)
    emit(phase="shuffle", step="single_device",
         distributed_equals_single=True,
         seconds=round(time.time() - t0, 2))
    return {"lineitem_rows_per_device": li,
            "all_to_all": hlo.count("all-to-all")}


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the distributed shuffle phase only")
    ap.add_argument("--seed", type=int, default=19920101)
    ap.add_argument("--full", action="store_true",
                    help="q3 three times and YCSB-E at 1M keys (bench.py's "
                         "sizes, about 40 minutes on an empty compile "
                         "cache); with --chips 4, the shuffle at SF1")
    args = ap.parse_args(argv)

    import cockroach_tpu  # noqa: F401  # crlint: allow-unused-import(side-effect import: package init enables jax x64, and a bare copy of this script must fail here)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke.py: jax found no TPU (platform "
              f"{dev['platform']!r}); this script has no CPU mode",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but jax reports "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 2

    if args.chips == 4:
        phases = [("shuffle", lambda: phase_shuffle(
            sf=1.0 if args.full else 0.05, seed=args.seed))]
    elif args.full:
        phases = [("sql", lambda: phase_sql(seed=args.seed)),
                  ("kv", phase_kv)]
    else:
        phases = [("sql", lambda: phase_sql(seed=args.seed, once=("q3",))),
                  ("kv", lambda: phase_kv(n_keys=4096, ops=64,
                                          concurrency=8))]
    for name, fn in [("device", phase_device)] + phases:
        t0 = time.time()
        try:
            fn()
        except BaseException as e:
            emit(phase=name, ok=False, seconds=round(time.time() - t0, 2),
                 error=f"{type(e).__name__}: {str(e)[:2000]}")
            raise
        emit(phase=name, ok=True, seconds=round(time.time() - t0, 2))
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
