"""Chip smoke: what no benchmark cell covers yet, on an attached TPU.

    python chip_smoke.py             # one chip: device, kv
    python chip_smoke.py --full      # the same with YCSB-E at 1M keys
    python chip_smoke.py --chips 4   # four chips: shuffle only (SF0.05)

The storage plane (YCSB-E and acknowledged SQL writes read back) and the
four-chip shuffle, each driven once through the entry points a user calls,
checked against an independent reference; the script exits non-zero on the
first phase that fails. The served SQL path is the benchmark's: `python3
benchmarks/run.py --workload tpch_sf1.q1 --seed 1 --seconds 5` is the quick
proof that it starts on the chip. Data is generated from --seed inside the
run. One process holds the chip: everything runs here, and no child that
needs JAX is started.

Sizes. A run must finish inside 1200 s on an empty compile cache, and on
this engine a cold run is almost all compile (PERF.md, PR 22: one MVCC
`lax.sort` instantiation takes minutes above 4,096 rows). So the default
run keeps the 1,000 acknowledged SQL writes and cuts YCSB-E's keyspace
(4,096 keys, where its sorts compile in seconds; its batched scans still go
through the Pallas scan filter on the chip, but nothing that small is
merged, so the merge gate is reached only by `--full`, at 1M keys: 1,351 s
on an empty cache when PR 22 ran it, 1,278 s of it compile). The
four-chip shuffle sends the served q3 text through a Session of a node
spanning the four chips, at SF0.05 by default because a four-chip call is
charged fourfold; `--full --chips 4` asks for SF1, which the benchmark's
cell `tpch_sf1_x4.q3_shuffle` runs in every check (since PR 48 the SF1
program compiles in minutes: 288 s + 72 s for a described v5e 2x2 on 8
cores, where the 13-sort, 140-scatter program before it was unfinished
after 263 CPU-minutes).

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
# kv


def phase_kv(n_keys: int = 1 << 20, ops: int = 512, concurrency: int = 128,
             n_rows: int = 1000) -> dict:
    """(a) YCSB-E over a WAL-backed 16-byte-key engine; (b) acknowledged
    SQL writes read back through a Session."""
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


def _pandas_q3(cat):
    """The repo's one pandas Q3 (benchmarks/oracles/tpch_q3.py, which is no
    package: loaded by path) over the generated catalog."""
    import importlib.util
    import pathlib
    import types

    from cockroach_tpu.bench import tpch

    spec = importlib.util.spec_from_file_location(
        "tpch_q3_oracle", pathlib.Path(__file__).resolve().parent
        / "benchmarks" / "oracles" / "tpch_q3.py")
    oracle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(oracle)
    loaded = types.SimpleNamespace(
        frame=lambda table, columns: tpch.to_pandas(cat, table)[columns])
    return oracle.answer(loaded, {"date": "1995-03-15"})


def phase_shuffle(sf: float = 1.0, seed: int = 19920101, n_devices: int = 4
                  ) -> dict:
    """The served q3 text through a Session of a node that spans
    `n_devices` devices (Session -> plan cache -> sql/distsql.py -> one SPMD
    program), against the same Session on one device and the pandas
    oracle."""
    import jax

    from cockroach_tpu.bench import tpch
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.catalog import Catalog
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.server.node import Node
    from cockroach_tpu.sql import Session, explain
    from cockroach_tpu.utils import metric, tracing

    check(len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, jax reports {len(jax.devices())}"))
    t0 = time.time()
    cat = tpch.gen_tpch(sf=sf, seed=seed)
    text = " ".join(TPCH_SQL["q3"].split())
    nrows = cat.get("lineitem").num_rows
    emit(phase="shuffle", step="load", sf=sf, lineitem_rows=nrows,
         seconds=round(time.time() - t0, 2))

    node = Node(devices=n_devices).start(pg_port=0)
    try:
        served, one = node._sql_catalog, Catalog()
        for name, table in cat.tables.items():
            served.tables[name] = one.tables[name] = table
        plan = explain(served, "EXPLAIN (DISTSQL) " + text)
        check("exchange (all-to-all)" in plan, plan)
        sess = Session(served)
        runs0 = metric.PLAN_CACHE_MESH_RUNS.value
        # the first run compiles the program at its first-guess caps and
        # learns them; the second compiles the fitted program and is what a
        # statement runs from then on
        got, seconds, compiles = None, [], []
        for _ in range(3):
            t0, c0 = time.time(), dispatch.compiles()
            got = sess.execute(text)
            seconds.append(round(time.time() - t0, 2))
            compiles.append(dispatch.compiles() - c0)
        check(metric.PLAN_CACHE_MESH_RUNS.value - runs0 >= 3,
              "q3 fell back to local execution")
        check(compiles[-1] == 0, compiles)
        li = cat.get("lineitem").mesh_shard_rows()
        check(li is not None and len(li) == n_devices,
              f"lineitem shards sit on {li}")
        check(sum(li.values()) == nrows, (li, nrows))
        # row-sharded in order, the per-device capacity padded to 1024
        # rows: every device holds 1/n of the rows, the last one short
        check(max(li.values()) - min(li.values()) < n_devices * 1024, li)
        pull = tracing.totals().get("flow/pull", {"tags": {}})["tags"]
        stages = int(pull.get("exchange_stages", 0)) // 3
        check(stages >= 1, pull)
        emit(phase="shuffle", step="program", local_fallback=False,
             lineitem_rows_per_device=li, all_to_all=stages,
             run_seconds=seconds, compiles=compiles,
             compile_seconds=tracing.compile_seconds())
        np.testing.assert_allclose(
            np.asarray(got["revenue"], dtype=np.float64),
            _pandas_q3(cat).revenue.to_numpy(), rtol=1e-9)
        emit(phase="shuffle", step="distributed", rows=len(got["revenue"]),
             equals_pandas=True, seconds=seconds[-1])
        # the same text on one device, last: on an empty cache it compiles
        # for longer than everything above
        t0 = time.time()
        want = Session(one).execute(text)
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
    finally:
        node.stop()
    return {"lineitem_rows_per_device": li, "all_to_all": stages}


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the distributed shuffle phase only")
    ap.add_argument("--seed", type=int, default=19920101)
    ap.add_argument("--full", action="store_true",
                    help="YCSB-E at 1M keys (over 20 minutes of sort compiles "
                         "on an empty compile cache); with --chips 4, the "
                         "shuffle at SF1")
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
        phases = [("kv", phase_kv)]
    else:
        phases = [("kv", lambda: phase_kv(n_keys=4096, ops=64,
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
