"""The benchmark's entry point: one cell, one run.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip and is the server: an in-process Node with a
pgwire listener (as chip_smoke.phase_sql builds it). The load generator is a
child (client.py) that never imports jax or cockroach_tpu. Everything that
belongs to one configuration, mix or per-layer metric is a file found by the
name BENCHMARK.json gives: configs/<config>.json with its loaders/<loader>.py,
traffic/<mix>.json with its oracles/<oracle>.py, metrics/<metric>.json with
its readers/<reader>.py. A new cell is new files and one entry, no edit here.

Phases: load (data from --seed), warm-up (every statement shape until a
pass compiles nothing), child connects and warms its connections, window
(closed loop for --seconds; statements in flight at the deadline are finished
and counted), check (the mix's oracle, after the window, outside set-up and
window), one JSON line. `setup_s` is process start to the window's opening.
No accelerator, or fewer chips than the cell asks for: exit 2, no result.
"""

from __future__ import annotations

import time

_T0 = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import select  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(manifest_path: str, workload: str):
    """BENCHMARK.json -> (manifest, cell, configuration, mix)."""
    manifest = _json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"{manifest_path}: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    import traffic

    return manifest, cell, config, traffic.load_mix(cell["traffic"])


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        try:
            ms = d.memory_stats() or {}
        except Exception:  # a backend without allocator statistics
            ms = {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


class _Compiles:
    """Compilations seen by the program's counter and by JAX itself."""

    def __init__(self):
        import jax.monitoring

        self.backend, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_kw):
        from readers.xla_compiles import COMPILE_EVENT

        if event == COMPILE_EVENT:
            self.backend += 1
            self.seconds += seconds

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)

    def program(self) -> int:
        from cockroach_tpu.flow import dispatch

        return dispatch.compiles()


TRACE_DELAY_S = 1.0  # into the window, past its first statements
WARMUP_PASSES = 4  # a ceiling: q3, the slowest to settle, compiles nothing in its second


def warm_up(mix: dict, seed: int, addr, log) -> None:
    """Every statement shape of the mix, ranged parameters at both ends,
    pass after pass until one compiles nothing (join emission caps are
    learned from a first run and re-specialize a second). "Nothing" is by
    the program's counter, which sees every program of a statement; JAX's
    own count is logged beside it but never settles, because the node's
    background loops compile small eager operations all the time (PERF.md,
    PR 24)."""
    import traffic
    from pgclient import PgClient

    compiles = _Compiles()
    statements = traffic.Stream(mix, seed + 2, 0).warmup()
    conn = PgClient(addr)
    try:
        for n in range(WARMUP_PASSES):
            c0, b0, s0, t0 = (compiles.program(), compiles.backend,
                              compiles.seconds, time.time())
            for _j, _p, sql in statements:
                _names, _rows, err = conn.query(sql)
                if err:
                    raise RuntimeError(f"warm-up statement failed: {err}")
            log(step="warmup", warmup_pass=n,
                compiles=compiles.program() - c0,
                backend_compiles=compiles.backend - b0,
                backend_compile_s=round(compiles.seconds - s0, 3),
                seconds=round(time.time() - t0, 3))
            if compiles.program() == c0:
                break
    finally:
        conn.close()
        compiles.close()


def _line_from(child, timeout: float) -> str:
    """The child's next line, or a failure once `timeout` seconds pass
    (it writes one whole line at a time, so the pipe's readiness is the
    line's)."""
    ready, _, _ = select.select([child.stdout], [], [], timeout)
    if not ready:
        raise RuntimeError(f"load generator silent for {timeout:.0f} s")
    return child.stdout.readline().strip()


class _Tracer(threading.Thread):
    """Traces a sub-window of the run with the JAX profiler."""

    def __init__(self, out_dir: str, delay: float, seconds: float):
        super().__init__(daemon=True)
        self.out_dir, self.delay, self.seconds = out_dir, delay, seconds
        self.stop_early = threading.Event()
        self.error: str | None = None

    def run(self) -> None:
        import jax.profiler

        if self.stop_early.wait(self.delay):
            return
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                self.stop_early.wait(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported: a traced run without a trace
            self.error = f"{type(e).__name__}: {e}"


def run_cell(args, log=None) -> tuple[int, dict | None]:
    """Everything after argument parsing. Returns (exit code, result)."""
    def emit(**kw):
        print(json.dumps(kw), flush=True)

    log = log or emit
    manifest, cell, config, mix = resolve(args.manifest, args.workload)

    import cockroach_tpu  # noqa: F401  (package init: x64; a bare copy fails here)
    import jax  # noqa: F401

    from cockroach_tpu.utils import backend, settings

    dev = device_info()
    if dev["platform"] != config["platform"]:
        print(f"run.py: configuration {config['name']!r} runs on "
              f"{config['platform']!r}, jax found {dev['platform']!r}",
              file=sys.stderr)
        return 2, None
    if dev["count"] < int(cell["chips"]):
        print(f"run.py: cell asks for {cell['chips']} chip(s), jax reports "
              f"{dev['count']}", file=sys.stderr)
        return 2, None
    timed = dev["platform"] != "cpu"  # a CPU rehearsal prints counts only
    peaks = _json(os.path.join(HERE, "peaks.json")).get(dev["kind"])
    if timed and peaks is None:
        print(f"run.py: no peaks for device {dev['kind']!r} in peaks.json",
              file=sys.stderr)
        return 2, None
    cache_dir = backend.enable_compile_cache()
    if args.trace:
        settings.set("sql.trace.xla_profile", True)
    log(step="start", cell=cell["name"], seed=args.seed, device=dev,
        compile_cache=cache_dir)

    workdir = tempfile.mkdtemp(prefix="bench_")
    loaded = child = tracer = None
    try:
        t0 = time.time()
        loader = importlib.import_module(f"loaders.{config['loader']}")
        loaded = loader.load(config, args.seed, workdir)
        log(step="load", seconds=round(time.time() - t0, 3), **loaded.info)
        warm_up(mix, args.seed, loaded.addr, log)

        import client

        plan = {"mix": cell["traffic"], "seed": args.seed,
                "addr": list(loaded.addr)}
        plan_path = os.path.join(workdir, "plan.json")
        out_path = os.path.join(workdir, "out.jsonl")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), plan_path,
             out_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)
        line = _line_from(child, client.READY_TIMEOUT_S + 60)
        if line != "READY":
            raise RuntimeError(f"load generator did not come up: {line!r}")

        readers = []
        for m in manifest["per_layer"]:
            if not _applies(m, cell["name"]):
                continue
            spec = _json(os.path.join(HERE, "metrics", m["name"] + ".json"))
            mod = importlib.import_module(f"readers.{spec['reader']}")
            readers.append((m, spec, mod))
        ctx = types.SimpleNamespace(
            config=config, mix=mix, loaded=loaded, peaks=peaks, seed=args.seed,
            control=bool(args.control), trace=None, window_wall0=time.time())
        states = [mod.begin(ctx, **spec["args"]) if hasattr(mod, "begin")
                  else None for _m, spec, mod in readers]
        if args.trace:
            tracer = _Tracer(os.path.join(workdir, "trace"),
                             TRACE_DELAY_S,
                             float(mix["trace_seconds"]))
            tracer.start()
        setup_s = time.time() - _T0
        child.stdin.write(f"GO {args.seconds}\n")
        child.stdin.flush()
        line = _line_from(child, args.seconds + client.DRAIN_TIMEOUT_S + 60)
        child.wait(timeout=60)
        if tracer is not None:
            tracer.stop_early.set()
            tracer.join(timeout=300)
        if line != "DONE":
            raise RuntimeError(f"load generator failed: {line!r}")

        records, tail = [], {}
        with open(out_path) as f:
            for ln in f:
                rec = json.loads(ln)
                if "errors" in rec:
                    tail = rec
                else:
                    records.append(rec)
        done = [r for r in records if r["err"] is None]
        # a client still stuck in a statement did less work than the window
        # asked for: it counts as a failure, as the child's exit code does
        stuck = int(tail.get("stuck_clients", 0))
        if child.returncode != 0 and not stuck:
            raise RuntimeError(f"load generator exited {child.returncode}")
        failed = (len(records) - len(done) + len(tail.get("errors", []))
                  + stuck)
        window_s = max((r["d"] for r in done), default=0.0)
        ctx.records, ctx.statements, ctx.window_s = (
            records, len(done), window_s)
        ctx.rate = len(done) / window_s if window_s else 0.0
        ctx.memory_peak_bytes = memory_peak_bytes()
        if tracer is not None and tracer.error is None:
            import reduce_trace

            ctx.trace = reduce_trace.reduce(tracer.out_dir)
            if args.keep_trace:
                shutil.copytree(tracer.out_dir, args.keep_trace,
                                dirs_exist_ok=True)
        layer = {}
        for (m, spec, mod), st in zip(readers, states):
            v = mod.read(ctx, st, **spec["args"])
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}

        # the check: after the window, outside set-up and window
        from pgclient import PgClient

        ctx.connect = lambda: PgClient(loaded.addr)
        t0 = time.time()
        oracle = importlib.import_module(f"oracles.{mix['oracle']}")
        compared = list(loaded.pinned) + oracle.check(ctx)
        if not done:
            compared.append({"name": "statements_answered", "value": 0.0,
                             "limit": 1.0, "op": ">="})
        correct = True
        for c in compared:
            ok = (c["value"] >= c["limit"] if c.get("op") == ">="
                  else c["value"] <= c["limit"])
            c["ok"] = bool(ok)
            if c.get("control"):
                c["ok"] = not ok  # a control has to come out as not correct
                c["control_failed_as_it_must"] = c["ok"]
            else:
                correct = correct and ok
            log(step="compare", **c)
        log(step="check", seconds=round(time.time() - t0, 3),
            errors=tail.get("errors", [])[:3],
            first_failure=next((r["err"] for r in records if r["err"]),
                               None))

        lat = sorted((r["d"] - r["s"]) * 1e3 if r["err"] is None
                     else args.seconds * 1e3 for r in records)
        e2e = {"stmts_per_s": ctx.rate, "setup_s": setup_s}
        if lat:
            e2e["latency_p95_ms"] = lat[min(len(lat) - 1,
                                            int(0.95 * len(lat)))]
        metrics = {}
        if args.trace:
            metrics = layer
        else:
            for m in manifest["end_to_end"]:
                if _applies(m, cell["name"]) and m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        if not timed:  # a CPU rehearsal: counts and `correct`, no timing
            metrics = {k: v for k, v in layer.items()
                       if v["unit"] == "count"}
        device = dict(dev, memory_peak_bytes=ctx.memory_peak_bytes)
        result = {"correct": bool(correct), "attempted": len(records),
                  "failed": failed, "metrics": metrics, "device": device,
                  "client": {
                      "statements": len(done),
                      "latency_p50_ms": lat[len(lat) // 2] if lat else None,
                      "max_start_lag_ms": 1e3 * max(
                          (r["s"] for r in records if r["gap"] == r["s"]),
                          default=0.0),
                      "mean_think_ms": 1e3 * sum(
                          r["gap"] for r in records) / max(1, len(records))}}
        if not timed:
            result["client"] = {"statements": len(done)}
        if ctx.trace:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            result["breakdown"] = {
                "device_ops": ctx.trace["device_ops"],
                "idle_gaps": ctx.trace["idle_gaps"]}
            log(step="trace", **{k: v for k, v in ctx.trace.items()
                                 if k not in ("device_ops", "idle_gaps")})
        elif args.trace:
            raise RuntimeError(f"traced run without a trace: "
                               f"{tracer.error if tracer else 'no tracer'}")
        return 0, result
    finally:
        if tracer is not None:
            tracer.stop_early.set()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait(timeout=30)
        if loaded is not None:
            loaded.close()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="tests point this at a manifest of tiny cells")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control's numbers (the builder's "
                         "calls; the driver's runs never do)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)
    rc, result = run_cell(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.stdout.flush()
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the node's background threads are stopped; nothing else may hold the
    # exit (a profiler or pgwire thread that outlives its owner)
    os._exit(rc)
