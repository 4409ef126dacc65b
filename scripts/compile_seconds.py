"""What each program of a TPC-H statement costs the chip's compiler, asked
without a chip (PR 28: q9's run from an empty compile cache took 948 s,
most of it nine int64 divisions compiled inside the aggregate's step).

Serves the statement through a Session on the CPU (three runs: learn,
re-specialized, settled), records every ``dispatch.jit`` trace with its
arguments' shapes, then lowers each program for a DESCRIBED v5e
(jax.experimental.topologies, as tests/test_tpu_compile.py does) and times
the TPU compiler. Nothing runs on a device; the seconds are this host's
CPU, so compare programs with each other and a tree with its parent, not
with the chip host's clock. One JSON line a program, then totals by run.

    JAX_PLATFORMS=cpu python -m scripts.compile_seconds [--query q9] [--sf 1.0]

Only one process may hold libtpu: run it alone, never under pytest.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--query", default="q9")
    ap.add_argument("--sf", type=float, default=1.0,
                    help="tile shapes follow the data: 1.0 is the benchmark's")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from cockroach_tpu.flow import dispatch

    traces: list = []
    run = {"n": -1}
    plain_jit = jax.jit  # crlint: allow-raw-jit(the jit dispatch.jit itself wraps: still counted there)

    def recording_jit(fn, **kw):
        jitted = plain_jit(fn, **kw)

        def call(*a, **k):
            c0 = dispatch.compiles()
            out = jitted(*a, **k)
            if dispatch.compiles() > c0:  # this call traced: a new program
                traces.append((run["n"], fn.__name__, jitted, a, k))
            return out

        call.lower = jitted.lower
        return call

    # dispatch.jit reaches jax only for these two
    dispatch.jax = types.SimpleNamespace(jit=recording_jit,
                                         named_scope=jax.named_scope)

    from cockroach_tpu.bench import tpch
    from cockroach_tpu.bench.tpch_sql import TPCH_SQL
    from cockroach_tpu.sql import Session

    text = " ".join(TPCH_SQL[args.query].split())
    sess = Session(tpch.gen_tpch(sf=args.sf, seed=2**31 + 5))
    for n in range(3):
        run["n"] = n
        c0, t0 = dispatch.compiles(), time.time()
        sess.execute(text)
        print(json.dumps({"run": n, "programs": dispatch.compiles() - c0,
                          "cpu_s": round(time.time() - t0, 1)}), flush=True)
    sess.close()

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # an entry written for a described chip cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def described(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        return x

    totals: dict = {}
    for n, name, jitted, a, k in traces:
        a, k = jax.tree_util.tree_map(described, (a, k))
        shapes = [x.shape for x in jax.tree_util.tree_leaves((a, k))
                  if hasattr(x, "shape")]
        t0 = time.time()
        try:
            jitted.lower(*a, **k).compile()
        except Exception as e:  # the chip's compiler refuses it: say so
            print(json.dumps({"run": n, "program": name,
                              "refused": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
            continue
        dt = time.time() - t0
        totals[n] = totals.get(n, 0.0) + dt
        print(json.dumps({"run": n, "program": name, "arrays": len(shapes),
                          "rows": max((s[0] for s in shapes if s), default=0),
                          "compile_s": round(dt, 2)}), flush=True)
    print(json.dumps({"compile_s_by_run":
                      {str(n): round(s, 1) for n, s in sorted(totals.items())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
