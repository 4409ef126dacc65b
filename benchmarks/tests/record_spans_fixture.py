"""Records the small v5e trace that test_reduce_spans.py reads, with the
program's own tracer mirroring into the profiler (PR 25):

    chiprun -- python benchmarks/tests/record_spans_fixture.py chiprun_out/fixture_spans

Three statements shaped like a served one, through cockroach_tpu's tracer
with `sql.trace.xla_profile` on: pgwire.read, sql.execute > query >
cockroach_tpu.query > flow/pull > (flow.dispatch of a kernel named through
dispatch.jit, flow.readback), pgwire.encode; a second thread opens
node.heartbeat sections meanwhile, as the node's liveness loop does. Prints what the trace holds and what reduce_spans.py makes of it."""

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def record(out_dir: str) -> None:
    import cockroach_tpu  # noqa: F401  (package init: x64)
    import jax.numpy as jnp
    import jax.profiler
    import numpy as np

    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.utils import settings, tracing

    step = dispatch.jit(lambda x: jnp.sort(x * 2 + 1).cumsum(),
                        name="fixture_step")
    x = jnp.arange(1 << 16, dtype=jnp.int32)
    step(x).block_until_ready()
    stop = threading.Event()

    def heartbeat():
        while not stop.wait(0.002):
            with tracing.timed("node.heartbeat"):
                time.sleep(0.003)

    settings.set("sql.trace.xla_profile", True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    loop = threading.Thread(target=heartbeat, daemon=True)
    loop.start()
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        for _ in range(3):
            with tracing.timed("pgwire.read"):
                time.sleep(0.0005)
            with tracing.span("sql.execute"):
                time.sleep(0.001)  # parse, bind, plan cache
                with tracing.leaf_span("query"), \
                        tracing.annotation("cockroach_tpu.query"), \
                        tracing.leaf_span("flow/pull"):
                    time.sleep(0.002)  # the host walks operators
                    y = step(x)
                    with tracing.annotation("flow.readback"):
                        np.asarray(y)
            with tracing.timed("pgwire.encode"):
                time.sleep(0.001)
            time.sleep(0.004)  # the client thinks
    finally:
        stop.set()
        loop.join(timeout=10)
        jax.profiler.stop_trace()
        settings.reset("sql.trace.xla_profile")


def main(out_dir: str) -> int:
    import jax

    import reduce_spans
    import reduce_trace

    if jax.devices()[0].platform != "tpu":
        print("record_spans_fixture.py: no TPU", file=sys.stderr)
        return 2
    record(out_dir)
    threads = reduce_spans.load_threads(out_dir)
    print(json.dumps([sorted({n for n, _s, _d in evs
                              if reduce_spans.layer_of(n)})
                      for evs in threads]))
    print(json.dumps(reduce_trace.reduce(out_dir)))
    print(json.dumps(reduce_spans.reduce(out_dir)))
    path = reduce_trace.find_xplane(out_dir)
    print("xplane bytes", os.path.getsize(path), path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
