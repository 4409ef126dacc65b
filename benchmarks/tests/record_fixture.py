"""Records the small v5e trace that test_reduce_trace.py reads:

    chiprun -- python benchmarks/tests/record_fixture.py chiprun_out/fixture

Three jitted programs, each under a `cockroach_tpu.query` annotation, with
host sleeps between them, so the reduction has busy time, gaps inside and
gaps between queries to find. Prints what the trace holds."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import jax.profiler

    import reduce_trace

    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def fixture_step(x):
        return jnp.sort(x * 2 + 1).cumsum()

    x = jnp.arange(1 << 16, dtype=jnp.int32)
    fixture_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("cockroach_tpu.query"):
            time.sleep(0.002)
            fixture_step(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    ev = reduce_trace.load_events(out_dir)
    print(json.dumps(reduce_trace.describe(ev), indent=1))
    print(json.dumps(reduce_trace.reduce_events(ev)))
    path = reduce_trace.find_xplane(out_dir)
    print("xplane bytes", os.path.getsize(path), path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
