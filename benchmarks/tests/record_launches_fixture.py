"""Records the small v5e trace that test_reduce_launches.py reads, with the
program's operator sections naming each dispatch's operator (PR 37):

    chiprun -- python benchmarks/tests/record_launches_fixture.py chiprun_out/fixture_launches

Three statements shaped like a served one (sql.execute > query >
cockroach_tpu.query > flow/pull with its operator record). In each, two
operators share ONE kernel name: `join.1` dispatches `fixture_step` once at
65,536 rows, `join.2` twice at 16,384 rows; `join.1` also drives
`fixture_loop`, whose `while` holds its body's operations; one eager
`jnp.add` runs outside every section and has no `flow.dispatch`. Prints
what reduce_launches.py makes of the trace."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class _Op:
    """What a section needs of an operator."""

    def __init__(self, label, what):
        self.label, self.what, self.KERNEL = label, what, "join"


def record(out_dir: str) -> None:
    import cockroach_tpu  # noqa: F401  (package init: x64)
    import jax
    import jax.numpy as jnp
    import jax.profiler
    import numpy as np

    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.utils import settings, tracing

    step = dispatch.jit(lambda x: jnp.sort(x * 2 + 1).cumsum(),
                        name="fixture_step")
    loop = dispatch.jit(
        lambda x: jax.lax.fori_loop(0, 8, lambda i, a: jnp.sort(a + i), x),
        name="fixture_loop")
    big = jnp.arange(1 << 16, dtype=jnp.int32)
    small = jnp.arange(1 << 14, dtype=jnp.int32)
    for warm in (step(big), step(small), loop(small), small + 1):
        warm.block_until_ready()
    first, second = _Op("join.1", "inner probe=a build=b"), _Op(
        "join.2", "inner probe=join.1 build=c")

    settings.set("sql.trace.xla_profile", True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        for _ in range(3):
            with tracing.span("sql.execute"):
                with tracing.leaf_span("query"), \
                        tracing.annotation("cockroach_tpu.query"), \
                        tracing.leaf_span("flow/pull") as psp, \
                        dispatch.operator_record(psp):
                    time.sleep(0.002)  # the host walks operators
                    with dispatch.section(second):
                        with dispatch.section(first):
                            a = step(big)
                            b = loop(small)
                        c = step(small)
                        d = step(c)
                    e = d + 1  # eager, outside every section
                    with tracing.annotation("flow.readback"):
                        np.asarray(a), np.asarray(b), np.asarray(e)
            time.sleep(0.004)  # the client thinks
    finally:
        jax.profiler.stop_trace()
        settings.reset("sql.trace.xla_profile")
    print(json.dumps(psp.to_dict()["records"]))


def main(out_dir: str) -> int:
    import jax

    import reduce_launches
    import reduce_trace

    if jax.devices()[0].platform != "tpu":
        print("record_launches_fixture.py: no TPU", file=sys.stderr)
        return 2
    record(out_dir)
    print(json.dumps(reduce_trace.reduce(out_dir)))
    got = reduce_launches.reduce(out_dir)
    print(reduce_launches.table(got))
    print(json.dumps(got))
    path = reduce_trace.find_xplane(out_dir)
    print("xplane bytes", os.path.getsize(path), path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
