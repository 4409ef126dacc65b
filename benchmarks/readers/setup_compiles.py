"""Seconds of the set-up that JAX spent in backend compiles, by what the
tracer's `compile_seconds()` holds at the window's opening, which is where
`setup_s` ends: `compile_s` (programs XLA compiled: a cold cache, and every
warm set-up's programs under the persistent cache's one-second floor) or
`cache_load_s` (programs the persistent cache answered). `begin` takes the
process's sums and `read` returns them; stderr lists the five dearest
programs. A program without `compile_seconds()` gives no reading."""

import sys


def _sums(ctx):
    if not hasattr(ctx, "setup_compiles"):
        from cockroach_tpu.utils import tracing

        ctx.setup_compiles = None
        by_program = getattr(tracing, "compile_seconds", None)
        if by_program is not None:
            progs = by_program()
            ctx.setup_compiles = {
                f: sum(p[f] for p in progs.values())
                for f in ("compile_s", "cache_load_s")}
            dear = sorted(progs.items(), key=lambda kv: -(
                kv[1]["compile_s"] + kv[1]["cache_load_s"]))[:5]
            # a compile of a second or more is one the cache will hold
            cold = {n: p["compile_s"] for n, p in progs.items()
                    if p["compiles"] and p["compile_s"] / p["compiles"] >= 1}
            print(f"setup_compiles: {len(progs)} programs, "
                  f"{ctx.setup_compiles}; dearest: {dear}; compiled at a "
                  f"second or more each: {cold}", file=sys.stderr)
    return ctx.setup_compiles


def begin(ctx, field):
    sums = _sums(ctx)
    return None if sums is None else sums[field]


def read(ctx, state, field):
    return state
