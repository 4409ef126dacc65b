"""Device-idle time of the traced stretch by the layer the host was in
(reduce_spans.py), in ms a traced statement. run.py hands readers the
reduced `ctx.trace` and not the trace's directory, so this finds the one
profile written since the window opened under the temporary directory
run.py works in (`bench_*/trace`); none or several: no reading, and stderr
says which."""

import glob
import os
import sys
import tempfile


def _find(ctx):
    found = [p for p in glob.glob(os.path.join(
        tempfile.gettempdir(), "bench_*", "trace", "**", "*.xplane.pb"),
        recursive=True) if os.path.getmtime(p) >= ctx.window_wall0]
    if len(found) != 1:
        print(f"idle_by_layer: {len(found)} profiles written since the "
              f"window opened under {tempfile.gettempdir()}/bench_*/trace, "
              f"want 1: no reading", file=sys.stderr)
        return None
    return found[0]


def _reduced(ctx):
    if not hasattr(ctx, "spans_by_layer"):  # one parse for every part
        import reduce_spans

        ctx.spans_by_layer = None
        path = _find(ctx) if ctx.trace else None
        if path is not None:
            ctx.spans_by_layer = reduce_spans.reduce(path)
            ctx.spans_by_layer = _checked(ctx.spans_by_layer)
    return ctx.spans_by_layer


def _checked(r):
    if r is None:
        print("idle_by_layer: the trace closes no whole statement, or the "
              "program does not mirror its spans: no reading",
              file=sys.stderr)
    elif abs(r["idle_unattributed_s"]) > 1e-3 * r["idle_total_s"]:
        # each part has a cover of its own: they must tile the stretch
        print(f"idle_by_layer: the layers' covers leave "
              f"{r['idle_unattributed_s']} s of {r['idle_total_s']} s of "
              f"idle time over: no reading", file=sys.stderr)
        return None
    return r


def read(ctx, state, part):
    r = _reduced(ctx)
    if not r:
        return None
    return 1e3 * r["idle_s"][part] / r["statements"]
