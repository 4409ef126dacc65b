"""Host ms a statement that plan operators spent in their own driving
code: the self seconds the tracer added up over the window under the names
`flow.op.<KERNEL>` (flow/dispatch.py's operator sections, PR 37; one close
an operator a statement), from `tracing.totals()` as the difference of two
snapshots. Self time is a section's wall minus nested sections and jitted
calls, so an operator's WAIT for the device in a sync of its own (a spool's
live count) is in it: it is host work only in a cell whose statement the
host bounds, and `BENCHMARK.json` lists those. A program with no such name
(no operator sections) gives no reading."""

PREFIX = "flow.op."


def _self_ms():
    from cockroach_tpu.utils import tracing

    totals = getattr(tracing, "totals", None)
    if totals is None:
        return None
    mine = [rec["self_s"] for name, rec in totals().items()
            if name.startswith(PREFIX)]
    return 1e3 * sum(mine) if mine else None


def begin(ctx):
    return _self_ms()


def read(ctx, state):
    now = _self_ms()
    if now is None or not ctx.statements:
        return None
    return (now - (state or 0.0)) / ctx.statements
