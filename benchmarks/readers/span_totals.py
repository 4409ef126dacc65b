"""Seconds (or a numeric tag's sum) that cockroach_tpu's tracer added up
over the window for some span and timed-section names, per statement or
per second of window, in ms. `names` are exact names, or prefixes where they
end in `.`; `tag` reads that tag's sum (already in ms) in place of the
seconds; `field` is which seconds: `total_s` (wall, the default) or
`cpu_s` (a timed section's own CPU seconds, its waits left out). From
`tracing.totals()`, which counts every close since the process started:
the window is the difference of two snapshots. A program without it
(before PR 25), or whose records lack `field`, gives no reading."""


def _sum(names, tag, field):
    from cockroach_tpu.utils import tracing

    totals = getattr(tracing, "totals", None)
    if totals is None:
        return None
    out = 0.0
    for name, rec in totals().items():
        if any(name == n or (n.endswith(".") and name.startswith(n))
               for n in names):
            if tag:
                out += rec["tags"].get(tag, 0.0)
            elif field not in rec:
                return None
            else:
                out += 1e3 * rec[field]
    return out


def begin(ctx, names, per, tag=None, field="total_s"):
    return _sum(names, tag, field)


def read(ctx, state, names, per, tag=None, field="total_s"):
    now = _sum(names, tag, field)
    if state is None or now is None:
        return None
    over = ctx.statements if per == "stmt" else ctx.window_s
    return (now - state) / over if over else None
