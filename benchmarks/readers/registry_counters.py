"""Growth of counters of the program's metric registry
(cockroach_tpu/utils/metric.py DEFAULT, by metric name) over the window:
the sum of `num`, a statement (`per: "stmt"`), or over the growth of the
sum of `den` (a ratio; `scale` 100 makes it a share in %, and `den` may
repeat a name of `num`: hits over hits + misses). A program without one of
the named counters (a parent of the PR that brought it) gives no reading,
and so does a window in which `den` did not move."""


def _sum(names):
    from cockroach_tpu.utils import metric

    total = 0.0
    for name in names:
        m = metric.DEFAULT._metrics.get(name)
        if m is None or not hasattr(m, "value"):
            return None
        total += float(m.value)
    return total


def begin(ctx, num, den=None, per=None, scale=1.0):
    return _sum(num), (_sum(den) if den else None)


def read(ctx, state, num, den=None, per=None, scale=1.0):
    n0, d0 = state
    n1 = _sum(num)
    if n0 is None or n1 is None:
        return None
    if den:
        d1 = _sum(den)
        if d0 is None or d1 is None or d1 == d0:
            return None
        return scale * (n1 - n0) / (d1 - d0)
    over = ctx.statements if per == "stmt" else 1
    return scale * (n1 - n0) / over if over else None
