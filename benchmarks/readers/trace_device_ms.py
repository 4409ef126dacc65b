"""Device time a statement, in ms, from the traced sub-window
(reduce_trace.py): the union of device-operation intervals over the
statements traced (`cockroach_tpu.query` annotations, which
sql.trace.xla_profile writes in a traced run); where the trace holds no
annotation, the busy share over the whole window's statement rate."""


def read(ctx, state):
    tr = ctx.trace
    if not tr or not tr["window_s"]:
        return None
    if tr["query_annotations"]:
        return 1e3 * tr["busy_s"] / tr["query_annotations"]
    if not ctx.rate:
        return None
    return 1e3 * (tr["busy_s"] / tr["window_s"]) / ctx.rate
