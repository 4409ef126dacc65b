"""Mean self time of a root span, in ms, from utils/tracing.py's ring of
finished roots (only the last 64 survive: a sample, not the window)."""


def read(ctx, state, root="sql.execute"):
    from cockroach_tpu.utils import tracing

    spans = [s for s in list(tracing.DEFAULT.finished)
             if s.name == root and s.start_wall >= ctx.window_wall0]
    if not spans:
        return None
    self_s = [max(0.0, s.duration - sum(c.duration for c in s.children))
              for s in spans]
    return 1e3 * sum(self_s) / len(self_s)
