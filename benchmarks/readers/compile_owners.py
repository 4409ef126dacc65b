"""Backend compiles in the window that cockroach_tpu's tracer puts down to
owners whose name starts with `prefix`: the timed section open on the
compiling thread (`node.heartbeat`, ...), `statement`, or `other`
(`tracing.compiles_by_owner()`; over all owners it counts what
kernels.xla_compiles_in_window counts). A program without it gives no
reading."""


def _count(prefix):
    from cockroach_tpu.utils import tracing

    by_owner = getattr(tracing, "compiles_by_owner", None)
    if by_owner is None:
        return None
    return float(sum(n for owner, n in by_owner().items()
                     if owner.startswith(prefix)))


def begin(ctx, prefix):
    return _count(prefix)


def read(ctx, state, prefix):
    now = _count(prefix)
    return None if state is None or now is None else now - state
