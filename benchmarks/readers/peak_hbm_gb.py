"""Peak device memory, GB, on the fullest chip (run.py reads it from
`device.memory_stats()` for the contract's `device` object too)."""


def read(ctx, state):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
