"""A program counter's growth over the window, whole or per statement.
`counter` is `<module>:<function>`, a function returning a number."""

import importlib


def _value(counter: str) -> float:
    mod, attr = counter.split(":")
    return float(getattr(importlib.import_module(mod), attr)())


def begin(ctx, counter, per_stmt=False):
    return _value(counter)


def read(ctx, state, counter, per_stmt=False):
    delta = _value(counter) - state
    if not per_stmt:
        return delta
    return delta / ctx.statements if ctx.statements else None
