"""Device time of the traced stretch by the plan operator whose dispatch
launched it (reduce_launches.py), in ms a traced statement: `top` is the
ONE operator label with the most, `unattributed` what ran in launches no
operator's dispatch claims. Finds the profile as idle_by_layer does; one
parse a run; stderr names the operators and prints the whole table. A
program without operator sections, a trace that closes no statement, a
kernel name with more dispatches than launches linked to them, or parts
that miss the trace's `busy_s` by more than 1%: no reading, and stderr says
which."""

import sys

import reduce_launches
from readers.idle_by_layer import _find


def _reduced(ctx):
    if not hasattr(ctx, "launches_by_operator"):  # one parse for both parts
        path = _find(ctx) if ctx.trace else None
        ctx.launches_by_operator = (None if path is None
                                    else _checked(ctx, path))
    return ctx.launches_by_operator


def _checked(ctx, path):
    try:
        r = reduce_launches.reduce(path)
    except reduce_launches.Refused as e:
        print(f"launches_by_operator: {e}: no reading", file=sys.stderr)
        return None
    print("launches_by_operator:\n" + reduce_launches.table(r),
          file=sys.stderr)
    parts, busy = r["attributed_s"] + r["unattributed_s"], ctx.trace["busy_s"]
    if abs(parts - busy) > 0.01 * busy:
        print(f"launches_by_operator: operators {r['attributed_s']} s + "
              f"unattributed {r['unattributed_s']} s miss the trace's "
              f"busy_s {busy}: no reading", file=sys.stderr)
        return None
    return r


def read(ctx, state, part):
    r = _reduced(ctx)
    if not r:
        return None
    if part == "unattributed":
        return 1e3 * r["unattributed_s"] / r["statements"]
    top = next(iter(r["operators"].values()))  # sorted by device time
    return 1e3 * top["device_s"] / r["statements"]
