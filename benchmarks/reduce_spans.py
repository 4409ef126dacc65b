"""Device-idle time put down to the layer the host was in.

    python benchmarks/reduce_spans.py <dir or file.xplane.pb>

While `sql.trace.xla_profile` is on, cockroach_tpu's tracer mirrors every
span and timed section into the profiler's trace as an annotation of the
same name on the thread that opened it (utils/tracing.py), and writes
`flow.dispatch` around each kernel dispatch and `flow.readback` around each
tile's readback. This reads them beside the device's operations (the
planes reduce_trace.py describes; same clock, nanoseconds):

  the stretch   first `cockroach_tpu.query` annotation's start to the last
                one's end on the serving threads, as reduce_trace.py takes
                it over all; a trace that closes no whole statement gives
                no result, not a guess
  idle          that stretch minus the union of chip 0's operations
  serving       a host thread that carries a node.* section (the node's
  threads       background loops) is a loop's thread and is left out: a
                sql.* or flow/* span a loop opens for its own work is not
                a statement's. Every other thread with annotations of ours
                is a serving thread. (The profiler drops a region the
                trace cuts, so a loop whose every pass outlasts the trace
                is not recognised; in q1 a pass takes under a second)
  serving       a serving thread is in the layer of its innermost
  thread's      annotation of ours: pgwire.* / sql.* (front end), query /
  layer         flow/pull / flow.dispatch / cockroach_tpu.query (flow: the
                host walks operators and enqueues) or flow.readback
                (readback: the wait for the device, the copy, the decode).
                Where no serving thread has any annotation of ours open,
                no statement is open (the wait for the client: front end).
                Names that are not ours (XLA's own, kv.*, ...) are looked
                through. With several serving threads an instant is flow
                if any is in flow, else readback if any is in readback,
                else front end
  parts         each idle interval is SPLIT over those layer intervals (not
                assigned by its middle). Every part is computed from its
                own cover, none as a remainder, so frontend + flow +
                readback equals the idle time only if the covers tile the
                stretch: `idle_unattributed_s` is what they leave over
                (zero but for rounding), and a reader refuses a result
                where it is not

A trace that holds `cockroach_tpu.query` and nothing else of ours comes from
a program that does not mirror its spans (before PR 25): no result.

Python threads are all lines named `python` in the host plane, which
reduce_trace.load_events merges by name; `load_threads` keeps them apart.
"""

from __future__ import annotations

import json
import sys

import reduce_trace
from reduce_trace import QUERY_ANNOTATION, union

HOST_PLANE = "/host:CPU"
LAYERS = ("frontend", "flow", "readback")
LOOPS = "loops"


def layer_of(name: str) -> str | None:
    """The layer an annotation of ours belongs to (`loops` marks a loop's
    thread); None for any other."""
    if name.startswith("node."):
        return LOOPS
    if name == "flow.readback":
        return "readback"
    if (name in ("query", QUERY_ANNOTATION)
            or name.startswith(("flow/", "flow."))):
        return "flow"
    if name.startswith(("pgwire.", "sql.")):
        return "frontend"
    return None


def load_threads(path: str) -> list[list[tuple[str, float, float]]]:
    """The host plane's lines, one list of (name, start_ns, duration_ns) a
    thread, same-named lines kept apart."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(reduce_trace.find_xplane(path))
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.append([(ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events])
    return out


def threads_of(planes: dict) -> list[list[tuple[str, float, float]]]:
    """Host threads of a `load_events` dictionary whose lines are already
    one a thread (events written by hand)."""
    return list(planes.get(HOST_PLANE, {}).values())


def innermost(evs: list[tuple[str, float, float]]
              ) -> list[tuple[str, float, float]]:
    """One thread's (layer, start, end) annotations, properly nested, to
    disjoint (layer, start, end) segments of the innermost one."""
    segs, stack, t = [], [], 0.0
    for layer, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                segs.append((top, t, end))
                t = end
        if stack:
            if s > t:
                segs.append((stack[-1][0], t, s))
            e = min(e, stack[-1][1])  # a child never outlives its parent
        stack.append((layer, e))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            segs.append((top, t, end))
            t = end
    return segs


def _overlap(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Intersection of two sorted disjoint covers."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _minus(a: list[tuple[float, float]],
           b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """`a` without `b`, both sorted disjoint covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def _length(cover: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in cover)


def reduce_events(planes: dict, threads: list | None = None) -> dict | None:
    """`planes` as reduce_trace.load_events gives them; `threads` as
    load_threads does (default: the host plane's lines as they are)."""
    if threads is None:
        threads = threads_of(planes)
    devices = sorted(p for p in planes if reduce_trace._DEVICE.match(p))
    if not devices:
        raise ValueError(
            f"no device plane in the trace (planes: {sorted(planes)})")
    # a loop's thread is not a serving thread, whatever else it opens
    threads = [evs for evs in threads
               if not any(layer_of(name) == LOOPS for name, _s, _d in evs)]
    queries = [(s, s + d) for evs in threads for name, s, d in evs
               if name == QUERY_ANNOTATION]
    if not queries:
        return None  # no whole statement: nothing to divide by
    if not any(layer_of(name) and name != QUERY_ANNOTATION
               for evs in threads for name, _s, _d in evs):
        return None  # the program does not mirror its spans
    lo, hi = min(s for s, _e in queries), max(e for _s, e in queries)
    window = [(lo, hi)]

    lines = planes[devices[0]]
    ops = [e for ln in reduce_trace._OPS_LINES for e in lines.get(ln, [])]
    if not ops:
        ops = [e for evs in lines.values() for e in evs]
    busy = _overlap(union([(s, s + d) for _n, s, d in ops if d > 0]), window)
    idle = _minus(window, busy)

    covers = {layer: [] for layer in LAYERS}
    opened = []  # where a serving thread has anything of ours open
    for evs in threads:
        ours = [(layer, s, s + d) for name, s, d in evs
                for layer in [layer_of(name)] if layer and d > 0]
        opened += [(s, e) for _layer, s, e in ours]
        for layer, s, e in innermost(ours):
            covers[layer].append((s, e))
    flow = union(covers["flow"])
    readback = _minus(union(covers["readback"]), flow)
    frontend = _minus(_minus(union(covers["frontend"]
                                   + _minus(window, union(opened))),
                             flow), readback)
    idle_s = {"frontend": _length(_overlap(idle, frontend)) / 1e9,
              "flow": _length(_overlap(idle, flow)) / 1e9,
              "readback": _length(_overlap(idle, readback)) / 1e9}
    total = _length(idle) / 1e9
    return {
        "statements": len(queries),
        "window_s": (hi - lo) / 1e9,
        "busy_s": _length(busy) / 1e9,
        "idle_total_s": total,
        "idle_s": idle_s,
        "idle_unattributed_s": total - sum(idle_s.values()),
    }


def reduce(path: str) -> dict | None:
    return reduce_events(reduce_trace.load_events(path), load_threads(path))


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
