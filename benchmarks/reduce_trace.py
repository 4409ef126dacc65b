"""From a profiler trace (.xplane.pb) to the numbers the benchmark prints.

    python benchmarks/reduce_trace.py <dir or file.xplane.pb>

Read with `jax.profiler.ProfileData` (nothing but JAX). What a v5e trace
holds (looked at by hand, PR 24): one plane per chip, `/device:TPU:<n>`,
whose line `XLA Ops` has one event per device operation and whose line
`XLA Modules` has one event per launched program, named `jit_<function>(<id>)`;
host threads are lines of the plane `/host:CPU`, where a
`jax.profiler.TraceAnnotation("cockroach_tpu.query")` is an event of that
name. All starts are nanoseconds on one clock.

  window_s    first query annotation's start to the last one's end (the
              whole trace where it holds no annotation)
  busy_s      union of the device-operation intervals inside that window,
              averaged over chips
  device_ops  the programs (`XLA Modules`; else operations) that took most
              device time, [[name, seconds], ...], at most 10
  idle_gaps   the longest intervals in which no operation ran on chip 0,
              each named `inside_query` or `between_queries` by whether its
              middle lies inside a query annotation, [[name, seconds], ...]
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

QUERY_ANNOTATION = "cockroach_tpu.query"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_events(path: str) -> dict:
    """-> {plane name: {line name: [(name, start_ns, duration_ns), ...]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of (start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_events(planes: dict, top: int = 10) -> dict:
    devices = sorted(p for p in planes if _DEVICE.match(p))
    if not devices:
        raise ValueError(
            f"no device plane in the trace (planes: {sorted(planes)})")
    lo, hi = float("inf"), float("-inf")
    queries: list[tuple[float, float]] = []
    for pname, lines in planes.items():
        for evs in lines.values():
            for name, s, d in evs:
                lo, hi = min(lo, s), max(hi, s + d)
                if name == QUERY_ANNOTATION and pname not in devices:
                    queries.append((s, s + d))
    n_queries = len(queries)
    queries = union(queries)
    if queries:
        # the host side of a long trace stops recording before the device
        # side does (seen on the v5e: annotations for 2.3 s of a 6.4 s
        # trace), so everything is read inside the annotated stretch
        lo, hi = queries[0][0], queries[-1][1]

    def clip(evs):
        return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                for n, s, d in evs if s < hi and s + d > lo]

    busy_ns, per_op, covers = 0.0, {}, []
    for dev in devices:
        lines = planes[dev]
        ops = clip([e for ln in _OPS_LINES for e in lines.get(ln, [])])
        if not ops:  # a backend without an op line: take every line
            ops = clip([e for evs in lines.values() for e in evs])
        cover = union([(s, s + d) for _n, s, d in ops if d > 0])
        covers.append(cover)
        busy_ns += sum(e - s for s, e in cover)
        named = (clip([e for ln in _MODULE_LINES
                       for e in lines.get(ln, [])]) or ops)
        for name, _s, d in named:
            name = re.sub(r"\(\d+\)$", "", name)
            per_op[name] = per_op.get(name, 0.0) + d
    gaps = []
    prev = lo
    for s, e in covers[0] + [(hi, hi)]:
        if s - prev > 1e3:  # a microsecond or more
            mid = (prev + s) / 2
            inside = any(a <= mid <= b for a, b in queries)
            gaps.append(("inside_query" if inside else "between_queries",
                         (s - prev) / 1e9))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    n = len(devices)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "chips": n,
        "query_annotations": n_queries,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_sorted[:top]],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
        "idle_inside_query_s": sum(v for k, v in gaps if k == "inside_query"),
        "idle_between_queries_s": sum(v for k, v in gaps
                                      if k == "between_queries"),
    }


def describe(planes: dict) -> dict:
    """What a trace holds, for a look by hand: planes, lines, counts and
    the first few event names of each line."""
    return {p: {ln: {"events": len(evs),
                     "names": sorted({e[0] for e in evs[:200]})[:8]}
                for ln, evs in lines.items()}
            for p, lines in planes.items()}


def reduce(path: str) -> dict:
    return reduce_events(load_events(path))


if __name__ == "__main__":
    ev = load_events(sys.argv[1])
    print(json.dumps(describe(ev), indent=1))
    print(json.dumps(reduce_events(ev), indent=1))
