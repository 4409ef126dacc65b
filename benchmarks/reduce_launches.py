"""Device time put down to the plan operator whose dispatch launched it.

    python benchmarks/reduce_launches.py <dir or file.xplane.pb>

While `sql.trace.xla_profile` is on, cockroach_tpu writes a `flow.dispatch`
annotation around every counted kernel call, with the kernel's name and the
label of the plan operator whose section was open (`kernel=`, `op=`;
flow/dispatch.py, PR 37). On the device's side a launch is one event of chip
0's `XLA Modules` line, `jit_<kernel>(<id>)`, and the operations it ran are
the events of `XLA Ops` inside its interval. This pairs the two:

  the stretch   first `cockroach_tpu.query` annotation's start to the last
                one's end, as reduce_trace.py takes it (so the sums here are
                parts of its `busy_s`); no whole statement: no result
  dispatches    every `flow.dispatch` that lies whole inside the stretch,
                on any host thread
  pairing       by the profiler's own links, the one rule: an event inside
                the dispatch carries a producer id (`_pt`, `_p`) that an
                event elsewhere consumes (`_ct`, `_c`), and so on down to
                the launch (on the v5e: `PJRT_LoadedExecutable_Execute
                linkage` -> `..._Execute` on the runtime's thread ->
                `tpu::System::Execute` -> `DoEnqueueProgram` -> the `XLA
                Modules` event). Host and device clocks stand a
                millisecond apart in a v5e trace, so a timestamp pairs
                nothing; nor does a name: two programs that compile to one
                executable are loaded once and BOTH launch under the first
                one's name (q13: `sort_spool_fused` over an aggregate
                whose finalize computes nothing launches as
                `jit_hashagg_finalize`; the kernel's row says
                `launched_as`). Where a dispatch inside the stretch has no
                such chain to a launch of its own, the whole reading is
                refused, with each kernel's count of dispatches and of
                those that found a launch
  a launch's    the union of the `XLA Ops` intervals inside the launch's
  device time   interval, so operators + unattributed add up to `busy_s`:
                the device's operations inside the stretch, widened on the
                device's clock to hold every claimed launch (a statement's
                first launch may start before the statement does: the
                clocks). reduce_trace.py's `busy_s` differs by those edges
  unattributed  what the device did in the stretch inside no claimed launch:
                eager `jnp` operations of the pull loop and the operators
                (`jit_add`, `jit_concatenate`), `compact`, the node's loops
  hlo           under each (operator, kernel): the operations of its
                launches grouped by fusion name or opcode, element type and
                leading dimension, by SELF time (a `while` holds its body's
                operations in its interval: they are not counted twice), in
                ms a launch

A trace whose `flow.dispatch` annotations carry no `op=` comes from a
program without operator sections (before PR 37): no result.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
import sys

import reduce_trace
from reduce_spans import _length, _minus
from reduce_trace import QUERY_ANNOTATION, union

DISPATCH = "flow.dispatch"
UNATTRIBUTED = "unattributed"
_LINK_DEPTH = 6
_INSTR = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<shape>.*?) (?P<opcode>[\w\-]+)\(")
_SHAPE = re.compile(r"(?P<type>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


class Refused(Exception):
    """The trace cannot be read the way this file reads it; why."""


def load(path: str) -> dict:
    """-> {"threads": [[(name, start_ns, dur_ns, stats), ...] a host line],
    "modules": [...], "ops": [...]} with chip 0's two lines; `stats` is a
    dict only for events that carry one of the keys read here."""
    from jax.profiler import ProfileData

    keep = ("kernel", "op", "_p", "_pt", "_c", "_ct")
    data = ProfileData.from_file(reduce_trace.find_xplane(path))
    out = {"threads": [], "modules": [], "ops": []}
    devices = sorted(p.name for p in data.planes
                     if reduce_trace._DEVICE.match(p.name))
    if not devices:
        raise ValueError("no device plane in the trace")
    for plane in data.planes:
        host = not reduce_trace._DEVICE.match(plane.name)
        if not host and plane.name != devices[0]:
            continue
        for line in plane.lines:
            if host:
                evs = []
                out["threads"].append(evs)
            elif line.name in reduce_trace._MODULE_LINES:
                evs = out["modules"]
            elif line.name in reduce_trace._OPS_LINES:
                evs = out["ops"]
            else:
                continue
            ops_line = evs is out["ops"]
            for ev in line.events:
                stats = None
                if not ops_line:
                    stats = {k: v for k, v in ev.stats if k in keep} or None
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns), stats))
    return out


def _program(module: str) -> str:
    """`jit_hashjoin_emit(5761856295263440818)` -> `jit_hashjoin_emit`"""
    return re.sub(r"\(\d+\)$", "", module)


def _pair_by_links(dispatches, threads, modules):
    """[module index or None a dispatch]: the first launch that the
    producer -> consumer ids lead to from inside the dispatch's interval
    and that no earlier dispatch has. Whatever the launch's name: see
    `pairing` above."""
    producers = []  # a host line: sorted [(start, end, (pt, p))]
    consumers: dict = {}  # (ct, c) -> [(line index, start, end)]
    for li, evs in enumerate(threads):
        mine = []
        for _n, s, d, st in evs:
            if not st:
                continue
            if "_p" in st:
                mine.append((s, s + d, (st.get("_pt"), st["_p"])))
            if "_c" in st:
                consumers.setdefault((st.get("_ct"), st["_c"]), []).append(
                    (li, s, s + d))
        mine.sort()
        producers.append(mine)
    launch_of = {}
    for mi, (_n, _s, _d, st) in enumerate(modules):
        if st and "_c" in st:
            launch_of[(st.get("_ct"), st["_c"])] = mi

    def reach(li, s, e, depth, seen):
        """Every launch the links lead to from [s, e] of host line li."""
        mine = producers[li]
        i = bisect.bisect_left(mine, (s,))
        while i < len(mine) and mine[i][0] <= e:
            key = mine[i][2]
            i += 1
            if key in seen:
                continue
            seen.add(key)
            if key in launch_of:
                yield launch_of[key]
            elif depth < _LINK_DEPTH:
                for cli, cs, ce in consumers.get(key, ()):
                    yield from reach(cli, cs, ce, depth + 1, seen)

    paired, taken = [None] * len(dispatches), set()
    for di, (li, s, e, _kernel, _op) in enumerate(dispatches):
        for mi in reach(li, s, e, 0, set()):
            if mi not in taken:
                paired[di] = mi
                taken.add(mi)
                break
    return paired


@functools.lru_cache(maxsize=None)
def hlo_group(text: str) -> str:
    """`%gather_fusion.3 = u32[524288]{0:T(1024)} fusion(...)` ->
    `gather_fusion u32[524288]`: a fusion by its name, any other
    instruction by its opcode, then the result's element type and leading
    dimension (a tuple's first element)."""
    m = _INSTR.match(text)
    if not m:
        return re.sub(r"\.\d+$", "", text.split(" ", 1)[0].lstrip("%"))
    what = m["opcode"]
    if what == "fusion":
        what = re.sub(r"\.\d+$", "", m["name"])
    sh = _SHAPE.search(m["shape"])
    if not sh:
        return what
    lead = sh["dims"].split(",")[0] if sh["dims"] else ""
    return f"{what} {sh['type']}[{lead}]"


def _self_times(ops):
    """[(name, self_ns)] of sorted, properly nested (name, start, end)."""
    out, stack = [], []  # stack of [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _end, self_ns = stack.pop()
            out.append((name, self_ns))

    for name, s, e in ops:
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def reduce_events(tr: dict) -> dict:
    """`tr` as `load` gives it (or written by hand). Raises `Refused` with
    the reason where the trace cannot be read."""
    threads, modules = tr["threads"], tr["modules"]
    queries = [(s, s + d) for evs in threads for name, s, d, _st in evs
               if name == QUERY_ANNOTATION]
    if not queries:
        raise Refused("the trace closes no whole statement")
    lo, hi = min(s for s, _e in queries), max(e for _s, e in queries)
    dispatches = []  # (host line, start, end, kernel, op)
    for li, evs in enumerate(threads):
        for name, s, d, st in evs:
            if name == DISPATCH and s >= lo and s + d <= hi:
                st = st or {}
                dispatches.append((li, s, s + d, st.get("kernel"),
                                   st.get("op")))
    if not dispatches:
        raise Refused("no flow.dispatch annotation inside the stretch")
    dispatches.sort(key=lambda d: d[1])  # in the order they were made
    if all(op is None for *_x, op in dispatches):
        raise Refused("flow.dispatch carries no op=: the program has no "
                      "operator sections")

    paired = _pair_by_links(dispatches, threads, modules)
    if any(mi is None for mi in paired):
        counts: dict = {}  # kernel -> [dispatches, of them with a launch]
        for (*_x, kernel, _op), mi in zip(dispatches, paired):
            c = counts.setdefault(kernel, [0, 0])
            c[0] += 1
            c[1] += mi is not None
        raise Refused("; ".join(
            f"{d} dispatches of {k!r} and {l} launches linked to them"
            for k, (d, l) in counts.items() if d != l)
            + ": the trace's links pair not every dispatch of the stretch")

    ops = sorted((s, s + d, name) for name, s, d, _st in tr["ops"] if d > 0)
    starts = [s for s, _e, _n in ops]
    operators: dict = {}
    claimed = []
    for (_li, _s, _e, kernel, op), mi in zip(dispatches, paired):
        mname, ms, md, _st = modules[mi]
        inside = []
        i = bisect.bisect_left(starts, ms)
        while i < len(ops) and ops[i][0] < ms + md:
            inside.append((ops[i][2], ops[i][0], min(ops[i][1], ms + md)))
            i += 1
        dev_ns = _length(union([(s, e) for _n, s, e in inside]))
        claimed.append((ms, ms + md))
        row = operators.setdefault(op or "none", {
            "launches": 0, "device_s": 0.0, "kernels": {}})
        k = row["kernels"].setdefault(kernel, {
            "launches": 0, "device_s": 0.0, "hlo": {}})
        for r in (row, k):
            r["launches"] += 1
            r["device_s"] += dev_ns / 1e9
        if _program(mname) != f"jit_{kernel}":
            k["launched_as"] = _program(mname)
        for name, self_ns in _self_times(inside):
            g = hlo_group(name)
            k["hlo"][g] = k["hlo"].get(g, 0.0) + self_ns
    for row in operators.values():
        for k in row["kernels"].values():
            k["hlo_ms_per_launch"] = sorted(
                ([g, ns / 1e6 / k["launches"]]
                 for g, ns in k.pop("hlo").items()), key=lambda x: -x[1])

    # on the device's clock the stretch holds every claimed launch: the two
    # clocks stand about a millisecond apart, and a statement's first launch
    # may start before the statement does
    lo = min(lo, min(s for s, _e in claimed))
    hi = max(hi, max(e for _s, e in claimed))
    busy = union([(max(s, lo), min(e, hi)) for s, e, _n in ops
                  if s < hi and e > lo])
    rest = _minus(busy, union(claimed))
    claimed_set = set(paired)
    loose: dict = {}
    for mi, (name, s, d, _st) in enumerate(modules):
        if mi not in claimed_set and lo <= s <= hi:
            r = loose.setdefault(_program(name), [0, 0.0])
            r[0] += 1
            r[1] += d / 1e9
    attributed = sum(r["device_s"] for r in operators.values())
    return {
        "statements": len(queries),
        "window_s": (hi - lo) / 1e9,
        "busy_s": _length(busy) / 1e9,
        "dispatches": len(dispatches),
        "operators": dict(sorted(operators.items(),
                                 key=lambda kv: -kv[1]["device_s"])),
        "attributed_s": attributed,
        "unattributed_s": _length(rest) / 1e9,
        "unattributed_launches": sorted(
            ([n, c, s] for n, (c, s) in loose.items()),
            key=lambda x: -x[2])[:12],
    }


def reduce(path: str) -> dict:
    return reduce_events(load(path))


def table(r: dict) -> str:
    """The reading as lines a person reads: ms a traced statement."""
    n = r["statements"]
    lines = [f"{n} statements, device busy "
             f"{1e3 * r['busy_s'] / n:.3f} ms a statement, "
             f"{r['dispatches']} dispatches paired with their launches"]
    for label, row in r["operators"].items():
        kernels = ", ".join(
            f"{k} x{v['launches'] / n:g} {1e3 * v['device_s'] / n:.3f}"
            + (f" as {v['launched_as']}" if "launched_as" in v else "")
            for k, v in row["kernels"].items())
        lines.append(f"  {label:<16} {row['launches'] / n:6g} launches "
                     f"{1e3 * row['device_s'] / n:10.3f} ms  [{kernels}]")
    top = next(iter(r["operators"].items()), None)
    if top is not None:  # the split of a launch, for the dearest operator
        for k, v in top[1]["kernels"].items():
            groups = ", ".join(f"{g} {ms:.3f}"
                               for g, ms in v["hlo_ms_per_launch"][:8])
            lines.append(f"    {top[0]} {k}, ms a launch: {groups}")
    lines.append(f"  {UNATTRIBUTED:<16} {'':>15}"
                 f"{1e3 * r['unattributed_s'] / n:10.3f} ms  "
                 f"{[[a, b / n, 1e3 * c / n] for a, b, c in r['unattributed_launches'][:6]]}")
    return "\n".join(lines)


if __name__ == "__main__":
    try:
        got = reduce(sys.argv[1])
    except Refused as e:
        print(f"reduce_launches: {e}: no reading", file=sys.stderr)
        sys.exit(1)
    print(table(got), file=sys.stderr)
    print(json.dumps(got, indent=1))
