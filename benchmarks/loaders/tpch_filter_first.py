"""Loader `tpch_filter_first`: the `tpch` loader for a configuration that
states, under `plans`, that the IN-subquery filters its table BEFORE any join
(Q18: the orders whose lines sum past QUANTITY are a dozen of 1.5M, and
`lineitem` is joined with those, never all 6.0M rows with all of `orders`).
The guarantee is tried before the data is made, from EXPLAIN of the served
text over SF0.001: the joins inside the probe side of the subquery's
semi-join are counted, and there have to be none. A program that joins first
cannot run the configuration inside a run's time (the parent of PR 32: 10.4 s
a settled statement, 303 s for a warm-up pass on a filled compile cache, over
900 s for the first statement on an empty one), so the run ends here,
non-zero, with the reason. What the probe read is pinned into the comparison
that decides `correct`."""

from __future__ import annotations

from loaders import tpch

SEMI, JOIN = "hash-join (semi)", "hash-join ("


def _served_text(quantity: int = 313) -> str:
    import traffic

    (template,) = traffic.load_mix("q18_stream")["templates"]
    return template["sql"].format(quantity=quantity)


def joins_below_the_in_filter(plan: str) -> int:
    """Joins in the probe side of the plan's (first) semi-join, from
    EXPLAIN's indented tree: 0 when the semi-join probes a scan. A plan
    without a semi-join has not decorrelated the IN at all: -1."""
    lines = [(len(ln) - len(ln.lstrip(" ")), ln.strip())
             for ln in plan.splitlines() if ln.strip().startswith("->")]
    at = next((i for i, (_d, ln) in enumerate(lines) if SEMI in ln), None)
    if at is None or at + 1 >= len(lines) or lines[at + 1][0] <= lines[at][0]:
        return -1
    probe_depth = lines[at + 1][0]  # the probe side is the first child
    n = int(JOIN in lines[at + 1][1])
    for depth, ln in lines[at + 2:]:
        if depth <= probe_depth:
            break
        n += JOIN in ln
    return n


def probe(seed: int) -> int:
    from cockroach_tpu import sql
    from cockroach_tpu.bench import tpch as gen

    return joins_below_the_in_filter(
        sql.explain(gen.gen_tpch(sf=0.001, seed=seed), _served_text()))


def load(config: dict, seed: int, workdir: str) -> tpch.Loaded:
    below = probe(seed)
    if below:
        raise SystemExit(
            f"loaders/tpch_filter_first.py: configuration {config['name']!r} "
            f"guarantees that the IN-subquery filters orders before any "
            f"join; this program's plan has {below} join(s) below the "
            f"subquery's semi-join (-1: no semi-join at all)")
    loaded = tpch.load(config, seed, workdir)
    loaded.pinned.append({"name": "joins_below_the_in_filter",
                          "value": float(below), "limit": 0.0})
    return loaded
