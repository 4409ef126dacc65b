"""Loader `tpch_mesh`: the `tpch` loader for a configuration whose node
spans several chips (`chips` of the configuration: `Node(devices=n)`, tables
row-sharded over the chips in primary-key order) and that states, under
`placement`, that the served statement runs across all of them with its
joins and its aggregate hash-routed.

First act, before any data is made: build the node and read EXPLAIN (DISTSQL)
of the mix's text over SF0.001 against the node's own serving catalog. A
program whose Node cannot span devices ends the run here, non-zero, in
seconds and with the reason (the constructor comes first: such a program's
own `explain_distributed` prints exchanges for a mesh no statement ever
reaches); so does a plan without an `exchange (all-to-all)`, and one whose
`lineitem` scan lists more columns than the statement reads. Then the data,
the same node, the tables adopted. What the probes read is pinned into the
comparison that decides `correct`; the shard sizes are read from the
resident shards when the comparison is made, after the window."""

from __future__ import annotations

import re

from loaders import tpch

MIX = "q3_stream"  # the text the probes read (as tpch_filter_first's q18)
EXCHANGE, JOIN, FINAL = "exchange (all-to-all)", "hash-join (", "mode=final"
LINEITEM_COLUMNS_READ = 4  # oracles/tpch_q3.py TOUCHES


def _served_texts(mix_name: str, seed: int) -> list[str]:
    import traffic

    return [sql for _j, _p, sql in
            traffic.Stream(traffic.load_mix(mix_name), seed + 2, 0).warmup()]


def _tree(plan: str) -> list[tuple[int, str]]:
    return [(len(ln) - len(ln.lstrip(" ")), ln.strip())
            for ln in plan.splitlines() if ln.strip().startswith("->")]


def exchanges(plan: str) -> dict:
    """What EXPLAIN (DISTSQL)'s indented tree says of the hash routing: the
    all-to-all stages in all, those that feed a join (a child of a hash-join
    line) and those that feed the final aggregate (a child of a `mode=final`
    group-by)."""
    lines = _tree(plan)
    out = {"all": 0, "join_key": 0, "group_key": 0}
    for i, (depth, ln) in enumerate(lines):
        if EXCHANGE not in ln:
            continue
        out["all"] += 1
        parent = next((p for d, p in reversed(lines[:i]) if d < depth), "")
        out["join_key"] += JOIN in parent
        out["group_key"] += FINAL in parent
    return out


def scan_columns(plan: str, table: str) -> int:
    """Columns the plan's (widest) scan of ``table`` lists."""
    found = re.findall(rf"-> scan {table} columns=\[([^\]]*)\]", plan)
    return max((len(cols.split(",")) for cols in found), default=0)


def _explain(node, text: str) -> str:
    from cockroach_tpu import sql

    return sql.explain(node._sql_catalog, "EXPLAIN (DISTSQL) " + text)


def _adopt(node, cat) -> None:
    for name, table in cat.tables.items():
        node._sql_catalog.tables[name] = table
    node._sql_catalog.bump_version()


class Loaded(tpch.Loaded):
    """`tpch.Loaded` whose pinned comparisons are read when they are asked
    for: the placement is a fact of the shards after the warm-up, not of
    the load."""

    def __init__(self, node, tables, info, pinned, placement):
        self._pinned, self._placement = pinned, placement
        super().__init__(node, tables, info, pinned)

    @property
    def pinned(self):
        return self._pinned + self._placement()

    @pinned.setter
    def pinned(self, value):
        self._pinned = value


def load(config: dict, seed: int, workdir: str) -> Loaded:
    from cockroach_tpu.bench import tpch as gen
    from cockroach_tpu.plan import distribute
    from cockroach_tpu.server.node import Node

    # the deployment's broadcast threshold: what the tree has (131,072) in
    # the cell's configuration; a rehearsal at a hundredth of the rows
    # states 0, so that its joins are hash-routed as they are at SF1
    distribute.BROADCAST_ROWS_DEFAULT = int(config["broadcast_rows"])
    chips = int(config["chips"])
    me = f"loaders/tpch_mesh.py: configuration {config['name']!r}"
    texts = _served_texts(MIX, seed)

    def node_over(cat):
        try:
            node = Node(devices=chips)
        except (TypeError, ValueError) as e:
            raise SystemExit(f"{me} needs one node spanning {chips} "
                             f"devices; this program's Node cannot: {e}")
        node.start(pg_port=0)
        _adopt(node, cat)
        return node

    # the probe's node lives for the probe alone: the node that serves is
    # started after the data is made, as `tpch`'s is (a node's background
    # loops compile as its store ages: 324 of them in a traced window of a
    # node started a minute of data generation earlier, PERF.md PR 48)
    probe = node_over(gen.gen_tpch(sf=0.001, seed=seed))
    try:
        plan = _explain(probe, texts[0])
    finally:
        probe.stop()
    if not exchanges(plan)["all"]:
        raise SystemExit(
            f"{me} guarantees hash-routed stages; this program's plan "
            f"of the served text has no {EXCHANGE!r}:\n{plan}")
    wide = scan_columns(plan, "lineitem")
    if wide > LINEITEM_COLUMNS_READ:
        raise SystemExit(
            f"{me}: the distributed plan scans {wide} columns of lineitem "
            f"where the statement reads {LINEITEM_COLUMNS_READ}: it is not "
            f"the served (pruned) plan")
    cat = gen.gen_tpch(sf=float(config["scale_factor"]), seed=seed)
    node = node_over(cat)
    try:
        unrouted = 0
        for text in texts:
            ex = exchanges(_explain(node, text))
            unrouted += not (ex["join_key"] and ex["group_key"])
    except BaseException:
        node.stop()
        raise
    rows = int(cat.get("lineitem").num_rows)
    mesh_devices = int(node.mesh.devices.size) if node.mesh is not None else 1
    pinned = [
        {"name": "lineitem_rows_off_pin",
         "value": float(abs(rows - int(config["lineitem_rows"]))),
         "limit": float(config["lineitem_rows_tolerance"])},
        {"name": "statements_planned_without_exchange",
         "value": float(unrouted), "limit": 0.0},
        {"name": "mesh_devices", "value": float(mesh_devices),
         "limit": float(chips), "op": ">="},
    ]

    def placement() -> list[dict]:
        """max - min of the live rows a chip of the resident `lineitem`
        shards; the whole table where no statement has sharded it."""
        held = cat.get("lineitem").mesh_shard_rows()
        spread = (float(rows) if not held or len(held) < chips
                  else float(max(held.values()) - min(held.values())))
        return [{"name": "lineitem_shard_rows_spread", "value": spread,
                 "limit": float(config["shard_rows_spread_limit"])}]

    return Loaded(node, dict(cat.tables),
                  {"n_rows": rows, "mesh_devices": mesh_devices},
                  pinned, placement)
