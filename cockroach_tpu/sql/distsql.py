"""Where a plan runs: on the node's one device, or across its mesh.

Reference: the session variable `distsql` and `shouldDistributePlan`
(pkg/sql/distsql_physical_planner.go: checkSupportForPlanNode says whether
a plan CAN be distributed, the session's mode whether it is). A node that
spans several devices (server/node.py `Node(devices=n)`, its serving
catalog's ``mesh``) row-shards its tables over them; `place` is the ONE site
that decides, once a plan-cache entry (sql/plancache.py `_build_tree`), and
`Rel.run_distributed` / `explain_distributed` go through it too:

  off     the local operator tree, always (a mesh node then reads a second
          copy of each scanned column on its first device);
  auto    the mesh when the node has one, the plan is distributable, its
          tables are placed on the mesh (host tables; a KV-backed table is
          read through the store) and it scans enough rows to be worth one
          program across the devices (``AUTO_MIN_ROWS`` a device); the
          local tree otherwise;
  on      the mesh whenever the plan is distributable;
  always  the mesh, or a loud error saying why not.

What is distributed is the plan as it is served: `rel.optimized_plan()`
(index selection, top-K, column pruning), parameterized by the plan cache.
"""

from __future__ import annotations

from ..plan import builder as plan_builder
from ..plan import spec as S

MODES = ("off", "auto", "on", "always")
AUTO_MIN_ROWS = 1024  # scanned rows a device below which `auto` stays local


def _scanned(plan, catalog) -> tuple[int, bool]:
    """(estimated rows the plan's table scans read, every scanned table
    keeps its own placement on a mesh: a host table, `Table.mesh_batch`)."""
    from ..plan.distribute import estimated_rows

    total, placed, stack = 0, True, [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, S.TableScan):
            total += estimated_rows(n, catalog)
            placed = placed and hasattr(catalog.get(n.table), "mesh_batch")
        for f in getattr(n, "__dataclass_fields__", {}):
            v = getattr(n, f)
            if isinstance(v, S.PlanNode):
                stack.append(v)
            elif isinstance(v, tuple):
                stack.extend(x for x in v if isinstance(x, S.PlanNode))
    return total, placed


def decide(plan, catalog, mode: str = "auto", mesh=None,
           broadcast_rows: int | None = None):
    """-> (distributed plan, None) or (None, why the plan runs locally)."""
    from ..parallel.mesh import AXIS
    from ..parallel.planner import _needs_local
    from ..plan.distribute import distribute

    if mode not in MODES:
        raise ValueError(f"distsql: {mode!r} is not one of {MODES}")
    mesh = mesh if mesh is not None else getattr(catalog, "mesh", None)
    why = None
    if mesh is None:
        why = "the node spans one device"
    elif mode == "off":
        why = "distsql=off"
    elif _needs_local(plan):
        why = "plan not distributable"
    elif mode == "auto":
        rows, placed = _scanned(plan, catalog)
        if not placed:
            # its rows live in the node's store: every launch would
            # snapshot and re-shard them
            why = "scans a KV-backed table"
        elif rows < AUTO_MIN_ROWS * mesh.shape[AXIS]:
            why = "too few rows to spread"
    if why is None:
        try:
            return distribute(plan, catalog, broadcast_rows), None
        except TypeError as e:  # a node the rewrite has no rule for
            why = f"plan not distributable: {e}"
    if mode == "always" and mesh is not None:
        from ..utils.errors import QueryError

        raise QueryError("distsql=always", ValueError(why))
    return None, why


def place(plan, catalog, mode: str = "auto", params=None, mesh=None,
          broadcast_rows: int | None = None):
    """The operator tree that runs ``plan``: a `MeshOp` over the one SPMD
    program of the distributed plan, or the local tree."""
    dplan, _why = decide(plan, catalog, mode, mesh, broadcast_rows)
    if dplan is None:
        return plan_builder.build(plan, catalog, params=params)
    from ..parallel.planner import DistributedQuery, MeshOp

    mesh = mesh if mesh is not None else catalog.mesh
    return MeshOp(DistributedQuery(dplan, catalog, mesh,
                                   already_distributed=True, params=params))


def explain(plan, catalog, mode: str = "auto", mesh=None,
            broadcast_rows: int | None = None) -> str:
    """EXPLAIN (DISTSQL): the plan `place` would run, and where."""
    from ..plan.explain import explain_plan

    dplan, why = decide(plan, catalog, mode, mesh, broadcast_rows)
    if dplan is None:
        return f"distribution: local ({why})\n" + explain_plan(plan, catalog)
    return explain_plan(dplan)
