"""Column pruning — every operator hands up only the columns read above it.

Reference: the optimizer's PruneCols rules (pkg/sql/opt/norm/prune_cols_funcs.go)
push each operator's needed-column set down its inputs until scans fetch
only those columns. Inside one XLA program a dead column costs nothing (the
compiler drops it); a tile a join EMITS or a build side COMPACTS is a
program's output, so every column of it is materialised. The binder scans
whole tables and narrows above the joins: this pass fills in
``TableScan.columns`` and remaps every position on the way back up.

The walk is top-down with the set of output positions the parent reads and
returns, beside the rewritten node, where each surviving old position sits
now. A node it has no rule for is a barrier: it requires ALL columns of its
inputs, and each input is pruned on its own from there. Unknown means
untouched, never guessed.
"""

from __future__ import annotations

import dataclasses

from ..ops import expr as ex
from . import spec as S
from .distribute import schema_of

# expression leaves that hold a column position
_POSITIONAL = {ex.ColRef: "idx", ex.CodeLookup: "col", ex.ParamLookup: "col"}

# consumers that run in one program with the chain below them, where the
# compiler already drops what they do not read: a Filter under one of these
# needs no narrowing Project of its own
_NARROWS = (S.Project, S.Filter, S.Aggregate, S.ScalarAggregate)


def expr_refs(e, out: set[int] | None = None) -> set[int]:
    """Column positions an expression reads."""
    out = set() if out is None else out
    if isinstance(e, tuple):
        for i in e:
            expr_refs(i, out)
    elif isinstance(e, ex.Expr):
        f = _POSITIONAL.get(type(e))
        if f is not None:
            out.add(getattr(e, f))
        else:
            for fld in dataclasses.fields(e):
                expr_refs(getattr(e, fld.name), out)
    return out


def remap_expr(e, m: dict[int, int]):
    """``e`` with every column position sent through ``m``; the same
    object where nothing moved."""
    if isinstance(e, tuple):
        new = tuple(remap_expr(i, m) for i in e)
        return e if all(a is b for a, b in zip(new, e)) else new
    if not isinstance(e, ex.Expr):
        return e
    f = _POSITIONAL.get(type(e))
    if f is not None:
        old = getattr(e, f)
        return e if m[old] == old else dataclasses.replace(e, **{f: m[old]})
    changes = {}
    for fld in dataclasses.fields(e):
        v = getattr(e, fld.name)
        nv = remap_expr(v, m)
        if nv is not v:
            changes[fld.name] = nv
    return dataclasses.replace(e, **changes) if changes else e


def prune_columns(plan: S.PlanNode, catalog) -> S.PlanNode:
    """``plan`` with every scan, join and build side cut to the columns
    the statement reads above it. The root keeps all its columns."""
    p = _Pruner(catalog)
    node, _ = p.prune(plan, frozenset(range(len(p.names(plan)))), None)
    return node


def _identity(n: int) -> dict[int, int]:
    return {i: i for i in range(n)}


class _Pruner:
    def __init__(self, catalog):
        self.catalog = catalog
        self._names: dict[int, tuple[str, ...]] = {}

    def names(self, n: S.PlanNode) -> tuple[str, ...]:
        """Output column names of the UNPRUNED node (memoised by identity:
        the walk asks for a join's probe side at every level)."""
        got = self._names.get(id(n))
        if got is None:
            got = self._names[id(n)] = schema_of(n, self.catalog).names
        return got

    # -- the walk ----------------------------------------------------------

    def prune(self, n: S.PlanNode, need: frozenset[int], parent):
        """(rewritten ``n``, {old output position: new position} over the
        columns that survive). ``need``: the positions ``parent`` reads;
        the survivors hold at least those."""
        if isinstance(n, (S.TableScan, S.IndexScan, S.PointLookup,
                          S.PKRange)):
            return self._scan(n, need)
        if isinstance(n, S.Filter):
            return self._filter(n, need, parent)
        if isinstance(n, S.Project):
            return self._project(n, need)
        if isinstance(n, S.HashJoin):
            return self._join(n, need)
        if isinstance(n, (S.Sort, S.TopK)):
            below = need | {k.col for k in n.keys}
            child, m = self.prune(n.input, frozenset(below), n)
            keys = tuple(_moved(k, "col", m) for k in n.keys)
            return dataclasses.replace(n, input=child, keys=keys), m
        if isinstance(n, S.Limit):
            child, m = self.prune(n.input, need, n)
            return dataclasses.replace(n, input=child), m
        if isinstance(n, S.Aggregate) and n.mode == "complete":
            below = set(n.group_cols) | {
                a.col for a in n.aggs if a.col is not None}
            child, m = self.prune(n.input, frozenset(below), n)
            return dataclasses.replace(
                n, input=child,
                group_cols=tuple(m[c] for c in n.group_cols),
                aggs=tuple(_moved(a, "col", m) for a in n.aggs),
            ), _identity(len(self.names(n)))
        if isinstance(n, S.ScalarAggregate) and n.mode == "complete":
            below = {a.col for a in n.aggs if a.col is not None}
            child, m = self.prune(n.input, frozenset(below), n)
            return dataclasses.replace(
                n, input=child,
                aggs=tuple(_moved(a, "col", m) for a in n.aggs),
            ), _identity(len(n.aggs))
        if isinstance(n, S.Distinct) and n.cols:
            child, m = self.prune(n.input, frozenset(n.cols), n)
            return dataclasses.replace(
                n, input=child, cols=tuple(m[c] for c in n.cols),
            ), _identity(len(n.cols))
        return self._barrier(n)

    def _barrier(self, n):
        """A node with no rule: every input keeps all its columns (so no
        position of ``n`` moves) and is pruned on its own below that."""
        changes = {}
        for f in dataclasses.fields(n):
            v = getattr(n, f.name)
            if isinstance(v, S.PlanNode):
                changes[f.name] = self._whole(v, n)
            elif isinstance(v, tuple) and v and isinstance(v[0], S.PlanNode):
                changes[f.name] = tuple(self._whole(c, n) for c in v)
        return dataclasses.replace(n, **changes), _identity(len(self.names(n)))

    def _whole(self, child, parent):
        width = len(self.names(child))
        new, m = self.prune(child, frozenset(range(width)), parent)
        assert m == _identity(width), "all columns required, some moved"
        return new

    def _scan(self, n, need):
        names = self.names(n)
        if isinstance(n, S.TableScan):
            from ..sql import crdb_internal

            if crdb_internal.is_virtual(n.table):
                return n, _identity(len(names))
        # a scan never hands up zero columns: count(*) keeps the first
        keep = sorted(need) or [0]
        if len(keep) == len(names):
            return n, _identity(len(names))
        return (dataclasses.replace(
                    n, columns=tuple(names[i] for i in keep)),
                {old: new for new, old in enumerate(keep)})

    def _filter(self, n, need, parent):
        refs = expr_refs(n.predicate)
        child, m = self.prune(n.input, frozenset(need | refs), n)
        out = dataclasses.replace(n, input=child, predicate=remap_expr(n.predicate, m))
        if not need or refs <= need or isinstance(parent, _NARROWS):
            return out, m
        # columns only the predicate reads stop here: they do not ride
        # into the join (or spool) above
        keep = sorted(need)
        names = self.names(n)
        out = S.Project(out, tuple(ex.ColRef(m[i]) for i in keep),
                        tuple(names[i] for i in keep))
        return out, {old: new for new, old in enumerate(keep)}

    def _project(self, n, need):
        keep = sorted(need) or list(range(len(n.exprs)))[:1]
        below = expr_refs(tuple(n.exprs[i] for i in keep))
        child, m = self.prune(n.input, frozenset(below), n)
        pos = {old: new for new, old in enumerate(keep)}
        return dataclasses.replace(
            n, input=child,
            exprs=tuple(remap_expr(n.exprs[i], m) for i in keep),
            names=tuple(n.names[i] for i in keep),
            dict_overrides=tuple(
                (pos[i], d) for i, d in n.dict_overrides if i in pos),
        ), pos

    def _join(self, n, need):
        np_ = len(self.names(n.probe))
        semi = n.spec.join_type in ("semi", "anti")
        need_p = {i for i in need if i < np_} | set(n.probe_keys)
        need_b = set(n.build_keys)
        if not semi:
            need_b |= {i - np_ for i in need if i >= np_}
        probe, pm = self.prune(n.probe, frozenset(need_p), n)
        build, bm = self.prune(n.build, frozenset(need_b), n)
        out = dict(pm)
        if not semi:
            off = len(pm)
            out.update({np_ + old: off + new for old, new in bm.items()})
        return dataclasses.replace(
            n, probe=probe, build=build,
            probe_keys=tuple(pm[k] for k in n.probe_keys),
            build_keys=tuple(bm[k] for k in n.build_keys),
        ), out


def _moved(spec, field: str, m: dict[int, int]):
    """A sort key or aggregate spec with its column sent through ``m``."""
    old = getattr(spec, field)
    if old is None or m[old] == old:
        return spec
    return dataclasses.replace(spec, **{field: m[old]})
