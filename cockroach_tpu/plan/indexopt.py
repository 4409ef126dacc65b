"""Index selection — rewrite Filter(TableScan) into IndexScan, or into
PointLookup where a conjunct pins the primary key, or into PKRange where
two conjuncts bound it on both sides.

Reference: the optimizer's GenerateIndexScans / GenerateConstrainedScans
exploration rules turn filtered full scans into constrained index scans
when a filter conjunct constrains an indexed column
(pkg/sql/opt/xform/select_funcs.go); the execbuilder then plans an index
join to fetch unindexed columns (pkg/sql/rowexec/joinreader.go).

Reduction: single-column indexes, conjuncts of the form
``col <cmp> literal`` (and BETWEEN, which the binder lowers to two
conjuncts — possibly as separate stacked Filter nodes, which the rewrite
walks as one chain). The whole original predicate stays as a residual
filter over the fetched rows — re-applying the bound conjunct is one fused mask op,
and it keeps boundary/NULL semantics independent of the span math.

The primary key is a route of its own, taken first and behind no setting
or statistic: ``pk = c`` or ``pk IN (c1 .. cn)`` (the binder lowers IN to an
OR of equalities) over a KV-backed table becomes ``PointLookup``; that
conjunct is answered by the lookup itself and the others stay as Filters
above it. Where no conjunct pins the key but the conjuncts bound it from
below AND from above (``pk BETWEEN a AND b``, ``pk >= a AND pk < b``) the
scan becomes ``PKRange``: a seek of the store for the span; the two
conjuncts are answered by the read and the others stay as Filters. A
one-sided bound stays a scan.

Selectivity gate: the scan flips to the index only when the constrained
value range is estimated under ``sql.opt.index_scan_max_frac`` of the
column's (lo, hi) span from table statistics — a full-table IndexScan
would be strictly worse than the resident columnar scan."""

from __future__ import annotations

from ..ops import expr as ex
from ..utils import settings
from . import spec as S

INDEX_SCAN_ENABLED = settings.register_bool(
    "sql.opt.index_scan.enabled", True,
    "plan index-backed reads for selective filters on indexed columns",
)
INDEX_SCAN_MAX_FRAC = settings.register_float(
    "sql.opt.index_scan.max_frac", 0.25,
    "estimated selected fraction above which a filtered full scan beats "
    "an index scan + fetch", lo=0.0, hi=1.0,
)


def _conjuncts(e: ex.Expr) -> list[ex.Expr]:
    if isinstance(e, ex.BoolOp) and e.op == "and":
        out = []
        for part in e.args:
            out.extend(_conjuncts(part))
        return out
    return [e]


def _col_bound(c: ex.Expr) -> tuple[int, str, int] | None:
    """(scan column index, cmp op, literal) for `col <cmp> int-literal`
    conjuncts, normalized so the column is on the left."""
    if not isinstance(c, ex.Cmp) or c.op == "ne":
        return None
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}
    left, right, op = c.left, c.right, c.op
    if isinstance(right, ex.ColRef) and isinstance(left, ex.Const):
        left, right, op = right, left, flip[op]
    if not (isinstance(left, ex.ColRef) and isinstance(right, ex.Const)):
        return None
    v = right.value
    if isinstance(v, bool) or not (
            isinstance(v, int) or hasattr(v, "__index__")):
        return None
    return left.idx, op, int(v)


def _bounds_for(conjs, names, indexed: dict[str, object]):
    """Tightest (index, lo, hi) over the conjuncts, or None."""
    best: dict[str, list] = {}
    for c in conjs:
        m = _col_bound(c)
        if m is None:
            continue
        i, op, v = m
        if i >= len(names) or names[i] not in indexed:
            continue
        lo, hi = best.setdefault(names[i], [None, None])
        if op == "eq":
            nlo, nhi = v, v
        elif op == "lt":
            nlo, nhi = None, v - 1
        elif op == "le":
            nlo, nhi = None, v
        elif op == "gt":
            nlo, nhi = v + 1, None
        else:  # ge
            nlo, nhi = v, None
        b = best[names[i]]
        b[0] = nlo if b[0] is None else (b[0] if nlo is None else max(b[0], nlo))
        b[1] = nhi if b[1] is None else (b[1] if nhi is None else min(b[1], nhi))
    for col, (lo, hi) in best.items():
        if lo is not None or hi is not None:
            return indexed[col], lo, hi
    return None


def _selective_enough(table, ix, lo, hi) -> bool:
    if lo is not None and hi is not None and hi < lo:
        return True  # empty span: the index scan is free
    stats = table.col_stats()
    b = stats.get(ix.col)
    if b is None:
        # no statistics: only a two-sided constraint is trusted
        return lo is not None and hi is not None
    clo, chi = int(b[0]), int(b[1])
    width = max(1, chi - clo + 1)
    elo = clo if lo is None else max(clo, lo)
    ehi = chi if hi is None else min(chi, hi)
    frac = max(0, ehi - elo + 1) / width
    return frac <= settings.get("sql.opt.index_scan.max_frac")


def _pk_points(c: ex.Expr, pk_pos: int) -> tuple[ex.Const, ...] | None:
    """The literals of `pk = c` or `pk = c1 OR pk = c2 ...`, else None."""
    parts = c.args if isinstance(c, ex.BoolOp) and c.op == "or" else (c,)
    keys = []
    for part in parts:
        m = _col_bound(part)
        if m is None or m[0] != pk_pos or m[1] != "eq":
            return None
        keys.append(part.right if isinstance(part.right, ex.Const)
                    else part.left)
    return tuple(keys) if len(keys) <= S.PointLookup.MAX_KEYS else None


def _point_lookup(scan: S.TableScan, table, preds) -> S.PlanNode | None:
    """Filter chain over `scan` -> PointLookup with the residual Filters,
    when a conjunct pins the primary key."""
    names = scan.columns or table.schema.names
    if table.pk not in names:
        return None
    pk_pos = names.index(table.pk)
    levels = [_conjuncts(p) for p in preds]
    for conjs in levels:
        for c in conjs:
            keys = _pk_points(c, pk_pos)
            if keys is None:
                continue
            node: S.PlanNode = S.PointLookup(scan.table, keys, scan.columns)
            for rest in reversed([[x for x in lv if x is not c]
                                  for lv in levels]):
                if rest:
                    node = S.Filter(node, rest[0] if len(rest) == 1
                                    else ex.BoolOp("and", tuple(rest)))
            return node
    return None


_INT64_MIN, _INT64_MAX = -(1 << 63), (1 << 63) - 1


def _pk_range(scan: S.TableScan, table, preds) -> S.PlanNode | None:
    """Filter chain over `scan` -> PKRange with the residual Filters, when
    one conjunct bounds the primary key from below and one from above. The
    bounds stay expressions (the conjunct's own literal, or one more or
    less for a strict comparison), so the plan cache makes them Params."""
    import dataclasses

    names = scan.columns or table.schema.names
    if table.pk not in names:
        return None
    pk_pos = names.index(table.pk)
    levels = [_conjuncts(p) for p in preds]
    lo = hi = None  # (conjunct, bound expression)
    for c in (c for conjs in levels for c in conjs):
        m = _col_bound(c)
        if m is None or m[0] != pk_pos or m[1] == "eq":
            continue
        _i, op, v = m
        const = c.right if isinstance(c.right, ex.Const) else c.left
        if op in ("ge", "gt") and lo is None:
            if op == "gt" and v == _INT64_MAX:
                return None
            lo = (c, const if op == "ge"
                  else dataclasses.replace(const, value=v + 1))
        elif op in ("le", "lt") and hi is None:
            if op == "lt" and v == _INT64_MIN:
                return None
            hi = (c, const if op == "le"
                  else dataclasses.replace(const, value=v - 1))
    if lo is None or hi is None:
        return None
    node: S.PlanNode = S.PKRange(scan.table, lo[1], hi[1], scan.columns)
    for rest in reversed([[x for x in lv if x is not lo[0] and x is not hi[0]]
                          for lv in levels]):
        if rest:
            node = S.Filter(node, rest[0] if len(rest) == 1
                            else ex.BoolOp("and", tuple(rest)))
    return node


def use_indexes(plan: S.PlanNode, catalog) -> S.PlanNode:
    """Recursively rewrite eligible Filter(TableScan) subtrees."""
    return _rewrite(plan, catalog,
                    settings.get("sql.opt.index_scan.enabled"))


def _rewrite(plan, catalog, secondary: bool):
    from ..kv.table import KVTable

    if isinstance(plan, S.Filter):
        # The binder pushes WHERE conjuncts down one at a time, so a
        # two-sided bound (k >= 30 AND k <= 36) arrives as STACKED Filter
        # nodes over the scan. Walk the whole chain and size the span over
        # the union of every level's conjuncts; the residual filters are
        # re-applied unchanged over the IndexScan.
        preds = [plan.predicate]
        inner = plan.input
        while isinstance(inner, S.Filter):
            preds.append(inner.predicate)
            inner = inner.input
        if isinstance(inner, S.TableScan):
            scan = inner
            table = catalog.tables.get(scan.table)
            if isinstance(table, KVTable) and scan.shard is None:
                node = (_point_lookup(scan, table, preds)
                        or _pk_range(scan, table, preds))
                if node is not None:
                    return node
            if (secondary and isinstance(table, KVTable) and table.indexes
                    and scan.shard is None):
                names = scan.columns or table.schema.names
                indexed = {ix.col: ix for ix in table.indexes}
                conjs = [c for p in preds for c in _conjuncts(p)]
                got = _bounds_for(conjs, names, indexed)
                if got is not None:
                    ix, lo, hi = got
                    if _selective_enough(table, ix, lo, hi):
                        node: S.PlanNode = S.IndexScan(
                            scan.table, ix.name, lo, hi, scan.columns)
                        for p in reversed(preds):
                            node = S.Filter(node, p)
                        return node
    # generic recursion over PlanNode dataclass fields
    import dataclasses

    if not dataclasses.is_dataclass(plan):
        return plan
    changes = {}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, S.PlanNode):
            nv = _rewrite(v, catalog, secondary)
            if nv is not v:
                changes[f.name] = nv
        elif isinstance(v, tuple) and v and isinstance(v[0], S.PlanNode):
            nv = tuple(_rewrite(x, catalog, secondary) for x in v)
            if any(a is not b for a, b in zip(nv, v)):
                changes[f.name] = nv
    return dataclasses.replace(plan, **changes) if changes else plan
