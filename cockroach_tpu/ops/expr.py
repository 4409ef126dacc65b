"""Scalar expression evaluation — fused selection/projection kernels.

Replaces the reference's generated selection/projection operators
(pkg/sql/colexec/colexecsel, colexecproj, colexecprojconst — one .eg.go kernel
per (operator, left type, right type) combination) with a single expression
tree walked inside a traced function: XLA fuses the whole expression into one
elementwise kernel over the tile, which is exactly what execgen's codegen was
approximating on CPU.

NULL semantics follow SQL three-valued logic (reference: the generated kernels'
null-handling in colexecproj + tree.DNull semantics): every node evaluates to
(data, valid); AND/OR implement Kleene logic.

Dictionary-coded strings: all string predicates (equality, LIKE, range) are
pre-evaluated per dictionary code on the host at plan time and become a
CodeLookup gather on device (see coldata.Dictionary).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.types import BOOL, DATE, FLOAT64, INT64, Family, Schema, SQLType

# ---------------------------------------------------------------------------
# Expression tree


class Expr:
    pass


@dataclass(frozen=True)
class ColRef(Expr):
    idx: int


@dataclass(frozen=True)
class Const(Expr):
    value: Any
    type: SQLType


@dataclass(frozen=True)
class Param(Expr):
    """A runtime-bound literal slot (the prepared-statement placeholder).

    The prepared-plan cache (sql/plancache.py) rewrites numeric Consts in
    filter predicates into Params so the literal becomes a jit ARGUMENT
    read from the active ``param_scope`` at trace time — a repeat query
    with different literals reuses the cached executables with zero new
    traces. Values arrive pre-scaled for DECIMAL (host-side, at bind)."""

    slot: int
    type: SQLType


@dataclass(frozen=True)
class BinOp(Expr):
    op: str  # + - * /
    left: Expr
    right: Expr


@dataclass(frozen=True)
class Cmp(Expr):
    op: str  # lt le gt ge eq ne
    left: Expr
    right: Expr


@dataclass(frozen=True)
class BoolOp(Expr):
    op: str  # and / or
    args: tuple[Expr, ...]


@dataclass(frozen=True)
class Not(Expr):
    arg: Expr


@dataclass(frozen=True)
class IsNull(Expr):
    arg: Expr
    negate: bool = False


@dataclass(frozen=True, eq=False)
class CodeLookup(Expr):
    """Gather `table[code]` for a dictionary-coded column: the device half of a
    host-prepared string operation (predicate table, rank table, hash table)."""

    col: int
    table: np.ndarray = field(hash=False)
    out_type: SQLType = BOOL


@dataclass(frozen=True)
class ParamLookup(Expr):
    """A CodeLookup whose table is a runtime-bound slot of the plan's
    ParamStore (sql/plancache.parameterize): `table[code]` with the table a
    jit ARGUMENT of pinned length (the column's dictionary) and dtype, so a
    statement that differs only in its string pattern (LIKE, IN, =) rebinds
    the table and reuses every executable. Structural (eq=True): the plan
    and kernel keys hold the shape and dtype, never the table's bytes."""

    col: int
    slot: int
    size: int
    dtype: str  # numpy dtype name of the bound table
    out_type: SQLType = BOOL


@dataclass(frozen=True)
class Case(Expr):
    whens: tuple[tuple[Expr, Expr], ...]
    otherwise: Expr


@dataclass(frozen=True)
class Cast(Expr):
    arg: Expr
    to: SQLType


@dataclass(frozen=True)
class ExtractYear(Expr):
    arg: Expr  # DATE


@dataclass(frozen=True)
class Func1(Expr):
    """Unary scalar builtin over a numeric expr (sem/builtins surface,
    pkg/sql/sem/builtins/math_builtins.go): abs | ceil | floor | round |
    sign | sqrt | cbrt | exp | ln | log10 | trunc | degrees | radians |
    sin | cos | tan | cot | asin | acos | atan | sinh | cosh | tanh."""

    func: str
    arg: Expr


# the trig/analytic family: always FLOAT64-valued, with a domain mask
_FUNC1_FLOAT = {
    "sqrt": (jnp.sqrt, lambda x: x >= 0),
    "cbrt": (jnp.cbrt, None),
    "exp": (jnp.exp, None),
    "ln": (jnp.log, lambda x: x > 0),
    "log10": (jnp.log10, lambda x: x > 0),
    "degrees": (jnp.degrees, None),
    "radians": (jnp.radians, None),
    "sin": (jnp.sin, None),
    "cos": (jnp.cos, None),
    "tan": (jnp.tan, None),
    "cot": (lambda x: 1.0 / jnp.tan(x), lambda x: jnp.tan(x) != 0),
    "asin": (jnp.arcsin, lambda x: jnp.abs(x) <= 1),
    "acos": (jnp.arccos, lambda x: jnp.abs(x) <= 1),
    "atan": (jnp.arctan, None),
    "sinh": (jnp.sinh, None),
    "cosh": (jnp.cosh, None),
    "tanh": (jnp.tanh, None),
}


@dataclass(frozen=True)
class Func2(Expr):
    """Binary scalar builtin (pow | mod | div | atan2 | round2 — round2 is
    round(x, n) with literal n; see builtins.go round/pow/mod/div)."""

    func: str
    left: Expr
    right: Expr


@dataclass(frozen=True)
class ExtractPart(Expr):
    """EXTRACT(part FROM date) over DATE (days since epoch): year | month |
    day | quarter | dow | isodow | doy | epoch | decade | century |
    millennium (sem/tree's extractTimeSpanFromDate)."""

    part: str
    arg: Expr


EXTRACT_PARTS = ("year", "month", "day", "quarter", "dow", "isodow",
                 "doy", "epoch", "decade", "century", "millennium")


@dataclass(frozen=True)
class Greatest(Expr):
    """GREATEST/LEAST(a, b, ...): extreme of the NON-NULL arguments
    (NULL only when every argument is NULL — Postgres semantics)."""

    args: tuple[Expr, ...]
    is_least: bool = False


@dataclass(frozen=True)
class BytesLike(Expr):
    """SQL LIKE over a raw text column (BYTES with `text`: CHAR(n)), on the
    device: `pattern` is the pattern's UTF-8 bytes, `%` any run of
    characters and `_` one character (the binder's LIKE has no escape).
    `ci` (ILIKE) folds ASCII letters only. The pattern is part of the plan:
    another pattern is another program."""

    arg: Expr
    pattern: bytes
    ci: bool = False


@dataclass(frozen=True)
class BytesLen(Expr):
    """Characters of a raw text column (its zero padding carries the end,
    UTF-8 continuation bytes are not counted)."""

    arg: Expr


@dataclass(frozen=True)
class Coalesce(Expr):
    """COALESCE(a, b, ...): first non-NULL argument."""

    args: tuple[Expr, ...]


def lit(value: Any, t: SQLType | None = None) -> Const:
    if t is None:
        if isinstance(value, bool):
            t = BOOL
        elif isinstance(value, (int, np.integer)):
            t = INT64
        elif isinstance(value, float):
            t = FLOAT64
        else:
            raise TypeError(f"cannot infer literal type for {value!r}")
    return Const(value, t)


def and_(*args: Expr) -> Expr:
    return BoolOp("and", tuple(args))


def or_(*args: Expr) -> Expr:
    return BoolOp("or", tuple(args))


def between(e: Expr, lo: Expr, hi: Expr) -> Expr:
    return and_(Cmp("ge", e, lo), Cmp("le", e, hi))


# ---------------------------------------------------------------------------
# Parameter scope (prepared-plan literal rebinding)

_PARAM_SCOPE = threading.local()


class param_scope:
    """Context manager installing the positional parameter values a traced
    predicate's Param leaves read. Thread-local (concurrent sessions trace
    on their own threads) and re-entrant (inner scope shadows outer)."""

    def __init__(self, values):
        self._values = tuple(values)

    def __enter__(self):
        self._prev = getattr(_PARAM_SCOPE, "values", None)
        _PARAM_SCOPE.values = self._values
        return self

    def __exit__(self, *exc):
        _PARAM_SCOPE.values = self._prev
        return False


def param_value(slot: int):
    values = getattr(_PARAM_SCOPE, "values", None)
    if values is None:
        raise RuntimeError(
            "Param evaluated outside a param_scope — parameterized "
            "predicates only run through operators built with a ParamStore"
        )
    return values[slot]


# ---------------------------------------------------------------------------
# Type inference


def expr_type(e: Expr, schema: Schema) -> SQLType:
    if isinstance(e, ColRef):
        return schema.types[e.idx]
    if isinstance(e, Const):
        return e.type
    if isinstance(e, Param):
        return e.type
    if isinstance(e, (Cmp, BoolOp, Not, IsNull)):
        return BOOL
    if isinstance(e, (CodeLookup, ParamLookup)):
        return e.out_type
    if isinstance(e, Cast):
        return e.to
    if isinstance(e, ExtractYear):
        return INT64
    if isinstance(e, Func1):
        at = expr_type(e.arg, schema)
        if e.func in _FUNC1_FLOAT:
            return FLOAT64
        if e.func in ("ceil", "floor", "round", "trunc"):
            return INT64 if at.family in (Family.INT,) else at
        if e.func == "sign":
            return INT64
        return at  # abs keeps the input type
    if isinstance(e, Func2):
        if e.func in ("pow", "atan2"):
            return FLOAT64
        if e.func in ("mod", "div"):
            lt = expr_type(e.left, schema)
            if lt.family is Family.FLOAT:
                return FLOAT64
            return INT64
        if e.func == "round2":
            return expr_type(e.left, schema)
        raise TypeError(f"unknown builtin {e.func}")
    if isinstance(e, (ExtractPart, BytesLen)):
        return INT64
    if isinstance(e, BytesLike):
        return BOOL
    if isinstance(e, Greatest):
        ts = [expr_type(a, schema) for a in e.args]
        fams = {t.family for t in ts}
        # single-family INT/BOOL/DATE compare on their raw representation;
        # same-scale DECIMALs compare exactly as scaled ints
        if fams in ({Family.INT}, {Family.BOOL}, {Family.DATE}):
            return ts[0]
        if fams == {Family.DECIMAL} and len({t.scale for t in ts}) == 1:
            return ts[0]
        if fams <= {Family.INT, Family.FLOAT, Family.DECIMAL}:
            # mixed numeric representations: compare in float64 space
            return FLOAT64
        # BOOL/DATE mixed with numerics has no sane unification
        # (Postgres rejects it too)
        raise TypeError(
            f"greatest/least cannot unify argument families {fams}"
        )
    if isinstance(e, Coalesce):
        return expr_type(e.args[0], schema)
    if isinstance(e, Case):
        return expr_type(e.whens[0][1], schema)
    if isinstance(e, BinOp):
        lt, rt = expr_type(e.left, schema), expr_type(e.right, schema)
        return _binop_type(e.op, lt, rt)
    raise TypeError(f"unknown expr {e}")


def _binop_type(op: str, lt: SQLType, rt: SQLType) -> SQLType:
    fams = (lt.family, rt.family)
    if Family.FLOAT in fams or op == "/":
        return FLOAT64
    if Family.DECIMAL in fams:
        ls = lt.scale if lt.family is Family.DECIMAL else 0
        rs = rt.scale if rt.family is Family.DECIMAL else 0
        scale = ls + rs if op == "*" else max(ls, rs)
        return SQLType(Family.DECIMAL, precision=38, scale=scale)
    if Family.DATE in fams:
        return DATE
    return INT64


# ---------------------------------------------------------------------------
# Evaluation (inside trace)


def expr_bounds(e: Expr, schema: Schema, col_stats: dict) -> tuple | None:
    """(lo, hi) value bounds of an integer-family expression, derived from
    input column stats — the statistics-propagation analog of the
    reference's statisticsBuilder (opt/memo/statistics_builder.go) applied
    to scalar projections, so dense-key planning (aggregation slots, packed
    join keys, sort operands) survives computed columns like
    EXTRACT(YEAR FROM o_orderdate)."""
    if isinstance(e, ColRef):
        s = col_stats.get(e.idx)
        return None if s is None else (int(s[0]), int(s[1]))
    if isinstance(e, Const):
        try:
            v = int(e.value)
        except (TypeError, ValueError):
            return None
        return (v, v)
    if isinstance(e, ExtractYear):
        b = expr_bounds(e.arg, schema, col_stats)
        if b is None:
            return None
        return (_year_of_day(b[0]), _year_of_day(b[1]))
    if isinstance(e, BinOp) and e.op in ("+", "-", "*"):
        lt = expr_type(e.left, schema)
        rt = expr_type(e.right, schema)
        # DECIMAL arithmetic rescales operands (scale alignment /
        # multiplication scale growth) — raw bounds would be in the wrong
        # units; only plain integer/date arithmetic propagates
        if (lt.family in (Family.FLOAT, Family.DECIMAL)
                or rt.family in (Family.FLOAT, Family.DECIMAL)):
            return None
        lb = expr_bounds(e.left, schema, col_stats)
        rb = expr_bounds(e.right, schema, col_stats)
        if lb is None or rb is None:
            return None
        if e.op == "+":
            return (lb[0] + rb[0], lb[1] + rb[1])
        if e.op == "-":
            return (lb[0] - rb[1], lb[1] - rb[0])
        prods = [a * b for a in lb for b in rb]
        return (min(prods), max(prods))
    if isinstance(e, Cast):
        if e.to.family in (Family.FLOAT, Family.STRING, Family.BYTES):
            return None
        # int-to-int casts preserve value bounds (the cast matrix rounds
        # DECIMAL scale changes; bounds stay conservative by using both)
        b = expr_bounds(e.arg, schema, col_stats)
        ft = expr_type(e.arg, schema)
        if b is None or ft.family is Family.FLOAT:
            return None
        if ft.family is Family.DECIMAL or e.to.family is Family.DECIMAL:
            return None  # scale changes rescale values; skip
        return b
    return None


def _year_of_day(days: int) -> int:
    import datetime

    return (datetime.date(1970, 1, 1)
            + datetime.timedelta(days=int(days))).year


def eval_expr(e: Expr, cols, schema: Schema):
    """Evaluate e over a batch's columns -> (data, valid). `cols` is the tuple
    of Column; arrays are full-tile, mask applied by the caller."""
    if isinstance(e, ColRef):
        c = cols[e.idx]
        return c.data, c.valid

    if isinstance(e, Const):
        n = cols[0].data.shape[0]
        if e.value is None:
            from ..coldata.types import zeros_like_type

            return (
                zeros_like_type(e.type, n),  # BYTES needs [n, W]
                jnp.zeros((n,), jnp.bool_),
            )
        v = e.value
        if e.type.family is Family.BYTES:
            row = np.frombuffer(bytes(v).ljust(e.type.width, b"\0"),
                                np.uint8)
            return (jnp.broadcast_to(jnp.asarray(row), (n, row.shape[0])),
                    jnp.ones((n,), jnp.bool_))
        if e.type.family is Family.DECIMAL:
            v = int(round(float(v) * 10**e.type.scale))
        return (
            jnp.full((n,), v, dtype=e.type.dtype),
            jnp.ones((n,), jnp.bool_),
        )

    if isinstance(e, Param):
        # the value is a traced argument (see param_scope), NOT a baked
        # constant — rebinding it later never invalidates the executable
        n = cols[0].data.shape[0]
        v = jnp.asarray(param_value(e.slot)).astype(e.type.dtype)
        # a BYTES slot is one row of the type's width (uint8[W])
        return (jnp.broadcast_to(v, (n,) + v.shape),
                jnp.ones((n,), jnp.bool_))

    if isinstance(e, (CodeLookup, ParamLookup)):
        c = cols[e.col]
        # a ParamLookup's table is a traced argument (see param_scope); a
        # CodeLookup's is baked into the executable as a constant
        table = jnp.asarray(param_value(e.slot)
                            if isinstance(e, ParamLookup) else e.table)
        codes = jnp.clip(c.data, 0, table.shape[0] - 1)
        data = table[codes].astype(e.out_type.dtype)
        return data, c.valid

    if isinstance(e, Cast):
        d, v = eval_expr(e.arg, cols, schema)
        ft = expr_type(e.arg, schema)
        return _cast(d, ft, e.to), v

    if isinstance(e, ExtractYear):
        d, v = eval_expr(e.arg, cols, schema)
        if expr_type(e.arg, schema).family is Family.TIMESTAMP:
            d = d.astype(jnp.int64) // (86400 * 1000000)
        return _year_from_days(d), v

    if isinstance(e, Func1):
        d, v = eval_expr(e.arg, cols, schema)
        at = expr_type(e.arg, schema)
        scale = 10 ** at.scale if at.family is Family.DECIMAL else 1
        if e.func == "abs":
            return jnp.abs(d), v
        if e.func == "sign":
            return jnp.sign(d).astype(jnp.int64), v
        if e.func in ("ceil", "floor", "round"):
            if at.family is Family.FLOAT:
                f = {"ceil": jnp.ceil, "floor": jnp.floor,
                     "round": jnp.round}[e.func]
                return f(d), v
            if at.family is Family.DECIMAL:
                # stay in scaled-int space: exact, no float round-trip
                q, r = d // scale, d % scale
                if e.func == "ceil":
                    out = (q + (r > 0)) * scale
                elif e.func == "floor":
                    out = q * scale
                else:  # round half away from zero (SQL numeric rounding)
                    out = _div_half_away(d, scale) * scale
                return out, v
            return d, v  # ints are already integral
        if e.func == "trunc":
            if at.family is Family.FLOAT:
                return jnp.trunc(d), v
            if at.family is Family.DECIMAL:
                q = jnp.where(d >= 0, d // scale, -((-d) // scale))
                return q * scale, v
            return d, v
        f64 = d.astype(jnp.float64) / scale
        if e.func in _FUNC1_FLOAT:
            fn, domain = _FUNC1_FLOAT[e.func]
            ok = v if domain is None else v & domain(f64)
            return fn(jnp.where(ok, f64, 1.0)), ok
        raise ValueError(f"unknown builtin {e.func}")

    if isinstance(e, Func2):
        lt, rt = expr_type(e.left, schema), expr_type(e.right, schema)
        ld, lv = eval_expr(e.left, cols, schema)
        rd, rv = eval_expr(e.right, cols, schema)
        valid = lv & rv
        if e.func in ("pow", "atan2"):
            lf, rf = _to_float(ld, lt), _to_float(rd, rt)
            if e.func == "atan2":
                return jnp.arctan2(lf, rf), valid
            out = jnp.power(lf, rf)
            # pow(0, negative) and negative**fractional are SQL errors;
            # surface them as NULL (the engine's error-as-NULL policy for
            # value-dependent domain faults)
            return jnp.where(jnp.isfinite(out), out, 0.0), \
                valid & jnp.isfinite(out)
        if e.func in ("mod", "div"):
            if lt.family is Family.FLOAT or rt.family is Family.FLOAT:
                lf, rf = _to_float(ld, lt), _to_float(rd, rt)
                ok = valid & (rf != 0)
                rf = jnp.where(rf == 0, 1.0, rf)
                q = jnp.trunc(lf / rf)
                return (lf - q * rf if e.func == "mod" else q), ok
            li, ri = ld.astype(jnp.int64), rd.astype(jnp.int64)
            ok = valid & (ri != 0)
            ri = jnp.where(ri == 0, 1, ri)
            # SQL mod/div truncate toward zero; the remainder takes the
            # DIVIDEND's sign (Postgres mod(7,-3)=1, mod(-7,3)=-1).
            # floor-div + sign fixup keeps everything exact in int64
            qf = li // ri
            r = li - qf * ri
            q = qf + ((r != 0) & ((li < 0) != (ri < 0)))
            return (li - q * ri if e.func == "mod" else q), ok
        if e.func == "round2":
            n = int(e.right.value)  # binder guarantees a literal
            if lt.family is Family.FLOAT:
                p = 10.0 ** n
                return jnp.round(ld * p) / p, valid
            if lt.family is Family.DECIMAL:
                if n >= lt.scale:
                    return ld, valid
                p = 10 ** (lt.scale - n)
                return _div_half_away(ld, p) * p, valid
            if n >= 0:
                return ld, valid
            p = 10 ** (-n)
            return _div_half_away(ld, p) * p, valid
        raise ValueError(f"unknown builtin {e.func}")

    if isinstance(e, ExtractPart):
        d, v = eval_expr(e.arg, cols, schema)
        d = d.astype(jnp.int64)
        if expr_type(e.arg, schema).family is Family.TIMESTAMP:
            if e.part == "epoch":
                return d // 1000000, v
            d = d // (86400 * 1000000)
        return _extract_part(e.part, d), v

    if isinstance(e, Greatest):
        out_t = expr_type(e, schema)

        def as_out(arg):
            dd, vv = eval_expr(arg, cols, schema)
            at = expr_type(arg, schema)
            if out_t.family is Family.FLOAT:
                dd = _to_float(dd, at)  # DECIMAL scales divide out here
            elif dd.dtype != out_t.dtype:
                dd = _cast(dd, at, out_t)
            return dd, vv

        d, v = as_out(e.args[0])
        pick = jnp.minimum if e.is_least else jnp.maximum
        for a in e.args[1:]:
            d1, v1 = as_out(a)
            both = v & v1
            ext = pick(d, d1)
            d = jnp.where(both, ext, jnp.where(v, d, d1))
            v = v | v1
        return d, v

    if isinstance(e, Coalesce):
        d, v = eval_expr(e.args[0], cols, schema)
        for a in e.args[1:]:
            d1, v1 = eval_expr(a, cols, schema)
            d = jnp.where(v, d, d1.astype(d.dtype))
            v = v | v1
        return d, v

    if isinstance(e, IsNull):
        _, v = eval_expr(e.arg, cols, schema)
        out = v if e.negate else ~v
        return out, jnp.ones_like(v)

    if isinstance(e, Not):
        d, v = eval_expr(e.arg, cols, schema)
        return ~d, v

    if isinstance(e, BoolOp):
        d0, v0 = eval_expr(e.args[0], cols, schema)
        for a in e.args[1:]:
            d1, v1 = eval_expr(a, cols, schema)
            if e.op == "and":
                # Kleene AND: known-false if either side known-false;
                # known-true only if both sides known-true.
                t = (v0 & d0) & (v1 & d1)
                f = (v0 & ~d0) | (v1 & ~d1)
            else:
                t = (v0 & d0) | (v1 & d1)
                f = (v0 & ~d0) & (v1 & ~d1)
            d0, v0 = t, t | f
        return d0, v0

    if isinstance(e, BytesLike):
        d, v = eval_expr(e.arg, cols, schema)
        return _bytes_like(d, e.pattern, e.ci), v

    if isinstance(e, BytesLen):
        d, v = eval_expr(e.arg, cols, schema)
        chars = (d != 0) & ((d & 0xC0) != 0x80)
        return jnp.sum(chars, axis=1, dtype=jnp.int64), v

    if isinstance(e, Cmp):
        lt, rt = expr_type(e.left, schema), expr_type(e.right, schema)
        if e.op not in ("eq", "ne") and not (
            lt.comparable_on_device and rt.comparable_on_device
        ):
            # STRING range predicates must be planned as rank-table CodeLookups
            # (coldata.Dictionary.ranks); raw codes don't order by byte value.
            raise TypeError(
                f"range comparison on {lt}/{rt} requires a host-prepared rank "
                "table (plan a CodeLookup, not a raw Cmp)"
            )
        ld, lv = eval_expr(e.left, cols, schema)
        rd, rv = eval_expr(e.right, cols, schema)
        if Family.BYTES in (lt.family, rt.family):
            if lt.family is not rt.family:
                raise TypeError(f"cannot compare {lt} with {rt}")
            return _bytes_cmp(e.op, ld, rd), lv & rv
        ld, rd = _align_numeric(ld, lt, rd, rt)
        fns = {
            "lt": jnp.less,
            "le": jnp.less_equal,
            "gt": jnp.greater,
            "ge": jnp.greater_equal,
            "eq": jnp.equal,
            "ne": jnp.not_equal,
        }
        return fns[e.op](ld, rd), lv & rv

    if isinstance(e, BinOp):
        lt, rt = expr_type(e.left, schema), expr_type(e.right, schema)
        ld, lv = eval_expr(e.left, cols, schema)
        rd, rv = eval_expr(e.right, cols, schema)
        out_t = _binop_type(e.op, lt, rt)
        valid = lv & rv
        if e.op == "/" or out_t.family is Family.FLOAT:
            lf = _to_float(ld, lt)
            rf = _to_float(rd, rt)
            if e.op == "/":
                valid = valid & (rf != 0)
                rf = jnp.where(rf == 0, 1.0, rf)
            fns = {
                "+": jnp.add,
                "-": jnp.subtract,
                "*": jnp.multiply,
                "/": jnp.divide,
            }
            return fns[e.op](lf, rf), valid
        if out_t.family is Family.DECIMAL:
            ls = lt.scale if lt.family is Family.DECIMAL else 0
            rs = rt.scale if rt.family is Family.DECIMAL else 0
            li, ri = ld.astype(jnp.int64), rd.astype(jnp.int64)
            if e.op == "*":
                return li * ri, valid
            s = max(ls, rs)
            li = li * (10 ** (s - ls))
            ri = ri * (10 ** (s - rs))
            return (li + ri if e.op == "+" else li - ri), valid
        fns = {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply}
        return fns[e.op](ld, rd).astype(out_t.dtype), valid

    if isinstance(e, Case):
        out_d, out_v = eval_expr(e.otherwise, cols, schema)
        # evaluate in reverse so earlier whens win
        for cond, val in reversed(e.whens):
            cd, cv = eval_expr(cond, cols, schema)
            vd, vv = eval_expr(val, cols, schema)
            take = cv & cd
            out_d = jnp.where(take, vd, out_d)
            out_v = jnp.where(take, vv, out_v)
        return out_d, out_v

    raise TypeError(f"cannot evaluate {e}")


def _bytes_cmp(op: str, ld, rd):
    """`ld <op> rd` over two zero-padded uint8[N, W] buffers, bytewise
    lexicographic (the order ops/keys.py sorts BYTES by): big-endian words,
    the first word that differs decides."""
    from ..coldata.batch import pack_be_words

    w = max(ld.shape[1], rd.shape[1])
    a, b = (pack_be_words(jnp.pad(d, ((0, 0), (0, w - d.shape[1]))))
            for d in (ld, rd))
    eq = jnp.all(a == b, axis=1)
    if op in ("eq", "ne"):
        return eq if op == "eq" else ~eq
    lt = jnp.zeros(eq.shape, jnp.bool_)
    for i in reversed(range(a.shape[1])):
        lt = (a[:, i] < b[:, i]) | ((a[:, i] == b[:, i]) & lt)
    return {"lt": lt, "le": lt | eq, "gt": ~(lt | eq), "ge": ~lt}[op]


def _bytes_like(data, pattern: bytes, ci: bool):
    """LIKE over zero-padded UTF-8 rows uint8[N, W]: state j says that
    pattern[:j] matches the text read so far; one pass over the W byte
    columns, the padding (NUL, which text never holds) changes nothing."""
    pct, one = ord("%"), ord("_")
    if ci:
        data = jnp.where((data >= 65) & (data <= 90), data + 32, data)
    n = data.shape[0]
    yes, no = jnp.ones((n,), jnp.bool_), jnp.zeros((n,), jnp.bool_)
    dp = [yes]
    for tok in pattern:  # a leading run of % matches the empty text
        dp.append(dp[-1] if tok == pct else no)

    def step(i, dp):
        ch = jax.lax.dynamic_index_in_dim(data, i, axis=1, keepdims=False)
        cont = (ch & 0xC0) == 0x80  # inside a multi-byte character
        new = [no]
        for j, tok in enumerate(pattern):
            if tok == pct:
                new.append(dp[j + 1] | new[j])
            elif tok == one:
                new.append((dp[j] & ~cont) | (dp[j + 1] & cont))
            else:
                new.append(dp[j] & (ch == tok))
        live = ch != 0
        return tuple(jnp.where(live, a, b) for a, b in zip(new, dp))

    return jax.lax.fori_loop(0, data.shape[1], step, tuple(dp))[-1]


def _align_numeric(ld, lt: SQLType, rd, rt: SQLType):
    """Bring two sides of a comparison to a common representation."""
    if Family.FLOAT in (lt.family, rt.family):
        return _to_float(ld, lt), _to_float(rd, rt)
    if Family.DECIMAL in (lt.family, rt.family):
        ls = lt.scale if lt.family is Family.DECIMAL else 0
        rs = rt.scale if rt.family is Family.DECIMAL else 0
        s = max(ls, rs)
        return (
            ld.astype(jnp.int64) * (10 ** (s - ls)),
            rd.astype(jnp.int64) * (10 ** (s - rs)),
        )
    return ld, rd


def _to_float(d, t: SQLType):
    if t.family is Family.DECIMAL:
        return d.astype(jnp.float64) / (10.0**t.scale)
    return d.astype(jnp.float64)


def _div_half_away(d, s: int):
    """Scaled-int division rounding half away from zero (SQL numeric
    rounding on precision reduction)."""
    pos = (d + s // 2) // s
    neg = -((-d + s // 2) // s)
    return jnp.where(d >= 0, pos, neg)


def _div_trunc(d, s: int):
    """Scaled-int division truncating toward zero (SQL cast to INT)."""
    return jnp.where(d >= 0, d // s, -((-d) // s))


def _cast(d, ft: SQLType, to: SQLType):
    if to.family is Family.FLOAT:
        return _to_float(d, ft)
    if to.family is Family.DECIMAL:
        if ft.family is Family.DECIMAL:
            diff = to.scale - ft.scale
            if diff >= 0:
                return d * (10**diff)
            return _div_half_away(d, 10**-diff)  # scale cut ROUNDS
        if ft.family is Family.FLOAT:
            return jnp.round(d * 10.0**to.scale).astype(jnp.int64)
        return d.astype(jnp.int64) * (10**to.scale)
    if to.family is Family.INT:
        if ft.family is Family.DECIMAL:
            # SQL casts numeric -> int by ROUNDING (Postgres semantics)
            return _div_half_away(d, 10**ft.scale).astype(to.dtype)
        if ft.family is Family.FLOAT:
            return jnp.round(d).astype(to.dtype)
        return d.astype(to.dtype)
    if to.family is Family.TIMESTAMP and ft.family is Family.DATE:
        return d.astype(jnp.int64) * (86400 * 1000000)
    if to.family is Family.DATE and ft.family is Family.TIMESTAMP:
        return (d // (86400 * 1000000)).astype(jnp.int32)
    if to.family is Family.BOOL:
        if ft.family is Family.DECIMAL:
            return d != 0
        return d.astype(jnp.bool_)
    return d.astype(to.dtype)


def _year_from_days(days):
    """Gregorian year from days-since-1970 (civil-from-days, integer only).
    In 32 bits, widened at the end: a DATE's days and a TIMESTAMP's (at
    most 1.07e8) fit with room for every product below, and the chip's
    compiler takes 24 s for these nine divisions on 64-bit integers
    against 0.25 s on 32-bit ones (a v5e described to XLA, PR 28)."""
    z = days.astype(jnp.int32) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    return jnp.where(m <= 2, y + 1, y).astype(jnp.int64)


def _civil_from_days(days):
    """(year, month, day, day-of-year) from days-since-1970 — Hinnant's
    civil_from_days, vectorized integer-only."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy_mar = doe - (365 * yoe + yoe // 4 - yoe // 100)  # 0 = March 1
    mp = (5 * doy_mar + 2) // 153
    d = doy_mar - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    # calendar day-of-year (Jan 1 = 1)
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    jan_feb = jnp.where(m <= 2, 0, jnp.where(leap, 60, 59))
    doy = jnp.where(m <= 2,
                    d + jnp.where(m == 2, 31, 0),
                    doy_mar + 1 + jan_feb)
    return y, m, d, doy


def _extract_part(part: str, days):
    """EXTRACT(part FROM date) over days-since-epoch int64."""
    if part == "epoch":
        return days * 86400
    if part == "dow":  # 0 = Sunday (1970-01-01 was a Thursday)
        return (days + 4) % 7
    if part == "isodow":  # 1 = Monday .. 7 = Sunday
        return (days + 3) % 7 + 1
    y, m, d, doy = _civil_from_days(days)
    if part == "year":
        return y
    if part == "month":
        return m
    if part == "day":
        return d
    if part == "doy":
        return doy
    if part == "quarter":
        return (m - 1) // 3 + 1
    if part == "decade":
        return jnp.where(y >= 0, y, y - 9) // 10
    if part == "century":
        return jnp.where(y > 0, (y - 1) // 100 + 1, -((-y) // 100) - 1)
    if part == "millennium":
        return jnp.where(y > 0, (y - 1) // 1000 + 1, -((-y) // 1000) - 1)
    raise ValueError(f"unknown extract part {part}")


# ---------------------------------------------------------------------------
# Batch-level entry points


def filter_mask(batch, schema: Schema, predicate: Expr) -> jax.Array:
    """New liveness mask: old mask AND predicate is TRUE (not false/NULL)."""
    d, v = eval_expr(predicate, batch.cols, schema)
    return batch.mask & d & v
