"""raw-jit pass: every jit entry point routes through flow/dispatch.jit.

flow/dispatch.py wraps ``jax.jit`` so every call of a compiled kernel bumps
``sql_kernel_dispatches`` — the metric the dispatch-budget guard
(scripts/check_dispatch_budget.py) and EXPLAIN ANALYZE's
``kernel dispatches:`` line are built on. A raw ``jax.jit`` anywhere else
creates kernels invisible to that accounting: the budget guard keeps
passing while real dispatch count regresses. (This is exactly how the
SPMD plane drifted: parallel/{shuffle,dist,planner}.py jitted raw, so
distributed kernels never counted until this pass flagged them.)

Flagged: any reference (call, ``functools.partial`` argument, assignment)
to ``jax.jit``, ``jax.pmap``, ``jax.shard_map``, or those names imported
from jax directly. ``shard_map`` alone is a transform, not an entry point
— it only dispatches once jitted, so it is flagged only as ``jax.shard_map``
reference when used to build a callable outside dispatch.

Also flagged: a ``dispatch.jit`` (called, or handed to
``functools.partial``) without a static ``name=``. The name becomes the XLA
module (``jit_<name>``) that profiler traces, the benchmark's
``breakdown.device_ops`` and the persistent compile cache all key on, so it
is ``<operator>_<role>`` and never a per-query value: a lower-case string
literal, or an f-string whose only interpolations are an operator's
``KERNEL`` class attribute or a plain parameter name (a helper's role tag).

Exempt: cockroach_tpu/flow/dispatch.py (the wrapper itself). Kernels that
deliberately stay outside flow accounting (storage-plane compaction/MVCC
kernels, the coldata compact helper counted via ``dispatch.note``) carry
``# crlint: allow-raw-jit(<why>)``.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, SourceFile, attr_chain
from .tracepurity import _is_dispatch_jit

RULE = "raw-jit"

EXEMPT = ("cockroach_tpu/lint/", "cockroach_tpu/flow/dispatch.py")
_ENTRY = {("jax", "jit"), ("jax", "pmap"), ("jax", "shard_map")}
_FROM_JAX = {"jit", "pmap"}
_NAME = re.compile(r"[a-z][a-z0-9_]*")
_NAME_PART = re.compile(r"[a-z0-9_]*")


def _static_name(value: ast.AST | None) -> bool:
    if isinstance(value, ast.Constant):
        return isinstance(value.value, str) and bool(
            _NAME.fullmatch(value.value))
    if not isinstance(value, ast.JoinedStr):
        return False
    for part in value.values:
        if isinstance(part, ast.Constant):
            if not _NAME_PART.fullmatch(str(part.value)):
                return False
        elif isinstance(part, ast.FormattedValue):
            v = part.value
            if not (isinstance(v, ast.Name) or (
                    isinstance(v, ast.Attribute) and v.attr == "KERNEL")):
                return False
    return True


def _unnamed_jit(call: ast.Call) -> bool:
    """A call that builds a dispatch.jit kernel (``dispatch.jit(fn, ...)``
    or ``functools.partial(dispatch.jit, ...)``) without a static name."""
    chain = attr_chain(call.func)
    if not (_is_dispatch_jit(call.func) or (
            chain and chain[-1] == "partial" and call.args
            and _is_dispatch_jit(call.args[0]))):
        return False
    name = next((k.value for k in call.keywords if k.arg == "name"), None)
    return not _static_name(name)


def check(src: SourceFile) -> list[Finding]:
    if src.rel.startswith(EXEMPT[0]) or src.rel == EXEMPT[1]:
        return []
    # names imported straight off jax: `from jax import jit as J` binds J
    from_jax: set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for a in node.names:
                if a.name in _FROM_JAX:
                    from_jax.add(a.asname or a.name)
    out: list[Finding] = []
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call) and _unnamed_jit(node):
            out.append(Finding(
                RULE, src.rel, node.lineno,
                "dispatch.jit without a static name= — the XLA module, "
                "the trace and the compile cache would carry a Python "
                "closure's name; give it name=\"<operator>_<role>\""))
        if isinstance(node, ast.Attribute):
            chain = attr_chain(node)
            if chain in _ENTRY:
                out.append(Finding(
                    RULE, src.rel, node.lineno,
                    f"raw {'.'.join(chain)} bypasses flow/dispatch "
                    "accounting — route through dispatch.jit so "
                    "sql_kernel_dispatches and the budget guard see it"))
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            if node.func.id in from_jax:
                out.append(Finding(
                    RULE, src.rel, node.lineno,
                    f"raw jax {node.func.id}() bypasses flow/dispatch "
                    "accounting — route through dispatch.jit"))
    return out
