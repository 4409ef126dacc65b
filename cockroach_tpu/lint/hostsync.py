"""host-sync pass: no implicit device->host transfer in the tile pull loop.

The overlapped-readback work (flow/runtime.py's double-buffered pull loop,
the speculative _ReadbackShrink) exists precisely because ONE per-tile host
sync serializes the whole pipeline against the device round trip. This pass
keeps that class of regression out of the hot-path modules:

- ``int()``/``float()``/``bool()`` over an expression that mentions
  ``jnp``/``jax`` (a traced or device value) blocks until the value lands
  on host;
- ``.item()`` is the same sync spelled as a method;
- ``np.asarray``/``np.array`` on a device array is a blocking readback
  (``jnp.asarray`` — host->device — is NOT flagged);
- ``jax.device_get``/``jax.block_until_ready`` are explicit syncs;
- a truth test (``if``/``while``/``assert``/``and``/``or``/``not``) over a
  ``jnp.*`` call forces __bool__ on a traced value;
- ``jax.debug.print``/``jax.debug.callback`` (and ``pure_callback``/
  ``io_callback``) stage a host callback into the traced kernel — one
  host round trip per launch, and ``ordered=True`` serializes the whole
  stream behind it. Debug prints belong OUTSIDE the jit or behind a
  pragma while actively debugging.

Scope: the hot-path modules only (flow/runtime.py, flow/fuse.py,
flow/operators.py, ops/*). Host-boundary modules whose whole JOB is the
device<->host transfer (flow/external.py, flow/wire.py) are allowlisted
wholesale — flagging them would drown the signal in pragmas.

Deliberate syncs (the one stacked count fetch at query end, decode of
host-resident dictionary columns) carry ``# crlint: allow-host-sync(...)``
pragmas stating why they are not per-tile.
"""

from __future__ import annotations

import ast

from .core import Finding, SourceFile, attr_chain

RULE = "host-sync"

HOT_FILES = (
    "cockroach_tpu/flow/runtime.py",
    "cockroach_tpu/flow/fuse.py",
    "cockroach_tpu/flow/operators.py",
)
HOT_DIRS = ("cockroach_tpu/ops/",)
# host-boundary modules: device<->host transfer IS their contract
ALLOWLIST = (
    "cockroach_tpu/flow/external.py",
    "cockroach_tpu/flow/wire.py",
)

_CASTS = {"int", "float", "bool"}
_NP_SYNCS = {("np", "asarray"), ("np", "array"),
             ("numpy", "asarray"), ("numpy", "array")}
_JAX_SYNCS = {("jax", "device_get"), ("jax", "block_until_ready")}
# host callbacks staged INTO traced code: each kernel launch round-trips
# through the host (jax.debug.print/debug.callback ride the same effect
# machinery as io_callback; ordered=True additionally serializes the
# stream). One per tile re-creates exactly the per-tile sync this pass
# exists to keep out of the pull loop.
_HOST_CALLBACKS = {("jax", "debug", "print"), ("jax", "debug", "callback"),
                   ("jax", "pure_callback"),
                   ("jax", "experimental", "io_callback")}
_DEVICE_ROOTS = {"jnp", "jax"}
# jnp attributes that are host-side metadata, not traced computation
_HOST_SAFE_ATTRS = {"issubdtype", "iinfo", "finfo", "dtype", "result_type",
                    "promote_types", "can_cast", "bool_", "ndim", "shape"}
# np.array over a literal/comprehension builds a host array from host
# python values — no device readback involved
_HOST_LITERALS = (ast.List, ast.Tuple, ast.Dict, ast.Constant, ast.ListComp,
                  ast.GeneratorExp)


def in_scope(rel: str) -> bool:
    if rel in ALLOWLIST:
        return False
    return rel in HOT_FILES or rel.startswith(HOT_DIRS)


def _mentions_device(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _DEVICE_ROOTS:
            return True
    return False


def _device_call(node: ast.AST) -> bool:
    """A direct jnp.*/jax.* call somewhere inside the expression (dtype
    metadata predicates like jnp.issubdtype excluded — they are host
    booleans)."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            chain = attr_chain(n.func)
            if (chain and chain[0] in _DEVICE_ROOTS
                    and chain[-1] not in _HOST_SAFE_ATTRS):
                return True
    return False


def check(src: SourceFile) -> list[Finding]:
    if not in_scope(src.rel):
        return []
    out: list[Finding] = []

    def flag(node: ast.AST, msg: str) -> None:
        out.append(Finding(RULE, src.rel, node.lineno, msg))

    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                flag(node, ".item() forces a device->host sync in a "
                          "hot-path module")
            elif (isinstance(node.func, ast.Name)
                    and node.func.id in _CASTS and node.args
                    and _mentions_device(node.args[0])):
                flag(node, f"{node.func.id}() over a jnp/jax expression "
                           "blocks on a device->host transfer")
            elif chain in _NP_SYNCS:
                if not (node.args
                        and isinstance(node.args[0], _HOST_LITERALS)):
                    flag(node, f"{'.'.join(chain)}() materializes its "
                               "argument on host (blocking readback for "
                               "device arrays)")
            elif chain in _JAX_SYNCS:
                flag(node, f"{'.'.join(chain)}() is an explicit device "
                           "sync in a hot-path module")
            elif chain in _HOST_CALLBACKS:
                flag(node, f"{'.'.join(chain)}() stages a host callback "
                           "into traced code (one host round trip per "
                           "kernel launch; ordered=True serializes the "
                           "stream)")
        elif isinstance(node, (ast.If, ast.While, ast.Assert)):
            if _device_call(node.test):
                flag(node, "truth test over a jnp/jax call forces __bool__ "
                           "on a traced value (hidden sync)")
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            if _device_call(node.operand):
                flag(node, "`not` over a jnp/jax call forces __bool__ on a "
                           "traced value (hidden sync)")
    return out
