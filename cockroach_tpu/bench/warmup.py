"""Cold-wall A/B: first-execution latency with and without the AOT menu.

One phase of the ``warmup`` bench job (bench.py runs ``warmup_off`` and
``warmup_on`` as SEPARATE worker subprocesses, each with a fresh
process-global kernel cache and the persistent XLA cache disabled, so
"first execution" is honestly cold):

- **off** — serve the ladder-shaped statements on a cold node: every
  first execution pays parse + plan + XLA compile. ``cold_s`` is that
  wall.
- **on** — build the warm menu first (``sql/warmmenu.py``, the
  readiness-gated server-start path), then serve the SAME statements:
  the menu already minted every (template, rung) kernel, so serving-path
  compiles must be 0 and ``cold_s`` is pure dispatch.

``cold_menu_speedup = cold_off / cold_on`` is the headline number;
``menu_oracle_ok`` (checksums equal across phases) is the bit-identity
guard — a warmed kernel must return byte-identical results to a
cold-compiled one.
"""

from __future__ import annotations

import hashlib
import time

__all__ = ["run_warmup_cold"]


def _checksum(out) -> str:
    """Stable digest of one statement's result columns."""
    import numpy as np

    h = hashlib.sha256()
    if isinstance(out, dict):
        for name in sorted(out):
            h.update(name.encode())
            col = out[name]
            try:
                h.update(np.asarray(col).tobytes())
            except (TypeError, ValueError):
                h.update(repr(col).encode())
    else:
        h.update(repr(out).encode())
    return h.hexdigest()[:16]


def run_warmup_cold(menu: bool, sf: float = 0.05) -> dict:
    """One warmup phase over a fresh TPC-H catalog. Returns cold wall,
    serving-path compile count, per-statement checksums, and (menu mode)
    the menu build cost — bench.py pairs two phases into the A/B."""
    from ..flow import dispatch
    from ..sql import warmmenu
    from ..sql.session import Session
    from ..utils import metric, settings
    from . import tpch

    # "first execution" must be honestly cold: no executable may come back
    # from the persistent cache an earlier job (or the other phase) filled
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    cat = tpch.gen_tpch_cached(sf=sf)
    boot = Session(catalog=cat)
    out: dict = {"menu": bool(menu)}
    try:
        stmts = warmmenu._ladder_statements(cat)
        out["statements"] = len(stmts)
        if menu:
            settings.set("sql.warmup.menu.enabled", True)
            t0 = time.perf_counter()
            k0 = dispatch.compiles()
            warmmenu.build_menu(cat, boot.db, block=True)
            out["menu_build_s"] = round(time.perf_counter() - t0, 2)
            out["menu_kernels"] = dispatch.compiles() - k0
        serve = Session(catalog=cat, db=boot.db, bootstrap=False)
        try:
            hits0 = metric.SQL_WARMUP_MENU_HITS.value
            c0 = dispatch.compiles()
            sums = []
            t0 = time.perf_counter()
            for s in stmts:
                sums.append(_checksum(serve.execute(s)))
            out["cold_s"] = round(time.perf_counter() - t0, 3)
            out["serving_compiles"] = dispatch.compiles() - c0
            out["menu_hits"] = metric.SQL_WARMUP_MENU_HITS.value - hits0
            out["checksums"] = sums
        finally:
            serve.close()
    finally:
        if menu:
            settings.set("sql.warmup.menu.enabled", False)
        boot.close()
    return out
