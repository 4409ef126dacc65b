"""Shared comparison for the TPC-H oracles: every answer the window
returned, against pandas for its parameter set.

Numbers compared (each printed beside its limit by run.py):
  key_mismatches   exact: row count, column names, group keys. Limit 0.
  max_rel_err      widest relative gap of a numeric column from float64
                   pandas. Limit from the configuration (`rel_tolerance`).
With `control=True` the same numbers are also read for the control: the
oracle itself computed in float32 and held against the float64 oracle.
"""

import importlib
import json

import numpy as np


def _compare(names, rows, want, mod):
    """-> (key mismatches, widest relative error)."""
    cols = list(want.columns)
    if names is None or sorted(names) != sorted(cols) or len(rows) != len(want):
        return 1 + abs(len(rows) - len(want)), 0.0
    got = {n: [r[j] for r in rows] for j, n in enumerate(names)}
    bad, rel = 0, 0.0
    for c in cols:
        w = want[c].to_numpy()
        if c in mod.VALUES:
            g = np.array([np.nan if v is None else float(v) for v in got[c]])
            w = w.astype(np.float64)
            r = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
            rel = max(rel, float(np.nan_to_num(r, nan=np.inf).max()))
        elif w.dtype.kind in "iu":
            g = np.array([-(1 << 62) if v is None else int(v)
                          for v in got[c]])
            bad += int((g != w.astype(np.int64)).sum())
        else:
            bad += sum(1 for a, b in zip(got[c], w) if a != str(b))
    return bad, rel


def _date_cols_to_days(names, rows, want):
    """pgwire sends a DATE as text; the oracle holds days since 1970."""
    import datetime

    out = [list(r) for r in rows]
    for j, n in enumerate(names or []):
        if n in want.columns and want[n].dtype.kind in "iu":
            for r in out:
                v = r[j]
                if isinstance(v, str) and len(v) == 10 and v[4] == "-":
                    r[j] = str((datetime.date.fromisoformat(v)
                                - datetime.date(1970, 1, 1)).days)
    return out


def check(ctx, query: str) -> list[dict]:
    mod = importlib.import_module(f"oracles.{query}")
    by_params: dict[str, list] = {}
    for rec in ctx.records:
        if rec["err"] is None:
            by_params.setdefault(json.dumps(rec["p"], sort_keys=True),
                                 []).append(rec)
    bad, rel, crel = 0, 0.0, None
    for key, recs in sorted(by_params.items()):
        params = json.loads(key)
        want = mod.answer(ctx.loaded, params)
        for rec in recs:
            rows = _date_cols_to_days(rec["names"], rec["rows"], want)
            b, r = _compare(rec["names"], rows, want, mod)
            bad, rel = bad + b, max(rel, r)
        if ctx.control:
            low = mod.answer(ctx.loaded, params, precision="float32")
            cols = [[str(v) for v in low[c].tolist()] for c in want.columns]
            _b, r = _compare(list(want.columns),
                             [list(row) for row in zip(*cols)], want, mod)
            crel = r if crel is None else min(crel, r)
    out = [{"name": "answers_checked",
            "value": float(sum(len(v) for v in by_params.values())), "limit": 1.0, "op": ">="},
           {"name": "key_mismatches", "value": float(bad), "limit": 0.0},
           {"name": "max_rel_err", "value": rel,
            "limit": float(ctx.config["rel_tolerance"])}]
    if crel is not None:
        out.append({"name": "control.min_rel_err_float32", "value": crel,
                    "limit": float(ctx.config["rel_tolerance"]),
                    "control": True})
    return out
