"""Bench-regression gate — compare a fresh BENCH JSON against the roofline.

The repo keeps one ``BENCH_r*.json`` per recorded bench run (wrapper dict
with the parsed one-line bench JSON under ``"parsed"``). This checker
takes a FRESH bench emission (a file holding ``python bench.py``'s one
JSON line, or ``-`` for stdin) and diffs its throughput surface against
the newest recorded baseline:

- every throughput series (any key ending ``_per_sec``, plus the
  top-level geomean ``value``) that dropped more than the threshold
  (default 20%) is flagged as a regression;
- every metric present in the baseline but ABSENT from the fresh run is
  flagged — a bench refactor that silently stops emitting a series must
  not pass as "no regressions".

Both runs must come from the same platform (an old CPU record diffed
against a tpu baseline would flag everything); mismatches flag, they do
not silently pass.

Usage (tier-2, run_chaos_matrix.py-style — not part of the tier-1 pytest
sweep; run it after a bench session, before committing a BENCH file):

    python bench.py > /tmp/bench_fresh.json
    python scripts/check_bench_regress.py /tmp/bench_fresh.json
    python scripts/check_bench_regress.py --threshold 0.3 /tmp/fresh.json
    python bench.py | python scripts/check_bench_regress.py -

Exit code is non-zero if ANY regression or missing metric is flagged; the
flags print one per line so the offending series are greppable.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def latest_baseline(repo_root: str = _REPO_ROOT,
                    host_class: str | None = None
                    ) -> tuple[str, dict] | None:
    """Newest BENCH_r*.json's parsed bench dict (path, parsed); None when
    no baseline has been recorded yet (first run is a free pass).

    With ``host_class``, only baselines of the SAME host class compare —
    a laptop run diffed against a TPU-pod baseline would flag every
    series. Baselines recorded before host_class stamping act as
    wildcards (they match any fresh host) rather than being skipped,
    so the gate keeps teeth across the transition."""
    paths = sorted(glob.glob(os.path.join(repo_root, "BENCH_r*.json")))
    for path in reversed(paths):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        # recorded files wrap the bench line under "parsed"; accept a bare
        # bench dict too so old/raw captures also work as baselines
        parsed = doc.get("parsed", doc)
        bhost = parsed.get("host_class")
        if host_class is None or bhost is None or bhost == host_class:
            return path, parsed
    return None


def flatten_throughput(bench: dict) -> dict[str, float]:
    """{series name: value} for every throughput figure in a bench dict:
    the top-level geomean plus each detail entry's *_per_sec keys."""
    out: dict[str, float] = {}
    if isinstance(bench.get("value"), (int, float)):
        out["value"] = float(bench["value"])
    for dname, d in (bench.get("detail") or {}).items():
        if not isinstance(d, dict):
            continue
        for k, v in d.items():
            if k.endswith("_per_sec") and isinstance(v, (int, float)):
                out[f"{dname}.{k}"] = float(v)
    return out


def compare(fresh: dict, baseline: dict, threshold: float = 0.2
            ) -> list[str]:
    """Flags (empty = clean): >threshold throughput drops vs baseline and
    baseline series missing from the fresh run."""
    flags: list[str] = []
    # comparability gate: the metric name encodes scale factor + platform
    # (tpch_sf0.5_cpu_...), so differing names means the runs measured
    # different configurations — flag, don't diff apples to oranges
    bname, fname = baseline.get("metric", ""), fresh.get("metric", "")
    if bname and fname and bname != fname:
        flags.append(
            f"config mismatch: baseline {bname!r} vs fresh {fname!r} "
            "(not comparable)")
        return flags
    base_t = flatten_throughput(baseline)
    fresh_t = flatten_throughput(fresh)
    for name, bval in sorted(base_t.items()):
        fval = fresh_t.get(name)
        if fval is None:
            flags.append(f"missing metric: {name} (baseline {bval:g})")
            continue
        if bval > 0 and fval < bval * (1.0 - threshold):
            drop = 100.0 * (1.0 - fval / bval)
            flags.append(
                f"regression: {name} {bval:g} -> {fval:g} "
                f"(-{drop:.1f}% > {threshold:.0%} threshold)")
    flags.extend(overload_oracle_flags(fresh))
    flags.extend(fanout_oracle_flags(fresh))
    flags.extend(views_oracle_flags(fresh))
    flags.extend(coalesce_oracle_flags(fresh))
    flags.extend(warmup_oracle_flags(fresh))
    return flags


def overload_oracle_flags(fresh: dict) -> list[str]:
    """The multi-tenant overload oracle is pass/fail, not a trend: when
    the fresh run carries ``mixed_load.overload_*`` figures, a false
    oracle bool flags regardless of any throughput threshold (goodput
    collapsing past saturation, untyped errors, or a noisy neighbor
    breaking per-tenant p99 isolation are correctness failures)."""
    ml = (fresh.get("detail") or {}).get("mixed_load")
    if not isinstance(ml, dict) or "overload_oracle_ok" not in ml:
        return []
    flags = []
    for key, what in (
            ("overload_oracle_goodput_ok",
             "goodput fell below 80% of saturation past the knee"),
            ("overload_oracle_typed_ok",
             "untyped errors (or zero shed) under overload"),
            ("overload_oracle_isolation_ok",
             "noisy neighbor pushed well-behaved p99 queue-wait past "
             "2x its solo baseline"),
    ):
        if not ml.get(key, True):
            flags.append(f"overload oracle: {what} "
                         f"(mixed_load.{key} = false)")
    if not ml["overload_oracle_ok"] and not flags:
        flags.append("overload oracle: mixed_load.overload_oracle_ok = "
                     "false")
    return flags


def fanout_oracle_flags(fresh: dict) -> list[str]:
    """The changefeed fan-out oracle is pass/fail, not a trend: when the
    fresh run carries ``fanout.*`` figures, a false oracle bool flags
    regardless of any throughput threshold (a subscriber losing or
    duplicating a version after dedup, or buffer bytes leaking past hub
    close, are correctness failures)."""
    fo = (fresh.get("detail") or {}).get("fanout")
    if not isinstance(fo, dict) or "fanout_oracle_ok" not in fo:
        return []
    if not fo["fanout_oracle_ok"]:
        return ["fanout oracle: a subscriber lost or duplicated a version "
                "after (ts, key) dedup, or fan-out buffer bytes leaked "
                "past hub close (detail.fanout.fanout_oracle_ok = false)"]
    return []


def views_oracle_flags(fresh: dict) -> list[str]:
    """The matview oracle is pass/fail, not a trend: when the fresh run
    carries ``views.*`` figures, a false oracle bool flags regardless of
    any throughput threshold (a standing view drifting from its defining
    query's rescan, or per-view dispatches creeping back into the flush
    path, are correctness failures)."""
    vw = (fresh.get("detail") or {}).get("views")
    if not isinstance(vw, dict) or "views_oracle_ok" not in vw:
        return []
    flags = []
    if not vw["views_oracle_ok"]:
        flags.append("views oracle: a sampled materialized view was not "
                     "bit-identical to a fresh rescan of its defining "
                     "query (detail.views.views_oracle_ok = false)")
    if not vw.get("views_dispatch_ok", True):
        flags.append("views oracle: flush cost scaled with the view count "
                     "or fell back to base rescans on the steady path "
                     "(detail.views.views_dispatch_ok = false)")
    return flags


def coalesce_oracle_flags(fresh: dict) -> list[str]:
    """The batch-coalescing oracle is pass/fail, not a trend: when the
    fresh run carries ``mixed_load.coalesce_*`` figures, a false oracle
    bool flags regardless of any throughput threshold (a coalesced op
    returning different bytes than its solo execution, or typed per-key
    errors leaking across sessions in a merged train, are correctness
    failures)."""
    ml = (fresh.get("detail") or {}).get("mixed_load")
    if not isinstance(ml, dict) or "coalesce_oracle_ok" not in ml:
        return []
    flags = []
    if not ml["coalesce_oracle_ok"]:
        flags.append("coalesce oracle: coalesced execution was not "
                     "bit-identical to per-session solo batches "
                     "(detail.mixed_load.coalesce_oracle_ok = false)")
    if ml.get("coalesce_errors", 0):
        flags.append(f"coalesce oracle: {ml['coalesce_errors']} op(s) "
                     "errored during the coalesce A/B "
                     "(detail.mixed_load.coalesce_errors != 0)")
    return flags


def warmup_oracle_flags(fresh: dict) -> list[str]:
    """The warm-menu oracle is pass/fail, not a trend: when the fresh run
    carries ``warmup.*`` figures, a warmed kernel returning different
    bytes than a cold-compiled one, or the menu failing to pre-mint the
    ladder (serving-path compiles > 0 with the menu on), flags regardless
    of any throughput threshold."""
    wu = (fresh.get("detail") or {}).get("warmup")
    if not isinstance(wu, dict):
        return []
    flags = []
    if not wu.get("menu_oracle_ok", True):
        flags.append("warmup oracle: menu-warmed results were not "
                     "bit-identical to cold-compiled results "
                     "(detail.warmup.menu_oracle_ok = false)")
    if wu.get("serving_compiles_on", 0):
        flags.append(f"warmup oracle: {wu['serving_compiles_on']} "
                     "serving-path compile(s) with the menu on — the AOT "
                     "ladder missed shapes it promises to cover "
                     "(detail.warmup.serving_compiles_on != 0)")
    return flags


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="flag >threshold throughput regressions vs the newest "
                    "recorded BENCH_r*.json")
    ap.add_argument("fresh", help="fresh bench JSON file, or - for stdin")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="fractional drop that counts as a regression "
                         "(default 0.2 = 20%%)")
    ap.add_argument("--baseline", default=None,
                    help="explicit baseline file (default: newest "
                         "BENCH_r*.json in the repo root)")
    args = ap.parse_args(argv)

    raw = (sys.stdin.read() if args.fresh == "-"
           else open(args.fresh, encoding="utf-8").read())
    # bench.py's contract is ONE JSON line, but stderr passthrough means a
    # captured file may carry '#' progress lines — take the last JSON line
    fresh = None
    for line in raw.strip().splitlines():
        line = line.strip()
        if line.startswith("{"):
            fresh = json.loads(line)
    if fresh is None:
        print("no JSON object found in fresh input", file=sys.stderr)
        return 2

    if args.baseline is not None:
        with open(args.baseline, encoding="utf-8") as f:
            doc = json.load(f)
        bpath, baseline = args.baseline, doc.get("parsed", doc)
    else:
        found = latest_baseline(host_class=fresh.get("host_class"))
        if found is None:
            print("no comparable BENCH_r*.json baseline recorded "
                  f"(host_class {fresh.get('host_class')!r}); nothing to "
                  "compare")
            return 0
        bpath, baseline = found

    flags = compare(fresh, baseline, args.threshold)
    if flags:
        print(f"bench regressions vs {os.path.basename(bpath)}:")
        for fl in flags:
            print(f"  {fl}")
        return 1
    n = len(flatten_throughput(baseline))
    print(f"ok: {n} throughput series within {args.threshold:.0%} of "
          f"{os.path.basename(bpath)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
