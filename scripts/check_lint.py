"""crlint tree gate — the static-analysis suite must be clean at HEAD.

Runs every crlint pass (cockroach_tpu/lint/: host-sync, raw-jit,
broad-except, unused-import, tracing-api, lock-order, shared-state,
mem-accounting, fault-coverage, untimed-wait, recompile-hazard,
race-coverage, unknown-pragma) over the package, the
scripts/ directory, the tests/ tree, and the repo-root entry points
(__graft_entry__.py, chip_smoke.py) and fails on any unsuppressed finding.
This is the nogo/roachvet analog: the lint rules are only worth having if the tree
is kept at zero findings, so the gate rides in tier-1 next to the
settings and dispatch-budget audits. Pure AST pass — nothing is
imported, so it runs without pulling in jax.

Deliberate exceptions carry an inline pragma with a mandatory reason:

    # crlint: allow-<rule>(<why this site is exempt>)

(same line, the line above, or on a `def` line to cover the function).
Silent `except Exception: pass` handlers in kv/, flow/ and server/ are
hard errors the pragma cannot suppress. Wired as a tier-1 test via
tests/test_lint.py; also runnable directly:

    python -m scripts.check_lint
    python -m cockroach_tpu.lint --rule host-sync cockroach_tpu scripts
"""

from __future__ import annotations

import pathlib
import sys


def check(repo_root: str | pathlib.Path | None = None,
          timings: dict | None = None) -> list[str]:
    """Returns a list of human-readable violations (empty = clean)."""
    from cockroach_tpu.lint import run_lint

    if repo_root is None:
        repo_root = pathlib.Path(__file__).resolve().parent.parent
    root = pathlib.Path(repo_root)
    paths = [root / "cockroach_tpu", root / "scripts", root / "tests"]
    # repo-root entry points ride along when present (fixture trees in
    # the lint tests call check() on trimmed copies without them)
    for entry in ("__graft_entry__.py", "chip_smoke.py"):
        if (root / entry).is_file():
            paths.append(root / entry)
    return [f.render() for f in run_lint(paths, timings=timings)]


def main() -> int:
    timings: dict = {}
    problems = check(timings=timings)
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    # per-pass wall time: the budget the shared TreeCache defends —
    # a regression in any single pass is attributable at a glance
    width = max((len(k) for k in timings), default=0)
    for name, secs in sorted(timings.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<{width}}  {secs:7.3f}s", file=sys.stderr)
    print(f"  {'total':<{width}}  {sum(timings.values()):7.3f}s",
          file=sys.stderr)
    if not problems:
        print("crlint clean: all passes over cockroach_tpu/, scripts/, "
              "tests/ and the repo-root entry points")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
