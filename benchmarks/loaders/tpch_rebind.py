"""Loader `tpch_rebind`: the `tpch` loader for a configuration that states
the guarantee `plans`: a statement that differs from one already served
only in a string pattern (Q9's COLOR) runs on the cached plan and compiles
no program. The guarantee is tried before the data is made, on `nation` (25
rows at every scale factor) through a Session of its own: a program that
compiles for a new pattern cannot run the configuration inside a run's time
(every colour of the window would compile every program of Q9 again), so
the run ends here, non-zero, with the reason. What the probe read is pinned
into the comparison that decides `correct`."""

from __future__ import annotations

from loaders import tpch

PROBE = "select count(*) from nation where n_name like '%{}%'"


def compiles_for_a_new_pattern(seed: int) -> int:
    """Programs compiled by a statement whose pattern was never sent, after
    two statements of the same shape have settled its plan."""
    from cockroach_tpu.bench import tpch as gen
    from cockroach_tpu.flow import dispatch
    from cockroach_tpu.sql import Session

    sess = Session(gen.gen_tpch(sf=0.001, seed=seed))
    try:
        sess.execute(PROBE.format("A"))
        sess.execute(PROBE.format("A"))
        c0 = dispatch.compiles()
        sess.execute(PROBE.format("B"))
        return dispatch.compiles() - c0
    finally:
        sess.close()


def load(config: dict, seed: int, workdir: str) -> tpch.Loaded:
    new = compiles_for_a_new_pattern(seed)
    if new:
        raise SystemExit(
            f"loaders/tpch_rebind.py: configuration {config['name']!r} "
            f"guarantees that a new string pattern runs on the cached plan; "
            f"this program compiled {new} program(s) for one")
    loaded = tpch.load(config, seed, workdir)
    loaded.pinned.append({"name": "compiles_for_a_new_pattern",
                          "value": float(new), "limit": 0.0})
    return loaded
