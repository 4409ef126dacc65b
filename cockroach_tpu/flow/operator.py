"""The Operator contract — the colexecop.Operator analog.

Reference: pkg/sql/colexecop/operator.go:21 — ``Operator { Init(ctx);
Next() coldata.Batch }``, pull-based, zero-length batch means exhausted. Here
``next_batch() -> Batch | None`` returns device-resident tiles; None means
exhausted. Device work inside an operator is jitted once per operator
instance (tiles share static shapes, so each op compiles exactly once).

Operators also surface plan-static metadata the reference carries in specs:
``output_schema`` and per-column string ``dictionaries`` (the host half of the
columnar string representation).
"""

from __future__ import annotations

import numpy as np

from ..coldata.batch import Batch, Dictionary
from ..coldata.types import Schema
from . import dispatch


class ComponentStats:
    """Per-operator execution stats — the execinfrapb.ComponentStats analog
    (execinfrapb/component_stats.proto), folded into EXPLAIN ANALYZE by
    plan/explain.py (the execstats/traceanalyzer.go role)."""

    __slots__ = ("batches", "rows", "time_s", "bytes", "kernel_dispatches",
                 "kernel_compiles", "max_mem_bytes", "spilled")

    def __init__(self):
        self.batches = 0
        self.rows = 0
        self.time_s = 0.0  # inclusive wall time in next_batch (incl. children)
        self.bytes = 0  # logical device bytes emitted (colmem accounting)
        # peak reserved bytes across this operator's memory accounts
        # (mon.BoundAccount high-water, shown as EXPLAIN ANALYZE "max mem")
        self.max_mem_bytes = 0
        # True once a memory account overflow swapped this operator to its
        # external variant (disk_spiller.go's spilled marker)
        self.spilled = False
        # XLA dispatches the whole query issued (flow/dispatch.py delta,
        # attributed to the ROOT's stats by run_operator — dispatches are
        # process-global, not attributable per operator without a sync)
        self.kernel_dispatches = 0
        # fresh XLA traces/compiles the query triggered (same root-level
        # attribution; 0 on the zero-recompile serving path)
        self.kernel_compiles = 0

    def exclusive(self, children: list["Operator"]) -> float:
        return self.time_s - sum(c.stats.time_s for c in children)


class Operator:
    """Base pull operator. Subclasses set output_schema/dictionaries in
    __init__ and implement _next().

    col_stats maps output column index -> (lo, hi) value bounds where known
    (from catalog table statistics, propagated like dictionaries). Sort and
    group-by kernels use them to bit-pack key columns into fewer sort
    operands (ops/keys.py) — the optimizer-statistics analog applied to
    kernel shape instead of plan choice."""

    output_schema: Schema
    dictionaries: dict[int, Dictionary]
    col_stats: dict[int, tuple]
    # the operator's part of its device programs' names (dispatch.jit
    # name=<KERNEL>_<role>): the class name in lower case without "op"
    # unless a class gives a shorter one. Static — never a per-query value.
    KERNEL = "operator"
    # which operator of its plan this is, set once when the tree is built
    # (plan/builder.py): `<KERNEL>.<pre-order position>` and what the plan
    # says of it (a scan's table, a join's type and sources). Static a plan,
    # never a per-query value, never part of a kernel's name or key: the
    # label of this operator's section (flow/dispatch.py). An operator built
    # outside a plan goes by its KERNEL alone.
    label: str | None = None
    what = ""
    # a tile comes out at the capacity it went in at, row for row in place
    # (the operator masks or computes columns, never moves rows): a join
    # above reads the capacity it will be handed through such links
    _passes_tiles = False
    # every tile this operator hands out has its live rows as a dense
    # prefix, mask[i] == (i < n): true only where the code that builds the
    # tile's mask guarantees it (a streaming AggregateOp) or passes such a
    # mask on untouched (ProjectOp, the fusion pass's barrier adapter). Known
    # from the plan's structure, never synced or set; a join build over
    # such a producer places its tiles at a running offset
    # (coldata/batch.py `concat_prefix`) where any other compacts them.
    # tests/test_live_prefix.py pulls every claimant's tiles and checks
    emits_live_prefix = False

    # between two runs this operator keeps nothing on the device and has
    # learned nothing: no spool, build side or emission cap,
    # only what init() makes again. A plan made of such operators alone
    # may be built more than once, a tree a concurrent session
    # (sql/plancache.py `_Entry`); one operator that says False keeps its
    # plan at one tree. Known from the operator's class, never set
    stateless_between_runs = False

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "KERNEL" not in cls.__dict__:
            cls.KERNEL = cls.__name__.strip("_").lower().removesuffix("op")

    def __init__(self):
        self.dictionaries = {}
        self.col_stats = {}
        self._initialized = False
        self.stats = ComponentStats()
        self._collect = False

    def init(self) -> None:
        """Init(ctx) analog — called once before the first next_batch."""
        self._initialized = True

    def next_batch(self) -> Batch | None:
        if not self._initialized:
            self.init()
        if not self._collect:
            with dispatch.section(self):
                return self._next()
        # one clock: the section's wall is the operator's time, the row
        # count's wait for the device included
        with dispatch.section(self, timed=True) as sec:
            b = self._next()
            if b is not None:
                # row counting forces a device sync, so exact per-operator
                # times and rows are an EXPLAIN ANALYZE-only cost (like the
                # reference's stats collection wrappers in colflow/stats.go)
                from .memory import batch_bytes

                self.stats.rows += int(np.asarray(b.mask).sum())
                self.stats.batches += 1
                self.stats.bytes += batch_bytes(b)
        self.stats.time_s += sec.wall_s
        return b

    def children(self) -> list["Operator"]:
        return []

    def collect_stats(self, enabled: bool = True) -> None:
        self._collect = enabled
        self.stats = ComponentStats()
        for c in self.children():
            c.collect_stats(enabled)

    def _next(self) -> Batch | None:
        raise NotImplementedError

    def stream_parts(self):
        """Fused-streaming contract: (source, fn, args) when this operator's
        output is a pure per-tile device function of a source's tiles —
        consumers compose the whole chain into one jit (flow/operators.py).
        None means this operator is a pipeline barrier."""
        return None

    def post_run_update(self, truncated: bool = False) -> bool:
        """End-of-query hook: adaptive operators fetch their deferred device
        counters here (ONE sync at query end, never per tile — a host sync
        stalls the pull loop for a device round trip) and update sticky
        execution choices. Returns True when this run's OUTPUT was invalid
        (e.g. a speculative emission capacity overflowed) and the runtime
        must re-run the query with the corrected choices. ``truncated``:
        an operator below overflowed, so this one's inputs were cut short
        and what it counted says nothing."""
        return False

    def close(self) -> None:
        """Closer analog (colexecop/operator.go:194)."""


class SourceOperator(Operator):
    """An operator with no inputs (scan, inbox)."""


class OneInputOperator(Operator):
    def __init__(self, child: Operator):
        super().__init__()
        self.child = child
        self.dictionaries = dict(child.dictionaries)
        self.col_stats = dict(child.col_stats)

    def init(self) -> None:
        self.child.init()
        super().init()

    def children(self) -> list[Operator]:
        return [self.child]

    def close(self) -> None:
        self.child.close()
