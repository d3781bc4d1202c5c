"""Operator base classes and metrics.

Reference: sql-plugin/.../GpuExec.scala:211 (`GpuExec` trait) and its metric
machinery at GpuExec.scala:45-135 (ESSENTIAL/MODERATE/DEBUG GpuMetric levels).

Execution model: pull-based `Iterator[ColumnarBatch]` per partition, exactly
like the reference (SURVEY.md §3.3) — but where the reference dispatches one
JNI kernel per op per batch, here each operator's per-batch computation is a
traced jnp function, so chains of narrow operators (project→filter→project)
fuse into one XLA executable per capacity bucket.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import pyarrow as pa

from ..batch import ColumnarBatch, Schema, to_arrow
from ..expressions.base import EvalContext

ESSENTIAL, MODERATE, DEBUG = 0, 1, 2


@dataclass
class Metric:
    """Reference: GpuMetric over Spark SQLMetric (GpuExec.scala:45)."""

    name: str
    level: int = MODERATE
    value: int = 0
    _lazy: list = field(default_factory=list)

    def add(self, v) -> None:
        self.value += int(v)

    def add_lazy(self, device_scalar) -> None:
        """Accumulate a traced/device scalar WITHOUT forcing a sync; it is
        resolved when the metric is read (reference: GPU-side metric
        accumulation flushed at task end)."""
        self._lazy.append(device_scalar)

    def total(self) -> int:
        if self._lazy:
            self.value += sum(int(x) for x in self._lazy)
            self._lazy.clear()
        return self.value


class Exec:
    """A physical operator. Subclasses define `output_schema` and
    `do_execute() -> Iterator[ColumnarBatch]`."""

    #: keyed programs this exec stated when it was built that the program
    #: table already had / had to make (``common.KernelPrograms.jit``);
    #: its first operator span carries them
    program_hits = 0
    program_misses = 0

    def __init__(self, children: Sequence["Exec"] = (),
                 ctx: EvalContext = EvalContext()):
        self.children: Tuple[Exec, ...] = tuple(children)
        self.ctx = ctx
        self.metrics: Dict[str, Metric] = {
            "numOutputRows": Metric("numOutputRows", ESSENTIAL),
            "numOutputBatches": Metric("numOutputBatches", MODERATE),
            "opTime": Metric("opTime", MODERATE),
        }

    # ---- plan surface ----
    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError(type(self).__name__)

    @property
    def num_partitions(self) -> int:
        """Spark RDD partition count. Narrow operators preserve their
        child's; exchanges define their own."""
        return self.children[0].num_partitions if self.children else 1

    @property
    def planned_partitions(self) -> int:
        """The partition count the PLAN states, for the planner to ask:
        it reads plan facts only and runs nothing, where ``num_partitions``
        (what executing parents call) may materialize an exchange to
        answer. It is over 1 wherever ``num_partitions`` can be (run-time
        coalescing only lowers a count, a stood-aside exchange has one).
        An exec that overrides ``num_partitions`` states this rule beside
        it."""
        return self.children[0].planned_partitions if self.children else 1

    def do_execute(self) -> Iterator[ColumnarBatch]:
        """All partitions chained (single-stream consumers / collect)."""
        for p in range(self.num_partitions):
            yield from self.do_execute_partition(p)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        """One partition's batches. Default: only valid single-partition."""
        if self.num_partitions != 1 or p != 0:
            raise NotImplementedError(
                f"{self.name} does not implement per-partition execution")
        yield from self.do_execute()

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)

    # ---- coalesce-goal contract (GpuCoalesceBatches.scala:156-228) ----
    def coalesce_goal_for_child(self, i: int):
        """The batch-size contract this operator declares for child ``i``:
        None (no requirement), TargetSize (feed me batches near the
        configured size) or RequireSingleBatch (I need the whole partition
        in one batch). The planner's transition pass inserts
        CoalesceBatchesExec to meet declared goals and verifies them."""
        return None

    @property
    def produces_single_batch(self) -> bool:
        """True when every partition of this exec yields at most ONE batch
        (satisfies RequireSingleBatch without a coalesce)."""
        return False

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        """Iterate one partition, maintaining the op's metrics: batch and
        row counts plus opTime (ns spent INSIDE this operator's iterator,
        including its children — the reference's NS_TIMING convention).
        With query tracing active the partition's iteration is one
        operator span (same name as the metric prefix) that closes when
        the consumer exhausts or abandons the iterator, and each pull is
        a *pull frame* of it (``trace.OperatorSpan``): what this thread
        records inside a pull is this operator's, what it records between
        pulls is the consumer's."""
        from .. import trace as qtrace
        it = self.do_execute_partition(p)
        op = qtrace.open_operator(self.name, p)
        if op is not None and (self.program_hits or self.program_misses):
            # once an exec, so that a query's spans add up to its programs
            op.note_programs(self.program_hits, self.program_misses)
            self.program_hits = self.program_misses = 0
        op_time = self.metrics["opTime"]
        try:
            while True:
                t0 = time.perf_counter_ns()
                if op is not None:
                    op.enter()
                try:
                    # (the batch yielded last stays referenced until this
                    # returns the next: buffers are freed as they were
                    # before there were pull frames)
                    batch = next(it)
                except BaseException as e:
                    dt = time.perf_counter_ns() - t0
                    if op is not None:
                        op.exit(dt)
                    if isinstance(e, StopIteration):
                        op_time.add(dt)
                        return
                    raise
                dt = time.perf_counter_ns() - t0
                op_time.add(dt)
                if op is not None:
                    op.exit(dt, batch)
                self.metrics["numOutputBatches"].add(1)
                self.metrics["numOutputRows"].add_lazy(batch.num_rows)
                yield batch
        finally:
            if op is not None:
                op.close()

    def collect_metrics(self, max_level: int = DEBUG) -> Dict[str, int]:
        """Aggregate this subtree's metrics up to a level (the
        SQLMetrics→driver roll-up; level filter = metricsLevel conf)."""
        out: Dict[str, int] = {}

        def walk(e: "Exec"):
            for name, m in e.metrics.items():
                v = m.total()
                if m.level <= max_level and v:
                    out[f"{e.name}.{name}"] = \
                        out.get(f"{e.name}.{name}", 0) + v
            for c in e.children:
                walk(c)
        walk(self)
        return out

    def close(self) -> None:
        """Release catalog-registered resources after the query finishes
        (the reference's closeOnExcept/TaskCompletion hooks). Subclasses
        override do_close(); the tree walk happens here."""
        for c in self.children:
            c.close()
        self.do_close()

    def do_close(self) -> None:
        pass

    # ---- debugging / explain ----
    @property
    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + f"*{self.name} [{self.output_schema}]\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def __repr__(self):
        return self.tree_string().rstrip()


class LeafExec(Exec):
    def __init__(self, ctx: EvalContext = EvalContext()):
        super().__init__((), ctx)


class UnaryExec(Exec):
    def __init__(self, child: Exec, ctx: Optional[EvalContext] = None):
        super().__init__((child,), ctx or child.ctx)

    @property
    def child(self) -> Exec:
        return self.children[0]


class BinaryExec(Exec):
    def __init__(self, left: Exec, right: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__((left, right), ctx or left.ctx)

    @property
    def left(self) -> Exec:
        return self.children[0]

    @property
    def right(self) -> Exec:
        return self.children[1]


def iter_subplan_tables(plan: Exec):
    """The "subplan produced" side of the collect seam: run a plan and
    yield one host Arrow table per output batch, in partition order.
    Stage re-planning and subplan result sharing materialize interior
    boundaries through this, so a captured subtree output is exactly
    what assemble_result() would have consumed."""
    from .. import trace as qtrace
    schema = plan.output_schema
    for b in plan.execute():
        # the query's one forced wait for the device
        with qtrace.span("result.d2h", kind="transfer") as sp:
            t = to_arrow(b, schema)
            if sp is not None:
                sp.attrs["bytes"] = t.nbytes
        yield t


def assemble_result(tables, schema) -> pa.Table:
    """The "query assembled" side of the collect seam: concatenate the
    per-batch tables (empty input keeps the declared schema)."""
    tables = list(tables)
    if not tables:
        from .. import types as T
        return pa.table({f.name: pa.array([], type=T.to_arrow(f.dtype))
                         for f in schema})
    return pa.concat_tables(tables)


def collect(plan: Exec) -> pa.Table:
    """Run a plan and pull the result to the host as one Arrow table — the
    test/collect boundary (reference: GpuColumnarToRowExec)."""
    return assemble_result(iter_subplan_tables(plan), plan.output_schema)
