"""Batch coalescing.

Reference: sql-plugin/.../GpuCoalesceBatches.scala (GpuCoalesceBatches:656,
AbstractGpuCoalesceIterator:237, CoalesceGoal hierarchy :156-228 —
TargetSize / RequireSingleBatch). Small batches starve the MXU/VPU exactly
the way they starve a GPU, so operators declare a goal and the planner
inserts this exec to meet it. Concatenation is the scatter kernel in
exec/common (cudf Table.concatenate analogue).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional

import jax.numpy as jnp

from ..batch import ColumnarBatch, Schema, bucket_capacity
from .base import Exec, UnaryExec
from .common import concat_batches_encoded


@dataclass(frozen=True)
class CoalesceGoal:
    pass


@dataclass(frozen=True)
class TargetSize(CoalesceGoal):
    """Accumulate up to this many bytes per output batch (reference:
    TargetSize(spark.rapids.sql.batchSizeBytes))."""

    bytes: int = 512 << 20


@dataclass(frozen=True)
class RequireSingleBatch(CoalesceGoal):
    """The consumer needs all rows in one batch (global sort, build side of
    a broadcast join…)."""


class CoalesceBatchesExec(UnaryExec):
    def __init__(self, child: Exec, goal: CoalesceGoal = TargetSize(),
                 max_rows: int = 1 << 22):
        super().__init__(child)
        self.goal = goal
        self.max_rows = max_rows
        self.metrics["numInputBatches"] = type(self.metrics["opTime"])(
            "numInputBatches")

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def _flush(self, pending: List[ColumnarBatch]) -> ColumnarBatch:
        if len(pending) == 1:
            return pending[0]
        cap = bucket_capacity(sum(b.capacity for b in pending))
        # eager boundary: the coalesce keeps string columns' dictionary
        # codes instead of decoding to padded bytes at the first concat
        return concat_batches_encoded(pending, cap)

    @property
    def produces_single_batch(self) -> bool:
        return isinstance(self.goal, RequireSingleBatch) \
            or self.child.produces_single_batch

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        pending: List[ColumnarBatch] = []
        pending_bytes = 0
        target = self.goal.bytes if isinstance(self.goal, TargetSize) else None
        for batch in self.child.execute_partition(p):
            self.metrics["numInputBatches"].add(1)
            b = batch.size_bytes()
            # RequireSingleBatch (target is None) never flushes mid-stream:
            # the whole partition concatenates into one output batch
            if target is not None and pending and (
                    pending_bytes + b > target
                    or sum(p.capacity for p in pending) + batch.capacity
                    > self.max_rows):
                yield self._flush(pending)
                pending, pending_bytes = [], 0
            pending.append(batch)
            pending_bytes += b
        if pending:
            yield self._flush(pending)


class CoalesceGoalError(RuntimeError):
    """A declared coalesce goal is not met by the converted plan."""


def verify_coalesce_goals(plan: Exec) -> None:
    """Planner-side verification (the 'verify' half of the contract): every
    child position whose parent declares RequireSingleBatch must be served
    by a single-batch producer (a RequireSingleBatch coalesce, or an exec
    that guarantees one batch per partition)."""
    for i, c in enumerate(plan.children):
        goal = plan.coalesce_goal_for_child(i)
        if isinstance(goal, RequireSingleBatch) and \
                not c.produces_single_batch:
            raise CoalesceGoalError(
                f"{plan.name} child {i} declares RequireSingleBatch but "
                f"{c.name} may emit multiple batches")
        verify_coalesce_goals(c)
    for extra in getattr(plan, "child_execs", []):
        verify_coalesce_goals(extra)
