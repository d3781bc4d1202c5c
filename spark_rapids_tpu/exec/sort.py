"""Sort and TopN.

Reference: sql-plugin/.../GpuSortExec.scala:83 (in-core), :246 (out-of-core
merge of spilled runs), SortUtils.scala GpuSorter; limit.scala
GpuTakeOrderedAndProjectExec.

TPU-native design: every sort key is normalized into rank-preserving unsigned
words (exec/common.sort_operands) and ONE multi-operand `lax.sort` orders any
schema — ints, floats (NaN greatest, Spark order), decimals, strings — in a
single fused XLA op, instead of cudf's orderBy dispatch. Global sort = local
sort per batch + device merge of runs (concat + one more sort; an N-way
priority-queue merge like the reference's OOC iterator arrives with spill).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..expressions.base import EvalContext, Expression, raw_eval
from .base import Exec, UnaryExec
from .basic import bind_all
from .common import KernelPrograms, concat_batches, cut_to_rows, gather, \
    gather_column, slice_batch, sort_permutation


#: a batch of at most this many slots is sorted as it stands: its key
#: passes cost less than the host read that would size it by its rows
_SORT_AS_IS_SLOTS = 1 << 14


@dataclass(frozen=True)
class SortOrder:
    """A sort key: expression + direction + null ordering (Spark SortOrder).

    Spark defaults: ascending nulls first, descending nulls last.
    """

    child: Expression
    descending: bool = False
    nulls_first: Optional[bool] = None

    @property
    def effective_nulls_first(self) -> bool:
        if self.nulls_first is None:
            return not self.descending
        return self.nulls_first

    def bind(self, schema: Schema) -> "SortOrder":
        return SortOrder(self.child.bind(schema), self.descending,
                         self.nulls_first)


def asc(e: Expression) -> SortOrder:
    return SortOrder(e, False)


def desc(e: Expression) -> SortOrder:
    return SortOrder(e, True)


def sort_batch(batch: ColumnarBatch, orders: Sequence[SortOrder],
               ctx: EvalContext = EvalContext()) -> ColumnarBatch:
    """Stable in-core sort of one batch (jit-traceable)."""
    live = batch.row_mask()
    # raw_eval: a dictionary-encoded string key sorts on its codes, one
    # lane (within one batch code order is string order)
    key_cols = [raw_eval(o.child, batch, ctx) for o in orders]
    perm = sort_permutation(batch, key_cols,
                            [o.descending for o in orders],
                            [o.effective_nulls_first for o in orders])
    return gather(batch, perm, batch.num_rows, live)


class SortExec(UnaryExec):
    def coalesce_goal_for_child(self, i):
        from .coalesce import TargetSize
        return TargetSize()

    @property
    def produces_single_batch(self):
        return self.global_sort

    def __init__(self, orders: Sequence[SortOrder], child: Exec,
                 global_sort: bool = True, ctx: Optional[EvalContext] = None,
                 max_rows: int = 1 << 22):
        super().__init__(child, ctx)
        self.orders = [o.bind(child.output_schema) for o in orders]
        self.global_sort = global_sort
        self.max_rows = max_rows
        self._sort_jit = KernelPrograms(self, ("orders",)).jit(
            "sort", lambda self, b: sort_batch(b, self.orders, self.ctx))

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    @property
    def num_partitions(self) -> int:
        return 1 if self.global_sort else self.child.num_partitions

    @property
    def planned_partitions(self) -> int:
        return 1 if self.global_sort else self.child.planned_partitions

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if not self.global_sort:
            for b in self.child.execute_partition(p):
                yield self._sort_jit(b)
            return
        # Global sort: accumulate input batches through the spill catalog so
        # the accumulation phase cannot blow the device budget (reference:
        # GpuOutOfCoreSortIterator spills pending batches; the final merge
        # still materializes the full result — OOC chunked merge is the
        # planned refinement). Registration AND the acquire-all merge run
        # under the OOM retry loop: a failed attempt unpins, spills, and
        # re-runs (the merge itself cannot split — the OOC path is the
        # bounded-memory fallback for oversized inputs).
        from ..memory import (acquire_with_retry, device_budget,
                              register_with_retry, with_retry_no_split)
        cat = device_budget()
        spillables = []
        schema = self.output_schema
        for cp in range(self.child.num_partitions):
            for b in self.child.execute_partition(cp):
                # registered handles start unpinned (spillable)
                spillables.append(register_with_retry(
                    b, schema, catalog=cat, name=self.name))
        if not spillables:
            return
        try:
            if len(spillables) == 1:
                yield self._sort_jit(self._cut_to_rows(acquire_with_retry(
                    spillables[0], name=self.name)))
                spillables[0].done_with()
                return

            def acquire_all():
                got = []
                try:
                    for sb in spillables:
                        got.append(sb.get())
                except BaseException:
                    for i in range(len(got)):
                        spillables[i].done_with()
                    raise
                for sb in spillables:
                    sb.done_with()
                return got

            caps = with_retry_no_split(acquire_all, catalog=cat,
                                       name=self.name)
            total_cap = sum(b.capacity for b in caps)
            if total_cap > self.max_rows:
                # out-of-core chunked merge (reference: GpuOutOfCoreSort)
                from .ooc_sort import OutOfCoreSorter
                sorter = OutOfCoreSorter(self.orders, schema,
                                         device_budget())
                yield from sorter.sort(iter(caps))
                return
            # sized by the rows held, not by the capacities
            rows = sum(int(b.num_rows) for b in caps)
            merged = concat_batches(caps, bucket_capacity(max(rows, 1)))
            yield self._sort_jit(merged)
        finally:
            for sb in spillables:
                sb.close()


    @staticmethod
    def _cut_to_rows(batch: ColumnarBatch) -> ColumnarBatch:
        """A global sort waits for all of its input anyway: read the rows
        the one batch holds and sort at THEIR capacity bucket. What is left
        of a selective filter (a hundred rows in 2^20 slots) would
        otherwise pay every key lane's pass at the filter's capacity."""
        if batch.capacity <= _SORT_AS_IS_SLOTS:
            return batch        # cheaper to sort than to wait for a count
        return cut_to_rows(batch, int(batch.num_rows))


class TakeOrderedAndProjectExec(UnaryExec):
    """TopN: per-batch sort+limit, tournament across batches, final project
    (reference: GpuTakeOrderedAndProjectExec, GpuOverrides.scala:3735)."""

    def coalesce_goal_for_child(self, i):
        from .coalesce import TargetSize
        return TargetSize()

    @property
    def produces_single_batch(self):
        return True

    def __init__(self, limit: int, orders: Sequence[SortOrder],
                 project: Optional[Sequence[Expression]], child: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.limit = limit
        self.orders = [o.bind(child.output_schema) for o in orders]
        self.project = bind_all(project, child.output_schema) if project else None
        from .basic import schema_of
        self._schema = schema_of(self.project) if self.project \
            else child.output_schema

        def topn(self, b: ColumnarBatch) -> ColumnarBatch:
            s = sort_batch(b, self.orders, self.ctx)
            n = jnp.minimum(s.num_rows, jnp.int32(self.limit))
            cut = bucket_capacity(min(self.limit, b.capacity))
            return slice_batch(s, jnp.int32(0), n, cut)

        programs = KernelPrograms(self, ("limit", "orders", "project"))
        self._topn_jit = programs.jit("topn", topn)

        def proj(self, b: ColumnarBatch) -> ColumnarBatch:
            cols = tuple(e.eval(b, self.ctx) for e in self.project)
            return ColumnarBatch(cols, b.num_rows)

        self._proj_jit = programs.jit("project", proj) \
            if self.project else None

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return 1

    planned_partitions = num_partitions    # a plan fact

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        best: Optional[ColumnarBatch] = None
        for batch in self.child.execute():
            cand = self._topn_jit(batch)
            if best is None:
                best = cand
            else:
                cap = bucket_capacity(best.capacity + cand.capacity)
                best = self._topn_jit(concat_batches([best, cand], cap))
        if best is None:
            return
        yield self._proj_jit(best) if self._proj_jit else best
