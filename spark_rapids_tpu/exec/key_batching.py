"""Key-batching: split partitions into bounded, key-complete batches.

Reference: GpuKeyBatchingIterator.scala (236 LoC) — the reference splits a
stream of batches on group-key boundaries so per-key operators (windows)
never see a key straddling two batches and never hold an unbounded batch.

TPU-first shape: a stream partition that fits the row target goes on as ONE
batch, unsorted: whole as it stands, it holds every group whole (the window
above sorts on partition and order keys whatever order it is given). Only a
partition over the target is sorted by the keys ONCE (one key sort, a
dictionary-encoded string key on its codes) and the group boundary
positions come back to the host, which picks cut points on whole groups
closest to the row target. Each emitted batch is a static-shape slice, so
downstream kernels compile once per bucket size. The concatenation is sized
by the rows the batches hold (the one host read), not by their capacities.

What this bounds: the DOWNSTREAM operator's per-batch working set (window
scans allocate several columns per expression over the batch). The
batching sort itself still materializes the partition once; a spill-aware
chunked pre-sort (through OutOfCoreSorter) is the refinement if window
inputs ever exceed HBM on their own.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..expressions.base import EvalContext, Expression, raw_eval
from ..trace import host_read
from .base import UnaryExec
from .basic import bind_all
from .common import (KernelPrograms, adjacent_equal, concat_batches_encoded,
                     cut_to_rows, gather_column, lex_sort_permutation,
                     slice_batch, sort_operands)


class KeyBatchingExec(UnaryExec):
    """Re-chunk each input partition into batches that hold WHOLE key
    groups and approach ``target_rows``. Downstream execs can detect the
    guarantee through ``key_complete_for`` and process batch-at-a-time
    instead of concatenating the partition."""

    def __init__(self, keys: Sequence[Expression], child,
                 target_rows: int = 1 << 20,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.keys = bind_all(keys, child.output_schema)
        self.target_rows = target_rows

        def prep(self, batch: ColumnarBatch):
            # raw_eval: a dictionary-encoded string key sorts and compares
            # on its codes (one lane; within one batch code order is string
            # order)
            key_cols = [raw_eval(e, batch, self.ctx) for e in self.keys]
            live = batch.row_mask()
            k = len(key_cols)
            from .common import may_skip_null_lane
            nullable = [not may_skip_null_lane(e) for e in self.keys]
            ops = sort_operands(key_cols, [False] * k, [True] * k, live,
                                nullable)
            perm = lex_sort_permutation(ops)
            cols = tuple(gather_column(c, perm) for c in batch.columns)
            skeys = [gather_column(c, perm) for c in key_cols]
            sorted_live = jnp.arange(batch.capacity) < batch.num_rows
            new_group = sorted_live & ~adjacent_equal(skeys)
            return ColumnarBatch(cols, batch.num_rows), new_group

        programs = KernelPrograms(self, ("keys",))
        self._prep_jit = programs.jit("prep", prep)
        self._slice_jit = programs.jit(
            "slice",
            lambda self, b, start, count, cap: slice_batch(b, start, count,
                                                           cap),
            static_argnums=3)

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    @property
    def key_complete_for(self) -> str:
        """Identity of the guarantee: every emitted batch contains whole
        groups of these (bound) keys."""
        return repr(list(self.keys))

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from .. import trace as qtrace
        batches = list(self.child.execute_partition(p))
        # a host read: the rows each batch holds
        rows = [int(n) for n in host_read("keyRows",
                                          [b.num_rows for b in batches])]
        total = sum(rows)
        if total == 0:
            return
        held = [b for b, r in zip(batches, rows) if r]
        # sized by the rows, not by the capacities
        if len(held) > 1:
            merged = concat_batches_encoded(held, bucket_capacity(total))
        else:
            merged = cut_to_rows(held[0], total)
        qtrace.count(keyBatchRowsIn=total)
        if total <= self.target_rows:
            # the whole partition in one batch holds every group whole as it
            # stands: nothing to cut, so nothing to sort (the window above
            # sorts on partition AND order keys whatever order it is given)
            yield merged
            return
        qtrace.count(keyBatchSlotsSorted=int(merged.capacity))
        srt, new_group = self._prep_jit(merged)
        # group start positions -> host; cut on whole groups at the LAST
        # start that keeps the batch <= target_rows (a batch exceeds the
        # target only when one single group does — the same bound
        # GpuKeyBatchingIterator guarantees)
        new_group, n = host_read("keyGroups", (new_group, srt.num_rows))
        starts = np.flatnonzero(new_group)
        n = int(n)
        cuts: List[int] = [0]
        prev = 0
        for s in list(starts[1:]) + [n]:
            if s - cuts[-1] > self.target_rows and prev > cuts[-1]:
                cuts.append(int(prev))
            prev = int(s)
        if cuts[-1] != n:
            cuts.append(n)
        qtrace.count(keyBatchCuts=len(cuts) - 2)
        for lo, hi in zip(cuts, cuts[1:]):
            if hi > lo:
                yield self._slice_jit(srt, lo, hi - lo,
                                      bucket_capacity(hi - lo))
