"""Out-of-core sort: spilled sorted runs + bounded chunked merge.

Reference: GpuSortExec.scala:246 GpuOutOfCoreSortIterator — sort each input
batch, spill the runs, then merge with a priority queue of spilled chunks
so device memory stays bounded. Same algorithm here with device-friendly
primitives: the "priority queue" becomes a pairwise CHUNKED MERGE TREE —
two sorted runs merge chunk-at-a-time (concat 2 chunks → one lax.sort →
emit only rows ≤ the smaller of the two chunk maxima, which are provably
globally placed), so peak device memory per merge is 4 chunks regardless
of run size. log2(runs) passes; every intermediate run lives in the spill
catalog.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..memory import (BufferCatalog, SpillableBatch, acquire_with_retry,
                      register_with_retry)
from ..trace import host_read
from .common import KernelPrograms, compact, concat_batches, jit_named, \
    slice_batch, sort_operands
from .sort import SortOrder, sort_batch


class _Run:
    """A sorted run stored as spillable fixed-size chunks."""

    def __init__(self, catalog: BufferCatalog, schema: Schema):
        self.catalog = catalog
        self.schema = schema
        self.chunks: List[SpillableBatch] = []

    def append(self, batch: ColumnarBatch) -> None:
        # register() leaves the handle unpinned (spillable) already; the
        # registration reserve runs under the OOM retry loop
        self.chunks.append(register_with_retry(
            batch, self.schema, catalog=self.catalog, name="ooc_sort.run"))

    def close(self) -> None:
        for c in self.chunks:
            c.close()
        self.chunks = []


class OutOfCoreSorter:
    """Merges any number of rows through a bounded device footprint."""

    def __init__(self, orders: Sequence[SortOrder], schema: Schema,
                 catalog: BufferCatalog, chunk_rows: int = 1 << 16):
        self.orders = orders
        self.schema = schema
        self.catalog = catalog
        self.chunk_rows = chunk_rows
        programs = KernelPrograms(self, ("orders",))
        self._sort_jit = programs.jit(
            "sort", lambda self, b: sort_batch(b, self.orders))
        self._split_jit = programs.jit("split", type(self)._split_kernel,
                                       static_argnums=(2,))
        self._slice_jit = jit_named("slice_batch", slice_batch,
                                    static_argnums=(3,))

    # ------------------------------------------------------------------

    def _key_rank_last(self, batch: ColumnarBatch):
        """uint operands of the LAST live row (a chunk's max key)."""
        # evaluate order keys; rows are already sorted, take row num_rows-1
        last = jnp.maximum(batch.num_rows - 1, 0)
        cols = [o.child.eval(batch) for o in self.orders]
        ops = sort_operands(cols, [o.descending for o in self.orders],
                            [o.effective_nulls_first for o in self.orders],
                            batch.row_mask())[1:]   # drop liveness operand
        return [op[last] for op in ops]

    def _split_kernel(self, merged: ColumnarBatch, bound_words, cap: int):
        """Emit rows whose key ≤ bound (they are globally placed); keep the
        rest. Returns (emit_batch, keep_batch)."""
        cols = [o.child.eval(merged) for o in self.orders]
        ops = sort_operands(cols, [o.descending for o in self.orders],
                            [o.effective_nulls_first for o in self.orders],
                            merged.row_mask())[1:]
        le = jnp.zeros(merged.capacity, bool)
        gt = jnp.zeros(merged.capacity, bool)
        decided = jnp.zeros(merged.capacity, bool)
        for op, bw in zip(ops, bound_words):
            gt = gt | (~decided & (op > bw))
            decided = decided | (op != bw)
        le = ~gt
        live = merged.row_mask()
        emit = compact(merged, le & live)
        keep = compact(merged, ~le & live)
        return emit, keep

    # ------------------------------------------------------------------

    def _append_chunked(self, run: _Run, batch: ColumnarBatch) -> None:
        """Re-chunk to chunk_rows so merge working sets stay bounded at
        every tree level (otherwise output chunks double per pass)."""
        cap = bucket_capacity(self.chunk_rows)
        if batch.capacity <= cap:
            run.append(batch)
            return
        n = int(host_read("oocRows", batch.num_rows))
        off = 0
        while off < max(n, 1):
            piece = self._slice_jit(batch, jnp.int32(off),
                                    jnp.int32(cap), cap)
            if host_read("oocRows", piece.num_rows) > 0 or n == 0:
                run.append(piece)
            off += cap
            if n == 0:
                break

    def make_run(self, batches: Iterator[ColumnarBatch]) -> List[_Run]:
        """Phase 1: per-batch device sort, spill each sorted run."""
        runs: List[_Run] = []
        for b in batches:
            run = _Run(self.catalog, self.schema)
            self._append_chunked(run, self._sort_jit(b))
            runs.append(run)
        return runs

    def merge_two(self, a: _Run, b: _Run) -> _Run:
        """Chunked 2-way merge with bounded device residency."""
        out = _Run(self.catalog, self.schema)
        ai = bi = 0
        buf: Optional[ColumnarBatch] = None   # carried unsafe remainder
        while ai < len(a.chunks) or bi < len(b.chunks):
            pieces = [buf] if buf is not None else []
            bounds = []
            if ai < len(a.chunks):
                ca = acquire_with_retry(a.chunks[ai], name="ooc_sort.merge")
                a.chunks[ai].done_with()
                ai += 1
                pieces.append(ca)
                bounds.append((self._key_rank_last(ca), ai >= len(a.chunks)))
            if bi < len(b.chunks):
                cb = acquire_with_retry(b.chunks[bi], name="ooc_sort.merge")
                b.chunks[bi].done_with()
                bi += 1
                pieces.append(cb)
                bounds.append((self._key_rank_last(cb), bi >= len(b.chunks)))
            cap = bucket_capacity(sum(p.capacity for p in pieces))
            merged = self._sort_jit(concat_batches(pieces, cap)) \
                if len(pieces) > 1 else self._sort_jit(pieces[0])
            a_done = ai >= len(a.chunks)
            b_done = bi >= len(b.chunks)
            if a_done and b_done:
                self._append_chunked(out, merged)
                buf = None
                break
            # safe bound: the smaller chunk-max among runs that still have
            # unloaded data — rows ≤ it cannot be displaced later
            exhausted_sides = []
            live_bounds = []
            if not a_done or not b_done:
                # bound of the run we just loaded from decides safety; use
                # the minimum of loaded-chunk maxima of NON-exhausted runs
                for words, exhausted in bounds:
                    if not exhausted:
                        live_bounds.append(words)
            if not live_bounds:
                self._append_chunked(out, merged)
                buf = None
                continue
            bound = live_bounds[0]
            for w in live_bounds[1:]:
                bound = _lex_min(bound, w)
            emit, keep = self._split_jit(merged, bound, merged.capacity)
            if host_read("oocRows", emit.num_rows) > 0:
                self._append_chunked(out, emit)
            buf = keep if host_read("oocRows", keep.num_rows) > 0 else None
        if buf is not None and host_read("oocRows", buf.num_rows) > 0:
            self._append_chunked(out, buf)
        a.close()
        b.close()
        return out

    def sort(self, batches: Iterator[ColumnarBatch]
             ) -> Iterator[ColumnarBatch]:
        runs = self.make_run(batches)
        if not runs:
            return
        while len(runs) > 1:
            nxt: List[_Run] = []
            for i in range(0, len(runs) - 1, 2):
                nxt.append(self.merge_two(runs[i], runs[i + 1]))
            if len(runs) % 2:
                nxt.append(runs[-1])
            runs = nxt
        final = runs[0]
        for sb in final.chunks:
            yield acquire_with_retry(sb, name="ooc_sort.emit")
            sb.done_with()
        final.close()


def _lex_min(a, b):
    """Lexicographic min of two key-word tuples (traced)."""
    out = []
    a_lt = jnp.zeros((), bool)
    decided = jnp.zeros((), bool)
    for x, y in zip(a, b):
        a_lt = a_lt | (~decided & (x < y))
        decided = decided | (x != y)
    for x, y in zip(a, b):
        out.append(jnp.where(a_lt, x, y))
    return out
