"""Shuffle and broadcast exchanges.

Reference: GpuShuffleExchangeExecBase.scala:152,262 (prepareBatchShuffleDependency:
partition-id eval → device slicing → serialized blocks),
GpuBroadcastExchangeExec.scala:319. This module is the DEFAULT/host-mediated
shuffle mode (SURVEY.md §2.10): per input batch, rows are split into their
target partitions' pieces ON DEVICE (``PartitioningExchangeExec.split``: one
program orders the row index by partition id and counts each partition, one
host read brings the counts, one gather a non-empty piece at its row-count
bucket), and re-coalesced on the read side. The ICI device-collective mode
lives in parallel/mesh.py; both sit behind the same exec surface the way the
reference's three shuffle modes sit behind one shuffle manager.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace as qtrace
from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..exec.base import Exec, UnaryExec
from ..exec.common import KernelPrograms, concat_batches, gather, \
    jit_named, lex_sort_permutation, slice_batch
from ..expressions.base import EvalContext
from ..memory.catalog import BufferCatalog, SpillableBatch
from .partitioning import Partitioning, RangePartitioning, SinglePartitioning

#: One reader partition = a list of (map-output partition, piece_lo, piece_hi)
#: piece ranges. This is the TPU analogue of Spark AQE's partition specs
#: (CoalescedPartitionSpec spans whole output partitions,
#: PartialReducerPartitionSpec takes a slice of one skewed partition).
ReadSpec = List[Tuple[int, int, int]]


def _coalesce_groups(counts: List[int], target_rows: int) -> List[List[int]]:
    """Greedy adjacent grouping of partitions so each group approaches
    target_rows (AQE coalesce-partitions)."""
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_rows = 0
    for p, c in enumerate(counts):
        if cur and cur_rows + c > target_rows:
            groups.append(cur)
            cur, cur_rows = [], 0
        cur.append(p)
        cur_rows += c
    if cur:
        groups.append(cur)
    return groups or [[0]]


class PartitioningExchangeExec(UnaryExec):
    """What every exchange the shuffle manager hands out shares: the
    partition count its PLAN states, standing aside, and the splitter
    (``split``: a batch into its partitions' pieces).

    The planner plants an exchange where the plan says its child MAY have
    more than one partition (``Overrides._partitioned``) and lets it stand
    aside (``aside``): if at the first pull the child turns out to have ONE
    partition (an adaptive exchange below coalesced to one), the exchange
    hands the child's batches through as its one partition, with no
    partition ids, no slices, no registration and no operator span of its
    own, and is not named among the execs that ran. The exchanges under a
    shuffled join never stand aside: both sides must hash alike."""

    #: what the consumer pulls when this exchange stands aside: its child,
    #: or the child under the coalesce the transition pass would have put
    #: between the two. None (a join's exchanges): never stands aside
    aside: Optional[Exec] = None
    #: whether the last execution stood aside (``executed_exec_names()``)
    stood_aside = False
    _standing: Optional[bool] = None     # this execution's, once asked

    def __init__(self, partitioning: Partitioning, child: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.partitioning = partitioning.bind(child.output_schema)

        def split_kernel(self, b: ColumnarBatch):
            n = self.partitioning.num_partitions
            pids = self.partitioning.partition_ids(b, self.ctx)
            # dead rows take the id n: they order last, past every piece
            ids = jnp.where(b.row_mask(), pids, n)
            perm = lex_sort_permutation([ids.astype(jnp.uint32)])
            # (a compare and a sum a partition, no scatter-add: n is
            # small and scatters are the slow primitive on this chip)
            parts = jnp.arange(n, dtype=jnp.int32)[:, None]
            return perm, jnp.sum(ids[None, :] == parts, axis=1,
                                 dtype=jnp.int32)

        def piece_kernel(self, b: ColumnarBatch, perm, off, rows,
                         cap: int) -> ColumnarBatch:
            # (padded, so that a slice near the end is not moved back)
            idx = jax.lax.dynamic_slice(jnp.pad(perm, (0, cap)), (off,),
                                        (cap,))
            return gather(b, idx, rows,
                          jnp.arange(cap, dtype=jnp.int32) < rows)

        self._split_jit = KernelPrograms(self, ("partitioning",)).jit(
            "split", split_kernel)
        # (one program a distinct piece capacity, offset and rows traced)
        self._piece_jit = KernelPrograms(self, ()).jit(
            "piece", piece_kernel, static_argnums=4)
        # one partition: its rows are the batch's first (the module's own
        # program, shared with every other ``slice_batch`` site)
        self._shrink_jit = jit_named("slice_batch", slice_batch,
                                     static_argnums=3)

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def split(self, b: ColumnarBatch
              ) -> Iterator[Tuple[int, ColumnarBatch, int]]:
        """``b``'s non-empty pieces as (partition, piece, rows), in
        partition order: each the partition's rows in ``b``'s order, at
        ``bucket_capacity(rows)`` (padding at the input's capacity would
        multiply device residency by the partition count), dictionaries
        riding along. One stable ordering of the row index by partition
        id, ONE host read (the counts of all partitions) and gathers that
        sum to about one pass over ``b``, whatever the partition count;
        ids come from the partitioning, capacities from the counts."""
        n = self.partitioning.num_partitions
        if n == 1:
            perm, counts = None, [int(qtrace.host_read("splitCounts",
                                                       b.num_rows))]
        else:
            perm, counts = self._split_jit(b)
            # the batch's ONE host read
            counts = qtrace.host_read("splitCounts", counts).tolist()
        # the piece program moves row lanes only: a dictionary is taken
        # off before it and put back, the same object, on every piece
        dicts = [(c.dict_data, c.dict_lengths) if c.is_dict else None
                 for c in b.columns]
        lanes = ColumnarBatch(tuple(
            c.replace(dict_data=None, dict_lengths=None) if d else c
            for c, d in zip(b.columns, dicts)), b.num_rows)
        off = pieces = gathered = 0
        for p, rows in enumerate(counts):
            if rows == 0:
                continue      # an absent piece reads as nothing downstream
            cap = min(bucket_capacity(rows), b.capacity)
            if perm is None and cap == b.capacity:
                piece = b
            else:
                piece = self._shrink_jit(lanes, np.int32(0), np.int32(rows),
                                         cap) if perm is None else \
                    self._piece_jit(lanes, perm, np.int32(off),
                                    np.int32(rows), cap)
                piece = ColumnarBatch(tuple(
                    c.replace(dict_data=d[0], dict_lengths=d[1]) if d else c
                    for c, d in zip(piece.columns, dicts)), piece.num_rows)
            off += rows
            pieces += 1
            gathered += cap
            yield p, piece, rows
        if n > 1:
            qtrace.count(splitBatches=1, splitPieces=pieces,
                         splitRowsSorted=b.capacity,
                         splitRowsGathered=gathered)

    @property
    def planned_partitions(self) -> int:
        # (a shuffled join sets the reader layout of its two exchanges,
        # skew split included: ``HashJoinExec.planned_partitions``)
        return self.partitioning.num_partitions

    def _stands_aside(self) -> bool:
        if self.aside is None:
            return False
        if self._standing is None:
            self._standing = self.stood_aside = \
                self.child.num_partitions == 1
        return self._standing

    @property
    def num_partitions(self) -> int:
        return 1 if self._stands_aside() else self._reader_partitions()

    def _reader_partitions(self) -> int:
        """The partitions a reader sees when the exchange runs."""
        return self.partitioning.num_partitions

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if self._stands_aside():
            return self.aside.execute_partition(p)
        return super().execute_partition(p)

    def close(self) -> None:
        super().close()
        self._standing = None     # a re-execution asks its child again


class ShuffleExchangeExec(PartitioningExchangeExec):
    """All-to-all redistribution of rows by a partitioning.

    Spill discipline (reference: RapidsShuffleIterator/ShuffleBufferCatalog):
    every materialized partition piece is SHRUNK to its row-count bucket and
    registered with the buffer catalog, so a shuffle larger than the device
    budget spills to host/disk instead of accumulating unbudgeted device
    lists; pieces are acquired per read partition and freed after that
    partition is consumed.
    """

    @property
    def produces_single_batch(self):
        # (one read-side concat a partition; what stands aside hands
        # through whatever its child yields)
        return self.aside is None or self.aside.produces_single_batch

    def __init__(self, partitioning: Partitioning, child: Exec,
                 ctx: Optional[EvalContext] = None, adaptive: bool = False,
                 target_rows: int = 1 << 20,
                 catalog: Optional[BufferCatalog] = None):
        super().__init__(partitioning, child, ctx)
        self._materialized: Optional[
            List[List[Tuple[SpillableBatch, int]]]] = None
        # AQE (reference: GpuCustomShuffleReaderExec): after the stage
        # materializes, adjacent small output partitions coalesce into one
        # reader partition using real row counts.
        self.adaptive = adaptive
        self.target_rows = target_rows
        self._specs: Optional[List[ReadSpec]] = None
        self._use_left: Optional[Dict[Tuple[int, int], int]] = None
        self._catalog = catalog

        from ..exec.base import DEBUG, MODERATE, Metric
        # wire-path visibility: serializeTime = framing/compression,
        # overlapTime = D2H staging hidden behind it (pipeline.py)
        self.metrics["serializeTime"] = Metric("serializeTime", MODERATE)
        self.metrics["overlapTime"] = Metric("overlapTime", MODERATE)
        self.metrics["prefetchWaitTime"] = Metric("prefetchWaitTime", DEBUG)

    def _cat(self) -> BufferCatalog:
        if self._catalog is None:
            from ..memory.catalog import device_budget
            self._catalog = device_budget()
        return self._catalog

    def _reader_partitions(self) -> int:
        if self._specs is not None:
            return len(self._specs)
        if self.adaptive:
            return len(self._reader_specs())
        return self.partitioning.num_partitions

    def partition_row_counts(self) -> List[int]:
        """Materialized row count per map-output partition (the stage
        statistics AQE reader planning runs on)."""
        return [sum(rows for _, rows in pieces)
                for pieces in self._materialize()]

    def piece_row_counts(self, p: int) -> List[int]:
        return [rows for _, rows in self._materialize()[p]]

    def set_reader_specs(self, specs: List[ReadSpec]) -> None:
        """Fix the reader-side partition layout. Called either internally
        (solo adaptive coalesce) or by a join coordinating BOTH of its
        exchanges onto one layout (coordinate_join_reads below). Pieces
        referenced by several specs (skew-split build replication) are
        refcounted and freed after their last read."""
        self._materialize()
        use: Dict[Tuple[int, int], int] = {}
        for spec in specs:
            for op_, lo, hi in spec:
                for i in range(lo, hi):
                    use[(op_, i)] = use.get((op_, i), 0) + 1
        self._specs = specs
        self._use_left = use

    def _reader_specs(self) -> List[ReadSpec]:
        if self._specs is None:
            parts = self._materialize()
            if self.adaptive:
                counts = [sum(rows for _, rows in pieces) for pieces in parts]
                groups = _coalesce_groups(counts, self.target_rows)
                if len(groups) < len(parts):
                    from ..plan.adaptive import record_decision
                    record_decision(
                        "coalesce",
                        f"solo exchange: {len(parts)} materialized "
                        f"partitions -> {len(groups)} reader partitions "
                        f"(targetRows={self.target_rows})",
                        n=len(parts) - len(groups))
            else:
                groups = [[p] for p in range(len(parts))]
            self.set_reader_specs(
                [[(p, 0, len(parts[p])) for p in g] for g in groups])
        return self._specs

    def _sample_range_bounds(self, batches: List[ColumnarBatch]) -> None:
        """Compute range bounds from the materialized input (reference:
        GpuRangePartitioner.sketch/determineBounds)."""
        from ..exec.common import (gather_column, lex_sort_permutation,
                                   sort_operands)
        part: RangePartitioning = self.partitioning
        n = self.partitioning.num_partitions
        # concat all key columns, sort, take n-1 evenly spaced bound rows
        key_batches = []
        counts = []
        for b in batches:
            cols = part.key_columns(b, self.ctx)
            key_batches.append(ColumnarBatch(tuple(cols), b.num_rows))
            counts.append(b.num_rows)
        cap = bucket_capacity(sum(kb.capacity for kb in key_batches))
        allk = concat_batches(key_batches, cap)

        # (locals, not ``part``: the program table keeps the kernel, and
        # the partitioning holds the sampled bounds on the device)
        descending, nulls_first = part._descending, part._nulls_first

        def bounds_kernel(self, kb: ColumnarBatch):
            live = kb.row_mask()
            ops = sort_operands(
                list(kb.columns), descending, nulls_first, live)
            perm = lex_sort_permutation(ops)
            skeys = [gather_column(c, perm) for c in kb.columns]
            total = kb.num_rows
            # bound i sits at row (i+1)*total/n
            pos = ((jnp.arange(n - 1, dtype=jnp.int64) + 1) * total) // n
            pos = jnp.clip(pos, 0, kb.capacity - 1).astype(jnp.int32)
            return [gather_column(c, pos) for c in skeys]

        bound_cols = KernelPrograms(
            self, (), also=[descending, nulls_first, n]).jit(
                "bounds", bounds_kernel)(allk)
        part.set_bounds(bound_cols, n - 1)

    def _materialize(self) -> List[List[Tuple[SpillableBatch, int]]]:
        if self._materialized is not None:
            return self._materialized
        n = self.partitioning.num_partitions   # write-side nominal count
        out: List[List[Tuple[SpillableBatch, int]]] = [[] for _ in range(n)]
        range_part = isinstance(self.partitioning, RangePartitioning)
        if range_part:
            # bounds need the whole input; sampling keeps only key columns
            batches = [b for cp in range(self.child.num_partitions)
                       for b in self.child.execute_partition(cp)]
            if batches:
                self._sample_range_bounds(batches)
            stream = iter(batches)
        else:
            # STREAM the child: one input batch on device at a time; its
            # pieces go straight into the catalog
            stream = (b for cp in range(self.child.num_partitions)
                      for b in self.child.execute_partition(cp))
        cat = self._cat()
        spill0 = cat.spilled_to_host + cat.spilled_to_disk
        from ..memory.retry import (SpillableInput, split_input_halves,
                                    with_retry)
        in_schema = self.child.output_schema

        def write_body(item: SpillableInput):
            """One write attempt over one (possibly split) input: split it
            and hand each piece to the spill catalog as it is made.
            Transactional — an OOM mid-loop frees this attempt's pieces
            so the retry (or the half-inputs after a split) starts
            clean."""
            b = item.acquire()
            staged: List[Tuple[int, SpillableBatch, int]] = []
            try:
                for p, piece, rows in self.split(b):
                    # registration leaves the entry unpinned → spillable
                    # under pressure
                    staged.append(
                        (p, SpillableBatch(cat, piece, self.output_schema),
                         rows))
            except BaseException:
                for _p, sb, _r in staged:
                    sb.close()
                raise
            finally:
                item.release()
            return staged

        try:
            for batch in stream:
                with qtrace.span(f"{self.name}.write", kind="shuffle"):
                    # the input batch rides the catalog across retry
                    # boundaries (SpillableColumnarBatch discipline); a
                    # repeated OOM halves it — half-inputs slice to the
                    # same pieces in the same order, so reads stay
                    # bit-for-bit
                    inp = SpillableInput.admit(batch, in_schema, cat,
                                               name=f"{self.name}.admit")
                    for staged in with_retry(inp, write_body,
                                             split=split_input_halves,
                                             catalog=cat, name=self.name):
                        for p, sb, rows in staged:
                            out[p].append((sb, rows))
        except BaseException:
            # a mid-stream failure (final OOM on a later batch, child
            # error) must free the pieces earlier batches already staged:
            # self._materialized is still None here, so do_close would
            # never see them
            for part in out:
                for sb, _rows in part:
                    sb.close()
            raise
        from ..exec.base import DEBUG, Metric
        self.metrics.setdefault(
            "spillBytes", Metric("spillBytes", DEBUG)).add(
            cat.spilled_to_host + cat.spilled_to_disk - spill0)
        self._materialized = out
        return out

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        spec = self._reader_specs()[p]
        parts = self._materialize()
        entries = [parts[op_][i] for op_, lo, hi in spec
                   for i in range(lo, hi)]
        if not entries:
            return
        # shuffle-read coalesce (reference: GpuShuffleCoalesceExec)
        cap = bucket_capacity(max(sum(rows for _, rows in entries), 1))
        from ..memory.retry import with_retry_no_split

        def assemble():
            """The pin loop, transactional: a mid-loop OOM from get()
            unpins the ALREADY-PINNED entries before propagating — the
            retry loop (or a coordinated re-read) finds every piece
            unpinned and spillable, and `use` refcounts are only
            committed after a successful read below."""
            pinned: List[SpillableBatch] = []
            try:
                got = []
                for sb, _ in entries:
                    got.append(sb.get())
                    pinned.append(sb)
                if len(got) == 1:
                    return pinned, got[0]
                # per-batch dictionaries unify to ONE merged dictionary
                # via a device code-remap (eager: we are between kernels
                # here), so the shuffle-read coalesce keeps string
                # columns encoded across the concat
                from ..dictenc import unify_dict_batches
                got = unify_dict_batches(got)
                return pinned, concat_batches(got, cap)
            except BaseException:
                for sb in pinned:
                    sb.done_with()
                raise

        pinned, batch = with_retry_no_split(assemble, catalog=self._cat(),
                                            name=f"{self.name}.read")
        pinned_ids = {id(sb) for sb in pinned}
        try:
            yield batch
        finally:
            # free a piece after its LAST referencing read partition
            # (skew-split replicates build pieces across readers). An
            # abandoned generator (limit early-exit) may be finalized
            # AFTER do_close() already reset the refcounts (use is None
            # -> idempotent close).
            use = self._use_left
            for op_, lo, hi in spec:
                for i in range(lo, hi):
                    sb = parts[op_][i][0]
                    if use is None:
                        sb.close()
                        continue
                    use[(op_, i)] -= 1
                    if use[(op_, i)] <= 0:
                        sb.close()
                    elif id(sb) in pinned_ids:
                        sb.done_with()

    def serialized_partitions(self, codec: Optional[str] = None,
                              depth: Optional[int] = None
                              ) -> Iterator[Tuple[int, List[bytes]]]:
        """Wire export of the materialized shuffle — the host-boundary /
        DCN path (reference: GpuPartitioning.scala:52 serialize-once
        slicing + GpuShuffleExchangeExecBase serialized blocks).

        Yields ``(reader_partition, [frame, ...])`` in partition order.
        Each piece is serialized exactly ONCE: device-resident pieces take
        a single D2H staging pass into a PackedTable and are framed from
        it; pieces the catalog already spilled to the host tier frame
        straight from their existing PackedTable with NO device
        round-trip (and no Arrow materialization anywhere). The D2H
        staging of the next piece overlaps the framing/compression of the
        current one through the bounded pipeline (prefetch.depth; 0 =
        synchronous)."""
        import time as _time
        from .. import trace as qtrace
        from ..pipeline import close_iterator, prefetched
        from .serializer import frame_packed, pack_batch
        specs = self._reader_specs()
        parts = self._materialize()

        from ..memory.retry import with_retry_no_split

        def staged():
            # producer stage: D2H (or host-tier view) per piece. The
            # pack/pin of each piece runs under the retry loop — an OOM
            # on the producer thread (pin of a spilled piece reserving
            # budget) spills/retries there; an unretryable one is
            # re-raised at the consumer by the pipeline.
            for p, spec in enumerate(specs):
                for op_, lo, hi in spec:
                    for i in range(lo, hi):
                        sb = parts[op_][i][0]

                        def pack_one(sb=sb):
                            pt = sb.host_view()
                            if pt is None:
                                batch = sb.get()
                                try:
                                    pt = pack_batch(batch)
                                finally:
                                    sb.done_with()
                            return pt

                        yield p, with_retry_no_split(
                            pack_one, catalog=self._cat(),
                            name=f"{self.name}.wire")

        if depth is None:
            from ..config import PREFETCH_DEPTH, PREFETCH_ENABLED, _REGISTRY
            depth = int(_REGISTRY[PREFETCH_DEPTH.key].default) \
                if _REGISTRY[PREFETCH_ENABLED.key].default else 0
        it = prefetched(staged(), depth, metrics=self.metrics,
                        name="exchange-wire", stage="wire")
        next_p, frames = 0, []
        try:
            for p, pt in it:
                while next_p < p:
                    yield next_p, frames
                    next_p, frames = next_p + 1, []
                t0 = _time.perf_counter_ns()
                with qtrace.span(f"{self.name}.serialize",
                                 kind="serializer"):
                    frames.append(frame_packed(pt, codec))
                self.metrics["serializeTime"].add(
                    _time.perf_counter_ns() - t0)
            while next_p < len(specs):
                yield next_p, frames
                next_p, frames = next_p + 1, []
        finally:
            close_iterator(it)

    def do_close(self) -> None:
        # partitions the consumer never read (limits, early exit) still
        # hold catalog entries; SpillableBatch.close is idempotent
        if self._materialized is not None:
            for pieces in self._materialized:
                for sb, _ in pieces:
                    sb.close()
            self._materialized = None
            self._specs = None
            self._use_left = None


def coordinate_join_reads(stream: "ShuffleExchangeExec",
                          build: "ShuffleExchangeExec",
                          target_rows: int,
                          skew_split_rows: Optional[int] = None) -> int:
    """Jointly plan the reader partitions of a co-partitioned join's two
    exchanges (the role of Spark AQE's ShufflePartitionsUtil +
    OptimizeSkewedJoin): groups are computed once on COMBINED row counts so
    both sides agree on the layout — independent per-side coalescing would
    silently break co-partitioning. A skewed map-output partition (stream
    rows > skew_split_rows) is split into piece-range reader partitions,
    each paired with a full replica of the matching build partition
    (PartialReducerPartitionSpec semantics). Returns the number of skew
    splits performed."""
    from ..plan.adaptive import record_decision
    sc = stream.partition_row_counts()
    bc = build.partition_row_counts()
    assert len(sc) == len(bc), (len(sc), len(bc))
    combined = [a + b for a, b in zip(sc, bc)]
    if skew_split_rows:
        # skewed partitions are NOT coalesceable (OptimizeSkewedJoin
        # runs before coalescing): each becomes its own singleton group
        # so the split branch below sees it, and only the thin runs
        # BETWEEN skewed partitions coalesce toward target_rows.
        groups = []
        run: List[int] = []
        for p, c in enumerate(combined):
            if sc[p] > skew_split_rows:
                if run:
                    groups += [[run[i] for i in g] for g in
                               _coalesce_groups([combined[i] for i in run],
                                                target_rows)]
                    run = []
                groups.append([p])
            else:
                run.append(p)
        if run:
            groups += [[run[i] for i in g] for g in
                       _coalesce_groups([combined[i] for i in run],
                                        target_rows)]
    else:
        groups = _coalesce_groups(combined, target_rows)
    if len(groups) < len(combined):
        record_decision(
            "coalesce",
            f"coordinated join exchanges: {len(combined)} materialized "
            f"partitions -> {len(groups)} reader partitions "
            f"(targetRows={target_rows})",
            n=len(combined) - len(groups))
    s_specs: List[ReadSpec] = []
    b_specs: List[ReadSpec] = []
    n_splits = 0
    for g in groups:
        if skew_split_rows and len(g) == 1 and sc[g[0]] > skew_split_rows:
            p = g[0]
            rows = stream.piece_row_counts(p)
            chunks: List[Tuple[int, int]] = []
            lo, cur = 0, 0
            for i, r in enumerate(rows):
                if cur and cur + r > skew_split_rows:
                    chunks.append((lo, i))
                    lo, cur = i, 0
                cur += r
            chunks.append((lo, len(rows)))
            np_build = len(build.piece_row_counts(p))
            if len(chunks) > 1:
                n_splits += len(chunks) - 1
                record_decision(
                    "skewSplit",
                    f"partition {p}: {sc[p]} stream rows > "
                    f"splitRows={skew_split_rows} -> {len(chunks)} "
                    f"piece-range reader partitions (build replicated)",
                    n=len(chunks) - 1)
            for c_lo, c_hi in chunks:
                s_specs.append([(p, c_lo, c_hi)])
                b_specs.append([(p, 0, np_build)])
        else:
            s_specs.append([(p, 0, len(stream.piece_row_counts(p)))
                            for p in g])
            b_specs.append([(p, 0, len(build.piece_row_counts(p)))
                            for p in g])
    stream.set_reader_specs(s_specs)
    build.set_reader_specs(b_specs)
    return n_splits


class BroadcastTooLargeError(MemoryError):
    """The broadcast relation exceeds spark.rapids.tpu.broadcast.maxBytes
    (Spark's 8GB broadcast hard limit analogue) — the planner should have
    chosen a shuffled join for this build side."""


class BroadcastExchangeExec(UnaryExec):
    """Replicate the child's full output as one batch (reference:
    GpuBroadcastExchangeExec — host-serialized concat batches rebuilt on
    device per executor; single-process here, so it is a concat + cache).

    The cached relation is catalog-registered (spillable between reads)
    and bounded by spark.rapids.tpu.broadcast.maxBytes."""

    @property
    def produces_single_batch(self):
        return True

    def __init__(self, child: Exec, ctx: Optional[EvalContext] = None,
                 max_bytes: Optional[int] = None,
                 catalog: Optional[BufferCatalog] = None):
        super().__init__(child, ctx)
        self._sb: Optional[SpillableBatch] = None
        if max_bytes is None:
            from ..config import BROADCAST_LIMIT, RapidsTpuConf
            max_bytes = RapidsTpuConf().get(BROADCAST_LIMIT.key)
        self.max_bytes = max_bytes
        self._catalog = catalog

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    @property
    def num_partitions(self) -> int:
        return 1

    planned_partitions = num_partitions    # a plan fact

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from ..memory.retry import acquire_with_retry, with_retry_no_split
        if self._sb is None:
            batches = [b for cp in range(self.child.num_partitions)
                       for b in self.child.execute_partition(cp)]
            if self._catalog is None:
                from ..memory.catalog import device_budget
                self._catalog = device_budget()

            def build():
                if not batches:
                    from ..batch import empty_batch
                    cached = empty_batch(self.output_schema)
                elif len(batches) == 1:
                    cached = batches[0]
                else:
                    cap = bucket_capacity(sum(b.capacity for b in batches))
                    cached = concat_batches(batches, cap)
                if cached.size_bytes() > self.max_bytes:
                    # NOT retryable: a planner-contract violation, no
                    # amount of spilling shrinks the relation
                    raise BroadcastTooLargeError(
                        f"broadcast relation is {cached.size_bytes()}b > "
                        f"spark.rapids.tpu.broadcast.maxBytes="
                        f"{self.max_bytes}; use a shuffled join for this "
                        f"build side")
                return SpillableBatch(self._catalog, cached,
                                      self.output_schema)

            self._sb = with_retry_no_split(build, catalog=self._catalog,
                                           name=self.name)
        batch = acquire_with_retry(self._sb, name=self.name)
        try:
            yield batch
        finally:
            self._sb.done_with()    # spillable again between reads

    def do_close(self) -> None:
        if self._sb is not None:
            self._sb.close()
            self._sb = None


_cached_shuffle_ids = itertools.count(1)


class CachedShuffleExchangeExec(PartitioningExchangeExec):
    """Device-resident CACHED shuffle mode (reference: RapidsCachingWriter
    + ShuffleBufferCatalog, RapidsShuffleInternalManagerBase.scala:876):
    map outputs are registered as spillable DEVICE blocks in a
    DeviceShuffleCache; readers take local blocks as device batches with
    ZERO serialization and pull remote peers' blocks through the TCP
    transport. Within one process every block is local — a fully
    device-resident exchange."""

    def __init__(self, partitioning: Partitioning, child: Exec,
                 ctx: Optional[EvalContext] = None, cache=None, conf=None):
        super().__init__(partitioning, child, ctx)
        self._shuffle_id = next(_cached_shuffle_ids)
        self._cache = cache
        self._conf = conf
        self._written = False
        self._write_lock = threading.Lock()

    def _get_cache(self):
        if self._cache is None:
            from .device_cache import shared_device_cache
            self._cache = shared_device_cache(getattr(self, "_conf", None))
        return self._cache

    def _write(self) -> None:
        # double-checked under the lock: concurrent reduce-partition
        # consumers must not both enter and register duplicate blocks
        # (same discipline DeviceShuffleCache uses internally)
        if self._written:
            return
        with self._write_lock:
            if self._written:
                return
            self._write_locked()

    def _write_locked(self) -> None:
        cache = self._get_cache()
        schema = self.child.output_schema
        m = 0
        for cp in range(self.child.num_partitions):
            for batch in self.child.execute_partition(cp):
                for r, piece, _rows in self.split(batch):
                    cache.add_batch(self._shuffle_id, m, r, piece, schema)
                m += 1
        self._n_maps = m
        self._written = True   # only after a COMPLETE write

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        self._write()
        cache = self._get_cache()
        schema = self.child.output_schema
        for m in range(self._n_maps):
            out = cache.get_local(self._shuffle_id, m, p)
            if out is not None:
                yield out

    def do_close(self) -> None:
        if self._written:
            self._get_cache().remove_shuffle(self._shuffle_id)
            self._written = False
