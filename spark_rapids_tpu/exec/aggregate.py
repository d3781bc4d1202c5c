"""Hash aggregate — sort-based segmented reduction.

Reference: sql-plugin/.../aggregate.scala (GpuHashAggregateExec:1372,
GpuHashAggregateIterator:182): per-batch cudf groupBy, then iterative
concat+re-aggregate of partial results, with a sort-based fallback when
merged results exceed the batch target.

TPU-native re-design: cudf's hash groupby is replaced by ONE device sort by
the grouping keys followed by segment reductions with a static segment count
(the capacity bucket). Sorting is XLA's bread and butter; every aggregate in
the batch then runs as fused `segment_sum/min/max` over the same sorted
layout — a single compiled computation per capacity bucket, versus one JNI
kernel launch per aggregation in the reference.

Modes mirror Spark's: Partial (update → buffers), PartialMerge/Final (merge
buffers), Complete (update + evaluate). Layout convention between stages:
``[group keys..., buffer columns...]`` in declaration order.
"""

from __future__ import annotations

import enum
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import MIN_CAPACITY, ColumnarBatch, DeviceColumn, Field, \
    Schema, bucket_capacity
from ..expressions.aggregates import AggregateFunction
from ..expressions.aggregates import _cumsum as prefix_sum
from ..expressions.base import Alias, EvalContext, Expression
from ..trace import host_read
from .base import Exec, UnaryExec
from .basic import bind_all, output_name
from .common import _batched_takes, adjacent_equal, adjacent_equal_ops, \
    KernelPrograms, compaction_indices, concat_batches, \
    concat_batches_encoded, cut_to_rows, dec128_role, gather_column, \
    jit_named, lex_sort_permutation, sort_operands

# dtypes whose device payload is a flat 1-D array (or, for a decimal past
# 18 digits, a limb matrix, gathered as rows the same way): the fast
# kernel gathers such columns through the key sort's permutation in
# batched row-gathers
_FLAT_KINDS = frozenset({
    T.TypeKind.INT8, T.TypeKind.INT16, T.TypeKind.INT32, T.TypeKind.INT64,
    T.TypeKind.FLOAT32, T.TypeKind.FLOAT64, T.TypeKind.BOOLEAN,
    T.TypeKind.DATE, T.TypeKind.TIMESTAMP,
})


def _is_flat(t: T.SqlType) -> bool:
    return t.kind in _FLAT_KINDS or t.kind is T.TypeKind.DECIMAL


def _pad_column(c: DeviceColumn, cap: int) -> DeviceColumn:
    """Zero/False-pad a [L]-capacity column up to [cap] rows. Dictionary
    lanes are CARD-leading and ride along unpadded — every layout tier
    must produce the same pytree structure for the lax.cond dispatch."""
    pad = cap - c.capacity
    if pad == 0:
        return c

    def pz(a):
        if a is None:
            return None
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

    return DeviceColumn(pz(c.data), pz(c.validity), pz(c.lengths), c.dtype,
                        pz(c.data2), c.dict_data, c.dict_lengths)


class AggregateMode(enum.Enum):
    PARTIAL = "Partial"
    PARTIAL_MERGE = "PartialMerge"
    FINAL = "Final"
    COMPLETE = "Complete"


def _unalias(e: Expression) -> Tuple[AggregateFunction, str]:
    if isinstance(e, Alias):
        assert isinstance(e.child, AggregateFunction)
        return e.child, e.name
    assert isinstance(e, AggregateFunction), f"not an aggregate: {e!r}"
    return e, type(e).__name__.lower()


class HashAggregateExec(UnaryExec):
    #: the fields of the exec that its kernels read (common.KernelPrograms)
    _PROGRAM_READS = (
        "mode", "group_exprs", "aggs", "key_fields", "buffer_fields",
        "sort_sensitive", "small_groups_bucket", "layout_tiers",
        "_upd_value_exprs", "_upd_per_agg", "_fast_update", "_fast_merge")

    def coalesce_goal_for_child(self, i):
        from .coalesce import TargetSize
        return TargetSize()

    def __init__(self, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Expression], child: Exec,
                 mode: AggregateMode = AggregateMode.COMPLETE,
                 ctx: Optional[EvalContext] = None,
                 max_result_rows: int = 1 << 22,
                 small_groups_bucket: int = 1 << 12,
                 layout_tiers: Optional[Sequence[int]] = None):
        self.layout_tiers = layout_tiers
        super().__init__(child, ctx)
        self.mode = mode
        self.max_result_rows = max_result_rows
        named = [_unalias(e) for e in agg_exprs]
        self.agg_names = [n for _, n in named]

        child_schema = child.output_schema
        if mode in (AggregateMode.PARTIAL, AggregateMode.COMPLETE):
            self.group_exprs = bind_all(group_exprs, child_schema)
            self.aggs = [a.bind(child_schema) for a, _ in named]
            self.key_fields = [
                Field(output_name(e, i), e.dtype, e.nullable)
                for i, e in enumerate(self.group_exprs)]
        else:
            # Buffer-layout input: keys first, then buffers in order. The
            # agg functions must be BOUND against the pre-aggregation schema
            # (Spark's planner shares the bound AggregateExpressions between
            # the Partial and Final stages); if the caller passed unresolved
            # ones, recover the bound instances from the partial stage below.
            self.aggs = [a for a, _ in named]
            if any(not c.resolved for a in self.aggs for c in a.children):
                src: Optional[Exec] = child
                while src is not None and \
                        not isinstance(src, HashAggregateExec):
                    src = src.children[0] if len(src.children) == 1 else None
                if src is None:
                    raise ValueError(
                        "Final-mode aggregate functions must be bound (or "
                        "the child chain must contain the Partial stage)")
                self.aggs = list(src.aggs)
            # keys are positional in the buffer layout — reference them by
            # ordinal, never re-evaluate the original grouping expressions
            # (they may be computed, e.g. group_by(year(col("d"))))
            nk = len(group_exprs)
            from ..expressions.base import BoundReference
            self.group_exprs = [
                BoundReference(i, f.dtype, f.nullable, f.name)
                for i, f in enumerate(child_schema.fields[:nk])]
            self.key_fields = [Field(f.name, f.dtype, f.nullable)
                               for f in child_schema.fields[:nk]]

        # buffer fields (inter-stage schema)
        self.buffer_fields: List[Field] = []
        for (agg, name) in zip(self.aggs, self.agg_names):
            for j, (bt, bn) in enumerate(zip(agg.buffer_types(),
                                             agg.buffer_nullable())):
                self.buffer_fields.append(Field(f"{name}#{j}", bt, bn))

        if mode in (AggregateMode.PARTIAL, AggregateMode.PARTIAL_MERGE):
            self._schema = Schema(self.key_fields + self.buffer_fields)
        else:
            self._schema = Schema(self.key_fields + [
                Field(n, a.dtype, a.nullable)
                for a, n in zip(self.aggs, self.agg_names)])

        self.sort_sensitive = [
            a for a in self.aggs
            if getattr(a, "requires_sorted_input", False)]
        if len(self.sort_sensitive) > 1:
            raise ValueError(
                "one sort-sensitive aggregate (percentile) per exec; the "
                "planner must split multi-percentile projections")
        if self.sort_sensitive and mode is not AggregateMode.COMPLETE:
            raise ValueError(
                f"{type(self.sort_sensitive[0]).__name__} supports "
                f"COMPLETE mode only (not decomposable)")

        # ---- fast path eligibility ----------------------------------
        # values follow the key sort's permutation; group-slot layout shrinks
        # to `small_groups_bucket` when the observed group count allows
        self.small_groups_bucket = small_groups_bucket
        self._upd_value_exprs: List[Expression] = []
        self._upd_per_agg: List[List[int]] = []
        index_of = {}
        for agg in self.aggs:
            idxs = []
            for c in agg.children:
                k = self._expr_key(c)
                if k not in index_of:
                    index_of[k] = len(self._upd_value_exprs)
                    self._upd_value_exprs.append(c)
                idxs.append(index_of[k])
            self._upd_per_agg.append(idxs)
        have_keys = len(self.group_exprs) > 0
        self._fast_update = (
            mode in (AggregateMode.PARTIAL, AggregateMode.COMPLETE)
            and have_keys and not self.sort_sensitive
            and all(_is_flat(c.dtype) for a in self.aggs for c in a.children)
            and all(_is_flat(bt) for a in self.aggs for bt in a.buffer_types()))
        self._fast_merge = (
            mode in (AggregateMode.PARTIAL_MERGE, AggregateMode.FINAL)
            and have_keys
            and all(_is_flat(f.dtype) for f in self.buffer_fields))

        # Limb buffers (a decimal sum past 18 digits: 32 bytes a row, six
        # f64 chunk lanes in the merge's stack) weigh several times what a
        # double buffer does: a merge window of 2^22 rows does not fit the
        # chip (22.7 GB of temporaries for TPC-H Q1's seven limb sums,
        # tools/aot_compile.py's method). So such an exec, told by its
        # buffer types alone, merges a quarter of the rows at a time, and
        # its programs carry another name. (Every exec cuts its partials
        # to their groups' bucket: do_execute_partition.)
        from ..expressions.decimal128 import is_dec128
        self._wide_buffers = any(is_dec128(f.dtype)
                                 for f in self.buffer_fields)

        # everything the kernels below read of this exec: the programs'
        # key, and all their stand-in has (common.KernelPrograms)
        programs = KernelPrograms(self, self._PROGRAM_READS)
        cls = type(self)
        def role(r):
            return r + "Dec128" if self._wide_buffers else r
        self._update_jit = programs.jit(role("update"), cls._update_kernel)
        self._merge_jit = programs.jit(
            role("merge"), lambda self, b: self._merge_kernel(b, final=False))
        self._final_jit = programs.jit(
            role("final"), lambda self, b: self._merge_kernel(b, final=True))
        self._eval_buffers_jit = programs.jit(
            role("evalBuffers"), cls._eval_buffers_kernel)

    @staticmethod
    def _expr_key(e: Expression):
        """Identity for payload dedup: two aggregates over the same bound
        column share one carried payload lane."""
        from ..expressions.base import BoundReference
        if isinstance(e, BoundReference):
            return ("ref", e.ordinal)
        return id(e)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------------
    # Shared segment machinery
    # ------------------------------------------------------------------

    def _segments(self, key_cols: List[DeviceColumn], live, cap: int,
                  value_cols: List[DeviceColumn] = ()):
        """Sort rows by key (+ optional value columns for sort-sensitive
        aggregates); return (perm, seg ids, new_group mask, count,
        sorted-live mask, live row count). ``live`` may exclude rows a
        fused upstream filter dropped — they sort last, exactly like
        padding rows, so no separate compaction pass is needed."""
        n_live = jnp.sum(live.astype(jnp.int32))
        if not key_cols and not value_cols:
            seg = jnp.where(live, 0, cap)
            new_group = jnp.arange(cap, dtype=jnp.int32) == 0
            return None, seg, new_group, jnp.asarray(1, jnp.int32), live, \
                n_live
        all_cols = list(key_cols) + list(value_cols)
        from .common import may_skip_null_lane
        nullable = [not may_skip_null_lane(e)
                    for e in self.group_exprs][:len(key_cols)] + \
            [True] * len(value_cols)
        if len(nullable) != len(all_cols):
            nullable = [True] * len(all_cols)
        ops = sort_operands(all_cols, [False] * len(all_cols),
                            [True] * len(all_cols), live, nullable)
        perm = lex_sort_permutation(ops)
        sorted_keys = [gather_column(c, perm) for c in key_cols]
        sorted_live = jnp.arange(cap, dtype=jnp.int32) < n_live
        if key_cols:
            eq = adjacent_equal(sorted_keys)
        else:
            # value-only sort (global percentile): one segment
            eq = jnp.concatenate([jnp.zeros(1, bool),
                                  jnp.ones(cap - 1, bool)])
        new_group = sorted_live & ~eq
        group_id = prefix_sum(new_group.astype(jnp.int32)) - 1
        seg = jnp.where(sorted_live, group_id, cap)
        count = jnp.sum(new_group.astype(jnp.int32))
        return perm, seg, new_group, count, sorted_live, n_live

    def _segment_layout(self, seg, count, num_rows, cap: int):
        """(starts, ends) row-index bounds per group slot, feeding the
        aggregates' segmented-scan reductions (segment_bounds context in
        expressions/aggregates.py) AND first-key placement. One native
        int32 scatter (`segment_min` of iota) — the flag-sort alternative
        measured ~3x slower through the old plug-in. Dead slots get ends < starts so their
        reductions resolve to the identity."""
        iota = jnp.arange(cap, dtype=jnp.int32)
        starts = jax.ops.segment_min(iota, seg, num_segments=cap,
                                     indices_are_sorted=True)
        nxt = jnp.concatenate([starts[1:], jnp.zeros(1, jnp.int32)])
        last = jnp.asarray(num_rows, jnp.int32) - 1
        ends = jnp.where(iota < count - 1, nxt - 1, last)
        starts = jnp.where(iota < count, starts, jnp.int32(1))
        ends = jnp.where(iota < count, ends, jnp.int32(0))
        return starts, ends

    def _group_first_keys(self, sorted_keys: List[DeviceColumn], perm,
                          count, cap: int) -> List[DeviceColumn]:
        """Place each segment's first-row key at its group slot — a gather
        through the slot order (segments ascend, so the g-th first-row IS
        group g's key; TPU scatters are ~40x slower than gathers)."""
        iota = jnp.arange(cap, dtype=jnp.int32)
        slot_live = iota < count
        # gather_column: dict-aware (codes gather, dictionary rides along)
        # and struct-recursive, with slot_live folded into validity
        return [gather_column(c, perm, slot_live) for c in sorted_keys]

    # ------------------------------------------------------------------
    # Fast kernel: ONE key sort (a row permutation; every aggregate input
    # is gathered through it); cumsum-diff reductions over the sorted
    # layout; dual small/large group-slot layout behind a lax.cond so the
    # common small-group-count case pays G-sized per-group gathers
    # instead of capacity-sized ones.
    # ------------------------------------------------------------------

    def _fast_group_kernel(self, batch: ColumnarBatch, mask,
                           merge: bool, final: bool) -> ColumnarBatch:
        cap = batch.capacity
        in_live = batch.row_mask()
        if mask is not None:
            in_live = in_live & mask
        nk = len(self.key_fields)
        if merge:
            key_cols = list(batch.columns[:nk])
            flat_vals = list(batch.columns[nk:])
            per_agg, off = [], 0
            for agg in self.aggs:
                nb = len(agg.buffer_types())
                per_agg.append(list(range(off, off + nb)))
                off += nb
            nullable = [f.nullable for f in self.key_fields]
            val_nullable = [f.nullable for f in self.buffer_fields]
        else:
            # raw_eval: dict-encoded string keys group on CODES — one u32
            # sort lane instead of max_len/8+1 word lanes, same order and
            # same group boundaries (sorted-dictionary invariant)
            from ..expressions.base import raw_eval
            key_cols = [raw_eval(e, batch, self.ctx)
                        for e in self.group_exprs]
            flat_vals = [e.eval(batch, self.ctx)
                         for e in self._upd_value_exprs]
            per_agg = self._upd_per_agg
            from .common import may_skip_null_lane
            nullable = [not may_skip_null_lane(e) for e in self.group_exprs]
            val_nullable = [e.nullable for e in self._upd_value_exprs]

        key_ops = sort_operands(key_cols, [False] * nk, [True] * nk,
                                in_live, nullable)
        nko = len(key_ops)
        iota = jnp.arange(cap, dtype=jnp.int32)
        # the sort orders a row index only; key words and aggregate inputs
        # are gathered through it (same-dtype lanes in one row-gather).
        # Provably non-null columns skip their validity lane; their sorted
        # views share ONE validity object (sorted_live), which also dedups
        # the per-aggregate non-null-count lanes downstream
        sperm = lex_sort_permutation(key_ops)
        payload: List[jax.Array] = list(key_ops)
        for c, nl in zip(flat_vals, val_nullable):
            payload.append(c.data)
            if nl:
                payload.append(c.validity)
        out = _batched_takes(payload, sperm)
        sorted_key_ops = out[:nko]
        n_live = jnp.sum(in_live.astype(jnp.int32))
        sorted_live = iota < n_live
        svals: List[DeviceColumn] = []
        j = nko
        for c, nl in zip(flat_vals, val_nullable):
            data = out[j]
            j += 1
            if nl:
                validity = out[j]
                j += 1
            else:
                validity = sorted_live
            svals.append(DeviceColumn(data, validity, None, c.dtype))
        eq = adjacent_equal_ops(sorted_key_ops[1:])  # skip the dead lane
        new_group = sorted_live & ~eq
        gid = prefix_sum(new_group.astype(jnp.int32)) - 1
        count = jnp.sum(new_group.astype(jnp.int32))

        from ..expressions.aggregates import (FastLanes, LaneResults,
                                              segment_bounds)

        # planning pass: batched aggregates register lanes on the builder;
        # the rest fall back to generic update/merge under segment_bounds
        lanes = FastLanes(sorted_live)
        plans = []
        for agg, idxs in zip(self.aggs, per_agg):
            views = [svals[i] for i in idxs]
            fin = (agg.fast_merge(views, sorted_live, lanes) if merge
                   else agg.fast_update(views, sorted_live, lanes))
            plans.append((agg, views, fin))
        # branch-independent segment ids for the suffix-scan ladders
        seg0 = jnp.where(sorted_live, gid, -1)

        def emit(L: int):
            slot = jnp.arange(L, dtype=jnp.int32)
            live_slot = slot < count
            pos = jnp.where(new_group & (gid < L), gid, L)
            starts = jnp.zeros(L + 1, jnp.int32).at[pos].set(
                iota, mode="drop")[:L]
            nxt = jnp.concatenate([starts[1:], jnp.zeros(1, jnp.int32)])
            ends = jnp.where(slot < count - 1, nxt - 1, n_live - 1)
            starts_m = jnp.where(live_slot, starts, 1)
            ends_m = jnp.where(live_slot, ends, 0)
            first_idx = jnp.take(sperm, jnp.where(live_slot, starts, 0))
            from .common import gather_columns
            out_cols = gather_columns(key_cols, first_idx, live_slot)
            res = LaneResults(lanes, seg0, starts_m, live_slot)
            seg = jnp.where(sorted_live & (gid < L), gid, L)
            with segment_bounds(starts_m, ends_m):
                for agg, views, fin in plans:
                    if fin is not None:
                        bufs = fin(res)
                    else:
                        bufs = (agg.merge(views, seg, sorted_live, L)
                                if merge
                                else agg.update(views, seg, sorted_live, L))
                    if merge and final:
                        out_cols.append(agg.evaluate(bufs, live_slot))
                    else:
                        out_cols.extend(bufs)
            return tuple(_pad_column(c, cap) for c in out_cols)

        # capacity-tiered layout: per-group gathers scale with the layout
        # size, so pick the smallest tier the observed group count fits
        # (nested lax.cond — only the selected tier executes). Tier count
        # is a compile-time/runtime trade: every tier re-traces the whole
        # reduction pipeline. Since the round-4 blocked scans shrank the
        # per-tier HLO, a THIRD mid tier (cap/4) is affordable and cuts the
        # group-starts row-gather 5x for mid-cardinality batches
        # (a round-4 chip profile: (4M,6) f64 gather 180 ms at L=4M vs
        # 33 ms at L=1M; on this installation's chip: not measured).
        G = min(self.small_groups_bucket, cap)
        default = (G, cap >> 2, cap) if cap >> 2 > G else (G, cap)
        tiers = sorted({t for t in (self.layout_tiers or default)
                        if 0 < t <= cap} | {cap})

        def select(ts):
            if len(ts) == 1:
                return emit(ts[0])
            return jax.lax.cond(count <= ts[0],
                                lambda: emit(ts[0]), lambda: select(ts[1:]))

        return ColumnarBatch(select(tiers), count)

    # ------------------------------------------------------------------
    # Kernels
    # ------------------------------------------------------------------

    def _update_kernel(self, batch: ColumnarBatch,
                       mask=None) -> ColumnarBatch:
        """input rows -> buffer-layout batch (Partial). ``mask`` fuses an
        upstream filter into the aggregation: masked rows become dead
        rows of the sort, skipping the separate compaction kernel
        (reference analogue: AST-fused filters)."""
        if self._fast_update:
            return self._fast_group_kernel(batch, mask, merge=False,
                                           final=False)
        cap = batch.capacity
        in_live = batch.row_mask()
        if mask is not None:
            in_live = in_live & mask
        from ..expressions.base import raw_eval
        key_cols = [raw_eval(e, batch, self.ctx)
                    for e in self.group_exprs]
        input_cols = [[c.eval(batch, self.ctx) for c in agg.children]
                      for agg in self.aggs]
        value_sort = []
        if self.sort_sensitive:
            si = self.aggs.index(self.sort_sensitive[0])
            value_sort = list(input_cols[si])
        perm, seg, new_group, count, live, n_live = self._segments(
            key_cols, in_live, cap, value_sort)
        if perm is not None:
            key_cols = [gather_column(c, perm) for c in key_cols]
            input_cols = [[gather_column(c, perm) for c in cols]
                          for cols in input_cols]
        from ..expressions.aggregates import segment_bounds
        starts, ends = self._segment_layout(seg, count, n_live, cap)
        out_cols = self._group_first_keys(key_cols, starts, count, cap)
        if perm is None:
            # unsorted (keyless) segments are not contiguous under a
            # fused mask — the scan path needs runs, use scatters
            for agg, cols in zip(self.aggs, input_cols):
                out_cols.extend(agg.update(cols, seg, live, cap))
        else:
            with segment_bounds(starts, ends):
                for agg, cols in zip(self.aggs, input_cols):
                    out_cols.extend(agg.update(cols, seg, live, cap))
        group_live = jnp.arange(cap, dtype=jnp.int32) < count
        out_cols = [c.replace(validity=c.validity & group_live)
                    if i < len(key_cols) else c
                    for i, c in enumerate(out_cols)]
        return ColumnarBatch(tuple(out_cols), count)

    def _merge_kernel(self, batch: ColumnarBatch, final: bool) -> ColumnarBatch:
        """buffer-layout rows -> merged buffer rows (or final results)."""
        if self._fast_merge:
            return self._fast_group_kernel(batch, None, merge=True,
                                           final=final)
        cap = batch.capacity
        nk = len(self.key_fields)
        key_cols = [batch.columns[i] for i in range(nk)]
        perm, seg, new_group, count, live, n_live = self._segments(
            key_cols, batch.row_mask(), cap)
        if perm is not None:
            cols = [gather_column(c, perm) for c in batch.columns]
        else:
            cols = list(batch.columns)
        from ..expressions.aggregates import segment_bounds
        starts, ends = self._segment_layout(seg, count, n_live, cap)
        out_cols = self._group_first_keys(cols[:nk], starts, count, cap)
        group_live = jnp.arange(cap, dtype=jnp.int32) < count
        off = nk
        with segment_bounds(starts, ends):
            for agg in self.aggs:
                nb = len(agg.buffer_types())
                bufs = cols[off:off + nb]
                merged = agg.merge(bufs, seg, live, cap)
                if final:
                    out_cols.append(agg.evaluate(merged, group_live))
                else:
                    out_cols.extend(merged)
                off += nb
        out_cols = [c.replace(validity=c.validity & group_live)
                    if i < nk else c for i, c in enumerate(out_cols)]
        return ColumnarBatch(tuple(out_cols), count)

    def _eval_buffers_kernel(self, batch: ColumnarBatch) -> ColumnarBatch:
        """buffer-layout rows -> final results WITHOUT a merge pass (the
        sort-sensitive COMPLETE path: groups are already unique)."""
        cap = batch.capacity
        nk = len(self.key_fields)
        group_live = batch.row_mask()
        out_cols = list(batch.columns[:nk])
        off = nk
        for agg in self.aggs:
            nb = len(agg.buffer_types())
            bufs = list(batch.columns[off:off + nb])
            out_cols.append(agg.evaluate(bufs, group_live))
            off += nb
        return ColumnarBatch(tuple(out_cols), batch.num_rows)

    # ------------------------------------------------------------------
    # Iterator (reference: GpuHashAggregateIterator.aggregateInputBatches +
    # tryMergeAggregatedBatches)
    # ------------------------------------------------------------------

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        # accumulated partials ride the spill catalog (reference:
        # LazySpillableColumnarBatch deque in GpuHashAggregateIterator);
        # registrations and the merge passes run under the OOM retry loop
        # (no split: re-ordering partial merges would change float
        # accumulation order — spill-and-retry keeps results bit-for-bit)
        from .. import trace as qtrace
        from ..memory import (SpillableBatch, device_budget,
                              register_with_retry)
        cat = device_budget()
        buf_schema = Schema(self.key_fields + self.buffer_fields)
        spillables: List[SpillableBatch] = []
        if self.sort_sensitive:
            # non-decomposable aggregates: ONE update over the whole
            # partition's rows, then evaluate (no merge step exists)
            raw = list(self.child.execute_partition(p))
            if not raw:
                if not self.key_fields and p == 0:
                    from ..batch import empty_batch
                    seed = empty_batch(Schema(self.key_fields
                                              + self.buffer_fields))
                    yield self._eval_buffers_jit(self._update_jit(
                        empty_batch(self.child.output_schema)))
                return
            if len(raw) == 1:
                whole = raw[0]
            else:
                whole = concat_batches(
                    raw, bucket_capacity(sum(b.capacity for b in raw)))
            yield self._eval_buffers_jit(self._update_jit(whole))
            return

        def register(part):
            # a partial is merged at the capacity bucket of the groups it
            # HOLDS, not of the batch it came from: padding rows carry no
            # value, and _merge_and_emit concatenates, sorts and scans
            # whatever capacity is recorded here. A partial whose bucket
            # is its capacity (groups ~ rows) is left exactly as it is.
            made = int(part.capacity)
            part = self._cut_to_groups(part)
            # registered handles start unpinned (spillable)
            spillables.append((register_with_retry(part, buf_schema,
                                                   catalog=cat,
                                                   name=self.name),
                               int(part.capacity)))
            qtrace.count(partialsCut=int(part.capacity < made),
                         partialRowsMade=made,
                         partialRowsKept=int(part.capacity))

        try:
            self._each_partial(p, register)
            finalize = self.mode in (AggregateMode.FINAL,
                                     AggregateMode.COMPLETE)
            if not spillables:
                if not self.key_fields and p == 0:
                    # global aggregate over empty input still yields one row
                    from ..batch import empty_batch
                    seed = empty_batch(buf_schema)
                    yield self._final_jit(seed) if finalize \
                        else self._merge_jit(seed)
                return
            if len(spillables) == 1 and self.mode is AggregateMode.PARTIAL:
                # ONE update's partial holds each of its groups once already
                # (and is cut to their bucket): merging it alone would sort
                # all of it again to change nothing
                from ..memory import acquire_with_retry
                only = spillables[0][0]
                yield acquire_with_retry(only, name=self.name)
                only.done_with()
                return
            yield from self._merge_and_emit(spillables, finalize, cat,
                                            buf_schema)
        finally:
            # (also what a failed registration leaves behind: the handles
            # registered before it)
            for sb, _ in spillables:
                sb.close()

    def _each_partial(self, p: int, register) -> None:
        """Hand ``register`` every partial of the partition, in batch
        order, each ONE BATCH LATE: the group count that ``register``
        reads waits for the update it belongs to, so batch k+1 is pulled
        and its update dispatched before partial k is handed over, and
        the device keeps one update in flight. Only the newest partial is
        ever unregistered behind the one in flight; the last is flushed
        after the loop."""
        updating = self.mode in (AggregateMode.PARTIAL,
                                 AggregateMode.COMPLETE)
        newest = None
        for batch in self.child.execute_partition(p):
            part = self._update_jit(batch) if updating else batch
            if newest is not None:
                register(newest)
            newest = part
        if newest is not None:
            register(newest)

    #: the name of ``_held_rows``' read (``trace.host_read``)
    _GROUPS_READ = "aggGroups"

    def _held_rows(self, batch: ColumnarBatch) -> int:
        """The rows a batch holds, on the host: waits for the program
        that made the batch."""
        return int(host_read(self._GROUPS_READ, batch.num_rows))

    def _cut_to_groups(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Slice a buffer-layout batch to the capacity bucket of the rows
        it holds (rows are compact from 0: one group a row). A batch at
        the smallest bucket is not even read."""
        if batch.capacity <= MIN_CAPACITY:
            return batch
        return cut_to_rows(batch, self._held_rows(batch))

    def _merge_and_emit(self, entries, finalize, cat, buf_schema):
        """Merge spilled partials WITHOUT ever acquiring more than
        ``max_result_rows`` of buffered rows at once (reference:
        tryMergeAggregatedBatches under targetMergeBatchSize,
        aggregate.scala:86-125). Two phases:

        1. windowed concat+merge passes — shrinks fast when keys repeat
           across batches;
        2. if a pass stops shrinking (high-cardinality keys), sort-based
           out-of-core fallback: key-sort all partials through the spilled
           chunked merge tree, then stream chunks in global key order,
           merging each and emitting every group except the boundary one
           (carried into the next chunk)."""
        from ..memory import register_with_retry, with_retry_no_split

        def _acquire_group(grp):
            """Pin a group of partials transactionally: a mid-loop OOM
            unpins what this attempt already pinned, so the retry loop
            re-runs against a clean (fully spillable) state."""
            got = []
            try:
                for sb, _ in grp:
                    got.append(sb.get())  # retry-ok: _acquire_group runs only inside final_merge/window_merge bodies under with_retry_no_split
            except BaseException:
                for j in range(len(got)):
                    grp[j][0].done_with()
                raise
            return got

        window = self.max_result_rows
        if self._wide_buffers:
            window = max(window >> 2, 1)
        while True:
            total = sum(c for _, c in entries)
            if len(entries) == 1 or total <= window:
                def final_merge():
                    batches = _acquire_group(entries)
                    merged = batches[0] if len(batches) == 1 else \
                        concat_batches_encoded(batches, bucket_capacity(total))
                    for sb, _ in entries:
                        sb.done_with()
                    return merged
                merged = with_retry_no_split(final_merge, catalog=cat,
                                             name=self.name)
                yield self._final_jit(merged) if finalize \
                    else self._merge_jit(merged)
                return
            # one windowed pre-merge pass
            new_entries, shrunk = [], 0
            i = 0
            while i < len(entries):
                grp, cap_sum = [], 0
                while i < len(entries) and (
                        not grp or cap_sum + entries[i][1] <= window):
                    grp.append(entries[i])
                    cap_sum += entries[i][1]
                    i += 1
                if len(grp) == 1:
                    new_entries.append(grp[0])
                    continue

                def window_merge(grp=grp, cap_sum=cap_sum):
                    batches = _acquire_group(grp)
                    merged = self._cut_to_groups(self._merge_jit(
                        concat_batches_encoded(batches, bucket_capacity(cap_sum))))
                    for sb, _ in grp:
                        sb.done_with()
                    return merged

                merged = with_retry_no_split(window_merge, catalog=cat,
                                             name=self.name)
                for sb, _ in grp:
                    sb.close()
                nsb = register_with_retry(merged, buf_schema, catalog=cat,
                                          name=self.name)
                new_entries.append((nsb, int(merged.capacity)))
                shrunk += cap_sum - int(merged.capacity)
            # mutate the caller's list so the finally-close sees live handles
            entries[:] = new_entries
            if shrunk * 10 < total:
                # high-cardinality: merging barely shrinks → sort-based OOC
                yield from self._ooc_sorted_merge(entries, finalize, cat,
                                                  buf_schema)
                return

    def _ooc_sorted_merge(self, entries, finalize, cat, buf_schema):
        """Sort-based OOC aggregation: global key order via the spilled
        chunked merge tree, then bounded per-chunk merges. Only the boundary
        group can span chunks, so it is carried forward and every other
        group is emitted as soon as its chunk is merged."""
        from ..expressions.base import BoundReference
        from .common import slice_batch
        from .ooc_sort import OutOfCoreSorter
        from .sort import SortOrder

        orders = [SortOrder(BoundReference(i, f.dtype, f.nullable, f.name))
                  for i, f in enumerate(self.key_fields)]
        chunk_rows = max(min(self.max_result_rows // 4, 1 << 16),
                         MIN_CAPACITY)
        sorter = OutOfCoreSorter(orders, buf_schema, cat,
                                 chunk_rows=chunk_rows)
        slice_jit = jit_named("slice_batch", slice_batch, static_argnums=3)

        def batches():
            from ..memory import acquire_with_retry
            for sb, _ in entries:
                b = acquire_with_retry(sb, name=self.name)
                sb.done_with()
                yield b

        carry: Optional[ColumnarBatch] = None
        for chunk in sorter.sort(batches()):
            if carry is not None:
                cap = bucket_capacity(carry.capacity + chunk.capacity)
                chunk = concat_batches([carry, chunk], cap)
            merged = self._merge_jit(chunk)
            n = int(host_read("aggMerged", merged.num_rows))
            if n == 0:
                carry = None
                continue
            if n == 1:
                carry = slice_jit(merged, jnp.int32(0), jnp.int32(1),
                                  MIN_CAPACITY)
                continue
            emit = slice_jit(merged, jnp.int32(0), jnp.int32(n - 1),
                             bucket_capacity(n - 1))
            carry = slice_jit(merged, jnp.int32(n - 1), jnp.int32(1),
                              MIN_CAPACITY)
            yield self._eval_buffers_jit(emit) if finalize else emit
        if carry is not None:
            yield self._eval_buffers_jit(carry) if finalize else carry


class RollupExec(HashAggregateExec):
    """The coarser levels of a rollup, made from the finest level's
    partials instead of from the rows (the planner puts it between the
    Partial and the Final stage where the aggregate's child is a
    rollup-shaped Expand, which then emits its finest projection alone).

    Level j of a rollup is level j-1 with more keys nulled and its literal
    keys (``spark_grouping_id``) replaced, and every buffer merges
    associatively, so merging level j-1's groups under level j's keys gives
    exactly the partial that aggregating projection j of the rows would
    have: a sum rolls up exactly. Each input batch (buffer layout, finest
    level) is handed on, then each coarser level in turn, merged from the
    level before it at the capacity bucket of the groups THAT level holds:
    the rows are sorted once at their own size and the levels at theirs,
    where an Expand sorts every row once a level. The Final stage merges
    whatever partials it is given, so several input batches (or several
    partitions) are fine.

    ``levels``: for each coarser level, finest first, ``(nulled, literals)``:
    the ordinals of the keys null at that level and ``{ordinal: int}`` for
    the literal keys."""

    _GROUPS_READ = "rollupGroups"

    def __init__(self, levels, group_exprs, agg_exprs,
                 child: "HashAggregateExec", **kw):
        super().__init__(group_exprs, agg_exprs, child,
                         AggregateMode.PARTIAL_MERGE, **kw)
        nk = len(self.key_fields)
        self.literal_keys = tuple(sorted({i for _, lits in levels
                                          for i in lits}))
        self._levels = [
            (np.asarray([i in nulled for i in range(nk)], bool),
             np.asarray([lits.get(i, 0) for i in range(nk)], np.int64))
            for nulled, lits in levels]

        def level(self, batch: ColumnarBatch, nulled, lits) -> ColumnarBatch:
            cols = list(batch.columns)
            for i in range(len(self.key_fields)):
                c = cols[i]
                if i in self.literal_keys:
                    cols[i] = c.replace(data=jnp.full_like(c.data, lits[i]))
                    continue
                keep = ~nulled[i]
                cols[i] = c.replace(
                    data=jnp.where(keep, c.data, jnp.zeros((), c.data.dtype)),
                    validity=c.validity & keep,
                    lengths=None if c.lengths is None
                    else jnp.where(keep, c.lengths, 0))
            return self._merge_kernel(
                ColumnarBatch(tuple(cols), batch.num_rows), final=False)

        self._level_jit = KernelPrograms(
            self, self._PROGRAM_READS + ("literal_keys",)).jit(
                dec128_role("level", (f.dtype for f in self.buffer_fields)),
                level)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from .. import trace as qtrace
        for batch in self.child.execute_partition(p):
            batch = self._cut_to_groups(batch)
            yield batch
            for nulled, lits in self._levels:
                qtrace.count(rollupLevels=1,
                             rollupSlotsMerged=int(batch.capacity))
                batch = self._cut_to_groups(
                    self._level_jit(batch, nulled, lits))
                yield batch
