"""Window exec.

Reference: sql-plugin/.../GpuWindowExec.scala:1876 (batched partitioned
windows; running-window :1534; cached double-pass :1846). See
expressions/window.py for the lowering strategy: one sort, then segmented
scans — every window expression in the projection shares the same sorted
layout and fuses into a single XLA computation per batch.

Output = child columns + one column per window expression, in the child's
original row order (results are scattered back through the sort
permutation), matching Spark's WindowExec contract.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn, Field, Schema, bucket_capacity
from ..expressions.aggregates import _cumsum as prefix_sum
from ..expressions.base import Alias, EvalContext, Expression, raw_eval
from ..expressions.window import (LagLead, NTile, Rank, RowNumber,
                                  WindowAgg, WindowExpression, WindowFrame,
                                  segmented_scan)
from ..trace import host_read
from ..types import TypeKind
from .base import Exec, UnaryExec
from .common import KernelPrograms, adjacent_equal, concat_batches_encoded, \
    gather_column, lex_sort_permutation, sort_operands


def _unalias(e: Expression) -> Tuple[WindowExpression, str]:
    if isinstance(e, Alias):
        return e.child, e.name
    return e, "window"


class WindowExec(UnaryExec):
    """All window expressions must share one WindowSpec (the planner splits
    multi-spec projections into a chain of WindowExecs, like the reference's
    GpuWindowExec partitioning of window ops)."""

    def coalesce_goal_for_child(self, i):
        from .coalesce import TargetSize
        return TargetSize()

    def __init__(self, window_exprs: Sequence[Expression], child: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        named = [_unalias(e) for e in window_exprs]
        self.exprs = [w.bind(child.output_schema) for w, _ in named]
        self.names = [n for _, n in named]
        # Expression __eq__ builds comparison trees, so compare specs by repr
        spec_keys = {(repr(w.spec.partition_keys), repr(w.spec.orders))
                     for w in self.exprs}
        if len(spec_keys) > 1:
            raise ValueError("one WindowExec handles one partition/order "
                             "spec; chain execs for multiple")
        self.spec = self.exprs[0].spec
        # fail fast on frames the device kernel cannot express — the planner
        # tags these for CPU fallback before ever constructing this exec;
        # without this guard a bounded RANGE frame would silently get ROWS
        # semantics from the shift-fold path
        from ..expressions.window import NthValue as _NV, WindowAgg as _WA, \
            unsupported_frame_reason
        for w in self.exprs:
            if isinstance(w.function, (_WA, _NV)):
                reason = unsupported_frame_reason(w.spec.frame, w.spec)
                if reason:
                    raise NotImplementedError(reason)
        fields = list(child.output_schema.fields)
        for w, n in zip(self.exprs, self.names):
            fields.append(Field(n, w.dtype, w.nullable))
        self._schema = Schema(fields)
        # everything the kernel reads of this exec: the program's key, and
        # all its stand-in has (common.KernelPrograms)
        self._kernel = KernelPrograms(self, ("exprs", "spec")).jit(
            "window", type(self)._window_kernel)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------------

    def _window_kernel(self, batch: ColumnarBatch) -> ColumnarBatch:
        cap = batch.capacity
        spec = self.spec
        live = batch.row_mask()
        # raw_eval: a dictionary-encoded string key sorts and compares on
        # its codes, one lane (within one column of one batch code order is
        # string order: dictenc.py invariant 2)
        pkeys = [raw_eval(e, batch, self.ctx) for e in spec.partition_keys]
        okeys = [raw_eval(o.child, batch, self.ctx) for o in spec.orders]

        ops = sort_operands(
            list(pkeys) + list(okeys),
            [False] * len(pkeys) + [o.descending for o in spec.orders],
            [True] * len(pkeys) + [o.effective_nulls_first
                                   for o in spec.orders], live)
        iota = jnp.arange(cap, dtype=jnp.int32)
        perm = lex_sort_permutation(ops)

        s_pkeys = [gather_column(c, perm) for c in pkeys]
        s_okeys = [gather_column(c, perm) for c in okeys]
        sorted_live = iota < batch.num_rows

        if s_pkeys:
            same_part = adjacent_equal(s_pkeys)
        else:
            same_part = jnp.concatenate(
                [jnp.zeros(1, bool), jnp.ones(cap - 1, bool)])
        head = sorted_live & ~same_part          # first row of each partition
        tail = sorted_live & jnp.concatenate(
            [~same_part[1:] | ~sorted_live[1:], jnp.ones(1, bool)])

        # peer groups (ties on order keys) for RANGE frames / rank
        if s_okeys:
            same_peer = same_part & adjacent_equal(s_okeys)
        else:
            same_peer = same_part
        peer_head = sorted_live & ~same_peer

        # back to the original row order: one inverse permutation for all
        inv = jnp.zeros(cap, jnp.int32).at[perm].set(iota)
        out_cols = [
            gather_column(self._eval_window(w, batch, perm, head, tail,
                                            peer_head, sorted_live, cap),
                          inv, live)
            for w in self.exprs]
        return ColumnarBatch(batch.columns + tuple(out_cols), batch.num_rows)

    # ------------------------------------------------------------------

    def _eval_window(self, w: WindowExpression, batch, perm, head, tail,
                     peer_head, live, cap: int) -> DeviceColumn:
        fn = w.function
        iota = jnp.arange(cap, dtype=jnp.int32)
        seg_start = segmented_scan(
            jnp.where(head, iota, 0), head, jnp.maximum)
        pos = iota - seg_start                      # 0-based row in partition

        if isinstance(fn, RowNumber):
            return DeviceColumn((pos + 1).astype(jnp.int32), live, None,
                                T.INT32)
        if isinstance(fn, Rank):
            peer_start = segmented_scan(
                jnp.where(peer_head, iota, 0), head, jnp.maximum)
            if fn.dense:
                v = segmented_scan(peer_head.astype(jnp.int32), head,
                                   jnp.add)
            else:
                v = peer_start - seg_start + 1
            return DeviceColumn(v.astype(jnp.int32), live, None, T.INT32)
        from ..expressions.window import CumeDist, NthValue, PercentRank
        if isinstance(fn, PercentRank):
            peer_start = segmented_scan(
                jnp.where(peer_head, iota, 0), head, jnp.maximum)
            rank = peer_start - seg_start + 1
            seg_len = self._seg_len(head, tail, iota, cap)
            v = jnp.where(seg_len > 1,
                          (rank - 1).astype(jnp.float64) /
                          jnp.maximum(seg_len - 1, 1).astype(jnp.float64),
                          0.0)
            return DeviceColumn(v, live, None, T.FLOAT64)
        if isinstance(fn, CumeDist):
            peer_tail = jnp.concatenate(
                [peer_head[1:], jnp.ones(1, bool)]) | tail
            pe = segmented_scan(jnp.where(peer_tail, iota, cap),
                                peer_tail, jnp.minimum, reverse=True)
            seg_len = self._seg_len(head, tail, iota, cap)
            v = (pe - seg_start + 1).astype(jnp.float64) / \
                jnp.maximum(seg_len, 1).astype(jnp.float64)
            return DeviceColumn(v, live, None, T.FLOAT64)
        if isinstance(fn, NthValue):
            src = fn.child.eval(batch, self.ctx)
            s = gather_column(src, perm)
            lo, hi = self._frame_bounds(w.spec.frame, head, tail,
                                        peer_head, live, iota, cap,
                                        (batch, perm))
            idx = lo + fn.n - 1
            ok = (idx <= hi) & (idx >= lo) & live
            v = gather_column(s, jnp.clip(idx, 0, cap - 1))
            return v.replace(validity=v.validity & ok)
        if isinstance(fn, NTile):
            seg_len = self._seg_len(head, tail, iota, cap)
            b = jnp.int32(fn.buckets)
            base, rem = seg_len // b, seg_len % b
            cut = rem * (base + 1)
            v = jnp.where(pos < cut, pos // jnp.maximum(base + 1, 1),
                          rem + (pos - cut) // jnp.maximum(base, 1)) + 1
            return DeviceColumn(v.astype(jnp.int32), live, None, T.INT32)
        if isinstance(fn, LagLead):
            src = fn.child.eval(batch, self.ctx)
            s = gather_column(src, perm)
            off = fn.offset if fn.is_lag else -fn.offset
            shifted_ix = jnp.clip(iota - off, 0, cap - 1)
            ok = (iota - off >= 0) & (iota - off < cap)
            sv = gather_column(s, shifted_ix)
            # same partition check: partition id = cumsum(head)
            pid = prefix_sum(head.astype(jnp.int32))
            same = ok & (jnp.take(pid, shifted_ix) == pid) & live
            data = sv.data
            validity = sv.validity & same
            if fn.default is not None:
                dcol = gather_column(
                    fn.default.eval(batch, self.ctx), perm)
                use_d = ~same & live
                if s.lengths is not None:
                    data = jnp.where(use_d[:, None], dcol.data, data)
                    lengths = jnp.where(use_d, dcol.lengths, sv.lengths)
                    validity = jnp.where(use_d, dcol.validity, validity)
                    return DeviceColumn(data, validity & live, lengths,
                                        fn.dtype)
                # (a decimal past 18 digits is a limb matrix)
                data = jnp.where(use_d[:, None] if data.ndim > 1 else use_d,
                                 dcol.data, data)
                validity = jnp.where(use_d, dcol.validity, validity)
            return DeviceColumn(data, validity & live, sv.lengths, fn.dtype)
        if isinstance(fn, WindowAgg):
            return self._eval_window_agg(fn, w.spec.frame, batch, perm,
                                         head, tail, peer_head, live, cap)
        raise NotImplementedError(type(fn).__name__)

    def _seg_len(self, head, tail, iota, cap):
        seg_start = segmented_scan(jnp.where(head, iota, 0), head,
                                   jnp.maximum)
        seg_end = segmented_scan(jnp.where(tail, iota, cap), tail,
                                 jnp.minimum, reverse=True)
        return seg_end - seg_start + 1

    def _eval_window_agg(self, fn: WindowAgg, frame: WindowFrame, batch,
                         perm, head, tail, peer_head, live, cap: int
                         ) -> DeviceColumn:
        from ..expressions.aggregates import (Average, Count, Max, Min, Sum)
        agg = fn.agg
        child_cols = [gather_column(c.eval(batch, self.ctx), perm)
                      for c in agg.children]
        col = child_cols[0] if child_cols else None
        iota = jnp.arange(cap, dtype=jnp.int32)

        if isinstance(agg, Count):
            x = ((col.validity & live) if col is not None else live
                 ).astype(jnp.int64)
            out_t = T.INT64
            v, valid = self._frame_reduce(x, jnp.add, jnp.int64(0), frame,
                                          head, tail, peer_head, live, iota,
                                          cap, (batch, perm))
            return DeviceColumn(v, live, None, out_t)
        if isinstance(agg, (Sum, Average)):
            acc_t = jnp.float64 if isinstance(agg, Average) or \
                agg.dtype.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64) \
                else jnp.int64
            ok = col.validity & live
            x = jnp.where(ok, col.data, 0).astype(acc_t)
            s, _ = self._frame_reduce(x, jnp.add, acc_t(0), frame, head,
                                      tail, peer_head, live, iota, cap,
                                      (batch, perm))
            n, _ = self._frame_reduce(ok.astype(jnp.int64), jnp.add,
                                      jnp.int64(0), frame, head, tail,
                                      peer_head, live, iota, cap,
                                      (batch, perm))
            if isinstance(agg, Average):
                v = s / jnp.maximum(n, 1).astype(jnp.float64)
                return DeviceColumn(jnp.where(n > 0, v, 0.0),
                                    (n > 0) & live, None, T.FLOAT64)
            return DeviceColumn(s.astype(agg.dtype.storage_dtype),
                                (n > 0) & live, None, agg.dtype)
        if isinstance(agg, (Min, Max)):
            is_min = isinstance(agg, Min)
            ok = col.validity & live
            if agg.dtype.kind is TypeKind.BOOLEAN:
                fill = jnp.asarray(is_min, bool)
                op = jnp.logical_and if is_min else jnp.logical_or
                x = jnp.where(ok, col.data, fill)
            elif agg.dtype.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64):
                fill = jnp.asarray(jnp.inf if is_min else -jnp.inf,
                                   col.data.dtype)
                op = jnp.minimum if is_min else jnp.maximum
                x = jnp.where(ok, col.data, fill)
            else:
                info = jnp.iinfo(col.data.dtype)
                fill = jnp.asarray(info.max if is_min else info.min,
                                   col.data.dtype)
                op = jnp.minimum if is_min else jnp.maximum
                x = jnp.where(ok, col.data, fill)
            v, _ = self._frame_reduce(x, op, fill, frame, head, tail,
                                      peer_head, live, iota, cap,
                                      (batch, perm))
            n, _ = self._frame_reduce(ok.astype(jnp.int64), jnp.add,
                                      jnp.int64(0), frame, head, tail,
                                      peer_head, live, iota, cap,
                                      (batch, perm))
            valid = (n > 0) & live
            return DeviceColumn(jnp.where(valid, v, jnp.zeros_like(v)),
                                valid, None, agg.dtype)
        raise NotImplementedError(type(agg).__name__)

    def _frame_reduce(self, x, op, identity, frame: WindowFrame, head, tail,
                      peer_head, live, iota, cap, src):
        """Reduce x over each row's frame; returns (values, None). ``src``:
        the (batch, sort permutation) the sorted layout came from."""
        if frame.is_full_partition:
            # segment total broadcast back: forward running to tail, gather
            run = segmented_scan(x, head, op)
            seg_end = segmented_scan(jnp.where(tail, iota, cap), tail,
                                     jnp.minimum, reverse=True)
            return jnp.take(run, jnp.clip(seg_end, 0, cap - 1)), None
        if frame.is_running:
            run = segmented_scan(x, head, op)
            if frame.is_rows:
                return run, None
            # RANGE running: value at each row = running at its peer END
            peer_tail = jnp.concatenate(
                [peer_head[1:], jnp.ones(1, bool)]) | tail
            pe = segmented_scan(jnp.where(peer_tail, iota, cap), peer_tail,
                                jnp.minimum, reverse=True)
            return jnp.take(run, jnp.clip(pe, 0, cap - 1)), None
        if frame.is_rows and frame.start is not None and \
                frame.end is not None and frame.end - frame.start < 64:
            # small literal ROWS windows: static shift fold beats the
            # scan/gather machinery (exact for every op, incl. floats)
            p, f = -frame.start, frame.end
            pid = prefix_sum(head.astype(jnp.int32))
            acc = jnp.full(x.shape, identity, x.dtype)
            for o in range(-p, f + 1):
                ix = jnp.clip(iota + o, 0, cap - 1)
                ok = (iota + o >= 0) & (iota + o < cap)
                same = ok & (jnp.take(pid, ix) == pid)
                contrib = jnp.where(same, jnp.take(x, ix), identity)
                acc = op(acc, contrib)
            return acc, None
        # general path: per-row [lo, hi] absolute bounds, then a
        # prefix-difference (sums) or sparse-table (min/max) reduction
        # (reference: GpuWindowExec.scala:1846 double-pass machinery)
        lo, hi = self._frame_bounds(frame, head, tail, peer_head, live,
                                    iota, cap, src)
        return self._reduce_between(x, op, identity, lo, hi, head, cap), None

    # ------------------------------------------------------------------
    # General frames (round 4 — VERDICT r3 Next #3)
    # ------------------------------------------------------------------

    def _partition_bounds(self, head, tail, iota, cap):
        seg_start = segmented_scan(jnp.where(head, iota, 0), head,
                                   jnp.maximum)
        seg_end = segmented_scan(jnp.where(tail, iota, cap), tail,
                                 jnp.minimum, reverse=True)
        return seg_start, seg_end

    def _frame_bounds(self, frame: WindowFrame, head, tail, peer_head,
                      live, iota, cap, src):
        """Absolute sorted-layout [lo, hi] index bounds of each row's
        frame (hi < lo = empty). ROWS bounds are positional; RANGE bounds
        with nonzero offsets rank shifted ORDER VALUES into the sorted
        layout via one merge-sort per bounded side."""
        seg_start, seg_end = self._partition_bounds(head, tail, iota, cap)
        if frame.is_rows:
            lo = seg_start if frame.start is None \
                else jnp.maximum(iota + frame.start, seg_start)
            hi = seg_end if frame.end is None \
                else jnp.minimum(iota + frame.end, seg_end)
            return lo, jnp.maximum(hi, lo - 1)
        # RANGE: peer-group bounds for CURRENT ROW ends; merge-rank for
        # value offsets
        peer_tail = jnp.concatenate(
            [peer_head[1:], jnp.ones(1, bool)]) | tail
        peer_start = segmented_scan(jnp.where(peer_head, iota, 0), head,
                                    jnp.maximum)
        peer_end = segmented_scan(jnp.where(peer_tail, iota, cap),
                                  peer_tail, jnp.minimum, reverse=True)
        if frame.start is None:
            lo = seg_start
        elif frame.start == 0:
            lo = peer_start
        else:
            lo = self._range_rank(frame.start, True, head, peer_start,
                                  peer_end, live, iota, cap, src)
        if frame.end is None:
            hi = seg_end
        elif frame.end == 0:
            hi = peer_end
        else:
            hi = self._range_rank(frame.end, False, head, peer_start,
                                  peer_end, live, iota, cap, src)
        return lo, hi

    def _range_rank(self, delta: int, is_lo: bool, head, peer_start,
                    peer_end, live, iota, cap, src):
        """Rank each row's shifted order value among the partition's rows:
        lo = first index with value >= v+delta, hi = last index with
        value <= v+delta. One (pid, null-rank, word, tag, iota) merge sort
        of 2n lanes; bound rows' sorted relative order equals their
        original order (values ascend within partitions), so
        count-of-data-before = merged position - own index. NULL order
        rows take their peer group (the SQL standard's all-nulls frame)."""
        from .common import orderable_words
        spec = self.spec
        o = spec.orders[0]
        # evaluated + sorted order column (CSE'd with the kernel's own
        # sort by XLA — identical subgraphs)
        batch, perm = src
        col = gather_column(o.child.eval(batch, self.ctx), perm)
        data = col.data

        def one_word(d):
            # f64 order values span two u32 words (hi, lo): fold them into
            # one u64 so a bound and a data row compare on a single lane
            ws = orderable_words(col.replace(data=d, validity=col.validity))
            if len(ws) == 1:
                return ws[0]
            return (ws[0].astype(jnp.uint64) << jnp.uint64(32)) \
                | ws[1].astype(jnp.uint64)

        if o.descending:
            # descending layouts sort by FLIPPED orderable words (~w,
            # bijective — value negation would merge INT_MIN with
            # INT_MIN+1); Spark's desc range frame covers values
            # [v-end, v-start], so the bound value is v - delta and only
            # the word domain flips
            shifted = self._sat_add(data, -delta)
            word = ~one_word(shifted)
            data_word = ~one_word(data)
        else:
            shifted = self._sat_add(data, delta)
            word = one_word(shifted)
            data_word = one_word(data)
        nulls_first = o.effective_nulls_first
        n_rank = jnp.where(col.validity,
                           jnp.uint8(1),
                           jnp.uint8(0 if nulls_first else 2))
        pid_raw = prefix_sum(head.astype(jnp.int32))
        pid = jnp.where(live, pid_raw, jnp.int32(2147483647))
        # tag: lo-side bounds sort BEFORE equal data (rank = count of
        # data strictly below); hi-side bounds sort AFTER equal data
        tag_data = jnp.full(cap, 1 if is_lo else 0, jnp.uint8)
        tag_bound = jnp.full(cap, 0 if is_lo else 1, jnp.uint8)
        # bounds carry their row's OWN null rank: null-row bounds stay
        # confined to the null region (their words are garbage; the rank
        # lane keeps them from interleaving among real-valued entries,
        # which preserves the bounds-sort-in-original-order identity the
        # count arithmetic relies on)
        pid = pid.astype(jnp.uint32)            # non-negative by construction
        perm2 = lex_sort_permutation([
            jnp.concatenate([pid, pid]),
            jnp.concatenate([n_rank, n_rank]),
            jnp.concatenate([data_word, word]),
            jnp.concatenate([tag_data, tag_bound]),
        ])
        inv = jnp.zeros(2 * cap, jnp.int32).at[perm2].set(
            jnp.arange(2 * cap, dtype=jnp.int32))
        count_before = inv[cap:] - iota          # data rows sorting before
        if is_lo:
            pos = count_before                   # first idx with w >= bound
        else:
            pos = count_before - 1               # last idx with w <= bound
        # null order rows: frame = their (all-null) peer group
        pos = jnp.where(col.validity, pos,
                        peer_start if is_lo else peer_end)
        return pos

    @staticmethod
    def _sat_add(x, d: int):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x + d
        info = jnp.iinfo(x.dtype)
        if d >= 0:
            return jnp.where(x > info.max - d, info.max, x + d)
        return jnp.where(x < info.min - d, info.min, x + d)

    def _reduce_between(self, x, op, identity, lo, hi, head, cap):
        """Per-row reduce of x over [lo, hi] (identity when hi < lo).
        Sums ride a segmented prefix difference (rounding stays partition-
        local); arbitrary ops (min/max/and/or — idempotent) use a doubling
        sparse table: result = op(T_j[lo], T_j[hi-2^j+1]) with
        j = floor(log2(len)), overlap harmless for idempotent ops."""
        iota = jnp.arange(cap, dtype=jnp.int32)
        ident = jnp.asarray(identity, x.dtype)
        empty = hi < lo
        lo_c = jnp.clip(lo, 0, cap - 1)
        hi_c = jnp.clip(hi, 0, cap - 1)
        if op is jnp.add:
            run = segmented_scan(x, head, jnp.add)
            seg_start = segmented_scan(jnp.where(head, iota, 0), head,
                                       jnp.maximum)
            upper = jnp.take(run, hi_c)
            lower = jnp.where(lo > seg_start,
                              jnp.take(run, jnp.clip(lo - 1, 0, cap - 1)),
                              jnp.zeros_like(ident))
            return jnp.where(empty, ident, upper - lower)
        levels = [x]
        d = 1
        while d < cap:
            top = levels[-1]
            shifted = jnp.concatenate(
                [top[d:], jnp.full((d,), ident, top.dtype)])
            levels.append(op(top, shifted))
            d <<= 1
        stacked = jnp.stack(levels)              # (J, cap)
        L = jnp.maximum(hi - lo + 1, 1)
        j = jnp.floor(jnp.log2(L.astype(jnp.float64))).astype(jnp.int32)
        flat = stacked.reshape(-1)
        a = jnp.take(flat, j * cap + lo_c)
        b_pos = jnp.clip(hi - jnp.left_shift(jnp.int32(1), j) + 1,
                         0, cap - 1)
        b = jnp.take(flat, j * cap + b_pos)
        return jnp.where(empty, ident, op(a, b))

    # ------------------------------------------------------------------

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        # windows need WHOLE window-partitions per batch. A key-batching
        # child guarantees that with bounded batch sizes (reference:
        # GpuKeyBatchingIterator) — process batch-at-a-time; otherwise
        # concat the stream partition into one batch.
        from .. import trace as qtrace
        qtrace.count(windowExprs=len(self.exprs))
        guarantee = getattr(self.child, "key_complete_for", None)
        if guarantee is not None and \
                guarantee == repr(list(self.spec.partition_keys)):
            for batch in self.child.execute_partition(p):
                qtrace.count(windowBatches=1,
                             windowSlots=int(batch.capacity))
                yield self._kernel(batch)
            return
        # accumulated input batches ride the spill catalog across the
        # retry boundary (SpillableColumnarBatch discipline); the concat +
        # window kernel re-runs after an OOM with pins released and the
        # store spilled (no split: a window partition must stay whole)
        from ..memory import admit_all, device_budget, with_retry_no_split
        cat = device_budget()
        in_schema = self.child.output_schema
        inputs = admit_all(self.child.execute_partition(p), in_schema, cat,
                           name=f"{self.name}.admit")
        if not inputs:
            return

        def assemble_and_run():
            got = []
            try:
                for item in inputs:
                    got.append(item.acquire())
                whole = got[0]
                if len(got) > 1:
                    # sized by the rows held, not by the capacities
                    rows = sum(int(n) for n in host_read(
                        "windowRows", [b.num_rows for b in got]))
                    whole = concat_batches_encoded(
                        got, bucket_capacity(max(rows, 1)))
                qtrace.count(windowBatches=1,
                             windowSlots=int(whole.capacity))
                return self._kernel(whole)
            finally:
                for j in range(len(got)):
                    inputs[j].release()

        try:
            yield with_retry_no_split(assemble_and_run, catalog=cat,
                                      name=self.name)
        finally:
            for item in inputs:
                item.close()
