"""Aggregate functions with Spark semantics.

Reference: sql-plugin/.../sql/rapids/AggregateFunctions.scala (2,154 LoC) —
each GPU aggregate declares update/merge cudf aggregations plus a final
projection. The TPU-native re-design: groups become XLA *segments*. After the
exec sorts a batch by its grouping keys, every aggregate is a
``jax.ops.segment_*`` reduction with a STATIC segment count (the capacity
bucket), so the whole update/merge pipeline is one fused XLA computation —
no per-aggregation kernel dispatch like the reference's per-agg JNI calls.

Buffer model mirrors Spark's ImperativeAggregate:
- ``update``  : input rows  -> per-group buffer columns (partial aggregation)
- ``merge``   : buffer rows -> per-group buffer columns (shuffle-side combine)
- ``evaluate``: buffer cols -> final result column

Type-widening rules follow Spark exactly: sum(int*)→bigint, sum(float*)→
double, avg(*)→double, count→bigint(never null), min/max preserve type,
stddev/variance→double (Welford/Chan parallel merge).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn
from ..types import SqlType, TypeKind
from .base import EvalContext, Expression


# NOTE on TPU cost model (docs/tpu_compat.md): jax.ops.segment_* lowers
# to scatters; 64-bit operands are EMULATED on v5e, which makes their
# scatters ~4.5x the 32-bit cost (340ms vs 74ms per 4M rows). The ms
# figures in this file's comments are round-3/4 profiles taken through the
# old plug-in; none is re-measured on this installation.
# When the aggregate exec publishes the per-group (start, end) row bounds
# it already computed (segment_bounds context), every segment reduction
# instead runs as a SEGMENTED HILLIS-STEELE SUFFIX SCAN inside one
# lax.fori_loop — log2(n) passes of roll+where+combine, all elementwise
# (36ms vs 329ms for a 4M f64 sum, round-3 profile), followed by one gather at the group
# starts. Exact for integers; for floats the pairwise tree is MORE
# accurate than sequential scatter accumulation. (lax.associative_scan
# unrolls: for v5e at 1M i32 rows it compiles to 38 MB of code in ~127 s,
# tools/aot_compile.py; the ladders here trace log2(block) static shifts.)

#: THREAD-LOCAL: the bounds are traced arrays published mid-trace, and
#: the serving tier runs N concurrent collects over one process
#: (server.concurrentCollects) — a module global here let one thread's
#: tracer leak into another's trace (UnexpectedTracerError under the
#: concurrent-client load test)
_SEG_TL = threading.local()


def _seg_bounds():
    return getattr(_SEG_TL, "bounds", None)


class segment_bounds:
    """Trace-time context: group-slot (start_row, end_row) bounds over the
    key-sorted batch, published by HashAggregateExec for the duration of
    the agg.update/merge calls (per thread; see _SEG_TL)."""

    def __init__(self, starts, ends):
        self._b = (starts, ends)

    def __enter__(self):
        self._prev = _seg_bounds()
        _SEG_TL.bounds = self._b

    def __exit__(self, *a):
        _SEG_TL.bounds = self._prev


def _seg_scan_reduce(x, seg, identity, op):
    """suffix[i] = OP over x[j] for j in [i .. end of i's segment]."""
    return _suffix_scan_ladder(x, seg, op, identity)


def _cumsum(x):
    """Inclusive prefix sum, as a static-shift Hillis-Steele ladder. XLA's
    cumulative reduce-window is what the TPU compiler labours on: for v5e
    at 1M rows ``jnp.cumsum`` costs ~40 s of compile on i32/f32 and ~260 s
    on f64 (the variadic pair lowering also exhausts scoped vmem inside
    large fused programs), the ladder ~3 s (tools/aot_compile.py)."""
    return _prefix_ladder(x)


# ---------------------------------------------------------------------------
# Batched lane reductions
#
# A 4M-row gather cost ~55–65 ms (round-4 profile, old plug-in) NO MATTER
# the element type,
# and sibling gathers do NOT fuse — but a [N, m] matrix ROW gather costs the
# same as one scalar gather. So the fast aggregation path batches EVERY
# per-group reduction into shared float64 lane stacks:
#   - sums/counts: one stacked inclusive-prefix ladder + ONE row-gather at
#     segment ends and ONE at segment starts for all lanes together;
#   - min/max: one segmented suffix-scan ladder per direction, row-gathered
#     at segment starts.
# Integer sums ride as THREE 22-bit chunk lanes (chunk sums stay < 2^44,
# exact in f64; recombination wraps mod 2^64 — Spark's non-ANSI overflow).
# ---------------------------------------------------------------------------

_I64_CHUNK = np.uint64((1 << 22) - 1)


def _enc_i64_lanes(x) -> List[jax.Array]:
    """int64 -> three exact f64 chunk lanes (bits 0-21, 22-43, 44-65)."""
    u = x.astype(jnp.uint64)
    return [((u >> jnp.uint64(22 * i)) & _I64_CHUNK).astype(jnp.float64)
            for i in range(3)]


def _dec_i64_lanes(l0, l1, l2) -> jax.Array:
    """chunk-sum lanes -> int64 sum, wrapping mod 2^64."""
    return (l0.astype(jnp.uint64)
            + (l1.astype(jnp.uint64) << jnp.uint64(22))
            + (l2.astype(jnp.uint64) << jnp.uint64(44))).astype(jnp.int64)


class FastLanes:
    """Collects reduction lanes during the fast kernel's planning pass.

    Lanes are tagged ``exact``: integer-valued f64 lanes (counts, int-sum
    chunks) whose prefix differences are exact, versus genuine float lanes
    whose group sums must stay numerically LOCAL to the group (a whole-
    batch prefix difference cancels small groups against the global
    running sum — confirmed on device)."""

    def __init__(self, live: jax.Array):
        self.live = live
        self.sum_lanes: List[jax.Array] = []
        self.sum_exact: List[bool] = []
        self.min_lanes: List[jax.Array] = []
        self.max_lanes: List[jax.Array] = []
        self._count_cache: List[Tuple[Optional[jax.Array], int]] = []
        self._wide_cache: list = []

    def sum_f64(self, x) -> int:
        self.sum_lanes.append(x.astype(jnp.float64))
        self.sum_exact.append(False)
        return len(self.sum_lanes) - 1

    def _sum_exact_lane(self, x) -> int:
        self.sum_lanes.append(x.astype(jnp.float64))
        self.sum_exact.append(True)
        return len(self.sum_lanes) - 1

    def sum_int(self, x) -> Tuple[int, int, int]:
        i = len(self.sum_lanes)
        for lane in _enc_i64_lanes(x):
            self._sum_exact_lane(lane)
        return (i, i + 1, i + 2)

    def sum_wide(self, data: jax.Array, validity: jax.Array):
        """A decimal's exact sum of up to 128 bits (``data`` an int64
        unscaled vector or a limb tensor) as 22-bit chunk lanes
        (decimal128.chunk_lanes): three a 64-bit word. Returns (what
        ``LaneResults.sum_wide`` takes, the count lane of the rows
        summed). Two aggregates over one column (sum and avg) share the
        lanes: the cache holds references, as ``count``'s does."""
        for d, v, hit in self._wide_cache:
            if d is data and v is validity:
                return hit
        from .decimal128 import chunk_lanes
        ok = self.live if validity is self.live else (validity & self.live)
        lanes, offsets, bias_bit = chunk_lanes(data, ok)
        refs = [self._sum_exact_lane(x) for x in lanes]
        hit = ((refs, offsets, bias_bit), self.count(ok))
        self._wide_cache.append((data, validity, hit))
        return hit

    def count(self, ok: Optional[jax.Array]) -> int:
        """Count of true rows; ok=None counts live rows. The cache holds a
        REFERENCE to each mask (identity alone could alias a recycled id
        from a freed temporary in eager execution)."""
        key = None if ok is None or ok is self.live else ok
        for cached, idx in self._count_cache:
            if cached is key:
                return idx
        idx = self._sum_exact_lane(
            (self.live if ok is None else ok).astype(jnp.float64))
        self._count_cache.append((key, idx))
        return idx

    def min_f64(self, x) -> int:
        self.min_lanes.append(x.astype(jnp.float64))
        return len(self.min_lanes) - 1

    def max_f64(self, x) -> int:
        self.max_lanes.append(x.astype(jnp.float64))
        return len(self.max_lanes) - 1


# Block width for the two-level scans. A flat Hillis-Steele ladder over n
# rows runs log2(n) full-array rounds; reshaping to (n/C, C) runs the heavy
# rounds along the SHORT axis only (log2(C) of them) plus a cheap n/C-sized
# second level. A round-4 chip profile: segmented suffix
# over (4M,6) f64 went 58 ms (flat, 22 rounds) -> 3.8 ms at C=512, exact to
# 2.8e-14.
_SCAN_BLOCK = 512


def _prefix_ladder_flat(m: jax.Array, op=jnp.add) -> jax.Array:
    n = m.shape[0]
    d = 1
    while d < n:
        pad = jnp.zeros((d,) + m.shape[1:], m.dtype)
        m = op(m, jnp.concatenate([pad, m[:-d]], axis=0))
        d <<= 1
    return m


def _running_max(x: jax.Array) -> jax.Array:
    """Inclusive running maximum of a non-negative 1-D lane: the prefix
    sum's two-level ladder with max for + (its zero pads are max's
    identity on such a lane)."""
    n = x.shape[0]
    C = _SCAN_BLOCK
    if n <= C or n % C != 0:
        return _prefix_ladder_flat(x, jnp.maximum)
    R = n // C
    acc = x.reshape(R, C)
    d = 1
    while d < C:
        z = jnp.zeros((R, d), x.dtype)
        acc = jnp.maximum(acc, jnp.concatenate([z, acc[:, :-d]], axis=1))
        d <<= 1
    heads = _prefix_ladder_flat(acc[:, -1], jnp.maximum)
    carry = jnp.concatenate([jnp.zeros(1, x.dtype), heads[:-1]])
    return jnp.maximum(acc, carry[:, None]).reshape(n)


def _prefix_ladder(m: jax.Array) -> jax.Array:
    """Inclusive prefix sum along axis 0 (native cumsum on emulated 64-bit
    lowers to a vmem-exhausting reduce-window; cumsum over (4M,6) f64 also
    took 160 ms where this blocked ladder took ~4 ms — round-4 profile, old
    plug-in)."""
    n = m.shape[0]
    C = _SCAN_BLOCK
    if n <= C or n % C != 0:
        return _prefix_ladder_flat(m)
    squeeze = m.ndim == 1
    if squeeze:
        m = m[:, None]
    R = n // C
    acc = m.reshape(R, C, m.shape[1])
    d = 1
    while d < C:
        z = jnp.zeros((R, d, acc.shape[2]), acc.dtype)
        acc = acc + jnp.concatenate([z, acc[:, :-d]], axis=1)
        d <<= 1
    totals = acc[:, -1, :]
    offs = _prefix_ladder_flat(totals) - totals     # exclusive row offsets
    out = (acc + offs[:, None, :]).reshape(n, -1)
    return out[:, 0] if squeeze else out


def _suffix_flat(m, seg, op, identity):
    n = m.shape[0]
    ident = jnp.full((1,) + m.shape[1:], identity, m.dtype)
    d = 1
    while d < n:
        sm = jnp.concatenate([m[d:], jnp.broadcast_to(
            ident, (d,) + m.shape[1:])], axis=0)
        sseg = jnp.concatenate([seg[d:], jnp.full((d,), -2, seg.dtype)])
        ok = (sseg == seg)
        m = op(m, jnp.where(ok[:, None] if m.ndim > 1 else ok, sm,
                            jnp.asarray(identity, m.dtype)))
        d <<= 1
    return m


def _suffix_scan_ladder(m: jax.Array, seg: jax.Array, op, identity) -> jax.Array:
    """Segmented suffix scan along axis 0: row i becomes OP over rows
    [i..end of i's segment] per lane.

    Two-level blocked form: within-block segmented suffix along the short
    axis (log2(C) rounds), then a block-start recurrence over n/C rows and
    one continuation combine. PRECONDITION (held by every caller): ``seg``
    is non-decreasing over the live prefix followed by a constant dead-tail
    sentinel — the kernels' key-sorted layouts. The second-level ladder
    jumps over intermediate blocks, which is only sound when equal
    block-head segments imply every block between is the same segment."""
    n = m.shape[0]
    C = _SCAN_BLOCK
    if n <= C or n % C != 0:
        return _suffix_flat(m, seg, op, identity)
    squeeze = m.ndim == 1
    if squeeze:
        m = m[:, None]
    R, k = n // C, m.shape[1]
    ident = jnp.asarray(identity, m.dtype)
    acc = m.reshape(R, C, k)
    s2 = seg.reshape(R, C)
    d = 1
    while d < C:
        sm = jnp.concatenate(
            [acc[:, d:], jnp.full((R, d, k), ident, acc.dtype)], axis=1)
        ss = jnp.concatenate(
            [s2[:, d:], jnp.full((R, d), -2, s2.dtype)], axis=1)
        ok = (ss == s2)[..., None]
        acc = op(acc, jnp.where(ok, sm, ident))
        d <<= 1
    # full suffix at each block start: segmented ladder over block heads
    head = acc[:, 0, :]
    seg_head, seg_tail = s2[:, 0], s2[:, -1]
    tot = head
    d = 1
    while d < R:
        sm = jnp.concatenate(
            [tot[d:], jnp.full((d, k), ident, tot.dtype)], axis=0)
        ss = jnp.concatenate(
            [seg_head[d:], jnp.full((d,), -2, seg_head.dtype)])
        ok = (ss == seg_head)[:, None]
        tot = op(tot, jnp.where(ok, sm, ident))
        d <<= 1
    # rows whose segment crosses the block end pick up the continuation
    cont = jnp.concatenate(
        [seg_tail[:-1] == seg_head[1:], jnp.zeros((1,), bool)])
    carry = jnp.concatenate(
        [tot[1:], jnp.full((1, k), ident, tot.dtype)], axis=0)
    cross = (s2 == seg_tail[:, None]) & cont[:, None]
    out = op(acc, jnp.where(cross[..., None], carry[:, None, :], ident))
    out = out.reshape(n, k)
    return out[:, 0] if squeeze else out


class LaneResults:
    """Per-branch resolved lane reductions at the [L] group-slot layout.

    Every reduction kind runs one blocked segmented suffix scan (group
    totals land on each group's first row) followed by ONE [L]-row-gather
    at group starts; the gather is the tier-dependent cost (a [4M,6] f64
    row-gather at L=4M was ~180 ms, ~33 ms at L=1M in the round-4 profile,
    old plug-in — pick tiers well)."""

    def __init__(self, lanes: FastLanes, seg: jax.Array,
                 starts: jax.Array, live_slot: jax.Array):
        self.live_slot = live_slot
        n = lanes.live.shape[0]
        s = jnp.clip(starts, 0, n - 1)
        self._sum_at = None
        if lanes.sum_lanes:
            # one two-level segmented suffix scan (group-local rounding,
            # ~4 ms per (4M,6) f64, round-4 profile) + ONE [L]-row-gather at group starts —
            # the cheapest shape at every tier now that the scan is blocked
            # (the old prefix-difference needed TWO gathers and was only
            # exact for integer lanes anyway)
            stack = jnp.stack(lanes.sum_lanes, axis=1)
            suf = _suffix_scan_ladder(stack, seg, jnp.add, 0.0)
            self._sum_at = jnp.take(suf, s, axis=0)
        self._min_at = None
        if lanes.min_lanes:
            m = _suffix_scan_ladder(jnp.stack(lanes.min_lanes, axis=1),
                                    seg, jnp.minimum, jnp.inf)
            self._min_at = jnp.take(m, s, axis=0)
        self._max_at = None
        if lanes.max_lanes:
            m = _suffix_scan_ladder(jnp.stack(lanes.max_lanes, axis=1),
                                    seg, jnp.maximum, -jnp.inf)
            self._max_at = jnp.take(m, s, axis=0)

    def sum_f64(self, ref: int) -> jax.Array:
        return jnp.where(self.live_slot, self._sum_at[:, ref], 0.0)

    def sum_int(self, refs) -> jax.Array:
        i0, i1, i2 = refs
        v = _dec_i64_lanes(self._sum_at[:, i0], self._sum_at[:, i1],
                           self._sum_at[:, i2])
        return jnp.where(self.live_slot, v, jnp.int64(0))

    def count(self, ref: int) -> jax.Array:
        return jnp.where(self.live_slot,
                         self._sum_at[:, ref].astype(jnp.int64),
                         jnp.int64(0))

    def sum_wide(self, wide, n_ok: jax.Array):
        """(limbs [L, 4], left 128 bits) of ``FastLanes.sum_wide``'s
        lanes, given the per-group count of the rows summed."""
        from .decimal128 import from_chunk_sums
        refs, offsets, bias_bit = wide
        return from_chunk_sums([self.count(r) for r in refs], offsets,
                               bias_bit, n_ok)

    def min_f64(self, ref: int) -> jax.Array:
        return self._min_at[:, ref]

    def max_f64(self, ref: int) -> jax.Array:
        return self._max_at[:, ref]


# value kinds a min/max can round-trip exactly through an f64 lane
_MINMAX_F64_KINDS = frozenset({
    TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.FLOAT32,
    TypeKind.FLOAT64, TypeKind.BOOLEAN, TypeKind.DATE,
})


def _at_group_starts(vals, default):
    starts, ends = _seg_bounds()
    out = jnp.take(vals, jnp.clip(starts, 0, vals.shape[0] - 1))
    return jnp.where(ends >= starts, out, default)


# The scatter fallbacks below do NOT promise indices_are_sorted: they
# serve exactly the paths whose segment ids are not contiguous runs
# (keyless aggregation under a fused filter mask interleaves the dead
# sentinel between live ids).
def _seg_sum(x, seg, cap):
    if _seg_bounds() is not None:
        # Segmented sum over key-sorted
        # rows = ONE cumsum + a window difference at the published group
        # bounds. cumsum was 3–19 ms per 4M f64 rows where the emulated-
        # 64-bit scatter was 285–320 ms (round-3 profile, old plug-in).
        # Integer cumsums wrap mod 2^w, so
        # the difference is exact under Spark's non-ANSI wraparound; float
        # sums trade the scatter's sequential rounding for the prefix
        # tree's (both order-dependent, like Spark itself). Dead slots use
        # the (start=1, end=0) convention: c[0]-c[1]+x[1] == 0.
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        starts, ends = _seg_bounds()
        n = x.shape[0]
        s = jnp.clip(starts, 0, n - 1)
        if jnp.issubdtype(x.dtype, jnp.floating):
            # floats: SEGMENTED suffix scan keeps rounding local to each
            # group — a whole-batch prefix difference cancels small groups
            # against the global running sum (confirmed on device)
            suf = _suffix_scan_ladder(x[:, None], seg, jnp.add,
                                      0.0)[:, 0]
            out = jnp.take(suf, s)
            return jnp.where(ends >= starts, out, jnp.zeros((), x.dtype))
        c = _cumsum(x)
        e = jnp.clip(ends, 0, n - 1)
        return jnp.take(c, e) - jnp.take(c, s) + jnp.take(x, s)
    return jax.ops.segment_sum(x, seg, num_segments=cap)


def _seg_count(ok, seg, cap):
    """True-count per segment, int64 result: the reduction itself runs in
    native int32 (one batch holds < 2^31 rows)."""
    return _seg_sum(ok.astype(jnp.int32), seg, cap).astype(jnp.int64)


def _minmax_identity(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if is_min else info.min, dtype)


def _seg_min(x, seg, cap):
    if _seg_bounds() is not None:
        ident = _minmax_identity(x.dtype, True)
        suf = _seg_scan_reduce(x, seg, ident, jnp.minimum)
        return _at_group_starts(suf, ident)
    return jax.ops.segment_min(x, seg, num_segments=cap)


def _seg_max(x, seg, cap):
    if _seg_bounds() is not None:
        ident = _minmax_identity(x.dtype, False)
        suf = _seg_scan_reduce(x, seg, ident, jnp.maximum)
        return _at_group_starts(suf, ident)
    return jax.ops.segment_max(x, seg, num_segments=cap)


@dataclass(frozen=True, eq=False)
class AggregateFunction(Expression):
    """Base. ``child`` may be None for count(*)."""

    child: Optional[Expression] = None

    @property
    def children(self):
        return (self.child,) if self.child is not None else ()

    def with_children(self, c):
        return type(self)(c[0] if c else None)

    # ---- buffer schema -------------------------------------------------
    def buffer_types(self) -> List[SqlType]:
        raise NotImplementedError

    def buffer_nullable(self) -> List[bool]:
        return [True] * len(self.buffer_types())

    # ---- segment pipeline ---------------------------------------------
    def update(self, inputs: List[DeviceColumn], seg: jax.Array,
               live: jax.Array, cap: int) -> List[DeviceColumn]:
        """Per-group partial buffers from input rows (rows pre-sorted by key;
        ``seg`` maps each live row to its group slot, dead rows to ``cap``)."""
        raise NotImplementedError

    def merge(self, buffers: List[DeviceColumn], seg: jax.Array,
              live: jax.Array, cap: int) -> List[DeviceColumn]:
        """Combine partial buffers that landed in the same group."""
        raise NotImplementedError

    def evaluate(self, buffers: List[DeviceColumn],
                 group_live: jax.Array) -> DeviceColumn:
        """Final result column from merged buffers."""
        raise NotImplementedError

    # ---- batched lane fast path (round 3) ------------------------------
    # Return a finisher ``f(res: LaneResults) -> List[DeviceColumn]`` after
    # registering reduction lanes on the builder, or None to run the
    # generic update/merge under segment_bounds instead.
    def fast_update(self, inputs: List[DeviceColumn], live: jax.Array,
                    B: "FastLanes"):
        return None

    def fast_merge(self, buffers: List[DeviceColumn], live: jax.Array,
                   B: "FastLanes"):
        return None


def _masked(col: DeviceColumn, live: jax.Array, fill) -> jax.Array:
    ok = col.validity & live
    return jnp.where(ok, col.data, fill), ok


class Sum(AggregateFunction):
    """sum(x): null iff no non-null input in the group. Non-ANSI integer sum
    wraps (Spark TryArithmetic disabled); float sums accumulate in float64."""

    @property
    def dtype(self) -> SqlType:
        k = self.child.dtype.kind
        if k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
            return T.FLOAT64
        if k is TypeKind.DECIMAL:
            # Spark widens to min(p+10, 38); past 18 digits the buffer is
            # a limb sum (decimal128.py), on the fused lanes where the
            # exec takes them (fast_update / fast_merge below)
            d = self.child.dtype
            return T.decimal(min(d.precision + 10, 38), d.scale)
        return T.INT64

    @property
    def _is_dec128(self):
        return self.dtype.kind is TypeKind.DECIMAL and \
            self.dtype.precision > 18

    def buffer_types(self):
        if self._is_dec128:
            # running limb sum, non-null count, overflow flag (Spark nulls
            # an overflowing decimal sum in non-ANSI mode)
            return [self.dtype, T.INT64, T.BOOLEAN]
        return [self.dtype, T.INT64]   # running sum, non-null count

    def update(self, inputs, seg, live, cap):
        col = inputs[0]
        if self._is_dec128:
            from .decimal128 import exceeds_digits, lift64, seg_sum128
            data = col.data if col.data.ndim > 1 else lift64(col.data)
            ok = col.validity & live
            s, ovf = seg_sum128(data, ok, seg, cap)
            if col.data.ndim == 1:
                # dec64 inputs widened to limbs: ≤ 2^31 rows × 10^18 stays
                # far below 2^127, overflow is impossible
                ovf = jnp.zeros(cap, bool)
            # Spark's precision cap nulls before the 128-bit range does
            ovf = ovf | exceeds_digits(s, self.dtype.precision)
            n = _seg_count(ok, seg, cap)
            return [DeviceColumn(s, n > 0, None, self.dtype),
                    DeviceColumn(n, jnp.ones(cap, bool), None, T.INT64),
                    DeviceColumn(ovf, jnp.ones(cap, bool), None, T.BOOLEAN)]
        acc_dtype = self.dtype.storage_dtype
        x, ok = _masked(col, live, jnp.zeros((), col.data.dtype))
        s = _seg_sum(x.astype(acc_dtype), seg, cap)
        n = _seg_count(ok, seg, cap)
        return [DeviceColumn(s, n > 0, None, self.dtype),
                DeviceColumn(n, jnp.ones(cap, bool), None, T.INT64)]

    def merge(self, buffers, seg, live, cap):
        if self._is_dec128:
            from .decimal128 import exceeds_digits, seg_sum128
            ok = buffers[0].validity & live
            ms, movf = seg_sum128(buffers[0].data, ok, seg, cap)
            mn = _seg_sum(jnp.where(live, buffers[1].data, 0), seg, cap)
            ovf = movf | exceeds_digits(ms, self.dtype.precision) | \
                (_seg_sum((live & buffers[2].data)
                          .astype(jnp.int32), seg, cap) > 0)
            return [DeviceColumn(ms, mn > 0, None, self.dtype),
                    DeviceColumn(mn, jnp.ones(cap, bool), None, T.INT64),
                    DeviceColumn(ovf, jnp.ones(cap, bool), None, T.BOOLEAN)]
        s, ok = _masked(buffers[0], live, jnp.zeros((), buffers[0].data.dtype))
        n = jnp.where(live, buffers[1].data, 0)
        ms = _seg_sum(s, seg, cap)
        mn = _seg_sum(n, seg, cap)
        return [DeviceColumn(ms, mn > 0, None, self.dtype),
                DeviceColumn(mn, jnp.ones(cap, bool), None, T.INT64)]

    def evaluate(self, buffers, group_live):
        valid = buffers[0].validity & group_live
        if self._is_dec128:
            valid = valid & ~buffers[2].data
        return DeviceColumn(buffers[0].data, valid, None, self.dtype)

    # ---- batched lanes -------------------------------------------------
    def _lane_refs(self, x_data, ok, B: "FastLanes"):
        if self.dtype.kind is TypeKind.FLOAT64:
            x = jnp.where(ok, x_data, 0.0).astype(jnp.float64)
            return ("f", B.sum_f64(x))
        x = jnp.where(ok, x_data.astype(jnp.int64), jnp.int64(0))
        return ("i", B.sum_int(x))

    def _lane_finish(self, kind_ref, nref, one_validity=None):
        kind, ref = kind_ref

        def finish(res: "LaneResults"):
            n = res.count(nref)
            s = res.sum_f64(ref) if kind == "f" else res.sum_int(ref)
            valid = n > 0
            return [DeviceColumn(s, valid, None, self.dtype),
                    DeviceColumn(n, jnp.ones(s.shape[0], bool), None,
                                 T.INT64)]
        return finish

    def _wide_finish(self, wide, nref, total=None, flagged=None):
        """Finisher of a limb sum on the fused lanes: the chunk lanes'
        sums back into limbs, Spark's precision cap on top of the 128-bit
        range, and the (sum, count, overflow) buffers of ``update``.
        ``total``/``flagged`` are a merge's count lanes (rows behind the
        partial sums; partials that had already overflowed)."""
        from .decimal128 import exceeds_digits

        def finish(res: "LaneResults"):
            n_ok = res.count(nref)
            limbs, ovf = res.sum_wide(wide, n_ok)
            ovf = ovf | exceeds_digits(limbs, self.dtype.precision)
            n = n_ok if total is None else res.sum_int(total)
            if flagged is not None:
                ovf = ovf | (res.count(flagged) > 0)
            one = jnp.ones(n.shape[0], bool)
            return [DeviceColumn(limbs, n > 0, None, self.dtype),
                    DeviceColumn(n, one, None, T.INT64),
                    DeviceColumn(ovf, one, None, T.BOOLEAN)]
        return finish

    def fast_update(self, inputs, live, B):
        col = inputs[0]
        if self._is_dec128:
            wide, nref = B.sum_wide(col.data, col.validity)
            return self._wide_finish(wide, nref)
        ok = live if col.validity is live else (col.validity & live)
        return self._lane_finish(self._lane_refs(col.data, ok, B),
                                 B.count(ok))

    def fast_merge(self, buffers, live, B):
        if self._is_dec128:
            wide, nref = B.sum_wide(buffers[0].data, buffers[0].validity)
            return self._wide_finish(
                wide, nref,
                total=B.sum_int(jnp.where(live, buffers[1].data,
                                          jnp.int64(0))),
                flagged=B.count(live & buffers[2].data))
        ok = buffers[0].validity & live
        kr = self._lane_refs(buffers[0].data, ok, B)
        ncnt = B.sum_int(jnp.where(live, buffers[1].data, jnp.int64(0)))
        kind, ref = kr

        def finish(res: "LaneResults"):
            n = res.sum_int(ncnt)
            s = res.sum_f64(ref) if kind == "f" else res.sum_int(ref)
            return [DeviceColumn(s, n > 0, None, self.dtype),
                    DeviceColumn(n, jnp.ones(s.shape[0], bool), None,
                                 T.INT64)]
        return finish


class Count(AggregateFunction):
    """count(x) / count(*): bigint, never null, 0 for empty groups."""

    @property
    def dtype(self):
        return T.INT64

    @property
    def nullable(self):
        return False

    def buffer_types(self):
        return [T.INT64]

    def buffer_nullable(self):
        return [False]

    def update(self, inputs, seg, live, cap):
        ok = (inputs[0].validity & live) if inputs else live
        n = _seg_count(ok, seg, cap)
        return [DeviceColumn(n, jnp.ones(cap, bool), None, T.INT64)]

    def merge(self, buffers, seg, live, cap):
        n = jnp.where(live, buffers[0].data, 0)
        return [DeviceColumn(_seg_sum(n, seg, cap),
                             jnp.ones(cap, bool), None, T.INT64)]

    def evaluate(self, buffers, group_live):
        return DeviceColumn(jnp.where(group_live, buffers[0].data, 0),
                            group_live, None, T.INT64)

    # ---- batched lanes -------------------------------------------------
    def fast_update(self, inputs, live, B):
        ok = None
        if inputs and inputs[0].validity is not live:
            ok = inputs[0].validity & live
        nref = B.count(ok)

        def finish(res: "LaneResults"):
            n = res.count(nref)
            return [DeviceColumn(n, jnp.ones(n.shape[0], bool), None,
                                 T.INT64)]
        return finish

    def fast_merge(self, buffers, live, B):
        nref = B.sum_int(jnp.where(live, buffers[0].data, jnp.int64(0)))

        def finish(res: "LaneResults"):
            n = res.sum_int(nref)
            return [DeviceColumn(n, jnp.ones(n.shape[0], bool), None,
                                 T.INT64)]
        return finish


class _MinMax(AggregateFunction):
    _is_min = True

    @property
    def dtype(self):
        return self.child.dtype

    def buffer_types(self):
        return [self.dtype]

    def _fill(self, dtype):
        if self.dtype.kind is TypeKind.BOOLEAN:
            return jnp.asarray(self._is_min, bool)
        return _minmax_identity(dtype, self._is_min)

    def update(self, inputs, seg, live, cap):
        col = inputs[0]
        if col.lengths is not None:
            return self._update_string(col, seg, live, cap)
        if col.data.ndim > 1:     # decimal128 limbs
            from .decimal128 import seg_minmax128
            ok = col.validity & live
            m = seg_minmax128(col.data, ok, seg, cap, self._is_min)
            valid = _seg_sum(ok.astype(jnp.int32), seg, cap) > 0
            return [DeviceColumn(jnp.where(valid[:, None], m, 0), valid,
                                 None, self.dtype)]
        x, ok = _masked(col, live, self._fill(col.data.dtype))
        if col.data.dtype == jnp.bool_:
            x = x.astype(jnp.uint8)
            m = (_seg_min if self._is_min else _seg_max)(x, seg, cap) > 0
        else:
            m = (_seg_min if self._is_min else _seg_max)(x, seg, cap)
        n = _seg_sum(ok.astype(jnp.int32), seg, cap)
        valid = n > 0
        zero = jnp.zeros((), m.dtype)
        return [DeviceColumn(jnp.where(valid, m, zero), valid, None, self.dtype)]

    def _update_string(self, col, seg, live, cap):
        # Segmented lexicographic argmin/argmax by iterative refinement over
        # the packed orderable words: narrow the candidate set one word at a
        # time (word count = max_len/8 segment_min passes), then take the
        # first surviving row per segment.
        from ..exec.common import orderable_words
        words = orderable_words(col)
        ok = col.validity & live
        segc = jnp.clip(seg, 0, cap - 1)
        candidate = ok
        worst = ~jnp.uint64(0)
        for w in words:
            key = w if self._is_min else ~w
            key = jnp.where(candidate, key, worst)
            m = _seg_min(key, seg, cap)
            candidate = candidate & (key == jnp.take(m, segc))
        idx = jnp.arange(col.capacity, dtype=jnp.int64)
        big = jnp.int64(col.capacity)
        pick = _seg_min(jnp.where(candidate, idx, big), seg, cap)
        any_ok = _seg_sum(ok.astype(jnp.int32), seg, cap) > 0
        g = jnp.clip(pick, 0, col.capacity - 1)
        data = jnp.take(col.data, g, axis=0)
        lengths = jnp.take(col.lengths, g, axis=0)
        zero = jnp.zeros_like(data)
        return [DeviceColumn(jnp.where(any_ok[:, None], data, zero),
                             any_ok, jnp.where(any_ok, lengths, 0),
                             self.dtype)]

    def merge(self, buffers, seg, live, cap):
        return self.update(buffers, seg, live, cap)

    def evaluate(self, buffers, group_live):
        b = buffers[0]
        return DeviceColumn(b.data, b.validity & group_live, b.lengths,
                            self.dtype)

    # ---- batched lanes -------------------------------------------------
    def _lane(self, col: DeviceColumn, live, B: "FastLanes"):
        if self.dtype.kind not in _MINMAX_F64_KINDS:
            return None     # int64/timestamp/decimal/string: not f64-exact
        ok = live if col.validity is live else (col.validity & live)
        data = col.data.astype(jnp.uint8) if col.data.dtype == jnp.bool_ \
            else col.data
        if self._is_min:
            x = jnp.where(ok, data.astype(jnp.float64), jnp.inf)
            ref, get = B.min_f64(x), "min_f64"
        else:
            x = jnp.where(ok, data.astype(jnp.float64), -jnp.inf)
            ref, get = B.max_f64(x), "max_f64"
        nref = B.count(ok)
        storage = self.dtype.storage_dtype

        def finish(res: "LaneResults"):
            n = res.count(nref)
            valid = n > 0
            m = getattr(res, get)(ref)
            if self.dtype.kind is TypeKind.BOOLEAN:
                out = jnp.where(valid, m > 0, False)
            else:
                out = jnp.where(valid, m, 0.0).astype(storage)
            return [DeviceColumn(out, valid, None, self.dtype)]
        return finish

    def fast_update(self, inputs, live, B):
        return self._lane(inputs[0], live, B)

    def fast_merge(self, buffers, live, B):
        return self._lane(buffers[0], live, B)


class Min(_MinMax):
    _is_min = True


class Max(_MinMax):
    _is_min = False


class Average(AggregateFunction):
    """avg(x) → double; buffer = (sum: double, count).

    avg over decimal(p, s) is Spark's: the buffer is ``sum``'s (a
    decimal(p+10, s) sum, in limbs past 18 digits, the non-null count and
    the limb sum's overflow flag), so it updates and merges as ``Sum``
    does, fused lanes included; the result is sum × 10^(s'−s) / count
    rounded HALF_UP once to decimal(min(p+4, 38), s' = min(s+4, 38))
    (decimal128.div_half_up), null where the sum overflowed or the
    quotient passes the precision."""

    @property
    def dtype(self):
        if self.child.dtype.kind is TypeKind.DECIMAL:
            d = self.child.dtype
            return T.decimal(min(d.precision + 4, 38), min(d.scale + 4, 38))
        return T.FLOAT64

    @property
    def _decimal_sum(self) -> Optional[Sum]:
        if self.child.dtype.kind is TypeKind.DECIMAL:
            return Sum(self.child)
        return None

    def buffer_types(self):
        ds = self._decimal_sum
        return ds.buffer_types() if ds else [T.FLOAT64, T.INT64]

    def update(self, inputs, seg, live, cap):
        ds = self._decimal_sum
        if ds:
            return ds.update(inputs, seg, live, cap)
        col = inputs[0]
        x, ok = _masked(col, live, jnp.zeros((), col.data.dtype))
        s = _seg_sum(x.astype(jnp.float64), seg, cap)
        n = _seg_count(ok, seg, cap)
        return [DeviceColumn(s, n > 0, None, T.FLOAT64),
                DeviceColumn(n, jnp.ones(cap, bool), None, T.INT64)]

    def merge(self, buffers, seg, live, cap):
        ds = self._decimal_sum
        if ds:
            return ds.merge(buffers, seg, live, cap)
        s = jnp.where(live & buffers[0].validity, buffers[0].data, 0.0)
        n = jnp.where(live, buffers[1].data, 0)
        ms = _seg_sum(s, seg, cap)
        mn = _seg_sum(n, seg, cap)
        return [DeviceColumn(ms, mn > 0, None, T.FLOAT64),
                DeviceColumn(mn, jnp.ones(cap, bool), None, T.INT64)]

    def _evaluate_decimal(self, ds: Sum, buffers, group_live):
        from .decimal128 import div_half_up, lift64, to_int64
        out = self.dtype
        total, n = buffers[0].data, buffers[1].data
        valid = buffers[0].validity & group_live & (n > 0)
        if len(buffers) > 2:
            valid = valid & ~buffers[2].data
        limbs = total if total.ndim > 1 else lift64(total)
        q, ovf = div_half_up(limbs, out.scale - ds.dtype.scale, n,
                             out.precision)
        valid = valid & ~ovf
        if out.precision > 18:
            return DeviceColumn(jnp.where(valid[:, None], q, 0), valid,
                                None, out)
        return DeviceColumn(jnp.where(valid, to_int64(q), 0), valid, None,
                            out)

    def evaluate(self, buffers, group_live):
        ds = self._decimal_sum
        if ds:
            return self._evaluate_decimal(ds, buffers, group_live)
        n = buffers[1].data
        valid = (n > 0) & group_live
        avg = buffers[0].data / jnp.where(n > 0, n, 1).astype(jnp.float64)
        return DeviceColumn(jnp.where(valid, avg, 0.0), valid, None, T.FLOAT64)

    # ---- batched lanes -------------------------------------------------
    def fast_update(self, inputs, live, B):
        ds = self._decimal_sum
        if ds:
            return ds.fast_update(inputs, live, B)
        col = inputs[0]
        ok = live if col.validity is live else (col.validity & live)
        sref = B.sum_f64(jnp.where(ok, col.data, 0).astype(jnp.float64))
        nref = B.count(ok)

        def finish(res: "LaneResults"):
            s, n = res.sum_f64(sref), res.count(nref)
            one = jnp.ones(s.shape[0], bool)
            return [DeviceColumn(s, n > 0, None, T.FLOAT64),
                    DeviceColumn(n, one, None, T.INT64)]
        return finish

    def fast_merge(self, buffers, live, B):
        ds = self._decimal_sum
        if ds:
            return ds.fast_merge(buffers, live, B)
        sref = B.sum_f64(jnp.where(live & buffers[0].validity,
                                   buffers[0].data, 0.0))
        nref = B.sum_int(jnp.where(live, buffers[1].data, jnp.int64(0)))

        def finish(res: "LaneResults"):
            s, n = res.sum_f64(sref), res.sum_int(nref)
            one = jnp.ones(s.shape[0], bool)
            return [DeviceColumn(s, n > 0, None, T.FLOAT64),
                    DeviceColumn(n, one, None, T.INT64)]
        return finish


@dataclass(frozen=True, eq=False)
class _CentralMoment(AggregateFunction):
    """Welford/Chan buffers (n, mean, m2) with parallel merge — the same
    decomposition cudf's STD/VARIANCE aggregations use."""

    @property
    def dtype(self):
        return T.FLOAT64

    def buffer_types(self):
        return [T.FLOAT64, T.FLOAT64, T.FLOAT64]  # n, mean, m2

    def update(self, inputs, seg, live, cap):
        col = inputs[0]
        ok = col.validity & live
        x = jnp.where(ok, col.data, 0).astype(jnp.float64)
        n = _seg_sum(ok.astype(jnp.float64), seg, cap)
        s = _seg_sum(x, seg, cap)
        nz = jnp.where(n > 0, n, 1.0)
        mean = s / nz
        centered = jnp.where(ok, (x - jnp.take(mean, jnp.clip(seg, 0, cap - 1))) ** 2, 0.0)
        m2 = _seg_sum(centered, seg, cap)
        one = jnp.ones(cap, bool)
        return [DeviceColumn(n, one, None, T.FLOAT64),
                DeviceColumn(mean, one, None, T.FLOAT64),
                DeviceColumn(m2, one, None, T.FLOAT64)]

    def merge(self, buffers, seg, live, cap):
        n = jnp.where(live, buffers[0].data, 0.0)
        mean = jnp.where(live, buffers[1].data, 0.0)
        m2 = jnp.where(live, buffers[2].data, 0.0)
        N = _seg_sum(n, seg, cap)
        Nz = jnp.where(N > 0, N, 1.0)
        gmean = _seg_sum(n * mean, seg, cap) / Nz
        gm = jnp.take(gmean, jnp.clip(seg, 0, cap - 1))
        # Chan's pairwise: m2_total = sum(m2_i) + sum(n_i * (mean_i - M)^2)
        M2 = _seg_sum(m2 + n * (mean - gm) ** 2, seg, cap)
        one = jnp.ones(cap, bool)
        return [DeviceColumn(N, one, None, T.FLOAT64),
                DeviceColumn(gmean, one, None, T.FLOAT64),
                DeviceColumn(M2, one, None, T.FLOAT64)]

    def _finish(self, n, m2):
        raise NotImplementedError

    def evaluate(self, buffers, group_live):
        n, m2 = buffers[0].data, buffers[2].data
        val, valid = self._finish(n, m2)
        valid = valid & group_live
        return DeviceColumn(jnp.where(valid, val, 0.0), valid, None, T.FLOAT64)


class VarianceSamp(_CentralMoment):
    def _finish(self, n, m2):
        return m2 / jnp.where(n > 1, n - 1, 1.0), n > 1


class VariancePop(_CentralMoment):
    def _finish(self, n, m2):
        return m2 / jnp.where(n > 0, n, 1.0), n > 0


class StddevSamp(_CentralMoment):
    def _finish(self, n, m2):
        return jnp.sqrt(m2 / jnp.where(n > 1, n - 1, 1.0)), n > 1


class StddevPop(_CentralMoment):
    def _finish(self, n, m2):
        return jnp.sqrt(m2 / jnp.where(n > 0, n, 1.0)), n > 0


@dataclass(frozen=True, eq=False)
class Percentile(AggregateFunction):
    """percentile(col, q): EXACT interpolated percentile (reference ships
    t-digest approx_percentile — GpuApproximatePercentile.scala; computing
    on the sorted segment layout makes the exact answer as cheap as the
    sketch here: the group's k-th value is one gather).

    Not decomposable: supports COMPLETE mode only; the planner routes raw
    rows through a key exchange first. Requires the exec to sort by
    (group keys, input value) — requires_sorted_input."""

    child: Optional[Expression] = None
    percentage: float = 0.5

    supports_partial = False
    requires_sorted_input = True

    def with_children(self, c):
        return Percentile(c[0] if c else None, self.percentage)

    @property
    def dtype(self):
        return T.FLOAT64

    def buffer_types(self):
        return [T.FLOAT64]

    def update(self, inputs, seg, live, cap):
        # rows are sorted by (keys, value) with nulls first inside each
        # segment (sort_operands null ordering), so the k-th VALID value of
        # segment g sits at seg_start[g] + null_count[g] + k
        col = inputs[0]
        ok = col.validity & live
        iota = jnp.arange(col.capacity, dtype=jnp.int64)
        seg_start = jax.ops.segment_min(
            jnp.where(seg < cap, iota, jnp.int64(col.capacity)),
            jnp.clip(seg, 0, cap), num_segments=cap + 1,
            indices_are_sorted=True)[:cap]
        cnt = _seg_sum(ok.astype(jnp.int64), seg, cap)
        rows = _seg_sum(live.astype(jnp.int64), seg, cap)
        nulls = rows - cnt
        r = self.percentage * jnp.maximum(cnt - 1, 0).astype(jnp.float64)
        lo = jnp.floor(r).astype(jnp.int64)
        hi = jnp.ceil(r).astype(jnp.int64)
        frac = r - lo.astype(jnp.float64)
        base = jnp.clip(seg_start, 0, col.capacity - 1) + nulls
        idx_lo = jnp.clip(base + lo, 0, col.capacity - 1)
        idx_hi = jnp.clip(base + hi, 0, col.capacity - 1)
        x = col.data.astype(jnp.float64)
        v = (1.0 - frac) * jnp.take(x, idx_lo) + frac * jnp.take(x, idx_hi)
        valid = cnt > 0
        return [DeviceColumn(jnp.where(valid, v, 0.0), valid, None,
                             T.FLOAT64)]

    def merge(self, buffers, seg, live, cap):
        raise NotImplementedError(
            "percentile is not decomposable; COMPLETE mode only")

    def evaluate(self, buffers, group_live):
        b = buffers[0]
        return DeviceColumn(b.data, b.validity & group_live, None,
                            T.FLOAT64)


@dataclass(frozen=True, eq=False)
class ApproxPercentile(Percentile):
    """approx_percentile(col, q[, accuracy]): answered EXACTLY.

    The reference builds t-digest sketches (GpuApproximatePercentile.scala)
    because a cudf hash aggregate cannot afford a global sort; the TPU
    aggregate already runs on fully sorted segments, so the exact quantile
    is one gather — and an exact answer satisfies any accuracy contract.
    The accuracy argument is accepted and ignored."""

    accuracy: int = 10000

    def with_children(self, c):
        return ApproxPercentile(c[0] if c else None, self.percentage,
                                self.accuracy)


@dataclass(frozen=True, eq=False)
class CollectList(AggregateFunction):
    """collect_list(x): nulls skipped (Spark), elements in value-sorted
    order (Spark's order is undefined; sorted is deterministic here).
    Device arrays are fixed-budget matrices (reference: cudf collect_list
    builds offsets+child; the static budget is the TPU trade, checked at
    the host boundary). COMPLETE-only, like percentile."""

    child: Optional[Expression] = None
    max_elems: int = 256

    supports_partial = False
    requires_sorted_input = True
    _dedupe = False

    def with_children(self, c):
        return type(self)(c[0] if c else None, self.max_elems)

    @property
    def dtype(self):
        return T.array(self.child.dtype, self.max_elems)

    def buffer_types(self):
        return [self.dtype]

    def update(self, inputs, seg, live, cap):
        col = inputs[0]
        is_string = col.lengths is not None
        ok = col.validity & live
        if self._dedupe:
            # rows are sorted by (keys, value): drop adjacent duplicates
            # (adjacent_equal owns the string/typed pairwise comparison)
            from ..exec.common import adjacent_equal
            same_seg = jnp.concatenate(
                [jnp.zeros(1, bool), seg[1:] == seg[:-1]])
            same_val = adjacent_equal([col])
            prev_ok = jnp.concatenate([jnp.zeros(1, bool), ok[:-1]])
            ok = ok & ~(same_seg & same_val & prev_ok)
        segc = jnp.clip(seg, 0, cap - 1)
        # position among the group's kept values (exclusive running count)
        run = _cumsum(ok.astype(jnp.int32))
        seg_base = jax.ops.segment_min(
            jnp.where(ok, run - 1, jnp.int32(1 << 30)), seg,
            num_segments=cap + 1, indices_are_sorted=True)[:cap]
        pos = (run - 1) - jnp.take(seg_base, segc)
        me = self.max_elems
        flat_target = jnp.where(ok & (pos < me),
                                segc.astype(jnp.int64) * me + pos,
                                jnp.int64(cap) * me)
        # counts stay UNCLAMPED: a group with more than max_elems values
        # surfaces as lengths > max_elems, which the host boundary
        # (to_arrow) rejects loudly — same contract as string max_len —
        # instead of silently truncating the list.
        counts = _seg_sum(ok.astype(jnp.int32), seg, cap)
        valid = jnp.ones(cap, bool)   # empty group -> empty list (not null)
        if is_string:
            # array<string>: 3D byte tensor [group, elem, max_len] with
            # per-element byte lengths in data2 (split()'s layout)
            ml = col.data.shape[1]
            mat = jnp.zeros((cap * me + 1, ml), col.data.dtype).at[
                flat_target].set(col.data, mode="drop")[
                : cap * me].reshape(cap, me, ml)
            elens = jnp.zeros(cap * me + 1, jnp.int32).at[
                flat_target].set(col.lengths, mode="drop")[
                : cap * me].reshape(cap, me)
            return [DeviceColumn(mat, valid, counts, self.dtype, elens)]
        mat = jnp.zeros(cap * me + 1, col.data.dtype).at[flat_target].set(
            col.data, mode="drop")[: cap * me].reshape(cap, me)
        return [DeviceColumn(mat, valid, counts, self.dtype)]

    def merge(self, buffers, seg, live, cap):
        raise NotImplementedError("collect_* is COMPLETE-only")

    def evaluate(self, buffers, group_live):
        b = buffers[0]
        return DeviceColumn(b.data, b.validity & group_live,
                            jnp.where(group_live, b.lengths, 0),
                            self.dtype, b.data2)


class CollectSet(CollectList):
    """collect_set(x): deduplicated (sorted) elements."""

    _dedupe = True


class First(AggregateFunction):
    """first(x, ignoreNulls=False) — order-dependent like the reference's
    (marked non-deterministic there too)."""

    _take_last = False

    @property
    def dtype(self):
        return self.child.dtype

    def buffer_types(self):
        return [self.dtype, T.BOOLEAN]   # value, has_value

    def update(self, inputs, seg, live, cap):
        col = inputs[0]
        order = jnp.arange(col.capacity, dtype=jnp.int64)
        if self._take_last:
            pick = _seg_max(jnp.where(live, order, -1), seg, cap)
        else:
            pick = _seg_min(jnp.where(live, order, jnp.int64(1 << 62)), seg, cap)
        has = _seg_sum(live.astype(jnp.int32), seg, cap) > 0
        g = jnp.clip(pick, 0, col.capacity - 1)
        data = jnp.take(col.data, g, axis=0)
        validity = jnp.take(col.validity, g, axis=0) & has
        lengths = jnp.take(col.lengths, g, axis=0) if col.lengths is not None else None
        data2 = jnp.take(col.data2, g, axis=0) if col.data2 is not None \
            else None
        return [DeviceColumn(data, validity, lengths, self.dtype, data2),
                DeviceColumn(has, jnp.ones(cap, bool), None, T.BOOLEAN)]

    def merge(self, buffers, seg, live, cap):
        # partials without a value (has=False) must not win first/last
        present = live & buffers[1].data
        return self.update([buffers[0]], seg, present, cap)

    def evaluate(self, buffers, group_live):
        val = buffers[0]
        has = buffers[1]
        return DeviceColumn(val.data, val.validity & has.data & group_live,
                            val.lengths, self.dtype)

    # ---- batched lanes: pick-index rides a min/max lane (row positions
    # are < 2^31, exact in f64), then one gather per First/Last resolves
    # the value from the sorted view.
    def _pick(self, col: DeviceColumn, present, B: "FastLanes"):
        cap = col.capacity
        order = jnp.arange(cap, dtype=jnp.int32).astype(jnp.float64)
        if self._take_last:
            ref, get = B.max_f64(jnp.where(present, order, -jnp.inf)), \
                "max_f64"
        else:
            ref, get = B.min_f64(jnp.where(present, order, jnp.inf)), \
                "min_f64"
        nref = B.count(present if present is not None else None)

        def finish(res: "LaneResults"):
            has = res.count(nref) > 0
            pick = getattr(res, get)(ref)
            idx = jnp.clip(jnp.where(has, pick, 0.0), 0, cap - 1) \
                .astype(jnp.int32)
            data = jnp.take(col.data, idx, axis=0)
            validity = jnp.take(col.validity, idx, axis=0) & has
            lengths = jnp.take(col.lengths, idx, axis=0) \
                if col.lengths is not None else None
            data2 = jnp.take(col.data2, idx, axis=0) \
                if col.data2 is not None else None
            one = jnp.ones(has.shape[0], bool)
            return [DeviceColumn(data, validity, lengths, self.dtype, data2),
                    DeviceColumn(has, one, None, T.BOOLEAN)]
        return finish

    def fast_update(self, inputs, live, B):
        return self._pick(inputs[0], live, B)

    def fast_merge(self, buffers, live, B):
        return self._pick(buffers[0], live & buffers[1].data, B)


class Last(First):
    _take_last = True


class CountDistinct(AggregateFunction):
    """count(DISTINCT x): bigint, never null, the non-null distinct values
    of ``x`` in the group. No kernel of its own: the planner rewrites an
    aggregate that holds it as Spark 3.3 plans one distinct column
    (``AggUtils.planAggregateWithOneDistinct``): an aggregate on the
    grouping keys and ``x`` that carries the other aggregates' partials,
    then one on the grouping keys where each (keys, x) group counts 1 if
    ``x`` is not null and the partials merge (``plan/overrides.py``,
    ``_convert_distinct``). The row interpreter counts it directly."""

    @property
    def dtype(self):
        return T.INT64

    @property
    def nullable(self):
        return False

    def buffer_types(self):
        return [T.INT64]

    def buffer_nullable(self):
        return [False]


# convenience constructors mirroring pyspark.sql.functions
def sum_(e) -> Sum:            # noqa: A001
    return Sum(e)


def count(e=None) -> Count:
    return Count(e)


def count_distinct(e) -> CountDistinct:
    return CountDistinct(e)


def min_(e) -> Min:
    return Min(e)


def max_(e) -> Max:
    return Max(e)


def avg(e) -> Average:
    return Average(e)


@dataclass(frozen=True, eq=False)
class PivotFirst(AggregateFunction):
    """PivotFirst(pivot, value, pivot_values): per-group FIRST of
    ``value`` for each literal pivot key, emitted as one array column the
    planner's pivot projection indexes (reference: GpuPivotFirst,
    GpuOverrides.scala:2022 — same array-of-buffers contract as Spark's
    PivotFirst). Missing combos are NULL elements (per-element validity
    rides the scalar-array data2 plane, consumed by element access)."""

    child: Optional[Expression] = None          # the value expression
    pivot: Optional[Expression] = None
    pivot_values: Tuple = ()

    @property
    def children(self):
        return (self.child, self.pivot)

    def with_children(self, c):
        return PivotFirst(c[0], c[1], self.pivot_values)

    @property
    def dtype(self):
        return T.array(self.child.dtype, max(len(self.pivot_values), 1))

    def buffer_types(self):
        return [self.child.dtype, T.BOOLEAN] * len(self.pivot_values)

    def _masks(self, pv_col, live):
        out = []
        for pv in self.pivot_values:
            if pv is None:
                out.append(live & ~pv_col.validity)
            elif pv_col.lengths is not None:
                # string pivot keys: canonical zero padding makes full-row
                # byte equality string equality
                b = str(pv).encode("utf-8")
                ml = pv_col.data.shape[1]
                padded = jnp.asarray(
                    bytearray(b[:ml] + b"\0" * max(ml - len(b), 0)),
                    jnp.uint8)
                eq = jnp.all(pv_col.data == padded[None, :], axis=1) & \
                    (len(b) <= ml)
                out.append(live & pv_col.validity & eq)
            else:
                out.append(live & pv_col.validity &
                           (pv_col.data == jnp.asarray(
                               pv, pv_col.data.dtype)))
        return out

    def update(self, inputs, seg, live, cap):
        val, pv = inputs
        f = First(self.child)
        bufs = []
        for mask in self._masks(pv, live):
            bufs.extend(f.update([val], seg, mask, cap))
        return bufs

    def merge(self, buffers, seg, live, cap):
        f = First(self.child)
        out = []
        for k in range(len(self.pivot_values)):
            v, has = buffers[2 * k], buffers[2 * k + 1]
            present = live & has.data
            out.extend(f.update([v], seg, present, cap))
        return out

    def evaluate(self, buffers, group_live):
        K = len(self.pivot_values)
        vals = [buffers[2 * k] for k in range(K)]
        has = [buffers[2 * k + 1] for k in range(K)]
        data = jnp.stack([v.data for v in vals], axis=1)
        ev = jnp.stack([v.validity & h.data for v, h in zip(vals, has)],
                       axis=1)
        cap = data.shape[0]
        return DeviceColumn(
            jnp.where(ev, data, jnp.zeros((), data.dtype)),
            group_live, jnp.where(group_live, K, 0),
            self.dtype, ev)
