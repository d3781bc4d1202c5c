"""DECIMAL128 device support: 4×32-bit limb arithmetic in int64 lanes.

Reference: the reference runs DECIMAL128 end-to-end on cudf's native
__int128 columns (GpuCast.scala, DecimalUtil.scala). XLA has no 128-bit
integer type, so precision 19-38 stores as ``int64[cap, 4]`` — four 32-bit
two's-complement limbs (l0 = least significant) each held in an int64
lane. The headroom above each limb makes segment SUMS safe without carry
handling until a single final normalization pass: 2^31 rows × (2^32-1)
per-limb still fits int64. Ordering/comparison collapses the limbs to an
(hi, lo) int64 key pair whose lexicographic order is the 128-bit order.

Scope: storage, comparisons, sort/group ordering, sum/min/max/first/last,
add/subtract/negate/abs, rescales by any power of ten with an overflow
flag, multiplication (64×64→128, 128×64→128, 128×128→128; magnitudes in
32-bit limbs, partial products in uint64 lanes) with overflow past 128 bits
or past the result's precision flagged, and division of a 128-bit sum by a
positive int64 count rounded HALF_UP (what ``avg`` needs). A sum can also
ride the aggregate's fused lanes as exact 22-bit chunk lanes
(``chunk_lanes`` / ``from_chunk_sums``). Still planner-gated to the CPU
interpreter: a product or sum whose Spark type cuts the SCALE (rounding
inside the arithmetic), and decimal ``/``, ``%``, ``div`` outside ``avg``.

The host side (``to_limbs_np`` / ``from_limbs_np`` and the Arrow buffer
views ``arrow_decimal_storage`` / ``storage_to_arrow_decimal``) is numpy
over whole columns; nothing loops per value.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..types import SqlType, TypeKind

MASK32 = (1 << 32) - 1


def is_dec128(t: SqlType) -> bool:
    return t.kind is TypeKind.DECIMAL and t.precision > 18


def to_limbs_np(unscaled) -> np.ndarray:
    """Signed integers → int64[n, 4] limbs (two's complement mod 2^128).
    An int64 array takes numpy's shifts; Python ints (possibly >64 bits)
    ride an object array, whose ``>>`` is arithmetic like the device's."""
    if isinstance(unscaled, np.ndarray) and unscaled.dtype == np.int64:
        x = unscaled
        ext = (x >> 63) & MASK32
        return np.stack([x & MASK32, (x >> 32) & MASK32, ext, ext], axis=1)
    a = np.empty(len(unscaled), object)
    a[:] = unscaled
    out = np.zeros((len(a), 4), np.int64)
    for j in range(4):
        out[:, j] = (a >> (32 * j)) & MASK32
    return out


def from_limbs_np(mat: np.ndarray) -> List[int]:
    m = (np.asarray(mat) & MASK32).astype(object)
    u = m[:, 0] | (m[:, 1] << 32) | (m[:, 2] << 64) | (m[:, 3] << 96)
    return (u - ((m[:, 3] >> 31) << 128)).tolist()


def arrow_decimal_storage(arr, precision: int,
                          validity: np.ndarray) -> np.ndarray:
    """An Arrow decimal128 array → the device encoding, from a numpy view
    of its 16-byte little-endian values (the array's offset honoured; a
    chunked array is combined by the caller): the low word as int64 for
    precision ≤ 18, the four 32-bit limbs as int64[n, 4] above. Null slots
    hold whatever the writer left there and become 0."""
    import pyarrow as pa
    if arr.type.byte_width != 16:
        arr = arr.cast(pa.decimal128(arr.type.precision, arr.type.scale))
    n = len(arr)
    buf = arr.buffers()[1]
    if n == 0 or buf is None:
        return np.zeros((0, 4) if precision > 18 else 0, np.int64)
    if precision > 18:
        words = np.frombuffer(buf, np.uint32, count=4 * n,
                              offset=16 * arr.offset).reshape(n, 4)
        out = words.astype(np.int64)
        return out if validity.all() else out * validity[:, None]
    low = np.frombuffer(buf, np.int64, count=2 * n,
                        offset=16 * arr.offset)[::2]
    return low.copy() if validity.all() else np.where(validity, low, 0)


def storage_to_arrow_decimal(data: np.ndarray, arrow_type,
                             validity: np.ndarray):
    """Inverse of ``arrow_decimal_storage``: int64[n] unscaled values or
    int64[n, 4] limbs → an Arrow decimal128 array built from buffers."""
    import pyarrow as pa
    n = data.shape[0]
    if data.ndim > 1:
        raw = np.ascontiguousarray(data.astype(np.uint32))
    else:
        raw = np.stack([data, data >> 63], axis=1)
    bitmap = None if validity.all() else pa.py_buffer(
        np.packbits(validity, bitorder="little").tobytes())
    return pa.Array.from_buffers(arrow_type, n,
                                 [bitmap, pa.py_buffer(raw.tobytes())])


def normalize(limbs: jnp.ndarray) -> jnp.ndarray:
    """Carry-propagate limb lanes back into [0, 2^32); result is the value
    mod 2^128 (two's complement semantics preserved)."""
    out = []
    carry = jnp.zeros(limbs.shape[:-1], jnp.int64)
    for j in range(4):
        v = limbs[..., j] + carry
        out.append(v & MASK32)
        carry = v >> 32       # arithmetic shift: correct for negative lanes
    return jnp.stack(out, axis=-1)


def order_key_pair(data: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) int64 pair whose lexicographic signed-then-ordered order is
    the 128-bit numeric order. hi = signed top half; lo = bottom half with
    the sign bit flipped so int64 compare matches unsigned order."""
    l0, l1, l2, l3 = (data[..., j] for j in range(4))
    hi = ((l3 << 32) | l2)                # l3 carries the 128-bit sign:
    # stored limbs are in [0, 2^32); (l3 << 32) overflows into the int64
    # sign bit exactly when the 128-bit value is negative
    lo = (((l1 - (1 << 31)) << 32) | l0)  # bias flip = unsigned order
    return hi, lo


def orderable_words128(data: jnp.ndarray) -> List[jnp.ndarray]:
    """uint64 word operands for lax.sort (ascending 128-bit order)."""
    hi, lo = order_key_pair(data)
    sign = jnp.uint64(1) << jnp.uint64(63)
    return [hi.astype(jnp.uint64) ^ sign, lo.astype(jnp.uint64) ^ sign]


def compare(a: jnp.ndarray, b: jnp.ndarray):
    """(lt, eq) bool arrays for two limb tensors."""
    ah, al = order_key_pair(a)
    bh, bl = order_key_pair(b)
    lt = (ah < bh) | ((ah == bh) & (al < bl))
    eq = (ah == bh) & (al == bl)
    return lt, eq


def seg_sum128(data: jnp.ndarray, live: jnp.ndarray, seg: jnp.ndarray,
               cap: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum limbs [cap, 4], overflow bool [cap]).

    Overflow detection: each input is encoded mod 2^128, so the lane sum
    decodes correctly iff the dropped carry-out equals the adjustment the
    encoding implies: with N = #negative inputs and C = carry out of the
    top lane, the true sum is U + 2^128·(C − N); it fits signed 128 bits
    iff (C − N, top bit of U) is (0, 0) or (−1, 1). Spark nulls the sum on
    overflow (non-ANSI)."""
    x = jnp.where(live[:, None], data, 0)
    s = jax.ops.segment_sum(x, seg, num_segments=cap,
                            indices_are_sorted=True)
    neg = live & (data[..., 3] >= (1 << 31))
    n_neg = jax.ops.segment_sum(neg.astype(jnp.int64), seg,
                                num_segments=cap, indices_are_sorted=True)
    out = []
    carry = jnp.zeros(s.shape[:-1], jnp.int64)
    for j in range(4):
        v = s[..., j] + carry
        out.append(v & MASK32)
        carry = v >> 32
    limbs = jnp.stack(out, axis=-1)
    d = carry - n_neg
    u_top = limbs[..., 3] >= (1 << 31)
    ok = ((d == 0) & ~u_top) | ((d == -1) & u_top)
    return limbs, ~ok


def seg_minmax128(data: jnp.ndarray, live: jnp.ndarray, seg: jnp.ndarray,
                  cap: int, take_min: bool) -> jnp.ndarray:
    """Two-pass lexicographic segment min/max over the (hi, lo) keys."""
    hi, lo = order_key_pair(data)
    # hi/lo span the FULL int64 range (l3 << 32 wraps), so sentinels must
    # be the true extremes; empty groups yield sentinel limbs that the
    # caller masks out via validity
    info = jnp.iinfo(jnp.int64)
    big = jnp.int64(info.max if take_min else info.min)
    op = jax.ops.segment_min if take_min else jax.ops.segment_max
    h = op(jnp.where(live, hi, big), seg, num_segments=cap,
           indices_are_sorted=True)
    at_best = live & (hi == h[seg])
    l = op(jnp.where(at_best, lo, big), seg, num_segments=cap,
           indices_are_sorted=True)
    # reconstruct limbs from the winning (hi, lo) pair
    l3 = (h >> 32) & MASK32
    l2 = h & MASK32
    l1 = ((l >> 32) + (1 << 31)) & MASK32
    l0 = l & MASK32
    return jnp.stack([l0, l1, l2, l3], axis=-1)


def lift64(x: jnp.ndarray) -> jnp.ndarray:
    """int64 unscaled values → limb tensor (sign-extended)."""
    l0 = x & MASK32
    l1 = (x >> 32) & MASK32
    ext = jnp.where(x < 0, jnp.int64(MASK32), jnp.int64(0))
    return jnp.stack([l0, l1, ext, ext], axis=-1)


def exceeds_digits(data: jnp.ndarray, digits: int = 38) -> jnp.ndarray:
    """|value| >= 10^digits — Spark's precision-overflow test (nulls the
    result even though the value still fits 128 bits)."""
    mag = abs128(data)
    # |-2^127| wraps back to itself; its (impossible for abs) sign bit
    # marks it as exceeding any decimal precision
    return (mag[..., 3] >= (1 << 31)) | magnitude_exceeds(mag, digits)


def add128(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return normalize(a + b)


def neg128(data: jnp.ndarray) -> jnp.ndarray:
    # two's complement: ~x + 1 limb-wise
    inv = (~data) & MASK32
    one = jnp.zeros_like(data).at[..., 0].set(1)
    return normalize(inv + one)


def abs128(data: jnp.ndarray) -> jnp.ndarray:
    neg = (data[..., 3] >> 31) & 1
    return jnp.where(neg[..., None] == 1, neg128(data), data)


def rescale_up(data: jnp.ndarray, factor: int) -> jnp.ndarray:
    """data × factor (a power of ten, scale alignment), mod 2^128: per-limb
    multiplies by at most 10^9 stay under int64 (2^32 × 10^9 < 2^62), then
    a carry pass. Carries can exceed 32 bits, so normalize twice. The
    caller rules overflow out (``magnitude_exceeds`` of the operand first)."""
    while factor > 1:
        step = min(factor, 10 ** 9)
        data = normalize(normalize(data * jnp.int64(step)))
        factor //= step
    return data


def pow10_limbs(digits: int) -> jnp.ndarray:
    return jnp.asarray(to_limbs_np([10 ** digits])[0])


def magnitude_exceeds(mag: jnp.ndarray, digits: int) -> jnp.ndarray:
    """mag ≥ 10^digits for a NON-NEGATIVE limb tensor (< 2^127)."""
    lt, _ = compare(mag, jnp.broadcast_to(pow10_limbs(digits), mag.shape))
    return ~lt


def to_int64(data: jnp.ndarray) -> jnp.ndarray:
    """The low 64 bits of a limb tensor as int64: the value itself where
    it has at most 18 digits."""
    return (data[..., 1] << 32) | data[..., 0]


def _mag_limbs(x: jnp.ndarray):
    """(sign, [uint64 limb, ...]) of an int64 vector (2 limbs) or a limb
    tensor (4 limbs): the magnitude as 32-bit digits in uint64 lanes."""
    if x.ndim == 1:
        neg = x < 0
        m = jnp.abs(x).astype(jnp.uint64)
        return neg, [m & jnp.uint64(MASK32), m >> jnp.uint64(32)]
    neg = x[..., 3] >= (1 << 31)
    m = abs128(x).astype(jnp.uint64)
    return neg, [m[..., j] for j in range(4)]


def _carry_u64(acc):
    """Carry-propagate uint64 accumulators of 32-bit digits."""
    out, carry = [], jnp.zeros_like(acc[0])
    for v in acc:
        v = v + carry
        out.append(v & jnp.uint64(MASK32))
        carry = v >> jnp.uint64(32)
    return out, carry


def mul128(a: jnp.ndarray, b: jnp.ndarray, digits: Optional[int] = 38):
    """(a × b as limbs [.., 4], overflow). Each operand is an int64
    vector (a decimal of ≤ 18 digits: two limbs) or a limb tensor (four),
    so Q1's 64×64→128 costs 4 partial products and its 128×64→128 costs
    8. Sign-and-magnitude schoolbook in base 2^32: every 32×32 product
    fits a uint64 lane, split into halves before it is accumulated (at
    most 8 halves of < 2^32 a digit). Overflow: a digit past the fourth,
    or a magnitude of 10^digits or more (Spark nulls the product, or
    raises in ANSI mode; the caller decides); None for ``digits`` where
    the operands' types rule it out, and the flag is then None."""
    an, al = _mag_limbs(a)
    bn, bl = _mag_limbs(b)
    acc = [jnp.zeros_like(al[0]) for _ in range(len(al) + len(bl))]
    for i, x in enumerate(al):
        for j, y in enumerate(bl):
            p = x * y
            acc[i + j] = acc[i + j] + (p & jnp.uint64(MASK32))
            acc[i + j + 1] = acc[i + j + 1] + (p >> jnp.uint64(32))
    digs, carry = _carry_u64(acc)
    mag = jnp.stack([d.astype(jnp.int64) for d in digs[:4]], axis=-1)
    ovf = None
    if digits is not None:
        ovf = (carry != 0) | (mag[..., 3] >= (1 << 31)) \
            | magnitude_exceeds(mag, digits)
        for d in digs[4:]:
            ovf = ovf | (d != 0)
    neg = (an != bn)
    return jnp.where(neg[..., None], neg128(mag), mag), ovf


def div_half_up(total: jnp.ndarray, shift: int, count: jnp.ndarray,
                digits: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(total × 10^shift / count rounded HALF_UP as limbs [.., 4],
    overflow) for a signed 128-bit ``total`` (|total| < 10^38), shift ≤ 9
    and an int64 ``count`` (a slot whose count is not positive divides by
    1; the caller nulls it). Restoring long division of the 160-bit
    magnitude, a bit a step: the remainder stays under the count, so
    2·r + 1 < 2^64 and a uint64 lane holds it."""
    neg = total[..., 3] >= (1 << 31)
    m = abs128(total).astype(jnp.uint64) * jnp.uint64(10 ** shift)
    digs, top = _carry_u64([m[..., j] for j in range(4)])
    digs.append(top)                        # < 2^30 for shift ≤ 9
    c = jnp.maximum(count, 1).astype(jnp.uint64)
    one = jnp.uint64(1)
    r = jnp.zeros_like(c)
    quo = []
    for digit in reversed(digs):
        def step(i, st, digit=digit):
            r, q = st
            bit = (digit >> (jnp.uint64(31) - i.astype(jnp.uint64))) & one
            r = (r << one) | bit
            ge = r >= c
            return jnp.where(ge, r - c, r), (q << one) | ge.astype(jnp.uint64)
        r, q = jax.lax.fori_loop(0, 32, step, (r, jnp.zeros_like(c)))
        quo.append(q)
    quo.reverse()
    quo[0] = quo[0] + (r >= c - r).astype(jnp.uint64)       # 2r ≥ c
    quo, carry = _carry_u64(quo)
    mag = jnp.stack([d.astype(jnp.int64) for d in quo[:4]], axis=-1)
    ovf = (quo[4] != 0) | (carry != 0) | (mag[..., 3] >= (1 << 31)) \
        | magnitude_exceeds(mag, digits)
    return jnp.where(neg[..., None], neg128(mag), mag), ovf


# ---- sums as exact lanes of the aggregate's fused stack ------------------
# The fused aggregation path (aggregates.FastLanes) sums f64 lanes whose
# values are whole numbers under 2^22, so a batch of up to 2^22 rows sums
# exactly. A decimal rides it BIASED to be non-negative (an int64 by 2^62,
# a limb tensor by 2^127: flip the top bit), as 22-bit chunks of its 64-bit
# words; the group's non-null count takes the bias out again. No count of
# negatives, no carry handling per row.

_CHUNK = (1 << 22) - 1
_WORD_OFFSETS = (0, 22, 44)


def chunk_lanes(data: jnp.ndarray, ok: jnp.ndarray):
    """(int64 chunk vectors, their bit offsets, the bias's bit) of an
    int64 unscaled vector (|x| < 2^62) or a limb tensor; rows not ``ok``
    contribute 0 to every chunk (and are not counted by the caller)."""
    if data.ndim == 1:
        words, bias_bit = [data + (jnp.int64(1) << 62)], 62
    else:
        top = data[..., 3] ^ (1 << 31)
        words = [(data[..., 1] << 32) | data[..., 0],
                 (top << 32) | data[..., 2]]
        bias_bit = 127
    lanes, offsets = [], []
    for w, word in enumerate(words):
        u = word.astype(jnp.uint64)
        for off in _WORD_OFFSETS:
            c = ((u >> jnp.uint64(off)) & jnp.uint64(_CHUNK)).astype(jnp.int64)
            lanes.append(jnp.where(ok, c, jnp.int64(0)))
            offsets.append(64 * w + off)
    return lanes, offsets, bias_bit


def _add_shifted(acc, v, offset: int, sign: int):
    """acc (five int64 digit lanes, base 2^32) += sign · v · 2^offset for a
    non-negative int64 ``v``, taken in 22-bit pieces so that a piece
    shifted inside its digit stays under 2^54."""
    for k in range(3):
        piece = (v >> (22 * k)) & _CHUNK
        at = offset + 22 * k
        d, sh = divmod(at, 32)
        if d > 4:
            continue        # only a zero piece of a small ``v`` lands here
        x = piece << sh
        acc[d] = acc[d] + sign * (x & MASK32)
        if d + 1 <= 4:
            acc[d + 1] = acc[d + 1] + sign * (x >> 32)


def from_chunk_sums(sums, offsets, bias_bit: int, n_ok: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(limbs [.., 4], overflow) of Σ values from the per-group sums of
    ``chunk_lanes``' lanes (each < 2^44) and the group's count of summed
    rows: Σ chunk·2^offset − n·2^bias in five signed digit lanes, carried
    once; the fifth digit says whether the sum left 128 bits."""
    acc = [jnp.zeros_like(n_ok) for _ in range(5)]
    for s, off in zip(sums, offsets):
        _add_shifted(acc, s, off, 1)
    _add_shifted(acc, n_ok, bias_bit, -1)
    out, carry = [], jnp.zeros_like(n_ok)
    for j in range(4):
        v = acc[j] + carry
        out.append(v & MASK32)
        carry = v >> 32
    top = acc[4] + carry                    # signed: 0 or −1 when it fits
    limbs = jnp.stack(out, axis=-1)
    u_top = limbs[..., 3] >= (1 << 31)
    ok = ((top == 0) & ~u_top) | ((top == -1) & u_top)
    return limbs, ~ok
