"""Spark-compatible Murmur3 x86_32 hashing, vectorized in jnp.

Reference parity: sql-plugin/.../HashFunctions.scala (GpuMurmur3Hash) and the
JNI murmur3 in spark-rapids-jni — Spark's Murmur3Hash expression (seed 42)
drives HashPartitioning, so shuffle placement is only compatible if this is
bit-exact with org.apache.spark.unsafe.hash.Murmur3_x86_32:

- int/short/byte/boolean/date -> hashInt(v)
- long/timestamp             -> hashLong(v)
- float  -> hashInt(floatToIntBits(v))  with -0.0 normalized to 0.0
- double -> hashLong(doubleToLongBits(v)) with -0.0 normalized
- string -> Spark's hashUnsafeBytes variant: 4-byte little-endian words,
  then each TAIL BYTE fully mixed (Spark diverges from standard murmur3 here)
- multiple columns fold left: hash = hash(col_i, seed=hash_so_far), start 42
- null values leave the running hash unchanged

All arithmetic in uint32 with explicit wraparound.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn
from ..types import TypeKind
from .base import EvalContext, Expression

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M = np.uint32(5)
_N = np.uint32(0xE6546B64)

DEFAULT_SEED = 42


def _rotl(x, r):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl(k1, 15)
    return k1 * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ _mix_k1(k1)
    h1 = _rotl(h1, 13)
    return h1 * _M + _N


def _fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length) if isinstance(length, int) else h1 ^ length
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def hash_int(v, seed):
    """Murmur3_x86_32.hashInt over an int32 array."""
    k = v.astype(jnp.int32).view(jnp.uint32) if hasattr(v, "view") else v
    h1 = _mix_h1(seed, k)
    return _fmix(h1, 4)


def _split_words_64(v) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(low, high) uint32 words of an int64 array, without 64-bit bitcasts.

    The TPU backend emulates 64-bit types and its X64 rewrite has no
    implementation for 64-bit bitcast-convert, so decompose arithmetically.
    """
    v = v.astype(jnp.int64)
    low = (v & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    high = ((v >> 32) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return low, high


def _exp2i(e) -> jnp.ndarray:
    """Exact 2.0**e for integer arrays with |e| <= 512, by bit decomposition
    (all multiplies by exact power-of-two constants; no transcendentals)."""
    neg = e < 0
    a = jnp.abs(e).astype(jnp.int32)
    f = jnp.ones(e.shape, jnp.float64)
    for k in range(10):  # bits up to 2^9 = 512
        c = jnp.float64(2.0 ** (1 << k))
        f = f * jnp.where((a >> k) & 1 == 1, c, jnp.float64(1.0))
    return jnp.where(neg, 1.0 / f, f)


def _double_bits_words(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """IEEE-754 bits of f64 as (low, high) uint32 words, computed purely
    arithmetically — the TPU backend has no 64-bit bitcast and its
    frexp/signbit lower to one. Matches Java Double.doubleToLongBits (NaN
    canonicalized to 0x7FF8000000000000) except that -0.0's sign is dropped;
    callers normalize -0.0 -> 0.0 first (Spark's hash does the same).
    """
    x = x.astype(jnp.float64)
    sign = x < 0
    ax = jnp.abs(x)
    # Stage the value into [2^-120, 2^120] with exact power-of-two multiplies
    # before ANY comparison/log2: the TPU backend emulates f64 as float32
    # pairs, so comparisons and transcendentals misbehave outside the f32
    # range (isinf/log2 of 1e200 are wrong there). All thresholds below are
    # f32-representable.
    e_adj = jnp.zeros(x.shape, jnp.int32)
    m0 = ax
    for _ in range(9):
        big = m0 > 2.0 ** 120
        m0 = jnp.where(big, m0 * 2.0 ** -120, m0)
        e_adj = e_adj + big.astype(jnp.int32) * 120
    for _ in range(9):
        small = (m0 < 2.0 ** -120) & (m0 > 0.0)
        m0 = jnp.where(small, m0 * 2.0 ** 120, m0)
        e_adj = e_adj - small.astype(jnp.int32) * 120
    is_inf = m0 > 2.0 ** 124  # only +/-inf survives staging above 2^120
    is_nan = x != x
    # exponent estimate via log2 on the staged value, then exact rescale
    safe_m0 = jnp.where((m0 > 0.0) & ~is_inf & ~is_nan, m0, 1.0)
    e = jnp.floor(jnp.log2(safe_m0)).astype(jnp.int32) + e_adj
    # For |x| < 2^-1021 (subnormals plus the lowest normal binade) the IEEE
    # bit pattern is EXACTLY |x| * 2^1074 — sidestep the boundary entirely.
    candidate_low = e <= -1018  # wide margin over log2's +/-1 error
    bits_low = (jnp.where(candidate_low, ax, 0.0)
                * (2.0 ** 537) * (2.0 ** 537)).astype(jnp.int64)
    use_low = candidate_low & (bits_low < (jnp.int64(1) << 53))
    normal = (ax > 0.0) & ~is_inf & ~is_nan & ~use_low
    e = jnp.clip(e, -1021, 1023)
    e1 = e // 2
    m = jnp.where(normal, ax, 1.0) * _exp2i(-e1) * _exp2i(-(e - e1))
    for _ in range(2):  # fix log2 rounding at power-of-two boundaries
        too_big = m >= 2.0
        m = jnp.where(too_big, m * 0.5, m)
        e = e + too_big
        too_small = m < 1.0
        m = jnp.where(too_small, m * 2.0, m)
        e = e - too_small
    biased = jnp.where(normal, (e + 1023).astype(jnp.int64), jnp.int64(0))
    mant = jnp.where(normal,
                     ((m - 1.0) * (2.0 ** 52)).astype(jnp.int64),
                     jnp.int64(0))
    body = jnp.where(use_low, bits_low, (biased << 52) | mant)
    body = jnp.where(is_inf, jnp.int64(2047) << 52, body)
    body = jnp.where(is_nan, (jnp.int64(2047) << 52) | (jnp.int64(1) << 51),
                     body)
    sign_bit = jnp.where(is_nan, jnp.int64(0), sign.astype(jnp.int64))
    bits = (sign_bit << 63) | body
    return _split_words_64(bits)


def hash_long(v, seed):
    """Murmur3_x86_32.hashLong: low word then high word."""
    low, high = _split_words_64(v)
    h1 = _mix_h1(seed, low)
    h1 = _mix_h1(h1, high)
    return _fmix(h1, 8)


def _hash_string(col: DeviceColumn, seed):
    """Spark hashUnsafeBytes over padded byte matrices + lengths."""
    data = col.data  # uint8[n, max_len]
    lengths = col.lengths
    n, max_len = data.shape
    h1 = jnp.broadcast_to(seed, (n,)).astype(jnp.uint32)
    # 4-byte aligned words, little-endian
    n_words = max_len // 4
    signed = data.view(jnp.int8)  # tail bytes are SIGNED in Spark
    for w in range(n_words):
        b0 = data[:, 4 * w].astype(jnp.uint32)
        b1 = data[:, 4 * w + 1].astype(jnp.uint32)
        b2 = data[:, 4 * w + 2].astype(jnp.uint32)
        b3 = data[:, 4 * w + 3].astype(jnp.uint32)
        word = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        mixed = _mix_h1(h1, word)
        h1 = jnp.where(lengths >= (w + 1) * 4, mixed, h1)
    # tail bytes, each fully mixed as a signed-byte int (Spark variant)
    for i in range(max_len):
        byte = signed[:, i].astype(jnp.int32).view(jnp.uint32)
        mixed = _mix_h1(h1, byte)
        in_tail = (i >= (lengths // 4) * 4) & (i < lengths)
        h1 = jnp.where(in_tail, mixed, h1)
    return _fmix(h1, lengths.astype(jnp.uint32))


_BITLEN_TABLE = None


def _hash_dec128(col: DeviceColumn, seed) -> jnp.ndarray:
    """Spark murmur3 of DECIMAL128: precision > 18 hashes the MINIMAL
    big-endian two's-complement byte array of the unscaled value
    (HashExpression: BigInteger.toByteArray → hashUnsafeBytes), so the
    byte count is data-dependent (1..16). Vectorized over the 4×32-bit
    limb lanes: build the 16 BE bytes, derive the minimal length from the
    bit length of v (or ~v when negative), shift the live bytes to the
    front, then run the 4-word + tail-byte mix predicated per row.

    Reference parity: spark-rapids-jni murmur3 decimal128 kernel
    (SURVEY §2.9 DecimalUtils); oracle = utils/murmur3.hash_decimal.
    """
    global _BITLEN_TABLE
    if _BITLEN_TABLE is None:
        _BITLEN_TABLE = jnp.asarray([x.bit_length() for x in range(256)],
                                    jnp.int32)
    limbs = col.data                       # int64[cap, 4], l0 least sig.
    neg = ((limbs[:, 3] >> 31) & 1) == 1
    # ~v (128-bit) == per-limb xor 0xFFFFFFFF; bit length of max(v, ~v)
    # gives Java BigInteger.bitLength()
    w = jnp.where(neg[:, None], limbs ^ jnp.int64(0xFFFFFFFF), limbs)

    def be_bytes(lanes):
        cols = []
        for j in range(16):            # j = 0 is the most significant byte
            li, sh = (15 - j) // 4, 8 * ((15 - j) % 4)
            cols.append(((lanes[:, li] >> sh) &
                         jnp.int64(0xFF)).astype(jnp.int32))
        return jnp.stack(cols, axis=1)       # int32[cap, 16] in [0, 255]

    wb = be_bytes(w)
    nz = wb != 0
    any_nz = jnp.any(nz, axis=1)
    j0 = jnp.argmax(nz, axis=1)              # first significant byte
    msb = jnp.take_along_axis(wb, j0[:, None], axis=1)[:, 0]
    msb_bits = jnp.take(_BITLEN_TABLE, msb)
    s = jnp.where(any_nz, (15 - j0) * 8 + msb_bits, 0)   # bitLength()
    n = s // 8 + 1                           # toByteArray length, 1..16
    vb = be_bytes(limbs)
    idx = (16 - n)[:, None] + jnp.arange(16, dtype=n.dtype)[None, :]
    seq = jnp.take_along_axis(vb, jnp.clip(idx, 0, 15), axis=1)
    h1 = seed
    nwords = n // 4
    useq = seq.astype(jnp.uint32)
    for wd in range(4):
        k = (useq[:, 4 * wd]
             | (useq[:, 4 * wd + 1] << 8)
             | (useq[:, 4 * wd + 2] << 16)
             | (useq[:, 4 * wd + 3] << 24))
        h1 = jnp.where(wd < nwords, _mix_h1(h1, k), h1)
    for i in range(16):
        b = seq[:, i]
        sb = jnp.where(b > 127, b - 256, b).astype(jnp.int32) \
                .view(jnp.uint32)
        in_tail = (i >= nwords * 4) & (i < n)
        h1 = jnp.where(in_tail, _mix_h1(h1, sb), h1)
    return _fmix(h1, n.astype(jnp.uint32))


def hash_column(col: DeviceColumn, seed) -> jnp.ndarray:
    """Hash one column with the running per-row seed; nulls pass seed through."""
    k = col.dtype.kind
    seed = jnp.broadcast_to(seed, col.validity.shape).astype(jnp.uint32)
    if k is TypeKind.STRING and col.dict_data is not None:
        # the per-row running seed differs row to row, so the per-entry
        # precompute below (murmur3_batch) does not apply — decode and
        # mix the bytes (still bit-exact)
        from ..dictenc import decode_column
        col = decode_column(col)
    if k is TypeKind.STRING:
        h = _hash_string(col, seed)
    elif k in (TypeKind.INT64, TypeKind.TIMESTAMP):
        h = hash_long(col.data, seed)
    elif k is TypeKind.FLOAT64:
        x = jnp.where(col.data == 0.0, 0.0, col.data)  # -0.0 -> 0.0
        low, high = _double_bits_words(x)
        h = _fmix(_mix_h1(_mix_h1(seed, low), high), 8)
    elif k is TypeKind.FLOAT32:
        import jax
        x = jnp.where(col.data == 0.0, jnp.float32(0.0), col.data)
        h = hash_int(jax.lax.bitcast_convert_type(x, jnp.uint32), seed)
    elif k is TypeKind.BOOLEAN:
        h = hash_int(col.data.astype(jnp.int32), seed)
    elif k is TypeKind.DECIMAL:
        if col.dtype.precision > 18:
            h = _hash_dec128(col, seed)
        else:
            # Spark hashes small decimals as their unscaled long
            h = hash_long(col.data, seed)
    else:  # int8/16/32, date
        h = hash_int(col.data.astype(jnp.int32), seed)
    return jnp.where(col.validity, h, seed)


def murmur3_batch(cols: Sequence[DeviceColumn],
                  seed: int = DEFAULT_SEED) -> jnp.ndarray:
    """Row hash across columns (Spark Murmur3Hash expression), as int32.

    Dict-encoded string columns in the LEADING position hash on codes:
    the seed is still the uniform constant there, so the byte mixing runs
    once per DISTINCT value ([card] rows) and per-row hashes are a single
    gather — bit-exact with Spark's hashUnsafeBytes over the decoded
    bytes, at card/n of the mixing cost. Later positions carry a per-row
    running seed and decode inside hash_column instead."""
    n = cols[0].validity.shape[0]
    h = jnp.full((n,), seed, jnp.uint32)
    leading = True
    for c in cols:
        if (leading and c.dtype.kind is TypeKind.STRING
                and not c.is_struct and c.dict_data is not None):
            from ..dictenc import dict_entries_column
            ents = dict_entries_column(c)
            card = c.dict_data.shape[0]
            eseed = jnp.full((card,), seed, jnp.uint32)
            eh = _hash_string(ents, eseed)
            hv = jnp.take(eh, jnp.clip(c.data, 0, card - 1))
            h = jnp.where(c.validity, hv, h)   # null keeps the seed
        else:
            h = hash_column(c, h)
        leading = False
    return h.view(jnp.int32)


@dataclass(frozen=True, eq=False)
class Murmur3Hash(Expression):
    exprs: Tuple[Expression, ...]
    seed: int = DEFAULT_SEED

    @property
    def children(self):
        return self.exprs

    def with_children(self, c):
        return Murmur3Hash(tuple(c), self.seed)

    @property
    def dtype(self):
        return T.INT32

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch, ctx=EvalContext()):
        cols = [e.eval(batch, ctx) for e in self.exprs]
        h = murmur3_batch(cols, self.seed)
        return DeviceColumn(h, batch.row_mask(), None, T.INT32)

    def __repr__(self):
        return f"murmur3({', '.join(map(repr, self.exprs))})"


def partition_ids(cols: Sequence[DeviceColumn], num_partitions: int) -> jnp.ndarray:
    """Spark HashPartitioning: pmod(murmur3(row), n)."""
    h = murmur3_batch(cols)
    m = h % jnp.int32(num_partitions)
    return jnp.where(m < 0, m + num_partitions, m)


# ---------------------------------------------------------------------------
# Spark-compatible XXH64 (reference: GpuOverrides XxHash64 rule; Spark
# catalyst XXH64 / XxHash64Function). All arithmetic in uint64 (emulated on
# TPU but elementwise-cheap); strings follow hashUnsafeBytes: 32-byte
# stripes, then 8-byte words, one 4-byte word, then tail bytes.
# ---------------------------------------------------------------------------

_XP1 = np.uint64(0x9E3779B185EBCA87)
_XP2 = np.uint64(0xC2B2AE3D27D4EB4F)
_XP3 = np.uint64(0x165667B19E3779F9)
_XP4 = np.uint64(0x85EBCA77C2B2AE63)
_XP5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    r = jnp.uint64(r)
    return (x << r) | (x >> (jnp.uint64(64) - r))


def _xx_avalanche(h):
    h = h ^ (h >> jnp.uint64(33))
    h = h * _XP2
    h = h ^ (h >> jnp.uint64(29))
    h = h * _XP3
    return h ^ (h >> jnp.uint64(32))


def _xx_u64(v) -> jnp.ndarray:
    """int64 array -> uint64 bits (arithmetic, no 64-bit bitcast)."""
    return v.astype(jnp.int64).astype(jnp.uint64)


def xxhash64_long(v, seed):
    """XXH64.hashLong(l, seed)."""
    h = seed + _XP5 + jnp.uint64(8)
    k1 = _rotl64(_xx_u64(v) * _XP2, 31) * _XP1
    h = h ^ k1
    h = _rotl64(h, 27) * _XP1 + _XP4
    return _xx_avalanche(h)

def xxhash64_int(v, seed):
    """XXH64.hashInt(i, seed): the int is zero-extended to a u32 lane."""
    h = seed + _XP5 + jnp.uint64(4)
    u = v.astype(jnp.int32).view(jnp.uint32).astype(jnp.uint64)
    h = h ^ (u * _XP1)
    h = _rotl64(h, 23) * _XP2 + _XP3
    return _xx_avalanche(h)


def _xx_word64(data, off):
    """Little-endian u64 word at byte offset ``off`` of each row."""
    w = jnp.zeros(data.shape[0], jnp.uint64)
    for b in range(8):
        w = w | (data[:, off + b].astype(jnp.uint64)
                 << jnp.uint64(8 * b))
    return w


def _xxhash64_string(col: DeviceColumn, seed):
    data, lengths = col.data, col.lengths
    n, max_len = data.shape
    length64 = lengths.astype(jnp.uint64)
    # stripe phase: rows with len >= 32 run 32-byte stripes through four
    # accumulators; stripe count = len // 32
    v1 = seed + _XP1 + _XP2
    v2 = seed + _XP2
    v3 = seed + jnp.uint64(0)
    v4 = seed - _XP1
    v1 = jnp.broadcast_to(v1, (n,))
    v2 = jnp.broadcast_to(v2, (n,))
    v3 = jnp.broadcast_to(v3, (n,))
    v4 = jnp.broadcast_to(v4, (n,))

    def stripe_round(acc, w):
        acc = acc + w * _XP2
        return _rotl64(acc, 31) * _XP1

    for s in range(max_len // 32):
        use = lengths >= (s + 1) * 32
        nv1 = stripe_round(v1, _xx_word64(data, 32 * s))
        nv2 = stripe_round(v2, _xx_word64(data, 32 * s + 8))
        nv3 = stripe_round(v3, _xx_word64(data, 32 * s + 16))
        nv4 = stripe_round(v4, _xx_word64(data, 32 * s + 24))
        v1 = jnp.where(use, nv1, v1)
        v2 = jnp.where(use, nv2, v2)
        v3 = jnp.where(use, nv3, v3)
        v4 = jnp.where(use, nv4, v4)

    merged = (_rotl64(v1, 1) + _rotl64(v2, 7) + _rotl64(v3, 12)
              + _rotl64(v4, 18))

    def merge_acc(h, acc):
        h = h ^ (_rotl64(acc * _XP2, 31) * _XP1)
        return h * _XP1 + _XP4

    merged = merge_acc(merged, v1)
    merged = merge_acc(merged, v2)
    merged = merge_acc(merged, v3)
    merged = merge_acc(merged, v4)
    short = seed + _XP5
    h = jnp.where(lengths >= 32, merged, jnp.broadcast_to(short, (n,)))
    h = h + length64

    # remaining 8-byte words from (len//32)*32 — always 8-aligned
    stripe_end = (lengths // 32) * 32
    word_end = stripe_end + ((lengths - stripe_end) // 8) * 8
    for o in range(0, max_len - 7, 8):
        use = (o >= stripe_end) & (o + 8 <= lengths)
        k1 = _rotl64(_xx_word64(data, o) * _XP2, 31) * _XP1
        nh = _rotl64(h ^ k1, 27) * _XP1 + _XP4
        h = jnp.where(use, nh, h)
    # one 4-byte word — always 4-aligned
    int_end = word_end + ((lengths - word_end) // 4) * 4
    for o in range(0, max_len - 3, 4):
        use = (o == word_end) & (o + 4 <= lengths)
        w = (data[:, o].astype(jnp.uint64)
             | (data[:, o + 1].astype(jnp.uint64) << jnp.uint64(8))
             | (data[:, o + 2].astype(jnp.uint64) << jnp.uint64(16))
             | (data[:, o + 3].astype(jnp.uint64) << jnp.uint64(24)))
        nh = _rotl64(h ^ (w * _XP1), 23) * _XP2 + _XP3
        h = jnp.where(use, nh, h)
    # tail bytes
    for o in range(max_len):
        use = (o >= int_end) & (o < lengths)
        b = data[:, o].astype(jnp.uint64)
        nh = _rotl64(h ^ (b * _XP5), 11) * _XP1
        h = jnp.where(use, nh, h)
    return _xx_avalanche(h)


def xxhash64_column(col: DeviceColumn, seed) -> jnp.ndarray:
    k = col.dtype.kind
    seed = jnp.broadcast_to(seed, col.validity.shape).astype(jnp.uint64)
    if k is TypeKind.STRING:
        h = _xxhash64_string(col, seed)
    elif k in (TypeKind.INT64, TypeKind.TIMESTAMP):
        h = xxhash64_long(col.data, seed)
    elif k is TypeKind.FLOAT64:
        x = jnp.where(col.data == 0.0, 0.0, col.data)
        low, high = _double_bits_words(x)
        bits = (high.astype(jnp.uint64) << jnp.uint64(32)) \
            | low.astype(jnp.uint64)
        h = xxhash64_long(bits.astype(jnp.int64), seed)
    elif k is TypeKind.FLOAT32:
        import jax
        x = jnp.where(col.data == 0.0, jnp.float32(0.0), col.data)
        h = xxhash64_int(
            jax.lax.bitcast_convert_type(x, jnp.uint32).view(jnp.int32),
            seed)
    elif k is TypeKind.BOOLEAN:
        h = xxhash64_int(col.data.astype(jnp.int32), seed)
    elif k is TypeKind.DECIMAL:
        h = xxhash64_long(col.data, seed)
    else:   # int8/16/32, date
        h = xxhash64_int(col.data.astype(jnp.int32), seed)
    return jnp.where(col.validity, h, seed)


@dataclass(frozen=True, eq=False)
class XxHash64(Expression):
    """xxhash64(cols...) — bigint row hash, seed 42 (Spark XxHash64)."""

    exprs: Tuple[Expression, ...]
    seed: int = DEFAULT_SEED

    @property
    def children(self):
        return self.exprs

    def with_children(self, c):
        return XxHash64(tuple(c), self.seed)

    @property
    def dtype(self):
        return T.INT64

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch, ctx=EvalContext()):
        h = jnp.full(batch.capacity, self.seed, jnp.uint64)
        for e in self.exprs:
            h = xxhash64_column(e.eval(batch, ctx), h)
        return DeviceColumn(h.astype(jnp.int64), batch.row_mask(), None,
                            T.INT64)
