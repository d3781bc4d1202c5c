"""Arithmetic expressions with Spark semantics.

Reference parity: sql-plugin/.../sql/rapids/arithmetic.scala (GpuAdd,
GpuSubtract, GpuMultiply, GpuDivide, GpuIntegralDivide, GpuRemainder,
GpuPmod, GpuUnaryMinus, GpuAbs). Non-ANSI mode: integer overflow wraps
(Java two's-complement — XLA integer ops match), division by zero yields
null. ANSI mode raises are handled at the engine boundary via overflow
flags (round 1: non-ANSI only; the planner tags ANSI for fallback).

Decimal +, - and * follow Spark 3.3's DecimalPrecision with
allowPrecisionLoss=true: result types from ``types.decimal_add_type`` /
``decimal_multiply_type``, operands aligned to the result's scale, results
of up to 38 digits in limbs (decimal128.py), a result past its precision
null (reported in ANSI mode). A result whose type cuts the scale falls
back to the CPU with a reason.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import types as T
from ..types import SqlType, TypeKind
from . import decimal128 as D128
from .base import (DeviceColumn, EvalContext, Expression, and_validity,
                   numeric_column)


def _decimal_pair(e):
    """(left, right) as the decimal types they enter decimal arithmetic
    with (an integral operand as ``DecimalType.forType``), or None where
    the expression is not decimal arithmetic (no decimal operand, or a
    float one: Spark then computes in double)."""
    lt, rt = e.left.dtype, e.right.dtype
    if TypeKind.DECIMAL not in (lt.kind, rt.kind):
        return None
    ld, rd = T.as_decimal(lt), T.as_decimal(rt)
    return None if ld is None or rd is None else (ld, rd)


def _as_storage(col: DeviceColumn, out: SqlType):
    """A numeric operand's payload in ``out``'s storage dtype; a decimal
    entering double arithmetic is divided by its scale first."""
    if col.dtype.kind is TypeKind.DECIMAL and out.is_fractional:
        return col.data.astype(jnp.float64) / (10.0 ** col.dtype.scale)
    return col.data.astype(out.storage_dtype)


@dataclass(frozen=True, eq=False)
class BinaryArithmetic(Expression):
    left: Expression
    right: Expression

    @property
    def children(self):
        return (self.left, self.right)

    def with_children(self, c):
        return type(self)(c[0], c[1])

    @property
    def dtype(self) -> SqlType:
        return T.common_numeric_type(self.left.dtype, self.right.dtype)

    def device_unsupported_reason(self):
        if self.dtype.is_fractional:
            for c in self.children:
                if D128.is_dec128(c.dtype):
                    return (f"{type(self).__name__} of {c.dtype} and a "
                            f"float: no decimal128-to-double kernel")
        return None

    def _operands(self, batch, ctx):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        out = self.dtype
        return (_as_storage(lc, out), _as_storage(rc, out),
                and_validity([lc, rc]), out)

    def __repr__(self):
        return f"({self.left!r} {self.SYMBOL} {self.right!r})"


def _rescaled(col: DeviceColumn, t: SqlType, out: SqlType):
    """(``col`` at ``out``'s scale in ``out``'s storage, fits): Spark 3.3
    casts each operand of + and - to the result type first
    (``promotePrecision``), and a value the result type cannot hold is
    null there. ``fits`` is None where the types rule that out."""
    k = out.scale - t.scale
    if out.precision <= 18:
        return col.data.astype(jnp.int64) * jnp.int64(10 ** k), None
    limbs = col.data if col.data.ndim > 1 \
        else D128.lift64(col.data.astype(jnp.int64))
    fits = None
    if t.precision + k > out.precision:
        fits = ~D128.magnitude_exceeds(D128.abs128(limbs),
                                       out.precision - k)
    return D128.rescale_up(limbs, 10 ** k), fits


class _AddSub(BinaryArithmetic):
    """+ and -: integer overflow wraps (reported in ANSI mode); decimals
    take Spark's result type, operands aligned to its scale, a sum past
    its precision null (reported in ANSI mode)."""

    _NEGATE_RIGHT = False

    @property
    def dtype(self) -> SqlType:
        pair = _decimal_pair(self)
        return T.decimal_add_type(*pair) if pair else super().dtype

    def _can_overflow(self, pair) -> bool:
        """The result's precision was capped at 38: a sum can pass it."""
        return max(p.precision - p.scale for p in pair) \
            + max(p.scale for p in pair) + 1 > T.MAX_DECIMAL_PRECISION

    @property
    def nullable(self):
        pair = _decimal_pair(self)
        return True if pair and self._can_overflow(pair) \
            else super().nullable

    def device_unsupported_reason(self):
        pair = _decimal_pair(self)
        if pair and self.dtype.scale < max(p.scale for p in pair):
            return (f"{pair[0]} {self.SYMBOL} {pair[1]} is {self.dtype}: "
                    f"the scale is cut, which rounds the operands; no "
                    f"device kernel rounds inside decimal arithmetic")
        return super().device_unsupported_reason()

    def _eval_decimal(self, batch, ctx, pair):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        out = self.dtype
        v = and_validity([lc, rc])
        l, lfit = _rescaled(lc, pair[0], out)
        r, rfit = _rescaled(rc, pair[1], out)
        if out.precision <= 18:
            # p = max integral digits + scale + 1: cannot leave int64
            return numeric_column(l - r if self._NEGATE_RIGHT else l + r,
                                  v, out)
        if self._NEGATE_RIGHT:
            r = D128.neg128(r)
        res = D128.add128(l, r)
        ok = jnp.ones(v.shape, bool)
        for fit in (lfit, rfit):
            if fit is not None:
                ok = ok & fit
        if self._can_overflow(pair):
            # both operands are under 10^38 in magnitude, so a sum that
            # wrapped 128 bits has the sign neither operand has
            sl, sr, ss = (x[..., 3] >= (1 << 31) for x in (l, r, res))
            ok = ok & ~((sl == sr) & (ss != sl)) \
                & ~D128.exceeds_digits(res, out.precision)
        ctx.report(~ok & v)
        v = v & ok
        return DeviceColumn(jnp.where(v[:, None], res, 0), v, None, out)


class Add(_AddSub):
    SYMBOL = "+"

    def eval(self, batch, ctx=EvalContext()):
        pair = _decimal_pair(self)
        if pair:
            return self._eval_decimal(batch, ctx, pair)
        l, r, v, d = self._operands(batch, ctx)
        res = l + r
        if ctx.ansi and d.is_integral:
            # two's-complement overflow: result sign differs from both
            ctx.report((((l ^ res) & (r ^ res)) < 0) & v)
        return numeric_column(res, v, d)


class Subtract(_AddSub):
    SYMBOL = "-"
    _NEGATE_RIGHT = True

    def eval(self, batch, ctx=EvalContext()):
        pair = _decimal_pair(self)
        if pair:
            return self._eval_decimal(batch, ctx, pair)
        l, r, v, d = self._operands(batch, ctx)
        res = l - r
        if ctx.ansi and d.is_integral:
            ctx.report((((l ^ r) & (l ^ res)) < 0) & v)
        return numeric_column(res, v, d)


class Multiply(BinaryArithmetic):
    """*: decimals take Spark's result type (p1 + p2 + 1, s1 + s2,
    adjusted); the exact product in int64 up to 18 digits and in limbs
    above (decimal128.mul128), null past the precision (reported in ANSI
    mode)."""

    SYMBOL = "*"

    @property
    def dtype(self):
        pair = _decimal_pair(self)
        return T.decimal_multiply_type(*pair) if pair else super().dtype

    def _can_overflow(self, pair) -> bool:
        return pair[0].precision + pair[1].precision + 1 \
            > T.MAX_DECIMAL_PRECISION

    @property
    def nullable(self):
        pair = _decimal_pair(self)
        return True if pair and self._can_overflow(pair) \
            else super().nullable

    def device_unsupported_reason(self):
        pair = _decimal_pair(self)
        if pair and self.dtype.scale < pair[0].scale + pair[1].scale:
            return (f"{pair[0]} * {pair[1]} is {self.dtype}: the scale is "
                    f"cut, which rounds the product HALF_UP; no device "
                    f"kernel rounds inside decimal arithmetic")
        return super().device_unsupported_reason()

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        d = self.dtype
        v = and_validity([lc, rc])
        pair = _decimal_pair(self)
        if pair and d.precision > 18:
            l, r = (c.data if c.data.ndim > 1 else c.data.astype(jnp.int64)
                    for c in (lc, rc))
            res, ovf = D128.mul128(
                l, r, d.precision if self._can_overflow(pair) else None)
            if ovf is not None:
                ctx.report(ovf & v)
                v = v & ~ovf
            return DeviceColumn(jnp.where(v[:, None], res, 0), v, None, d)
        l = _as_storage(lc, d)
        r = _as_storage(rc, d)
        res = l * r
        if ctx.ansi and d.is_integral:
            # detect via truncating re-division: res / r != l (r != 0)
            safe_r = jnp.where(r == 0, 1, r)
            q = jnp.sign(res) * jnp.sign(safe_r) * \
                (jnp.abs(res) // jnp.abs(safe_r))
            ctx.report(((r != 0) & (q != l)) & v)
        return numeric_column(res, v, d)


class Divide(BinaryArithmetic):
    """Spark `/`: true division, result is DOUBLE (decimal deferred);
    x/0 -> null in non-ANSI mode."""

    @property
    def nullable(self):
        # zero divisors null the result in non-ANSI mode regardless of
        # child nullability — the static flag must admit it (a lying
        # False lets sorts drop this key's null lane)
        return True


    SYMBOL = "/"

    @property
    def dtype(self):
        return T.FLOAT64

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        l = lc.data.astype(jnp.float64)
        r = rc.data.astype(jnp.float64)
        both = and_validity([lc, rc])
        if ctx.ansi:
            ctx.report(both & (r == 0.0), "DIVIDE_BY_ZERO")
        valid = both & (r != 0.0)
        safe_r = jnp.where(r == 0.0, 1.0, r)
        return numeric_column(l / safe_r, valid, T.FLOAT64)


class IntegralDivide(BinaryArithmetic):
    """Spark `div`: integral division returning LONG; x div 0 -> null.
    Java semantics: truncation toward zero."""

    @property
    def nullable(self):
        # zero divisors null the result in non-ANSI mode regardless of
        # child nullability — the static flag must admit it (a lying
        # False lets sorts drop this key's null lane)
        return True


    SYMBOL = "div"

    @property
    def dtype(self):
        return T.INT64

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        l = lc.data.astype(jnp.int64)
        r = rc.data.astype(jnp.int64)
        both = and_validity([lc, rc])
        if ctx.ansi:
            ctx.report(both & (r == 0), "DIVIDE_BY_ZERO")
        valid = both & (r != 0)
        safe_r = jnp.where(r == 0, 1, r)
        q = jnp.sign(l) * jnp.sign(safe_r) * (jnp.abs(l) // jnp.abs(safe_r))
        return numeric_column(q, valid, T.INT64)


class Remainder(BinaryArithmetic):
    """Spark `%`: sign follows the dividend (Java %), x%0 -> null."""

    @property
    def nullable(self):
        # zero divisors null the result in non-ANSI mode regardless of
        # child nullability — the static flag must admit it (a lying
        # False lets sorts drop this key's null lane)
        return True


    SYMBOL = "%"

    def eval(self, batch, ctx=EvalContext()):
        l, r, v, d = self._operands(batch, ctx)
        if d.is_fractional:
            valid = v & (r != 0.0)
            safe_r = jnp.where(r == 0.0, 1.0, r)
            rem = jnp.fmod(l, safe_r)  # fmod: sign of dividend, like Java %
        else:
            valid = v & (r != 0)
            safe_r = jnp.where(r == 0, 1, r)
            rem = jnp.sign(l) * (jnp.abs(l) % jnp.abs(safe_r))
        return numeric_column(rem, valid, d)


class Pmod(BinaryArithmetic):
    """Spark pmod: non-negative modulus (reference: GpuPmod)."""

    @property
    def nullable(self):
        # zero divisors null the result in non-ANSI mode regardless of
        # child nullability — the static flag must admit it (a lying
        # False lets sorts drop this key's null lane)
        return True


    SYMBOL = "pmod"

    def eval(self, batch, ctx=EvalContext()):
        l, r, v, d = self._operands(batch, ctx)
        if d.is_fractional:
            valid = v & (r != 0.0)
            safe_r = jnp.where(r == 0.0, 1.0, r)
        else:
            valid = v & (r != 0)
            safe_r = jnp.where(r == 0, 1, r)
        m = jnp.mod(l, safe_r)  # python-style mod: sign of divisor
        m = jnp.where(m < 0, m + jnp.abs(safe_r), m)
        return numeric_column(m, valid, d)


@dataclass(frozen=True, eq=False)
class UnaryMinus(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return UnaryMinus(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        if c.data.ndim > 1:        # decimal128 limbs
            return DeviceColumn(D128.neg128(c.data), c.validity, None,
                                self.dtype)
        return numeric_column(-c.data, c.validity, self.dtype)

    def __repr__(self):
        return f"(- {self.child!r})"


@dataclass(frozen=True, eq=False)
class Abs(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Abs(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        if c.data.ndim > 1:        # decimal128 limbs
            return DeviceColumn(D128.abs128(c.data), c.validity, None,
                                self.dtype)
        return numeric_column(jnp.abs(c.data), c.validity, self.dtype)

    def __repr__(self):
        return f"abs({self.child!r})"


@dataclass(frozen=True, eq=False)
class BitwiseOp(Expression):
    left: Expression
    right: Expression
    op: str = "and"  # and|or|xor

    @property
    def children(self):
        return (self.left, self.right)

    def with_children(self, c):
        return BitwiseOp(c[0], c[1], self.op)

    @property
    def dtype(self):
        return T.common_numeric_type(self.left.dtype, self.right.dtype)

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        d = self.dtype
        l = lc.data.astype(d.storage_dtype)
        r = rc.data.astype(d.storage_dtype)
        fn = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
              "xor": jnp.bitwise_xor}[self.op]
        return numeric_column(fn(l, r), and_validity([lc, rc]), d)

    def __repr__(self):
        return f"({self.left!r} {self.op} {self.right!r})"


@dataclass(frozen=True, eq=False)
class BitwiseNot(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return BitwiseNot(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        return numeric_column(jnp.bitwise_not(c.data), c.validity, self.dtype)


@dataclass(frozen=True, eq=False)
class Shift(Expression):
    """shiftleft/shiftright/shiftrightunsigned (reference:
    GpuOverrides shift operator rules). Java semantics: the shift amount
    wraps modulo the value's bit width (32 for int, 64 for long)."""

    left: Expression
    right: Expression
    op: str = "left"        # left | right | right_unsigned

    @property
    def children(self):
        return (self.left, self.right)

    def with_children(self, c):
        return Shift(c[0], c[1], self.op)

    @property
    def dtype(self):
        # Spark: INT or BIGINT result; narrower inputs are promoted to INT
        # (the analyzer inserts the cast — mirror it here)
        if self.left.dtype.kind is TypeKind.INT64:
            return self.left.dtype
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        v = lc.data.astype(self.dtype.storage_dtype)
        width = v.dtype.itemsize * 8
        amt = rc.data.astype(jnp.int32) & jnp.int32(width - 1)
        if self.op == "left":
            out = v << amt.astype(v.dtype)
        elif self.op == "right":
            out = v >> amt.astype(v.dtype)   # arithmetic (signed input)
        else:
            u = v.astype(jnp.uint32 if width == 32 else jnp.uint64)
            out = (u >> amt.astype(u.dtype)).astype(v.dtype)
        return numeric_column(out, and_validity([lc, rc]), self.dtype)
