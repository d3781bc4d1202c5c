"""Comparison and null-test expressions (reference: predicates.scala,
nullExpressions.scala — GpuEqualTo, GpuLessThan, GpuIsNull, GpuEqualNullSafe,
GpuIn, GpuNot)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp

from .. import types as T
from ..batch import DeviceColumn
from ..types import TypeKind
from .base import (EvalContext, Expression, and_validity, lit_if_needed,
                   string_compare_lt, string_equal)


def _bool_col(data, validity):
    return DeviceColumn(data & validity, validity, None, T.BOOLEAN)


def _compare_data(lc: DeviceColumn, rc: DeviceColumn, op: str):
    """Raw comparison payload ignoring validity."""
    if lc.dtype.kind is TypeKind.STRING:
        eq = string_equal(lc, rc)
        if op == "eq":
            return eq
        lt = string_compare_lt(lc, rc)
        return {"lt": lt, "le": lt | eq, "gt": ~(lt | eq), "ge": ~lt}[op]
    if lc.data.ndim > 1 or rc.data.ndim > 1:    # decimal128 limbs
        from .decimal128 import compare, lift64, rescale_up
        ld = lc.data if lc.data.ndim > 1 else lift64(lc.data)
        rd = rc.data if rc.data.ndim > 1 else lift64(rc.data)
        # align scales before comparing unscaled values; the planner gates
        # a rescale past 38 digits (decimal_cmp_unsupported_reason)
        ls, rs = lc.dtype.scale, rc.dtype.scale
        if ls < rs:
            ld = rescale_up(ld, 10 ** (rs - ls))
        elif rs < ls:
            rd = rescale_up(rd, 10 ** (ls - rs))
        lt, eq = compare(ld, rd)
        return {"eq": eq, "lt": lt, "le": lt | eq,
                "gt": ~(lt | eq), "ge": ~lt}[op]
    if lc.dtype.kind is TypeKind.DECIMAL and \
            rc.dtype.kind is TypeKind.DECIMAL and \
            lc.dtype.scale != rc.dtype.scale:
        # dec64 pair with different scales: align in int64 (the planner
        # gates combinations that could overflow)
        ls, rs = lc.dtype.scale, rc.dtype.scale
        l = lc.data * (10 ** max(0, rs - ls))
        r = rc.data * (10 ** max(0, ls - rs))
        return {"eq": l == r, "lt": l < r, "le": l <= r,
                "gt": l > r, "ge": l >= r}[op]
    # promote to a common dtype for mixed-width comparisons
    if lc.data.dtype != rc.data.dtype:
        d = jnp.promote_types(lc.data.dtype, rc.data.dtype)
        l, r = lc.data.astype(d), rc.data.astype(d)
    else:
        l, r = lc.data, rc.data
    return {"eq": l == r, "lt": l < r, "le": l <= r,
            "gt": l > r, "ge": l >= r}[op]


def decimal_cmp_unsupported_reason(lt, rt):
    """Mismatched-scale decimal comparison needs a device rescale; gate
    combinations whose rescaled unscaled value could overflow its storage."""
    if lt.kind is not TypeKind.DECIMAL or rt.kind is not TypeKind.DECIMAL:
        return None
    if lt.scale == rt.scale:
        return None
    diff = abs(lt.scale - rt.scale)
    small, big = (lt, rt) if lt.scale < rt.scale else (rt, lt)
    if small.precision <= 18 and big.precision <= 18:
        if small.precision + diff > 18:
            return (f"comparing {small} to {big} rescales past the int64 "
                    f"unscaled range")
        return None
    if small.precision + diff > 38:
        return f"comparing {small} to {big} rescales past 38 digits"
    return None


def _dict_pushdown(child: Expression, batch, ctx,
                   eval_entries) -> "Optional[DeviceColumn]":
    """Compressed-predicate evaluation: when ``child`` is a bare reference
    to a dict-encoded string column, run ``eval_entries(entries_column)``
    over the [card] DISTINCT dictionary entries and gather the boolean
    result through the codes — the predicate cost drops from n rows to
    card entries (the compressed-execution win from 'GPU Acceleration of
    SQL Analytics on Compressed Data'). Returns None when not applicable.

    Only a (possibly aliased) BARE reference qualifies — a computed child
    is never dict-encoded, and resolve_stored_column probes without
    evaluating it."""
    from .base import resolve_stored_column
    from ..types import TypeKind as TK
    if child.dtype.kind is not TK.STRING:
        return None
    col = resolve_stored_column(child, batch)
    if col is None or col.is_struct or col.dict_data is None:
        return None
    from ..batch import ColumnarBatch
    from ..dictenc import dict_entries_column
    ents = dict_entries_column(col)
    card = col.dict_data.shape[0]
    ebatch = ColumnarBatch((ents,), jnp.asarray(card, jnp.int32))
    emask = eval_entries(ents, ebatch)            # bool[card]
    data = jnp.take(emask, jnp.clip(col.data, 0, card - 1))
    return _bool_col(data, col.validity)


@dataclass(frozen=True, eq=False)
class BinaryComparison(Expression):
    left: Expression
    right: Expression
    OP = "eq"

    @property
    def children(self):
        return (self.left, self.right)

    def with_children(self, c):
        return type(self)(c[0], c[1])

    @property
    def dtype(self):
        return T.BOOLEAN

    def device_unsupported_reason(self):
        if self.left.resolved and self.right.resolved:
            return decimal_cmp_unsupported_reason(self.left.dtype,
                                                  self.right.dtype)
        return None

    def _dict_fast(self, batch, ctx):
        """string-column <op> literal over a dict column: compare the
        dictionary entries, gather [card] booleans by code."""
        from .base import Literal

        def side(child, litexpr, op):
            if not isinstance(litexpr, Literal) or litexpr.value is None:
                return None
            return _dict_pushdown(
                child, batch, ctx,
                lambda ents, eb: _compare_data(
                    ents, litexpr.eval(eb, ctx), op))

        r = side(self.left, self.right, self.OP)
        if r is not None:
            return r
        flipped = {"eq": "eq", "lt": "gt", "le": "ge",
                   "gt": "lt", "ge": "le"}[self.OP]
        return side(self.right, self.left, flipped)

    def eval(self, batch, ctx=EvalContext()):
        fast = self._dict_fast(batch, ctx)
        if fast is not None:
            return fast
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        return _bool_col(_compare_data(lc, rc, self.OP), and_validity([lc, rc]))

    def __repr__(self):
        return f"({self.left!r} {self.OP} {self.right!r})"


class EqualTo(BinaryComparison):
    OP = "eq"


class LessThan(BinaryComparison):
    OP = "lt"


class LessThanOrEqual(BinaryComparison):
    OP = "le"


class GreaterThan(BinaryComparison):
    OP = "gt"


class GreaterThanOrEqual(BinaryComparison):
    OP = "ge"


class EqualNullSafe(BinaryComparison):
    """<=>: null <=> null is true; never returns null."""

    OP = "eq"

    def eval(self, batch, ctx=EvalContext()):
        lc = self.left.eval(batch, ctx)
        rc = self.right.eval(batch, ctx)
        eq = _compare_data(lc, rc, "eq")
        both_valid = lc.validity & rc.validity
        both_null = ~lc.validity & ~rc.validity
        data = (both_valid & eq) | both_null
        return DeviceColumn(data & batch.row_mask(), batch.row_mask(),
                            None, T.BOOLEAN)


@dataclass(frozen=True, eq=False)
class Not(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Not(c[0])

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        return _bool_col(~c.data, c.validity)

    def __repr__(self):
        return f"NOT {self.child!r}"


@dataclass(frozen=True, eq=False)
class IsNull(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return IsNull(c[0])

    @property
    def dtype(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        mask = batch.row_mask()
        return DeviceColumn(~c.validity & mask, mask, None, T.BOOLEAN)

    def __repr__(self):
        return f"isnull({self.child!r})"


class IsNotNull(IsNull):
    def with_children(self, c):
        return IsNotNull(c[0])

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        mask = batch.row_mask()
        return DeviceColumn(c.validity & mask, mask, None, T.BOOLEAN)

    def __repr__(self):
        return f"isnotnull({self.child!r})"


@dataclass(frozen=True, eq=False)
class IsNaN(Expression):
    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return IsNaN(c[0])

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        return _bool_col(jnp.isnan(c.data), c.validity)


@dataclass(frozen=True, eq=False)
class In(Expression):
    """value IN (literals...). Spark 3VL: null if value is null, or if no
    match and the list contains a null."""

    child: Expression
    values: Tuple = ()

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return In(c[0], self.values)

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, batch, ctx=EvalContext()):
        from .base import Literal
        non_null = [v for v in self.values if v is not None]
        has_null_item = len(non_null) != len(self.values)

        def entries_in(ents, eb):
            f = jnp.zeros(eb.capacity, bool)
            for v in non_null:
                litc = Literal.of(v, self.child.dtype).eval(eb, ctx)
                f = f | _compare_data(ents, litc, "eq")
            return f

        fast = _dict_pushdown(self.child, batch, ctx, entries_in)
        if fast is not None:
            if has_null_item:
                return _bool_col(fast.data,
                                 fast.validity & fast.data)
            return fast
        c = self.child.eval(batch, ctx)
        found = jnp.zeros(batch.capacity, bool)
        for v in non_null:
            litc = Literal.of(v, self.child.dtype).eval(batch, ctx)
            found = found | _compare_data(c, litc, "eq")
        validity = c.validity & found if has_null_item else c.validity
        return _bool_col(found, validity)

    def __repr__(self):
        return f"{self.child!r} IN {self.values!r}"
