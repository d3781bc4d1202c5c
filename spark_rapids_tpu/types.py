"""SQL type system for the TPU-native accelerator.

Mirrors the role of Spark's DataType lattice plus the reference's TypeSig gating
(reference: sql-plugin/.../TypeChecks.scala:171 `TypeSig`), re-designed for the
XLA compilation model: every SQL type maps to a fixed JAX storage dtype so that
columns are static-shaped, fixed-width device arrays.

Design notes (TPU-first):
- Nullability lives in a separate validity mask, never in the storage dtype.
- Strings are fixed-width padded UTF-8 byte matrices (``uint8[rows, max_len]``)
  plus a length vector — TPU vector units want rectangular data; cudf's
  offsets+chars layout (reference GpuColumnVector.java) would force dynamic
  shapes through XLA.
- Decimals with precision <= 18 are scaled int64 (DECIMAL64); wider ones
  (DECIMAL128) are four 32-bit limbs in int64 lanes
  (expressions/decimal128.py). Arithmetic result types follow Spark 3.3's
  DecimalPrecision with allowPrecisionLoss=true (``decimal_add_type``,
  ``decimal_multiply_type``).
- Dates are days-since-epoch int32; timestamps are microseconds-since-epoch
  int64 (Spark's internal representation, which is also MXU/VPU friendly).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class TypeKind(enum.Enum):
    BOOLEAN = "boolean"
    INT8 = "tinyint"
    INT16 = "smallint"
    INT32 = "int"
    INT64 = "bigint"
    FLOAT32 = "float"
    FLOAT64 = "double"
    DECIMAL = "decimal"
    STRING = "string"
    DATE = "date"
    TIMESTAMP = "timestamp"
    NULL = "void"
    ARRAY = "array"
    STRUCT = "struct"
    MAP = "map"


_INTEGRALS = {TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64}
_FRACTIONALS = {TypeKind.FLOAT32, TypeKind.FLOAT64}

# JAX storage dtype per kind (strings/nested handled specially).
_STORAGE = {
    TypeKind.BOOLEAN: jnp.bool_,
    TypeKind.INT8: jnp.int8,
    TypeKind.INT16: jnp.int16,
    TypeKind.INT32: jnp.int32,
    TypeKind.INT64: jnp.int64,
    TypeKind.FLOAT32: jnp.float32,
    TypeKind.FLOAT64: jnp.float64,
    TypeKind.DECIMAL: jnp.int64,
    TypeKind.DATE: jnp.int32,
    TypeKind.TIMESTAMP: jnp.int64,
    TypeKind.NULL: jnp.int8,
}


@dataclass(frozen=True)
class SqlType:
    """A SQL data type. Hashable, usable as static (non-pytree) metadata."""

    kind: TypeKind
    # decimal parameters
    precision: int = 0
    scale: int = 0
    # string parameter: max encoded byte length (static per column)
    max_len: int = 0
    # nested element types (arrays/maps/structs)
    children: Tuple["SqlType", ...] = field(default_factory=tuple)
    # struct field names (parallel to children; empty for non-structs)
    names: Tuple[str, ...] = field(default_factory=tuple)

    # ---- predicates -------------------------------------------------
    @property
    def is_integral(self) -> bool:
        return self.kind in _INTEGRALS

    @property
    def is_fractional(self) -> bool:
        return self.kind in _FRACTIONALS

    @property
    def is_numeric(self) -> bool:
        return self.is_integral or self.is_fractional or self.kind is TypeKind.DECIMAL

    @property
    def is_string(self) -> bool:
        return self.kind is TypeKind.STRING

    @property
    def is_nested(self) -> bool:
        return self.kind in (TypeKind.ARRAY, TypeKind.STRUCT, TypeKind.MAP)

    @property
    def is_datetime(self) -> bool:
        return self.kind in (TypeKind.DATE, TypeKind.TIMESTAMP)

    # ---- storage ----------------------------------------------------
    @property
    def storage_dtype(self):
        """JAX dtype of the device storage array (payload for strings)."""
        if self.kind is TypeKind.STRING:
            return jnp.uint8
        if self.kind not in _STORAGE:
            raise TypeError(f"no device storage for {self}")
        return _STORAGE[self.kind]

    def __str__(self) -> str:
        if self.kind is TypeKind.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        if self.kind is TypeKind.STRING and self.max_len:
            return f"string[{self.max_len}]"
        if self.kind is TypeKind.ARRAY:
            return f"array<{self.children[0]}>"
        if self.kind is TypeKind.MAP:
            return f"map<{self.children[0]},{self.children[1]}>"
        if self.kind is TypeKind.STRUCT:
            names = self.names or tuple(
                f"f{i}" for i in range(len(self.children)))
            inner = ", ".join(f"{n}: {c}"
                              for n, c in zip(names, self.children))
            return f"struct<{inner}>"
        return self.kind.value


# Canonical singletons -----------------------------------------------
BOOLEAN = SqlType(TypeKind.BOOLEAN)
INT8 = SqlType(TypeKind.INT8)
INT16 = SqlType(TypeKind.INT16)
INT32 = SqlType(TypeKind.INT32)
INT64 = SqlType(TypeKind.INT64)
FLOAT32 = SqlType(TypeKind.FLOAT32)
FLOAT64 = SqlType(TypeKind.FLOAT64)
DATE = SqlType(TypeKind.DATE)
TIMESTAMP = SqlType(TypeKind.TIMESTAMP)
NULL = SqlType(TypeKind.NULL)


def decimal(precision: int, scale: int) -> SqlType:
    # precision > 18 (DECIMAL128) stores as limbs; an operator's TypeSig
    # (max_decimal_precision) says whether it has a kernel for them.
    return SqlType(TypeKind.DECIMAL, precision=precision, scale=scale)


def string(max_len: int = 64) -> SqlType:
    return SqlType(TypeKind.STRING, max_len=max_len)


STRING = string()


def array(elem: SqlType, max_elems: int = 256) -> SqlType:
    """array<elem> with a static device element budget (max_len field),
    the same fixed-width strategy as strings."""
    return SqlType(TypeKind.ARRAY, max_len=max_elems, children=(elem,))


def struct(*fields: SqlType, names: Optional[Tuple[str, ...]] = None
           ) -> SqlType:
    """struct<name: type, ...> — stored on device as one lane-set per leaf
    field plus a struct-level validity lane (a null struct nulls every
    field; Spark's reverse inference does not apply)."""
    if names is None:
        names = tuple(f"f{i}" for i in range(len(fields)))
    if len(names) != len(fields):
        raise ValueError("struct names/fields length mismatch")
    return SqlType(TypeKind.STRUCT, children=tuple(fields),
                   names=tuple(names))


def map_(key: SqlType, value: SqlType, max_elems: int = 256) -> SqlType:
    """map<key,value> with a static entry budget — stored on device as two
    zipped fixed-budget matrices (keys, values) sharing one lengths vector."""
    return SqlType(TypeKind.MAP, max_len=max_elems, children=(key, value))


# ---- numeric promotion (Spark's findTightestCommonType subset) ------
_NUM_ORDER = [TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
              TypeKind.FLOAT32, TypeKind.FLOAT64]


def common_numeric_type(a: SqlType, b: SqlType) -> SqlType:
    """Tightest common numeric type for binary arithmetic (Spark promotion)."""
    if a.kind is TypeKind.DECIMAL or b.kind is TypeKind.DECIMAL:
        # Spark's WIDER decimal type (comparisons, coalesce); arithmetic
        # results take decimal_add_type / decimal_multiply_type below
        if a.kind is TypeKind.DECIMAL and b.kind is TypeKind.DECIMAL:
            scale = max(a.scale, b.scale)
            prec = max(a.precision - a.scale, b.precision - b.scale) + scale
            return decimal(min(prec, 38), scale)
        other = b if a.kind is TypeKind.DECIMAL else a
        dec = a if a.kind is TypeKind.DECIMAL else b
        if other.is_fractional:
            return FLOAT64
        if other.kind not in _INTEGRALS:
            raise TypeError(f"no common numeric type for {a}, {b}")
        digits = _INTEGRAL_DIGITS[other.kind]
        prec = max(dec.precision - dec.scale, digits) + dec.scale
        return decimal(min(prec, 38), dec.scale)
    if not (a.is_numeric and b.is_numeric):
        raise TypeError(f"no common numeric type for {a}, {b}")
    ia, ib = _NUM_ORDER.index(a.kind), _NUM_ORDER.index(b.kind)
    return SqlType(_NUM_ORDER[max(ia, ib)])


# ---- decimal arithmetic result types (Spark 3.3 DecimalPrecision) ---
MAX_DECIMAL_PRECISION = 38
_MIN_ADJUSTED_SCALE = 6
# Spark DecimalType.forType: the decimal an integral operand is cast to
_INTEGRAL_DIGITS = {TypeKind.INT8: 3, TypeKind.INT16: 5,
                    TypeKind.INT32: 10, TypeKind.INT64: 20}


def as_decimal(t: SqlType) -> Optional[SqlType]:
    """The decimal type ``t`` enters decimal arithmetic as: itself, or an
    integral's ``DecimalType.forType``; None for anything else."""
    if t.kind is TypeKind.DECIMAL:
        return t
    if t.kind in _INTEGRAL_DIGITS:
        return decimal(_INTEGRAL_DIGITS[t.kind], 0)
    return None


def adjust_precision_scale(precision: int, scale: int) -> SqlType:
    """DecimalType.adjustPrecisionScale (allowPrecisionLoss=true): past 38
    digits the integral digits are kept and the scale is cut, to no less
    than min(scale, 6)."""
    if precision <= MAX_DECIMAL_PRECISION:
        return decimal(precision, scale)
    int_digits = precision - scale
    min_scale = min(scale, _MIN_ADJUSTED_SCALE)
    return decimal(MAX_DECIMAL_PRECISION,
                   max(MAX_DECIMAL_PRECISION - int_digits, min_scale))


def decimal_add_type(a: SqlType, b: SqlType) -> SqlType:
    """Result of ``a + b`` / ``a - b``: scale max(s1, s2), precision
    max(p1 - s1, p2 - s2) + scale + 1, then adjusted."""
    scale = max(a.scale, b.scale)
    return adjust_precision_scale(
        max(a.precision - a.scale, b.precision - b.scale) + scale + 1, scale)


def decimal_multiply_type(a: SqlType, b: SqlType) -> SqlType:
    """Result of ``a * b``: precision p1 + p2 + 1, scale s1 + s2, then
    adjusted."""
    return adjust_precision_scale(a.precision + b.precision + 1,
                                  a.scale + b.scale)


# ---- host<->device conversion helpers -------------------------------
def numpy_dtype(t: SqlType) -> np.dtype:
    return np.dtype(_STORAGE[t.kind]) if t.kind in _STORAGE else np.dtype(np.uint8)


def from_arrow(arrow_type: Any, max_len: int = 64) -> SqlType:
    """Map a pyarrow DataType to a SqlType."""
    import pyarrow as pa

    if pa.types.is_boolean(arrow_type):
        return BOOLEAN
    if pa.types.is_int8(arrow_type):
        return INT8
    if pa.types.is_int16(arrow_type):
        return INT16
    if pa.types.is_int32(arrow_type):
        return INT32
    if pa.types.is_int64(arrow_type):
        return INT64
    if pa.types.is_float32(arrow_type):
        return FLOAT32
    if pa.types.is_float64(arrow_type):
        return FLOAT64
    if pa.types.is_decimal(arrow_type):
        return decimal(arrow_type.precision, arrow_type.scale)
    if pa.types.is_string(arrow_type) or pa.types.is_large_string(arrow_type):
        return string(max_len)
    if pa.types.is_dictionary(arrow_type):
        # dictionary encoding is a COLUMN property (dictenc.py), not a
        # type: dictionary<string> scans type as plain string
        return from_arrow(arrow_type.value_type, max_len)
    if pa.types.is_date32(arrow_type):
        return DATE
    if pa.types.is_timestamp(arrow_type):
        return TIMESTAMP
    if pa.types.is_map(arrow_type):
        return map_(from_arrow(arrow_type.key_type, max_len),
                    from_arrow(arrow_type.item_type, max_len))
    if pa.types.is_list(arrow_type):
        return array(from_arrow(arrow_type.value_type, max_len))
    if pa.types.is_struct(arrow_type):
        return struct(*(from_arrow(f.type, max_len) for f in arrow_type),
                      names=tuple(f.name for f in arrow_type))
    if pa.types.is_null(arrow_type):
        return NULL
    raise TypeError(f"unsupported arrow type {arrow_type}")


def to_arrow(t: SqlType):
    import pyarrow as pa

    m = {
        TypeKind.BOOLEAN: pa.bool_(),
        TypeKind.INT8: pa.int8(),
        TypeKind.INT16: pa.int16(),
        TypeKind.INT32: pa.int32(),
        TypeKind.INT64: pa.int64(),
        TypeKind.FLOAT32: pa.float32(),
        TypeKind.FLOAT64: pa.float64(),
        TypeKind.STRING: pa.string(),
        TypeKind.DATE: pa.date32(),
        TypeKind.TIMESTAMP: pa.timestamp("us", tz="UTC"),
        TypeKind.NULL: pa.null(),
    }
    if t.kind is TypeKind.DECIMAL:
        return pa.decimal128(t.precision, t.scale)
    if t.kind is TypeKind.ARRAY:
        return pa.list_(to_arrow(t.children[0]))
    if t.kind is TypeKind.MAP:
        return pa.map_(to_arrow(t.children[0]), to_arrow(t.children[1]))
    if t.kind is TypeKind.STRUCT:
        names = t.names or tuple(f"f{i}" for i in range(len(t.children)))
        return pa.struct([pa.field(n, to_arrow(c), nullable=True)
                          for n, c in zip(names, t.children)])
    return m[t.kind]


# ---- TypeSig: per-operator supported-type signatures ----------------
class TypeSig:
    """Set-algebra over TypeKind used to gate operator placement.

    Reference: TypeChecks.scala `TypeSig` — drives both CPU-fallback decisions
    and the generated supported-ops documentation.
    """

    def __init__(self, kinds: frozenset, note: str = "",
                 max_decimal_precision: int = 18):
        self.kinds = frozenset(kinds)
        self.note = note
        self.max_decimal_precision = max_decimal_precision

    def __add__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.kinds | other.kinds,
                       max_decimal_precision=max(self.max_decimal_precision,
                                                 other.max_decimal_precision))

    def supports(self, t: SqlType) -> Optional[str]:
        """None if supported, else a human-readable fallback reason."""
        if t.kind not in self.kinds:
            return f"{t} is not supported"
        if t.kind is TypeKind.DECIMAL and t.precision > self.max_decimal_precision:
            return (f"decimal precision {t.precision} exceeds supported "
                    f"maximum {self.max_decimal_precision}")
        if t.is_nested:
            for c in t.children:
                r = self.supports(c)
                if r is not None:
                    return f"nested: {r}"
        return None

    @staticmethod
    def of(*kinds: TypeKind) -> "TypeSig":
        return TypeSig(frozenset(kinds))


integral = TypeSig.of(TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64)
fp = TypeSig.of(TypeKind.FLOAT32, TypeKind.FLOAT64)
numeric = integral + fp + TypeSig.of(TypeKind.DECIMAL)
comparable = numeric + TypeSig.of(TypeKind.BOOLEAN, TypeKind.STRING, TypeKind.DATE,
                                  TypeKind.TIMESTAMP)
all_basic = comparable + TypeSig.of(TypeKind.NULL)
orderable = comparable
