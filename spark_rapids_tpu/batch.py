"""Columnar data plane: device columns and batches.

TPU-native analogue of the reference's column bridge
(reference: sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java —
Spark ColumnVector over cudf columns) re-designed for XLA:

- A ``DeviceColumn`` is a fixed-capacity JAX array plus a validity mask. The
  capacity is **static** (bucketed to powers of two) so that every operator
  compiles once per bucket instead of once per row count — cudf kernels accept
  any shape, XLA wants static shapes; this bucketed-padding scheme is the
  central architectural divergence called out in SURVEY.md §7.
- ``num_rows`` is a traced scalar: rows in ``[num_rows, capacity)`` are padding
  and always invalid. Filters clear validity instead of compacting, so a whole
  scan→project→filter→aggregate stage fuses into one XLA computation with no
  host round-trips; compaction happens only at exchange boundaries.
- Strings are fixed-width padded UTF-8 byte matrices ``uint8[cap, max_len]``
  with a separate length vector (rectangular data for the VPU; see types.py).

Host interchange is Arrow (pyarrow) — the same interchange layer the
reference uses between the JVM and Python workers (GpuArrowEvalPythonExec).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

import flax.struct

from . import types as T
from .types import SqlType, TypeKind

MIN_CAPACITY = 128  # one TPU lane row


class StringOverflowError(ValueError):
    """A string exceeded its column's device max_len byte budget."""


class CapacityError(ValueError):
    """A fixed device budget (array max_elems, …) was exceeded; the result
    would be silently truncated, so the host boundary fails loud instead."""


def bucket_capacity(n: int, minimum: int = MIN_CAPACITY) -> int:
    """Round a row count up to the compile-cache bucket (next power of two)."""
    if n <= minimum:
        return minimum
    return 1 << (n - 1).bit_length()


@dataclass(frozen=True)
class Field:
    name: str
    dtype: SqlType
    nullable: bool = True


@dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    def __init__(self, fields: Sequence[Field]):
        object.__setattr__(self, "fields", tuple(fields))

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, i):
        return self.fields[i]

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(f"column {name!r} not in schema {self.names}")

    def field(self, name: str) -> Field:
        return self.fields[self.index_of(name)]

    def __str__(self):
        inner = ", ".join(f"{f.name}: {f.dtype}" for f in self.fields)
        return f"Schema({inner})"


@flax.struct.dataclass
class DeviceColumn:
    """One column resident in HBM: payload + validity (+ lengths for strings).

    STRUCT columns (reference carries structs through every operator —
    GpuColumnVector.java struct paths, complexTypeExtractors.scala:355)
    hold a TUPLE of child DeviceColumns in ``data`` — one lane-set per leaf
    field — plus the struct-level validity lane. The tuple is a pytree
    node, so struct columns trace through jit like any other column;
    generic primitives (gather/compact/concat) recurse into the children.
    """

    data: jax.Array                 # [cap] | [cap, max_len] uint8 strings
    #                               | int32[cap] codes (dict strings)
    #                               | tuple[DeviceColumn, ...] for structs
    validity: jax.Array             # bool[cap]; False beyond num_rows
    lengths: Optional[jax.Array] = None   # int32[cap], strings/arrays/maps
    dtype: SqlType = flax.struct.field(pytree_node=False, default=T.INT32)
    # maps only: the VALUES matrix [cap, max_elems] (``data`` holds keys).
    # A map column is two zipped fixed-budget arrays sharing one lengths
    # vector — the TPU answer to cudf's LIST<STRUCT<K,V>> layout.
    data2: Optional[jax.Array] = None
    # dictionary-encoded STRING columns only (dictenc.py): sorted-distinct
    # padded entries + per-entry byte lengths; ``data`` holds the codes
    # and ``lengths`` is None (rematerialized at decode). Invariants —
    # including why code order == string order — live in dictenc.py.
    dict_data: Optional[jax.Array] = None     # uint8[card, max_len]
    dict_lengths: Optional[jax.Array] = None  # int32[card]

    @property
    def capacity(self) -> int:
        # validity is always a flat [cap] lane, even for structs where
        # ``data`` is a tuple of child columns
        return self.validity.shape[0]

    @property
    def is_struct(self) -> bool:
        return isinstance(self.data, tuple)

    @property
    def is_dict(self) -> bool:
        return self.dict_data is not None

    @property
    def struct_fields(self) -> Tuple["DeviceColumn", ...]:
        return self.data

    def with_validity(self, validity: jax.Array) -> "DeviceColumn":
        return self.replace(validity=validity)

    def size_bytes(self) -> int:
        if self.is_struct:
            return (sum(c.size_bytes() for c in self.data)
                    + self.validity.size)
        n = self.data.size * self.data.dtype.itemsize + self.validity.size
        if self.lengths is not None:
            n += self.lengths.size * 4
        if self.data2 is not None:
            n += self.data2.size * self.data2.dtype.itemsize
        if self.dict_data is not None:
            n += self.dict_data.size + self.dict_lengths.size * 4
        return n


@flax.struct.dataclass
class ColumnarBatch:
    """A batch of columns with a traced row count and static capacity."""

    columns: Tuple[DeviceColumn, ...]
    num_rows: jax.Array  # int32 scalar

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def row_mask(self) -> jax.Array:
        """bool[cap] — True for live (within num_rows) positions."""
        cap = self.capacity
        return jnp.arange(cap, dtype=jnp.int32) < self.num_rows

    def size_bytes(self) -> int:
        return sum(c.size_bytes() for c in self.columns)


# ---------------------------------------------------------------------------
# Construction helpers
# ---------------------------------------------------------------------------

def make_column(values: np.ndarray, validity: np.ndarray, dtype: SqlType,
                capacity: int, lengths: Optional[np.ndarray] = None,
                values2: Optional[np.ndarray] = None) -> DeviceColumn:
    """Pad host arrays to capacity and move to device.

    For strings, pass the exact byte ``lengths``; deriving them from the
    zero-padded matrix would drop trailing NUL bytes.
    """
    n = values.shape[0]
    if n > capacity:
        raise ValueError(f"{n} rows exceed capacity {capacity}")
    if dtype.kind is TypeKind.STRING:
        ml = dtype.max_len
        padded = np.zeros((capacity, ml), dtype=np.uint8)
        padded[:n] = values
        plen = np.zeros(capacity, dtype=np.int32)
        plen[:n] = values_lengths(values) if lengths is None else lengths
        val = np.zeros(capacity, dtype=bool)
        val[:n] = validity
        return DeviceColumn(jnp.asarray(padded), jnp.asarray(val),
                            jnp.asarray(plen), dtype)
    if dtype.kind in (TypeKind.ARRAY, TypeKind.MAP):
        padded = np.zeros((capacity,) + values.shape[1:], dtype=values.dtype)
        padded[:n] = values
        plen = np.zeros(capacity, dtype=np.int32)
        plen[:n] = lengths
        val = np.zeros(capacity, dtype=bool)
        val[:n] = validity
        p2 = None
        if values2 is not None:
            p2 = np.zeros((capacity,) + values2.shape[1:],
                          dtype=values2.dtype)
            p2[:n] = values2
            p2 = jnp.asarray(p2)
        return DeviceColumn(jnp.asarray(padded), jnp.asarray(val),
                            jnp.asarray(plen), dtype, p2)
    if values.ndim > 1:     # decimal128 limb matrices
        padded = np.zeros((capacity,) + values.shape[1:], dtype=values.dtype)
        padded[:n] = values
        val = np.zeros(capacity, dtype=bool)
        val[:n] = validity
        return DeviceColumn(jnp.asarray(padded), jnp.asarray(val), None,
                            dtype)
    padded = np.zeros(capacity, dtype=T.numpy_dtype(dtype))
    padded[:n] = values
    val = np.zeros(capacity, dtype=bool)
    val[:n] = validity
    return DeviceColumn(jnp.asarray(padded), jnp.asarray(val), None, dtype)


def values_lengths(byte_matrix: np.ndarray) -> np.ndarray:
    """Recover string byte lengths from a zero-padded byte matrix."""
    nz = byte_matrix != 0
    return (byte_matrix.shape[1] - np.argmax(nz[:, ::-1], axis=1)) * nz.any(axis=1)


def _strings_to_matrix(arr: pa.Array, max_len: int,
                       truncate: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Encode an arrow string array into (byte_matrix, lengths).

    Vectorized over the arrow offsets/data buffers (no per-row Python on the
    scan hot path). Raises on strings longer than ``max_len`` unless
    ``truncate`` — silent truncation is data corruption; the planner
    re-buckets max_len or falls back to CPU instead (config.STRING_MAX_BYTES).
    """
    n = len(arr)
    if n == 0:
        return np.zeros((0, max_len), np.uint8), np.zeros(0, np.int32)
    if arr.type == pa.large_string():
        arr = arr.cast(pa.string())
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], dtype=np.int32, count=n + 1,
                            offset=arr.offset * 4).astype(np.int64)
    data = (np.frombuffer(bufs[2], dtype=np.uint8)
            if bufs[2] is not None else np.zeros(0, np.uint8))
    lengths = np.diff(offsets).astype(np.int32)
    if arr.null_count:
        valid = np.asarray(arr.is_valid())
        lengths = np.where(valid, lengths, 0)
    over = lengths > max_len
    if over.any():
        if not truncate:
            raise StringOverflowError(
                f"string of {int(lengths.max())} bytes exceeds device "
                f"max_len {max_len}; re-bucket the column or fall back to CPU")
        lengths = np.minimum(lengths, max_len)
    col_idx = np.arange(max_len, dtype=np.int64)[None, :]
    mask = col_idx < lengths[:, None]
    if data.size:
        gather = np.minimum(offsets[:-1, None] + col_idx, data.size - 1)
        out = np.where(mask, data[gather], 0).astype(np.uint8)
    else:
        out = np.zeros((n, max_len), np.uint8)
    if over.any():
        # repair rows whose truncation split a multi-byte codepoint: find the
        # start of the trailing char; drop it only if its sequence is cut
        for i in np.nonzero(over)[0]:
            row = out[i]
            ln = int(lengths[i])
            p = ln - 1
            while p >= 0 and (row[p] & 0xC0) == 0x80:
                p -= 1
            if p >= 0:
                lead = int(row[p])
                char_len = 1 if lead < 0x80 else \
                    2 if lead < 0xE0 else 3 if lead < 0xF0 else 4
                if p + char_len > ln:  # incomplete sequence — drop it
                    out[i, p:] = 0
                    lengths[i] = p
    return out, lengths


def _scalar_storage(arr: pa.Array, dtype: SqlType,
                    validity: np.ndarray) -> np.ndarray:
    """Arrow scalar array → numpy storage values (the device encoding):
    decimal → unscaled int64, date → epoch days, timestamp → epoch micros,
    numerics/bools pass through. Shared by top-level columns and
    array/map ELEMENT buffers so nested data gets identical encoding."""
    n = len(arr)
    if dtype.kind is TypeKind.DECIMAL:
        # a numpy view of Arrow's 16-byte values: the low word up to 18
        # digits, the four 32-bit limbs above (decimal128.py)
        from .expressions.decimal128 import arrow_decimal_storage
        return arrow_decimal_storage(arr, dtype.precision, validity)
    if dtype.kind is TypeKind.TIMESTAMP:
        np_vals = np.zeros(n, dtype=np.int64)
        tmp = arr.cast(pa.timestamp("us")).to_numpy(zero_copy_only=False)
        np_vals[validity] = tmp[validity].astype(
            "datetime64[us]").astype(np.int64)
        return np_vals
    if dtype.kind is TypeKind.DATE:
        np_vals = np.zeros(n, dtype=np.int32)
        tmp = arr.to_numpy(zero_copy_only=False)
        np_vals[validity] = np.asarray(
            tmp[validity], dtype="datetime64[D]").astype(np.int32)
        return np_vals
    # Null slots become 0 in the payload (validity carries nullness);
    # keeps integer dtypes intact and avoids NaN poisoning reductions.
    filled = arr.fill_null(False) if dtype.kind is TypeKind.BOOLEAN \
        else arr.fill_null(0) if arr.null_count else arr
    return np.asarray(filled.to_numpy(zero_copy_only=False),
                      dtype=T.numpy_dtype(dtype))


def column_from_arrow(arr: pa.Array, dtype: SqlType, capacity: int,
                      truncate_strings: bool = False,
                      name: str = "",
                      allow_dict: bool = True,
                      dict_conf: Optional[tuple] = None) -> DeviceColumn:
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr

    if pa.types.is_dictionary(arr.type):
        # RLE_DICTIONARY scan hand-off: keep the codes, build the byte
        # matrix once per DISTINCT value (dictenc.py). Nested positions
        # and over-threshold cardinalities decode to the padded path.
        if dtype.kind is TypeKind.STRING and allow_dict:
            from .dictenc import column_from_arrow_dictionary
            col = column_from_arrow_dictionary(arr, dtype, capacity,
                                               truncate_strings, name,
                                               dict_conf)
            if col is not None:
                return col
        arr = arr.cast(arr.type.value_type)

    n = len(arr)
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
    else:
        validity = np.ones(n, dtype=bool)

    if dtype.kind is TypeKind.STRUCT:
        # one lane-set per leaf field + struct-level validity; a field of
        # a null struct is null (validity AND), struct-of-struct recurses
        pval = np.zeros(capacity, dtype=bool)
        pval[:n] = validity
        pval_dev = jnp.asarray(pval)
        kids = []
        for i, ct in enumerate(dtype.children):
            # struct leaf lanes stay plain: generic struct recursion
            # (gather/concat/serialize) does not carry dictionaries
            kid = column_from_arrow(arr.field(i), ct, capacity,
                                    truncate_strings, allow_dict=False)
            kids.append(kid.with_validity(kid.validity & pval_dev))
        return DeviceColumn(tuple(kids), pval_dev, None, dtype)

    if dtype.kind is TypeKind.STRING:
        mat, lengths = _strings_to_matrix(arr, dtype.max_len, truncate_strings)
        return make_column(mat, validity, dtype, capacity, lengths)

    if dtype.kind is TypeKind.ARRAY:
        # list column → fixed-budget matrix data[cap, max_elems] + lengths,
        # the same layout collect_list produces on-device (docstring at top).
        # String elements use a 3D byte tensor with per-element byte lengths
        # in data2 (split()'s output layout).
        elem_t = dtype.children[0]
        if elem_t.kind in (TypeKind.ARRAY, TypeKind.STRUCT, TypeKind.MAP):
            raise TypeError(
                f"array<{elem_t}> nested elements have no device layout; "
                f"the planner must fall back to CPU")
        me = dtype.max_len
        offsets = np.asarray(arr.offsets)
        counts = np.diff(offsets).astype(np.int32)
        counts = np.where(validity, counts, 0)
        if counts.size and int(counts.max()) > me:
            raise CapacityError(
                f"list of {int(counts.max())} elements exceeds the device "
                f"array budget of {me}; raise max_elems in the scan schema "
                f"or fall back to CPU")
        values = arr.values
        if values.null_count:
            raise TypeError(
                "arrays with null elements are outside the device subset "
                "(fixed-budget arrays hold non-null elements; CPU fallback)")
        col_idx = np.arange(me)[None, :]
        mask = col_idx < counts[:, None]
        start = offsets[:-1]
        src_idx = (start[:, None] + col_idx)[mask]
        if elem_t.kind is TypeKind.STRING:
            smat, slens = _strings_to_matrix(values, elem_t.max_len,
                                             truncate_strings)
            mat = np.zeros((n, me, elem_t.max_len), np.uint8)
            el_lens = np.zeros((n, me), np.int32)
            mat[mask] = smat[src_idx]
            el_lens[mask] = slens[src_idx]
            return make_column(mat, validity, dtype, capacity,
                               counts.astype(np.int32), values2=el_lens)
        flat = _scalar_storage(values, elem_t,
                               np.ones(len(values), dtype=bool))
        mat = np.zeros((n, me), dtype=flat.dtype)
        # rows are laid out consecutively in the flat values buffer; the
        # masked scatter below is the inverse of to_arrow's masked gather
        mat[mask] = flat[src_idx]
        return make_column(mat, validity, dtype, capacity,
                           counts.astype(np.int32))

    if dtype.kind is TypeKind.MAP:
        key_t, val_t = dtype.children
        for t in (key_t, val_t):
            if t.kind in (TypeKind.STRING, TypeKind.ARRAY, TypeKind.STRUCT,
                          TypeKind.MAP):
                raise TypeError(
                    f"map<{key_t},{val_t}> device layout is fixed-width "
                    f"scalars only; the planner must fall back to CPU")
        me = dtype.max_len
        offsets = np.asarray(arr.offsets)
        counts = np.diff(offsets).astype(np.int32)
        counts = np.where(validity, counts, 0)
        if counts.size and int(counts.max()) > me:
            raise CapacityError(
                f"map of {int(counts.max())} entries exceeds the device "
                f"budget of {me}")
        if arr.keys.null_count or arr.items.null_count:
            raise TypeError(
                "maps with null keys/values are outside the device subset "
                "(fixed-budget matrices hold non-null entries; CPU fallback)")
        keys = _scalar_storage(arr.keys, key_t,
                               np.ones(len(arr.keys), dtype=bool))
        items = _scalar_storage(arr.items, val_t,
                                np.ones(len(arr.items), dtype=bool))
        kmat = np.zeros((n, me), dtype=keys.dtype)
        vmat = np.zeros((n, me), dtype=items.dtype)
        col_idx = np.arange(me)[None, :]
        mask = col_idx < counts[:, None]
        src_idx = (offsets[:-1][:, None] + col_idx)[mask]
        kmat[mask] = keys[src_idx]
        vmat[mask] = items[src_idx]
        return make_column(kmat, validity, dtype, capacity,
                           counts.astype(np.int32), values2=vmat)

    return make_column(_scalar_storage(arr, dtype, validity), validity,
                       dtype, capacity)


def schema_from_arrow(schema: pa.Schema, string_max_len: int = 64) -> Schema:
    return Schema([Field(f.name, T.from_arrow(f.type, string_max_len), f.nullable)
                   for f in schema])


def from_arrow(table: pa.Table, capacity: Optional[int] = None,
               schema: Optional[Schema] = None,
               string_max_len: int = 64,
               truncate_strings: bool = False,
               dict_conf: Optional[tuple] = None
               ) -> Tuple[ColumnarBatch, Schema]:
    """Build a device batch from an Arrow table (the scan H2D boundary).

    Nullability is tightened from the DATA (null_count metadata, free in
    Arrow): a null-free column becomes non-nullable, which lets the
    aggregation fast path skip its validity payload lane and share one
    count lane across aggregates (the reference's readers track per-batch
    null counts the same way)."""
    if schema is None:
        schema = schema_from_arrow(table.schema, string_max_len)
        tight = []
        for i, f in enumerate(schema):
            nullable = f.nullable and table.column(i).null_count > 0
            tight.append(Field(f.name, f.dtype, nullable))
        schema = Schema(tight)
    n = table.num_rows
    cap = capacity or bucket_capacity(n)

    def convert(i):
        f = schema.fields[i]
        return column_from_arrow(table.column(i), f.dtype, cap,
                                 truncate_strings, name=f.name,
                                 dict_conf=dict_conf)

    cols: List[Optional[DeviceColumn]] = [None] * len(schema.fields)
    decimals = [i for i, f in enumerate(schema)
                if f.dtype.kind is TypeKind.DECIMAL]
    if decimals:
        # the decimal columns together, under a span of their own: Arrow's
        # 16-byte values to padded device columns (a child of the scan's
        # ``scan.h2d`` where a scan asked)
        from .trace import span
        with span("scan.h2d.decimal", kind="transfer", values=n,
                  columns=len(decimals),
                  bytes=sum(table.column(i).nbytes for i in decimals)):
            for i in decimals:
                cols[i] = convert(i)
    for i in range(len(cols)):
        if cols[i] is None:
            cols[i] = convert(i)
    return ColumnarBatch(tuple(cols), jnp.asarray(n, jnp.int32)), schema


def empty_column(dtype: SqlType, capacity: int = MIN_CAPACITY
                 ) -> DeviceColumn:
    validity = jnp.zeros(capacity, bool)
    if dtype.kind is TypeKind.STRUCT:
        kids = tuple(empty_column(c, capacity) for c in dtype.children)
        return DeviceColumn(kids, validity, None, dtype)
    if dtype.kind is TypeKind.STRING:
        return DeviceColumn(jnp.zeros((capacity, dtype.max_len), jnp.uint8),
                            validity, jnp.zeros(capacity, jnp.int32), dtype)
    return DeviceColumn(jnp.zeros(capacity, dtype.storage_dtype),
                        validity, None, dtype)


def empty_batch(schema: Schema, capacity: int = MIN_CAPACITY) -> ColumnarBatch:
    cols = [empty_column(f.dtype, capacity) for f in schema]
    return ColumnarBatch(tuple(cols), jnp.asarray(0, jnp.int32))


# ---------------------------------------------------------------------------
# Device -> host (the C2R / collect boundary)
# ---------------------------------------------------------------------------

def _storage_to_arrow(flat: np.ndarray, dtype: SqlType) -> pa.Array:
    """Inverse of _scalar_storage for non-null element buffers."""
    if dtype.kind is TypeKind.DECIMAL:
        from .expressions.decimal128 import storage_to_arrow_decimal
        return storage_to_arrow_decimal(
            flat, T.to_arrow(dtype), np.ones(flat.shape[0], bool))
    if dtype.kind is TypeKind.TIMESTAMP:
        return pa.array(flat.astype("datetime64[us]"),
                        type=T.to_arrow(dtype))
    if dtype.kind is TypeKind.DATE:
        return pa.array(flat.astype("datetime64[D]"),
                        type=T.to_arrow(dtype))
    return pa.array(flat, type=T.to_arrow(dtype))


def to_arrow(batch: ColumnarBatch, schema: Schema) -> pa.Table:
    n = int(batch.num_rows)
    arrays = [_col_to_arrow(col, f.dtype, f.name, n)
              for col, f in zip(batch.columns, schema)]
    return pa.table(arrays, names=schema.names)


def _col_to_arrow(col: DeviceColumn, dtype: SqlType, name: str,
                  n: int) -> pa.Array:
    """One device column → one arrow array (recursive for structs)."""
    validity = np.asarray(col.validity[:n])
    if dtype.kind is TypeKind.NULL:
        return pa.nulls(n)
    if dtype.kind is TypeKind.STRUCT:
        names = dtype.names or tuple(
            f"f{i}" for i in range(len(dtype.children)))
        kids = [_col_to_arrow(c, ct, f"{name}.{nm}", n)
                for c, ct, nm in zip(col.struct_fields,
                                     dtype.children, names)]
        return pa.StructArray.from_arrays(
            kids, names=list(names),
            mask=pa.array(~validity) if not validity.all() else None)
    if dtype.kind is TypeKind.STRING:
        if col.is_dict:
            # lazy decode at the collect boundary: gather the dictionary
            # on HOST (codes + small dict came down; bytes never lived
            # per-row on device)
            dmat = np.asarray(col.dict_data)
            dlens = np.asarray(col.dict_lengths)
            codes = np.clip(np.asarray(col.data[:n]), 0,
                            max(dmat.shape[0] - 1, 0))
            mat = dmat[codes] if dmat.shape[0] else \
                np.zeros((n, dtype.max_len), np.uint8)
            lens = np.where(validity,
                            dlens[codes] if dlens.shape[0]
                            else 0, 0).astype(np.int32)
        else:
            mat = np.asarray(col.data[:n])
            lens = np.where(validity, np.asarray(col.lengths[:n]), 0)
        # vectorized: row-major masked bytes ARE the arrow data buffer
        mask = np.arange(mat.shape[1])[None, :] < lens[:, None]
        flat = np.ascontiguousarray(mat)[mask]
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        return pa.StringArray.from_buffers(
            n, pa.py_buffer(offsets.tobytes()),
            pa.py_buffer(flat.tobytes()),
            pa.py_buffer(np.packbits(validity, bitorder="little").tobytes())
            if not validity.all() else None)
    if dtype.kind is TypeKind.ARRAY:
        mat = np.asarray(col.data[:n])
        counts = np.where(validity, np.asarray(col.lengths[:n]), 0)
        if counts.size and int(counts.max()) > mat.shape[1]:
            raise CapacityError(
                f"array column '{name}' holds a list of "
                f"{int(counts.max())} elements but the device budget is "
                f"{mat.shape[1]}; raise max_elems (collect_list/set) or "
                f"fall back to CPU")
        mask2 = np.arange(mat.shape[1])[None, :] < counts[:, None]
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(counts, out=offsets[1:])
        elem_t = T.to_arrow(dtype.children[0])
        if dtype.children[0].kind is TypeKind.STRING:
            # 3D byte tensor [n, me, max_len]; per-element byte lengths
            # ride in data2
            el_lens = np.asarray(col.data2[:n])
            live_el = mat[mask2]                     # [k, max_len]
            live_lens = el_lens[mask2]
            bmask = np.arange(mat.shape[2])[None, :] < live_lens[:, None]
            str_offsets = np.zeros(len(live_lens) + 1, np.int32)
            np.cumsum(live_lens, out=str_offsets[1:])
            values = pa.StringArray.from_buffers(
                len(live_lens),
                pa.py_buffer(str_offsets.tobytes()),
                pa.py_buffer(np.ascontiguousarray(live_el)[bmask]
                             .tobytes()))
        else:
            values = _storage_to_arrow(mat[mask2],
                                       dtype.children[0])
        la = pa.ListArray.from_arrays(pa.array(offsets, pa.int32()),
                                      values)
        if not validity.all():
            # rebuild with a null mask (from_arrays has no mask param
            # for offsets-based construction)
            la = pa.ListArray.from_arrays(
                pa.array(offsets, pa.int32()), values)
            pl = la.to_pylist()
            la = pa.array([v if ok else None
                           for v, ok in zip(pl, validity)],
                          type=pa.list_(elem_t))
        return la
    if dtype.kind is TypeKind.MAP:
        kmat = np.asarray(col.data[:n])
        vmat = np.asarray(col.data2[:n])
        counts = np.where(validity, np.asarray(col.lengths[:n]), 0)
        mask2 = np.arange(kmat.shape[1])[None, :] < counts[:, None]
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(counts, out=offsets[1:])
        key_t, val_t = dtype.children
        ma = pa.MapArray.from_arrays(
            pa.array(offsets, pa.int32()),
            _storage_to_arrow(kmat[mask2], key_t),
            _storage_to_arrow(vmat[mask2], val_t))
        if not validity.all():
            pl = ma.to_pylist()
            ma = pa.array([v if ok else None
                           for v, ok in zip(pl, validity)],
                          type=pa.map_(T.to_arrow(key_t),
                                       T.to_arrow(val_t)))
        return ma
    data = np.asarray(col.data[:n])
    if dtype.kind is TypeKind.DECIMAL:
        from .expressions.decimal128 import storage_to_arrow_decimal
        return storage_to_arrow_decimal(data, T.to_arrow(dtype), validity)
    if dtype.kind is TypeKind.TIMESTAMP:
        return pa.array(data.astype("datetime64[us]"),
                        type=T.to_arrow(dtype), mask=~validity)
    if dtype.kind is TypeKind.DATE:
        return pa.array(data.astype("datetime64[D]"),
                        type=T.to_arrow(dtype), mask=~validity)
    return pa.array(data, type=T.to_arrow(dtype), mask=~validity)


def to_pandas(batch: ColumnarBatch, schema: Schema):
    return to_arrow(batch, schema).to_pandas()
