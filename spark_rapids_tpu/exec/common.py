"""Shared device kernels used by every operator.

These are the TPU-native replacements for libcudf's table primitives
(reference contract in SURVEY.md §2.9: gather 13 call sites, filter 77,
concatenate 11, orderBy 4, partition 5). Everything here is shape-static and
jit-traceable: row counts are traced scalars, capacities are static ints, so
operator pipelines fuse into single XLA computations.

Key primitives:
- ``compact``     — stable scatter-compaction of kept rows (cudf filter).
- ``gather``      — row gather with out-of-bounds-as-null (cudf gather map).
- ``concat``      — batch concatenation at a given capacity (cudf concatenate).
- ``sort_keys``   — rank-preserving normalization of any SQL column into
                    uint-comparable operands for ``lax.sort`` (cudf orderBy).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn, Field, Schema
from ..types import SqlType, TypeKind


def jit_named(name: str, fun, key: Optional[str] = None, owner=None,
              **jit_kwargs):
    """``jax.jit(fun)`` as the program ``jit_<name>``: the one door through
    which ``exec/``, ``io/``, ``shuffle/`` and ``memory/`` jit, so that a
    device trace, a lowering's ``fun`` and the compile cache name a program
    ``<Exec>_<role>`` and not ``_lambda_`` or ``kernel``. (No
    ``functools.wraps``: JAX names a program after what ``inspect.unwrap``
    finds.)

    JAX keys its trace, its lowering and its loaded executable on the
    function object, so WHICH object this returns decides what a rebuilt
    exec pays:

    - with a ``key`` it is the process's one entry for ``(name, key, jit
      arguments)`` in ``compile_cache.program_table()``, made from ``fun``
      by the first caller to state that key. Every later caller gets the
      same callable and its calls take JAX's C++ fast path: no trace, no
      lowering, no executable load, and ``fun`` itself is dropped, its
      Python body never run. ``key`` therefore says everything ``fun``
      reads when it is traced other than its arguments (``program_key``;
      shapes, dtypes and pytree structure are JAX's own key), and ``fun``
      closes over those compile-time values only (``KernelPrograms``): the
      table keeps it for the life of the process. ``owner``, where given,
      counts what the table said in its ``program_hits`` /
      ``program_misses``, for its operator span.
    - a function already called ``name`` (a module-level one such as
      ``slice_batch``) is jitted as it is: JAX finds its trace and its
      executables by the function, across wrappers.
    - anything else with no key goes through a wrapper of that name, which
      like the lambda, closure or bound method it stands for is a new
      function, traced anew, in every exec instance (counted ``unkeyed``).
    """
    def build():
        if getattr(fun, "__name__", None) == name:
            return jax.jit(fun, **jit_kwargs)

        def named(*args, **kwargs):
            return fun(*args, **kwargs)
        named.__name__ = named.__qualname__ = name
        return jax.jit(named, **jit_kwargs)

    from ..compile_cache import program_table
    if key is not None:
        fn, hit = program_table().get_or_build(
            (name, key, repr(sorted(jit_kwargs.items()))), build)
        if owner is not None:
            tally = "program_hits" if hit else "program_misses"
            setattr(owner, tally, getattr(owner, tally, 0) + 1)
        return fn
    if getattr(fun, "__name__", None) != name:
        program_table().note_unkeyed()
    return build()


#: the ``key`` of a kernel that reads nothing but its arguments
PURE = "pure"


def dec128_role(role: str, types) -> str:
    """``role`` + "Dec128" where one of ``types`` is a decimal past 18
    digits: a program that computes on limb matrices says so in its name
    (``jit_ProjectExec_projectDec128``), and a device trace tells it from
    its int64 sibling."""
    from ..expressions.decimal128 import is_dec128
    return role + "Dec128" if any(is_dec128(t) for t in types) else role


class _Unkeyable(Exception):
    """A part of a program key that cannot be written down."""


def _key_doc(v):
    """``v`` as a JSON-able document that differs wherever a kernel reading
    ``v`` could trace differently. The plan dialect's ``encode_value``
    (expressions field by field with literal VALUES, refusing one whose
    fields do not state it; ``SqlType``, ``Schema``, ``SortOrder``, the
    enums execs hold) plus what only execs hold: a ``Field``, the
    evaluation context's flags, and objects that state their own
    ``program_key()``."""
    from ..expressions.base import EvalContext
    from ..server.plandoc import encode_value
    if isinstance(v, EvalContext):
        if v.errors is not None or v.batch_seed is not None:
            raise _Unkeyable("an evaluation context with trace-time state")
        return {"$ctx": [v.ansi]}
    if isinstance(v, Field):
        return {"$field": [v.name, encode_value(v.dtype), v.nullable]}
    if isinstance(v, (list, tuple)):
        return {"$l": [_key_doc(x) for x in v]}
    own = getattr(v, "program_key", None)
    if own is not None:
        parts = own()
        if parts is None:
            raise _Unkeyable(type(v).__name__)
        return {"$k": [type(v).__name__, _key_doc(parts)]}
    return encode_value(v)


def program_key(*parts) -> Optional[str]:
    """The digest ``jit_named`` takes as ``key``: of everything a kernel
    reads at trace time besides its arguments, stated by its exec. None
    where a part has no encoding (an expression over a Python callable, a
    partitioning that holds sampled bounds): that exec keeps a program of
    its own. Two execs with equal keys MUST trace to equal jaxprs; a key
    may say too much, never too little."""
    from ..plan.plancache import _hash
    from ..server.plandoc import PlanDecodeError
    try:
        return _hash([_key_doc(p) for p in parts])
    except (_Unkeyable, PlanDecodeError):
        return None


class KernelPrograms:
    """The programs of one exec (or sorter, writer, ...) ``owner`` whose
    kernels read the fields ``reads`` of it, and ``ctx``.

    ``jit(role, kernel)`` jits ``kernel(stand_in, *args)`` as
    ``<Owner>_<role>``, where ``stand_in`` is an object of the owner's
    class that has those fields and NOTHING else of the owner: no
    children, no metrics, no runtime cache, so the table entry made from it
    pins no query, and a kernel that reads a field its exec did not state
    fails when it is traced instead of sharing a program under too small a
    key. The key is the digest of the same fields plus ``also`` (what a
    kernel closes over besides the owner) plus, for an exec, its
    children's schemas and its own."""

    def __init__(self, owner, reads: Sequence[str], also: Sequence = ()):
        self._owner = owner
        cls = type(owner)
        self.stand_in = cls.__new__(cls)
        fields = [("ctx", owner.ctx)] if hasattr(owner, "ctx") else []
        fields += [(f, getattr(owner, f)) for f in reads]
        for f, v in fields:
            setattr(self.stand_in, f, v)
        schemas = []
        if hasattr(owner, "children"):
            schemas = [c.output_schema for c in owner.children] \
                + [owner.output_schema]
        self.key = program_key(f"{cls.__module__}.{cls.__qualname__}",
                               [[f, v] for f, v in fields], schemas,
                               list(also))

    def jit(self, role: str, kernel, **jit_kwargs):
        return jit_named(f"{type(self._owner).__name__}_{role}",
                         functools.partial(kernel, self.stand_in),
                         key=self.key, owner=self._owner, **jit_kwargs)


# ---------------------------------------------------------------------------
# Gather / compact / concat
# ---------------------------------------------------------------------------

def _and_validity_deep(col: DeviceColumn, mask: jax.Array) -> DeviceColumn:
    """AND ``mask`` into a column's validity (struct children included, so
    padded/OOB rows read as null at every nesting level)."""
    if col.is_struct:
        kids = tuple(_and_validity_deep(c, mask) for c in col.data)
        return col.replace(data=kids, validity=col.validity & mask)
    return col.replace(validity=col.validity & mask)


def gather_column(col: DeviceColumn, indices: jax.Array,
                  row_valid: Optional[jax.Array] = None) -> DeviceColumn:
    """Gather rows of ``col`` at ``indices`` (int32[out_cap]).

    ``row_valid`` marks which output slots hold a real gathered row; slots
    outside it become null (the cudf gather-map convention where an OOB index
    yields null — used by outer joins).
    """
    if col.is_struct:
        return gather_columns([col], indices, row_valid)[0]
    idx = jnp.clip(indices, 0, col.capacity - 1)
    data = jnp.take(col.data, idx, axis=0)
    validity = jnp.take(col.validity, idx, axis=0)
    lengths = jnp.take(col.lengths, idx, axis=0) if col.lengths is not None else None
    data2 = jnp.take(col.data2, idx, axis=0) if col.data2 is not None else None
    if row_valid is not None:
        validity = validity & row_valid
    # dict strings: the CODES are the row lane; the dictionary rides along
    # untouched (its leading dim is card, not cap)
    return DeviceColumn(data, validity, lengths, col.dtype, data2,
                        col.dict_data, col.dict_lengths)


def _batched_takes(arrays: Sequence[jax.Array], idx: jax.Array
                   ) -> List[jax.Array]:
    """Gather many same-length arrays at ONE index set with as few device
    gathers as possible: same-dtype 1-D arrays stack into a [n, m] matrix
    for a single row-gather (a round-3 chip profile had a 4M-row gather
    cost the same whatever the row width, and sibling gathers NOT fuse; on
    this installation's chip: not measured)."""
    from collections import defaultdict
    byd = defaultdict(list)
    for i, a in enumerate(arrays):
        byd[(a.dtype, a.ndim)].append(i)
    out: List[Optional[jax.Array]] = [None] * len(arrays)
    for (dt, nd), idxs in byd.items():
        if nd != 1 or len(idxs) == 1:
            for i in idxs:
                out[i] = jnp.take(arrays[i], idx, axis=0)
        else:
            m = jnp.stack([arrays[i] for i in idxs], axis=1)
            g = jnp.take(m, idx, axis=0)
            for j, i in enumerate(idxs):
                out[i] = g[:, j]
    return out


def gather_columns(cols: Sequence[DeviceColumn], indices: jax.Array,
                   row_valid: Optional[jax.Array] = None
                   ) -> List[DeviceColumn]:
    """Gather MANY columns at one index set, batching the underlying takes
    (data lanes by dtype, all validity lanes together, lengths with other
    int32 lanes)."""
    if not cols:
        return []
    cap = cols[0].capacity
    idx = jnp.clip(indices, 0, cap - 1)
    # dictionaries are NOT row lanes — strip them before the flatten so
    # they are never row-gathered, reattach after (codes gather like any
    # int32 lane)
    dicts = [(c.dict_data, c.dict_lengths)
             if not c.is_struct and c.dict_data is not None else None
             for c in cols]
    stripped = [c.replace(dict_data=None, dict_lengths=None)
                if d is not None else c for c, d in zip(cols, dicts)]
    # every array lane (incl. struct leaf lanes — DeviceColumn is a
    # pytree and struct children are pytree nodes) flattens into one
    # batched-take set; unflatten restores the column structure
    leaves, treedef = jax.tree_util.tree_flatten(list(stripped))
    taken = _batched_takes(leaves, idx)
    out = list(jax.tree_util.tree_unflatten(treedef, taken))
    for i, d in enumerate(dicts):
        if d is not None:
            out[i] = out[i].replace(dict_data=d[0], dict_lengths=d[1])
    if row_valid is not None:
        out = [_and_validity_deep(c, row_valid) for c in out]
    return list(out)


def gather(batch: ColumnarBatch, indices: jax.Array, num_rows: jax.Array,
           row_valid: Optional[jax.Array] = None) -> ColumnarBatch:
    cols = tuple(gather_columns(batch.columns, indices, row_valid))
    return ColumnarBatch(cols, jnp.asarray(num_rows, jnp.int32))


def compaction_indices(keep: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Map a keep-mask to (gather_indices, kept_count).

    Stable: kept rows retain relative order. A stable sort on the drop
    flag, not the classic cumsum-scatter: sorts and gathers are the
    primitives this engine leans on everywhere else.
    """
    indices = lex_sort_permutation([(~keep).astype(jnp.uint8)])
    return indices, jnp.sum(keep.astype(jnp.int32))


def compact(batch: ColumnarBatch, keep: jax.Array) -> ColumnarBatch:
    """Remove rows where ``keep`` is False (cudf ``Table.filter``)."""
    keep = keep & batch.row_mask()
    indices, count = compaction_indices(keep)
    live = jnp.arange(batch.capacity, dtype=jnp.int32) < count
    return gather(batch, indices, count, live)


def concat_columns(cols: Sequence[DeviceColumn], counts: Sequence[jax.Array],
                   capacity: int) -> DeviceColumn:
    """Concatenate columns into one of ``capacity`` rows.

    Rows of piece i land at offset sum(counts[:i]); done with one scatter per
    piece. Counts are traced, so offsets are traced too.
    """
    first = cols[0]
    if any(not c.is_struct and c.dict_data is not None for c in cols):
        shared = (not first.is_struct and first.dict_data is not None
                  and all(c.dict_data is first.dict_data for c in cols))
        if shared:
            # all pieces share ONE dictionary object (sliced from one
            # batch, or pre-unified by dictenc.unify_dict_batches): the
            # codes concatenate like a plain int32 lane
            plain = [c.replace(dict_data=None, dict_lengths=None)
                     for c in cols]
            out = concat_columns(plain, counts, capacity)
            return out.replace(dict_data=first.dict_data,
                               dict_lengths=first.dict_lengths)
        # distinct per-piece dictionaries under tracing: decode (one
        # gather each) and concatenate the padded form — callers that can
        # run eagerly unify first and keep the encoding
        from ..dictenc import decode_column
        cols = [decode_column(c) if not c.is_struct else c for c in cols]
        first = cols[0]
    if first.is_struct:
        kids = tuple(
            concat_columns([c.data[j] for c in cols], counts, capacity)
            for j in range(len(first.data)))
        validity = jnp.zeros(capacity, bool)
        offset = jnp.asarray(0, jnp.int32)
        for col, n in zip(cols, counts):
            src = jnp.arange(col.capacity, dtype=jnp.int32)
            dest = jnp.where(src < n, src + offset, capacity)
            validity = validity.at[dest].set(col.validity, mode="drop")
            offset = offset + jnp.asarray(n, jnp.int32)
        return DeviceColumn(kids, validity, None, first.dtype)
    is_var = first.lengths is not None     # strings / arrays / maps
    if first.data.ndim > 1:
        data = jnp.zeros((capacity,) + first.data.shape[1:],
                         first.data.dtype)
    else:
        data = jnp.zeros(capacity, first.data.dtype)
    lengths = jnp.zeros(capacity, jnp.int32) if is_var else None
    data2 = None
    if first.data2 is not None:
        data2 = jnp.zeros((capacity,) + first.data2.shape[1:],
                          first.data2.dtype)
    validity = jnp.zeros(capacity, bool)
    offset = jnp.asarray(0, jnp.int32)
    for col, n in zip(cols, counts):
        cap_i = col.capacity
        src = jnp.arange(cap_i, dtype=jnp.int32)
        live = src < n
        dest = jnp.where(live, src + offset, capacity)
        data = data.at[dest].set(col.data, mode="drop")
        validity = validity.at[dest].set(col.validity, mode="drop")
        if is_var:
            lengths = lengths.at[dest].set(col.lengths, mode="drop")
        if data2 is not None:
            data2 = data2.at[dest].set(col.data2, mode="drop")
        offset = offset + jnp.asarray(n, jnp.int32)
    return DeviceColumn(data, validity, lengths, first.dtype, data2)


def concat_batches(batches: Sequence[ColumnarBatch], capacity: int) -> ColumnarBatch:
    """cudf ``Table.concatenate`` — the coalesce kernel."""
    counts = [b.num_rows for b in batches]
    ncols = batches[0].num_columns
    cols = tuple(
        concat_columns([b.columns[i] for b in batches], counts, capacity)
        for i in range(ncols))
    total = sum(jnp.asarray(c, jnp.int32) for c in counts)
    return ColumnarBatch(cols, jnp.asarray(total, jnp.int32))


def concat_batches_encoded(batches: Sequence[ColumnarBatch],
                           capacity: int) -> ColumnarBatch:
    """``concat_batches`` at an EAGER boundary (a coalesce, an aggregate's
    merge, a window's input): string columns that every piece carries as
    dictionary codes are put over one dictionary first (equal
    dictionaries, the usual case of columns from one build side, share
    theirs), so the result keeps its codes and a sort above it pays one
    lane a key instead of ``max_len/8 + 1``. Never under tracing."""
    from ..dictenc import unify_dict_batches
    return concat_batches(unify_dict_batches(batches), capacity)


def slice_batch(batch: ColumnarBatch, start: jax.Array, count: jax.Array,
                capacity: Optional[int] = None) -> ColumnarBatch:
    """Rows [start, start+count) as a new batch (cudf Table slice)."""
    cap = capacity or batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int32) + jnp.asarray(start, jnp.int32)
    n = jnp.minimum(jnp.asarray(count, jnp.int32),
                    jnp.maximum(batch.num_rows - start, 0))
    live = jnp.arange(cap, dtype=jnp.int32) < n
    return gather(batch, idx, n, live)


def cut_to_rows(batch: ColumnarBatch, rows: int) -> ColumnarBatch:
    """``batch``, whose ``rows`` rows (a count the HOST holds) are compact
    from 0, at the capacity bucket of those rows: what runs above it pays
    for slots, not rows. As it is where that is its capacity already."""
    from ..batch import bucket_capacity
    cap = bucket_capacity(max(rows, 1))
    if cap >= batch.capacity:
        return batch
    return jit_named("slice_batch", slice_batch, static_argnums=3)(
        batch, jnp.int32(0), jnp.int32(rows), cap)


# ---------------------------------------------------------------------------
# Sort-key normalization (cudf orderBy contract)
# ---------------------------------------------------------------------------

def _float_orderable(x: jax.Array, bits) -> jax.Array:
    """IEEE754 total order as unsigned ints; NaN sorts greatest (Spark)."""
    u = x.view(bits.dtype)
    sign = bits.dtype.type(1) << (bits.dtype.itemsize * 8 - 1)
    flipped = jnp.where(u & sign != 0, ~u, u | sign)
    nan = jnp.isnan(x)
    return jnp.where(nan, ~bits.dtype.type(0), flipped)


def orderable_words(col: DeviceColumn) -> List[jax.Array]:
    """Normalize a column into unsigned arrays whose lexicographic order is
    the column's SQL ascending order. Strings produce several word operands."""
    d = col.dtype
    k = d.kind
    if k is TypeKind.STRUCT:
        raise TypeError("struct sort/partition keys have no device order "
                        "(planner tags them for CPU fallback)")
    if k is TypeKind.STRING and col.dict_data is not None:
        # dict-encoded strings: the dictionary is sorted by (bytes, length)
        # — dictenc.py invariant 2 — so the CODE is a complete orderable
        # word. One u32 lane through the sort instead of max_len/8 + 1.
        # Only valid within one column (codes from different dictionaries
        # are not comparable; cross-batch sites unify or decode first).
        return [col.data.astype(jnp.uint32)]
    if k is TypeKind.STRING:
        # big-endian packed padded bytes: byte-wise lexicographic == uint64
        # word-wise lexicographic; zero padding sorts shorter strings first,
        # matching UTF-8 byte order because 0x00 is below any content byte.
        cap, ml = col.data.shape
        words = []
        for w in range(0, ml, 8):
            chunk = col.data[:, w:w + 8]
            if chunk.shape[1] < 8:
                chunk = jnp.pad(chunk, ((0, 0), (0, 8 - chunk.shape[1])))
            word = jnp.zeros(cap, jnp.uint64)
            for b in range(8):
                word = (word << jnp.uint64(8)) | chunk[:, b].astype(jnp.uint64)
            words.append(word)
        # length tiebreak: strings may legally CONTAIN 0x00 bytes, which the
        # zero padding would otherwise make indistinguishable from absent
        # bytes ("a" vs "a\x00"); byte-wise order puts the shorter first
        words.append(col.lengths.astype(jnp.uint64))
        return words
    data = col.data
    if k is TypeKind.DECIMAL and d.precision > 18:
        from ..expressions.decimal128 import orderable_words128
        return orderable_words128(data)
    if k is TypeKind.BOOLEAN:
        return [data.astype(jnp.uint8)]
    if k in (TypeKind.FLOAT32,):
        return [_float_orderable(data, jnp.zeros((), jnp.uint32))]
    if k in (TypeKind.FLOAT64,):
        # NO f64→u64 bitcast: the TPU compiler carries f64 as an f32 pair
        # and its x64 rewrite has no 64-bit bitcast-convert (an f64 lane
        # as a native sort key compiles, but ~7x slower than an i32 one).
        # The IEEE bits come arithmetically (hashing._double_bits_words:
        # NaN canonical, -0.0 == 0.0 — Spark's ordering treats both so);
        # sign-flipped they order as the doubles do, NaN greatest.
        from ..expressions.hashing import _double_bits_words
        low, high = _double_bits_words(data)
        neg = (high >> 31) != 0
        sign = jnp.uint32(1) << 31
        return [jnp.where(neg, ~high, high | sign),
                jnp.where(neg, ~low, low)]
    # integral / date / timestamp / decimal: flip the sign bit
    u = data.astype({1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32,
                     8: jnp.uint64}[data.dtype.itemsize])
    sign = u.dtype.type(1) << (u.dtype.itemsize * 8 - 1)
    return [u ^ sign]


def may_skip_null_lane(expr) -> bool:
    """True when a sort key expression PROVABLY never yields null rows, so
    its null-rank operand can be dropped. Only a direct reference to a
    schema-non-nullable column qualifies: computed expressions may
    produce runtime nulls (divide-by-zero, failed casts) whatever their
    static flag claims — those ops also override .nullable to True, but
    the restriction here is the defense in depth."""
    from ..expressions.base import BoundReference
    return isinstance(expr, BoundReference) and not expr.nullable


def sort_operands(cols: Sequence[DeviceColumn], descending: Sequence[bool],
                  nulls_first: Sequence[bool], live: jax.Array,
                  nullable: Optional[Sequence[bool]] = None
                  ) -> List[jax.Array]:
    """Build the lax.sort key operands for a multi-column sort.

    Dead rows (beyond num_rows) always sort last regardless of direction.
    ``nullable[i]=False`` (a schema-level guarantee) drops that column's
    null-rank operand — one fewer u8 lane through the whole sort.
    """
    ops: List[jax.Array] = [(~live).astype(jnp.uint8)]  # live rows first
    if nullable is None:
        nullable = [True] * len(cols)
    for col, desc, nf, nl in zip(cols, descending, nulls_first, nullable):
        if nl:
            null_rank = jnp.where(col.validity, jnp.uint8(1),
                                  jnp.uint8(0) if nf else jnp.uint8(2))
            ops.append(jnp.where(live, null_rank, jnp.uint8(3)))
        for w in orderable_words(col):
            if nl:
                # zero the word lanes of null rows: the rank lane already
                # dominates the ORDER; equal words make null==null rows
                # adjacent-EQUAL too, which the aggregate's word-level
                # group-boundary detection relies on
                w = jnp.where(col.validity, w, jnp.zeros((), w.dtype))
            ops.append(~w if desc else w)
    return ops


def _i32_lanes(ops: Sequence[jax.Array]) -> List[jax.Array]:
    """Unsigned key words -> int32 lanes with the same lexicographic order
    (most significant first): u64 words split in two, and neighbouring
    narrow words (the u8 dead/null-rank lanes) pack into one lane while
    they fit 32 bits. Signed i32, not u32: the TPU compiler builds the
    unsigned comparator ~40% slower."""
    words: List[Tuple[jax.Array, int]] = []      # (u32 value, bits used)
    for w in ops:
        if w.dtype == jnp.bool_:
            w = w.astype(jnp.uint8)
        assert jnp.issubdtype(w.dtype, jnp.unsignedinteger), w.dtype
        if w.dtype.itemsize == 8:
            words.append(((w >> jnp.uint64(32)).astype(jnp.uint32), 32))
            words.append((w.astype(jnp.uint32), 32))
            continue
        bits = w.dtype.itemsize * 8
        w = w.astype(jnp.uint32)
        if words and words[-1][1] + bits <= 32:
            prev, used = words.pop()
            words.append(((prev << jnp.uint32(bits)) | w, used + bits))
        else:
            words.append((w, bits))
    sign = jnp.uint32(1) << 31
    return [jax.lax.bitcast_convert_type(w ^ sign, jnp.int32)
            for w, _ in words]


def lex_sort_permutation(ops: Sequence[jax.Array]) -> jax.Array:
    """Stable permutation ordering rows by the unsigned key words ``ops``
    (as ``sort_operands`` builds them), most significant first.

    THE one place a key sort is built. Least-significant-lane-first passes
    of ONE two-operand stable ``lax.sort`` (i32 lane, i32 row index) inside
    a ``lax.scan``, so the program holds a single small sort whatever the
    number and width of the keys; payload columns are gathered through the
    permutation afterwards, never carried. The TPU compiler's time for a
    sort follows the comparator and the operand count, not the row count:
    for v5e at 1M rows (tools/aot_compile.py) a lone i32 key costs ~30 s,
    three keys + index ~180 s, one f64 key ~210 s, an i32 key carrying
    i64/f64/f64 payload ~125 s — while this loop costs ~40 s for any key
    list and a 64-bit gather ~2 s. At run time it is the gathers that cost:
    on a v5 lite at 1M rows a pass is ~10 ms (the sort 2 ms of it), so three
    words take 28.5 ms where the one 4-operand sort took 3.0 ms
    (tools/chip_probe.py, PR 25; PERF.md weighs the trade)."""
    lanes = _i32_lanes(ops)
    iota = jnp.arange(lanes[0].shape[0], dtype=jnp.int32)

    def one_pass(perm, lane):
        _, perm = jax.lax.sort((jnp.take(lane, perm), perm), num_keys=1,
                               is_stable=True)
        return perm, None

    return jax.lax.scan(one_pass, iota, jnp.stack(lanes[::-1]))[0]


def adjacent_equal_ops(ops: Sequence[jax.Array]) -> jax.Array:
    """eq[i] = position i matches position i-1 on EVERY operand; eq[0]=False.

    Word-level group-boundary detection over the SORTED key operands of
    ``sort_operands`` (null word lanes are zeroed there, so null==null holds
    without consulting validity). Avoids gathering the original key columns
    just to compare them.
    """
    cap = ops[0].shape[0]
    eq = jnp.ones(cap - 1, bool)
    for w in ops:
        eq = eq & (w[1:] == w[:-1])
    return jnp.concatenate([jnp.zeros(1, bool), eq])


def sort_permutation(batch: ColumnarBatch, key_cols: Sequence[DeviceColumn],
                     descending: Sequence[bool], nulls_first: Sequence[bool]
                     ) -> jax.Array:
    """Stable permutation ordering the batch by the given keys."""
    live = batch.row_mask()
    return lex_sort_permutation(
        sort_operands(key_cols, descending, nulls_first, live))


# ---------------------------------------------------------------------------
# Group-key equality over sorted rows (aggregate/window boundary detection)
# ---------------------------------------------------------------------------

def adjacent_equal(cols: Sequence[DeviceColumn]) -> jax.Array:
    """eq[i] = row i has the same key (incl. null==null) as row i-1; eq[0]=False.

    Call on ALREADY SORTED/GATHERED key columns.
    """
    cap = cols[0].capacity
    eq = jnp.ones(cap, bool)
    for c in cols:
        if c.lengths is not None:
            same = jnp.all(c.data[1:] == c.data[:-1], axis=1) & \
                (c.lengths[1:] == c.lengths[:-1])
        elif c.data.ndim > 1:   # decimal128 limb matrices
            same = jnp.all(c.data[1:] == c.data[:-1], axis=1)
        else:
            same = c.data[1:] == c.data[:-1]
        vsame = c.validity[1:] == c.validity[:-1]
        # null==null counts equal; value comparison only if both valid
        pair = vsame & (same | ~c.validity[1:])
        eq = eq & jnp.concatenate([jnp.zeros(1, bool), pair])
    return eq
