"""Joins.

Reference: sql-plugin/.../execution/GpuHashJoin.scala:811 (gather-map hash
join core with BaseHashJoinIterator batched output sizing),
GpuShuffledHashJoinExec.scala:85, GpuBroadcastNestedLoopJoinExec.

TPU-native re-design (no cudf hash table, no dynamic shapes):
1. BUILD: hash the build keys to 32 bits (or take a single fixed-width
   key's orderable word) and sort them — a sorted hash column IS the hash
   table. Its distinct words get a directory of buckets by their offset
   from the smallest (``ProbeTable``).
2. COUNT: each probe row binary-searches its bucket's runs (a few rounds
   where a search of the whole build takes the bit length of its capacity);
   the candidate count is the matching run's length. One scalar (total
   candidates) syncs to the host to pick the output capacity bucket — the
   same two-phase sizing cudf's join gather-maps do (reference: join
   output sizing in JoinGatherer).
3. EXPAND: each output slot finds its (probe row, candidate ordinal) via
   searchsorted over the cumulative counts, gathers both sides, then
   VERIFIES real key equality (hash collisions are rejected here, so join
   results are exact, not probabilistic). Outer/semi/anti variants derive
   from verified per-row match counts — all in the same fused computation.

Null semantics: SQL equi-join keys never match NULL; null-keyed rows surface
only through outer sides. The optional non-equi ``condition`` is evaluated on
the candidate pair batch (the reference compiles an AST for this; here it is
just another traced expression).
"""

from __future__ import annotations

import enum
from typing import Iterator, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn, Field, Schema, bucket_capacity
from ..expressions.aggregates import _cumsum as prefix_sum, _running_max
from ..expressions.base import EvalContext, Expression
from ..expressions.hashing import murmur3_batch
from ..trace import host_read
from ..types import TypeKind
from .base import BinaryExec, Exec
from .basic import bind_all
from .common import PURE, KernelPrograms, compact, concat_batches, gather, \
    gather_column, jit_named


class JoinType(enum.Enum):
    INNER = "Inner"
    LEFT_OUTER = "LeftOuter"
    RIGHT_OUTER = "RightOuter"
    FULL_OUTER = "FullOuter"
    LEFT_SEMI = "LeftSemi"
    LEFT_ANTI = "LeftAnti"
    EXISTENCE = "Existence"   # left cols + exists flag (IN-subquery rewrite)
    CROSS = "Cross"


#: the least slots of one part of a pair batch cut to a bound: a probe
#: batch smaller than this still makes parts of this size, so that a hot
#: key's fan-out is not paid in launches
PAIR_PART_MIN_SLOTS = 1 << 18

_PAIR_TYPES = (JoinType.INNER, JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
               JoinType.FULL_OUTER)


def _hash64(cols: Sequence[DeviceColumn], valid: jnp.ndarray) -> jnp.ndarray:
    """32-bit row hash in a uint32 lane. 64-bit integers are EMULATED on
    TPU, which made the searchsorted probes ~3x slower; 32-bit collisions
    only create extra CANDIDATE pairs, and every candidate is verified by
    exact key comparison (_keys_equal), so a narrower hash trades a few
    false candidates for native-width searches. Invalid rows get the max
    value so they sort last and never collide with probe hashes that are
    themselves forced to a DIFFERENT sentinel (top bit cleared for real
    rows)."""
    h = murmur3_batch(cols, 42).view(jnp.uint32)
    h = h >> jnp.uint32(1)
    return jnp.where(valid, h, ~jnp.uint32(0))


def _keys_equal(a: List[DeviceColumn], b: List[DeviceColumn]) -> jnp.ndarray:
    eq = None
    for x, y in zip(a, b):
        if x.dict_data is not None or y.dict_data is not None:
            # the two sides carry DIFFERENT dictionaries (codes are not
            # comparable across columns) — verify on decoded bytes; the
            # decode gathers fuse into this kernel
            from ..dictenc import decode_column
            x, y = decode_column(x), decode_column(y)
        if x.lengths is not None:
            e = jnp.all(x.data == y.data, axis=1) & (x.lengths == y.lengths)
        elif x.data.ndim > 1:      # decimal128 limb matrices
            e = jnp.all(x.data == y.data, axis=1)
        else:
            e = x.data == y.data
        e = e & x.validity & y.validity
        eq = e if eq is None else eq & e
    return eq


def _bit_length(x: jnp.ndarray) -> jnp.ndarray:
    """int32 bit length of an unsigned or non-negative scalar: a sum of
    compares over the shifts (no 64-bit count-leading-zeros on the chip)."""
    bits = jnp.iinfo(x.dtype).bits
    shifts = jnp.arange(bits, dtype=x.dtype)
    return jnp.sum(((x >> shifts) != 0).astype(jnp.int32))


class ProbeTable(NamedTuple):
    """What a probe searches: the build's distinct live words and a
    directory over them. A word's bucket, ``min((w - first) >> shift,
    buckets - 1)`` (0 below ``first``), is monotone in the word, so the
    lower bound of a probe word lies among its bucket's runs, and a search
    takes ``rounds`` dependent gathers (the widest bucket's bit length)
    where a search of the whole build took the bit length of its
    capacity. Bounds are stored as pairs, so that each is one row gather."""
    n_valid: jnp.ndarray    # live rows with a non-null key: a sorted prefix
    first: jnp.ndarray      # the smallest live word
    dense: jnp.ndarray      # unique contiguous words: a probe is an index
    words: jnp.ndarray      # [cap] each run's word, in order (junk past them)
    spans: jnp.ndarray      # [cap + 1, 2] each run's live sorted rows [lo, hi)
    directory: jnp.ndarray  # [buckets, 2] each bucket's runs [lo, hi)
    shift: jnp.ndarray
    rounds: jnp.ndarray


def _bucket(w, first, shift, buckets: int) -> jnp.ndarray:
    b = jnp.minimum((w - first) >> shift, jnp.asarray(buckets - 1, w.dtype))
    return jnp.where(w < first, 0, b).astype(jnp.int32)


def _pairs(bounds: jnp.ndarray) -> jnp.ndarray:
    """[n + 1] ascending bounds -> [n, 2] rows (bounds[i], bounds[i + 1])."""
    return jnp.stack([bounds[:-1], bounds[1:]], axis=1)


def _probe_table(sorted_h: jnp.ndarray, n_valid: jnp.ndarray) -> ProbeTable:
    """The runs of the sorted build words (live non-null rows first, dead
    rows after them with the largest word) and their directory. One bucket
    a slot at most, ``buckets = 2^ceil(log2(cap))``; ``shift`` is the least
    that brings the live words' span under ``buckets``, so uniform keys
    land about one distinct word a bucket, on the exact path (orderable
    key words) and the hash path alike."""
    cap = sorted_h.shape[0]
    k = max(1, (cap - 1).bit_length())
    buckets = 1 << k
    iota = jnp.arange(cap, dtype=jnp.int32)
    prev_ne = jnp.concatenate(
        [jnp.ones(1, bool), sorted_h[1:] != sorted_h[:-1]])
    gid = prefix_sum(prev_ne.astype(jnp.int32)) - 1
    run_start = jax.ops.segment_min(
        iota, gid, num_segments=cap, indices_are_sorted=True)
    valid_runs = jnp.where(
        n_valid > 0, jnp.take(gid, jnp.maximum(n_valid - 1, 0)) + 1, 0)
    in_runs = iota < valid_runs
    words = jnp.take(sorted_h, jnp.clip(run_start, 0, cap - 1))
    # a run of the largest word may reach into the dead tail: the next
    # run's start, clamped at n_valid, is where a run's live rows end
    nv = n_valid[None]
    spans = _pairs(jnp.concatenate(
        [jnp.where(in_runs, run_start, n_valid), nv, nv]))
    first = sorted_h[0]
    last = jnp.take(sorted_h, jnp.maximum(n_valid - 1, 0))
    shift = jnp.maximum(_bit_length(last - first) - k, 0) \
        .astype(sorted_h.dtype)
    run_bucket = jnp.where(in_runs, _bucket(words, first, shift, buckets),
                           buckets)
    per_bucket = jax.ops.segment_sum(
        jnp.ones(cap, jnp.int32), run_bucket, num_segments=buckets + 1,
        indices_are_sorted=True)[:buckets]
    directory = _pairs(jnp.concatenate(
        [jnp.zeros(1, jnp.int32), prefix_sum(per_bucket)]))
    # dense-unique detection (exact-probe only): dimension PKs are
    # typically a contiguous range, making the probe a DIRECT index —
    # no search at all (reference: cudf builds a hash table; a
    # contiguous sorted build IS a perfect hash). Uniqueness is part
    # of the predicate: span == n-1 alone holds for {0,2,2}, where a
    # direct landing would hit mid-run and miss candidates.
    dense = (n_valid > 0) & (valid_runs == n_valid) & \
        ((last - first) == (n_valid - 1).astype(sorted_h.dtype))
    return ProbeTable(n_valid, first, dense, words, spans, directory, shift,
                      _bit_length(jnp.max(per_bucket)))


def _table_search(t: ProbeTable, h: jnp.ndarray):
    """(lo, run length) of every probe word: ``lo`` the sorted position of
    the first live word >= h (n_valid where none is), the length that
    word's live run where it equals h, else 0 — what a lower-bound search
    of the whole sorted build gives, from a search of h's bucket alone
    (a loop of ``t.rounds`` rounds, read on the device)."""
    b = _bucket(h, t.first, t.shift, t.directory.shape[0])
    bounds = jnp.take(t.directory, b, axis=0, mode="clip")

    def halve(_, state):
        # runs below lo are under h, runs from hi on at least h; ``eq``:
        # the run at hi holds h (hi still the bucket's end: it does not)
        lo, hi, eq = state
        mid = (lo + hi) >> 1
        w = jnp.take(t.words, mid, mode="clip")
        go = lo < hi
        up = go & (w >= h)
        return (jnp.where(go & (w < h), mid + 1, lo), jnp.where(up, mid, hi),
                jnp.where(up, w == h, eq))

    _, r, hit = jax.lax.fori_loop(
        0, t.rounds, halve, (bounds[:, 0], bounds[:, 1],
                             jnp.zeros(h.shape, bool)))
    span = jnp.take(t.spans, r, axis=0, mode="clip")
    return span[:, 0], jnp.where(hit, span[:, 1] - span[:, 0], 0)


def _search_slot_rows(offsets: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """Each pair slot's probe row, the first whose inclusive offset passes
    it, by a binary search of the offsets: ``ceil(log2(n + 1))`` rounds of
    a gather a slot (``n`` probe rows)."""
    return jnp.searchsorted(offsets, j, side="right",
                            method="scan").astype(jnp.int32)


def _map_slot_rows(offsets: jnp.ndarray, counts: jnp.ndarray, base,
                   out_cap: int) -> jnp.ndarray:
    """Each of the slots ``[base, base + out_cap)``'s probe row in one pass:
    every probe row whose candidates start inside the part marks its first
    slot with its index, the row holding ``base`` (a search of that one
    slot) marks slot 0, and a running maximum carries each mark over its
    row's slots. No two marks share a place, the other rows each going to
    one of its own past the end, so the scatter is of unique indices (the
    fastest form on the chip: docs/tpu_compat.md). Slots from the total on
    read the last row with candidates (the search reads ``n``): the caller
    masks them."""
    n = offsets.shape[0]
    first = offsets - counts - base
    rows = jnp.arange(n, dtype=jnp.int32)
    inside = (counts > 0) & (first >= 0) & (first < out_cap)
    marks = jnp.zeros(out_cap, jnp.int32).at[
        jnp.where(inside, first, out_cap + rows)].set(
            rows, mode="drop", unique_indices=True)
    head = jnp.minimum(_search_slot_rows(offsets, base), n - 1)
    return _running_max(marks.at[0].max(head))


# The map costs a scatter of the probe rows and a ladder over the slots
# (about 2 / 3.4 / 5.7 ms at 2^18 / 2^19 / 2^20 probe rows), the search a
# gather a slot a round (27-33 ns a slot-round below 2^17 slots). On one
# TPU v5 lite the map wins from 2^12 slots of 2^18 rows (19 rounds: 0.30
# slot-rounds a row; 1.98 against 2.59 ms) and 2^14 of 2^20 (0.33; 5.70
# against 8.82), the search at 256 of 2^18 (0.019; 0.68 against 2.05):
# the map where the slot-rounds reach a quarter of the probe rows
# (docs/tpu_compat.md, "The pair slots' probe rows").
MAP_MIN_SLOT_ROUNDS_PER_ROW = 0.25


def _maps_slots(probe_cap: int, out_cap: int) -> bool:
    """Whether a pair batch of ``out_cap`` slots from a probe batch of
    ``probe_cap`` rows takes its slots' probe rows from the one-pass map
    (else from the search): both capacities are static in the program."""
    return out_cap * probe_cap.bit_length() >= \
        MAP_MIN_SLOT_ROUNDS_PER_ROW * probe_cap


from functools import partial  # noqa: E402


@partial(jit_named, "HashJoinExec_sliceTile", key=PURE, static_argnums=3)
def _slice_tile(build, off, count, cap):
    from .common import slice_batch
    return slice_batch(build, off, count, cap)


def _null_gather(batch: ColumnarBatch, out_cap: int) -> List[DeviceColumn]:
    """All-null columns shaped like ``batch`` at out_cap (outer padding)."""
    zero_idx = jnp.zeros(out_cap, jnp.int32)
    none = jnp.zeros(out_cap, bool)
    return [gather_column(c, zero_idx, none) for c in batch.columns]


class HashJoinExec(BinaryExec):
    """Equi-join; left child streams, right child builds (the planner swaps
    children to put the smaller side on the right, like the reference's
    build-side selection in GpuShuffledHashJoinExec)."""

    def coalesce_goal_for_child(self, i):
        # stream side wants sized batches; the build side is concatenated
        # whole (RequireSingleBatch — reference: GpuShuffledHashJoinExec
        # build-side single-batch contract)
        from .coalesce import RequireSingleBatch, TargetSize
        return TargetSize() if i == 0 else RequireSingleBatch()

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: JoinType,
                 left: Exec, right: Exec,
                 condition: Optional[Expression] = None,
                 broadcast_build: bool = True,
                 ctx: Optional[EvalContext] = None,
                 max_build_rows: int = 1 << 22,
                 skew_split_rows: Optional[int] = None,
                 broadcast_switch_rows: Optional[int] = None):
        super().__init__(left, right, ctx)
        # AQE runtime broadcast switch: in the co-partitioned mode, a
        # build side that MEASURES at or under this many rows after its
        # shuffle materializes is replicated to every stream partition
        # instead of co-partition-probed (the planner's byte estimate
        # said shuffle; the measured rows say broadcast). None = off.
        # do_close restores the planned mode so a re-execute re-decides
        # from fresh statistics.
        self.broadcast_switch_rows = broadcast_switch_rows
        self._planned_broadcast = broadcast_build
        # AQE skew-join: in the co-partitioned mode, a stream-side reader
        # partition larger than this is split, replicating the matching
        # build partition (reference: OptimizeSkewedJoin /
        # GpuCustomShuffleReaderExec PartialReducerPartitionSpec). None =
        # off. Coordination also keeps adaptive partition-coalescing
        # CONSISTENT across the two exchanges — see _maybe_coordinate.
        self.skew_split_rows = skew_split_rows
        self._coordinated = False
        # Build relation materialized ONCE for a runtime broadcast
        # switch: the switched-to build side is a ShuffleExchangeExec
        # whose spillable pieces are freed after their single
        # refcounted read, so re-reading it per stream partition would
        # hit closed pieces. A PLANNED broadcast reads a
        # BroadcastExchangeExec, which is multi-read safe, and keeps
        # its per-read spill discipline (no caching there).
        self._switch_build_cache: Optional[List[ColumnarBatch]] = None
        # broadcast_build: build side replicated (broadcast hash join).
        # False = co-partitioned inputs (shuffled hash join); requires both
        # children hash-partitioned on the join keys by an exchange.
        self.broadcast_build = broadcast_build
        # Oversized-build sub-partitioning (reference: GpuHashJoin.scala:811
        # build-side sub-partitioning in GpuShuffledHashJoinExec): when the
        # build side exceeds this row budget, grace-hash split BOTH sides
        # into murmur3(key) % S buckets and join bucket-by-bucket — every
        # join type stays correct because equal keys land in the same
        # bucket and each build/stream row lands in exactly one.
        self.max_build_rows = max_build_rows
        if join_type is JoinType.CROSS:
            raise ValueError("use BroadcastNestedLoopJoinExec for cross joins")
        self.join_type = join_type
        self.left_keys = bind_all(left_keys, left.output_schema)
        self.right_keys = bind_all(right_keys, right.output_schema)
        for lk, rk in zip(self.left_keys, self.right_keys):
            if lk.dtype != rk.dtype:
                raise TypeError(f"join key type mismatch {lk.dtype} vs "
                                f"{rk.dtype}; planner must insert casts")

        lf, rf = left.output_schema.fields, right.output_schema.fields
        l_nullable = join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)
        r_nullable = join_type in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
        if join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            self._schema = left.output_schema
        elif join_type is JoinType.EXISTENCE:
            self._schema = Schema(list(lf) + [Field("exists", T.BOOLEAN,
                                                    False)])
        else:
            self._schema = Schema(
                [Field(f.name, f.dtype, f.nullable or l_nullable) for f in lf]
                + [Field(f.name, f.dtype, f.nullable or r_nullable) for f in rf])
        self.condition = condition.bind(self._pair_schema()) if condition else None

        # single fixed-width key: probe the key's orderable word EXACTLY
        # (sorted keys ARE the hash table; zero false candidates, so the
        # optimistic fused-output bucket never overflows on FK joins).
        # Multi-key / float / string keys keep the 32-bit hash probe with
        # equality verification.
        _EXACT_KINDS = (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32,
                        TypeKind.INT64, TypeKind.DATE, TypeKind.TIMESTAMP,
                        TypeKind.BOOLEAN)
        self._exact_probe = (
            len(self.right_keys) == 1
            and self.right_keys[0].dtype.kind in _EXACT_KINDS)

        programs = KernelPrograms(self, (
            "join_type", "left_keys", "right_keys", "condition",
            "_exact_probe"))
        cls = type(self)
        self._build_jit = programs.jit("build", cls._build_kernel)
        self._count_jit = programs.jit("count", cls._count_kernel)
        self._expand_jit = programs.jit("expand", cls._expand_kernel,
                                        static_argnums=(4,))
        self._expand_part_jit = programs.jit(
            "expandPart", cls._expand_part_kernel, static_argnums=(4,))
        self._semi_jit = programs.jit("semi", cls._semi_kernel,
                                      static_argnums=(4,))

    def _probe_words(self, keys, valid, build_side: bool) -> jnp.ndarray:
        """The sorted/probed search key: exact orderable word (single
        fixed-width key) or verified 32-bit hash."""
        if self._exact_probe:
            from .common import orderable_words
            w = orderable_words(keys[0])[0]
            if build_side:
                # dead/invalid build rows take the MAX word so they sort
                # last; the validity tie-break in _build_kernel puts real
                # max-key rows BEFORE them, and _count_kernel clamps
                # search bounds by the live count — so padding can never
                # inflate candidate counts (a padded dim batch otherwise
                # makes every key-0 probe match the whole dead tail)
                return jnp.where(valid, w, ~jnp.zeros((), w.dtype))
            return w
        h = _hash64(keys, valid)
        if not build_side:
            return jnp.where(valid, h, ~jnp.uint32(0) - 1)
        return h

    def _pair_schema(self) -> Schema:
        return Schema(list(self.left.output_schema.fields)
                      + list(self.right.output_schema.fields))

    @property
    def output_schema(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------------

    def _build_kernel(self, build: ColumnarBatch):
        """Sort the build side by probe word and MATERIALIZE it in that
        order. Expansion then gathers build columns directly at sorted
        positions — no perm indirection per probe batch (a 1M-row index
        gather cost ~7 ms in the old plug-in's profile; the build-side gather here is
        paid once and amortizes over every probe batch)."""
        keys = [e.eval(build, self.ctx) for e in self.right_keys]
        live = build.row_mask()
        valid = live
        for k in keys:
            valid = valid & k.validity
        h = self._probe_words(keys, valid, build_side=True)
        iota = jnp.arange(build.capacity, dtype=jnp.int32)
        # three-way rank tie-break: valid-keyed rows sort by word first,
        # then LIVE null-keyed rows (outer tails still need them), then
        # dead padding — so live rows stay a prefix in sorted order
        rank = jnp.where(valid, 0, jnp.where(live, 1, 2)).astype(jnp.uint8)
        from .common import lex_sort_permutation
        perm = lex_sort_permutation([h, rank])
        sorted_h = jnp.take(h, perm)
        n_valid = jnp.sum(valid.astype(jnp.int32)).astype(jnp.int32)
        from .common import gather_columns
        sorted_live = iota < build.num_rows
        sorted_cols = gather_columns(list(build.columns), perm, sorted_live)
        sorted_build = ColumnarBatch(tuple(sorted_cols), build.num_rows)
        # the runs and their directory: build-side work that every probe
        # batch of this build reuses
        return _probe_table(sorted_h, n_valid), sorted_build, valid

    def _count_kernel(self, stream: ColumnarBatch, table: ProbeTable):
        keys = [e.eval(stream, self.ctx) for e in self.left_keys]
        live = stream.row_mask()
        valid = live
        for k in keys:
            valid = valid & k.validity
        # hash path: probe sentinel 0xFFFFFFFE ≠ build null sentinel
        # 0xFFFFFFFF, both outside the >>1 hash range, so null/dead
        # probes find nothing. Exact path: no sentinel — counts are only
        # taken where `valid` (below), and a wrong-landing probe fails the
        # word-equality check.
        h = self._probe_words(keys, valid, build_side=False)

        def dense_path():
            # unique contiguous build (a dimension PK): position is
            # (key - first) and presence is a RANGE test — the whole probe
            # is elementwise, zero gathers, zero searches
            off = h - table.first
            in_r = (h >= table.first) & (off < table.n_valid.astype(h.dtype))
            lo = jnp.where(in_r, off, 0).astype(jnp.int32)
            counts = jnp.where(valid & in_r, 1, 0).astype(jnp.int32)
            return lo, counts

        def general_path():
            # a binary search of the probe word's bucket of the directory
            # (a loop with no sort: jnp.searchsorted's method="sort" costs
            # 54-113 s of TPU compile at 1M rows, tools/aot_compile.py)
            lo, run_len = _table_search(table, h)
            return lo, jnp.where(valid, run_len, 0)
        lo, counts = jax.lax.cond(table.dense, dense_path, general_path) \
            if self._exact_probe else general_path()
        offsets = prefix_sum(counts)
        # int32 offsets keep the searches native-width; the 64-bit total
        # lets the host detect candidate counts that would wrap them
        total64 = jnp.sum(counts.astype(jnp.int64))
        return lo, counts, offsets, total64

    def _side_gather(self, batch, keys, idx, ok, need_keys: bool,
                     subst=None):
        """ONE batched gather per side (sibling gathers
        don't fuse; stacked row-gathers are width-flat). Key columns that
        are plain references reuse the already-gathered output column
        instead of adding a duplicate gather lane; on the exact-probe path
        keys aren't gathered at all (word equality IS key equality).
        ``subst`` maps an output ordinal to a pre-known column (the build
        key equals the probe key on exact matches — no gather needed)."""
        from ..expressions.base import BoundReference
        from .common import gather_columns
        subst = subst or {}
        cols = list(batch.columns)
        gathered_idx = [i for i in range(len(cols)) if i not in subst]
        extra, key_src = [], []
        if need_keys:
            for e in keys:
                if isinstance(e, BoundReference) and e.ordinal not in subst:
                    key_src.append(("col", e.ordinal))
                else:
                    key_src.append(("extra", len(extra)))
                    extra.append(e.eval(batch, self.ctx))
        g = gather_columns([cols[i] for i in gathered_idx] + extra, idx, ok)
        out_cols: List[Optional[DeviceColumn]] = [None] * len(cols)
        for j, i in enumerate(gathered_idx):
            out_cols[i] = g[j]
        for i, c in subst.items():
            out_cols[i] = c
        key_cols = [out_cols[i] if kind == "col"
                    else g[len(gathered_idx) + i]
                    for (kind, i) in key_src]
        return out_cols, key_cols

    def _exact_subst(self, key_col_at_pairs, pair_ok):
        """Exact-probe: the build key column's output values equal the
        probe key values on every surviving slot, so substitute instead of
        gathering (kills the build side's whole i32 gather group for a
        typical star-schema dim). Returns {build ordinal: column} or {}."""
        from ..expressions.base import BoundReference
        rk = self.right_keys[0] if self._exact_probe else None
        if not isinstance(rk, BoundReference) or key_col_at_pairs is None:
            return {}
        return {rk.ordinal: key_col_at_pairs.replace(
            validity=key_col_at_pairs.validity & pair_ok)}

    def _gather_pairs(self, stream, build, lo, counts, offsets, out_cap,
                      base=None):
        """Candidate pair gather + key verification (+ condition).
        ``build`` is the build-kernel's SORTED build batch, so candidate
        positions index it directly (no perm indirection). ``base`` (a
        traced scalar) makes the batch candidates ``[base, base +
        out_cap)`` of the probe batch's, in the same order: one part of a
        pair batch cut to a bound (``_probe``)."""
        j = jnp.arange(out_cap, dtype=jnp.int32)
        if base is not None:
            j = j + base
        total = offsets[-1]
        in_range = j < total
        # slots past the total read the last row, as the clipped search does
        if _maps_slots(stream.capacity, out_cap):
            probe_row = jnp.where(
                in_range, _map_slot_rows(offsets, counts,
                                         0 if base is None else base,
                                         out_cap), stream.capacity - 1)
        else:
            probe_row = jnp.clip(_search_slot_rows(offsets, j), 0,
                                 stream.capacity - 1)
        start = jnp.take(offsets, probe_row) - jnp.take(counts, probe_row)
        ordinal = j - start
        build_row = jnp.take(lo, probe_row) + ordinal
        build_row = jnp.clip(build_row, 0, build.capacity - 1).astype(jnp.int32)

        # exact-probe candidates already matched on the full key word, so
        # no key re-gather or equality verification is needed; the hash
        # path gathers keys and rejects collisions here
        need_keys = not self._exact_probe
        s_cols, s_keys = self._side_gather(stream, self.left_keys,
                                           probe_row, in_range, need_keys)
        from ..expressions.base import BoundReference
        lk = self.left_keys[0]
        key_at_pairs = s_cols[lk.ordinal] \
            if self._exact_probe and isinstance(lk, BoundReference) else None
        b_cols, b_keys = self._side_gather(
            build, self.right_keys, build_row, in_range, need_keys,
            self._exact_subst(key_at_pairs, in_range))
        pair_ok = in_range if self._exact_probe \
            else in_range & _keys_equal(s_keys, b_keys)
        if self.condition is not None:
            rows = total if base is None else \
                jnp.clip(total - base, 0, out_cap).astype(jnp.int32)
            pair_batch = ColumnarBatch(tuple(s_cols + b_cols), rows)
            c = self.condition.eval(pair_batch, self.ctx)
            pair_ok = pair_ok & c.data & c.validity
        return s_cols, b_cols, pair_ok, probe_row, build_row

    def _expand_kernel(self, stream, build, lo_counts, matched_build_in,
                       out_cap: int):
        lo, counts, offsets = lo_counts
        # FK fast path (the overwhelmingly common star-schema shape):
        # when every probe has AT MOST ONE candidate, the expansion is a
        # 1:1 row mapping — no cumulative-offset search, no out_cap-wide
        # pair gathers, no pair compaction. Selected per batch by
        # lax.cond; both branches produce the same [out_cap] layout.
        if self.condition is None and \
                self.join_type in (JoinType.INNER, JoinType.LEFT_OUTER) \
                and out_cap >= stream.capacity:
            unique = jnp.max(counts) <= 1
            return jax.lax.cond(
                unique,
                lambda: self._expand_unique(stream, build, lo,
                                            counts, matched_build_in,
                                            out_cap),
                lambda: self._expand_general(stream, build, lo,
                                             counts, offsets,
                                             matched_build_in, out_cap))
        return self._expand_general(stream, build, lo, counts,
                                    offsets, matched_build_in, out_cap)

    def _unique_probe_cols(self, stream, build, lo, counts):
        """Shared <=1-match-per-probe verification: gather build columns
        1:1 at stream layout and compute the verified pair mask (exact
        path: word equality IS key equality + key substitution; hash
        path: gather keys and reject collisions)."""
        matched = counts > 0
        build_row = jnp.clip(lo, 0, build.capacity - 1)
        if self._exact_probe:
            pair_ok = matched & stream.row_mask()
            from ..expressions.base import BoundReference
            key_col = self.left_keys[0].eval(stream, self.ctx) \
                if isinstance(self.right_keys[0], BoundReference) else None
            b_cols, _ = self._side_gather(
                build, self.right_keys, build_row, matched, False,
                self._exact_subst(key_col, pair_ok))
        else:
            b_cols, b_keys = self._side_gather(build, self.right_keys,
                                               build_row, matched, True)
            s_keys = [e.eval(stream, self.ctx) for e in self.left_keys]
            pair_ok = matched & stream.row_mask() & \
                _keys_equal(s_keys, b_keys)
        return b_cols, pair_ok

    def _expand_unique(self, stream, build, lo, counts,
                       matched_build_in, out_cap: int):
        """<=1 match per probe: direct row mapping at stream capacity."""
        b_cols, pair_ok = self._unique_probe_cols(stream, build, lo, counts)
        # only RIGHT/FULL outer consume build-match state, and this path
        # serves INNER/LEFT only — skip the scatter
        matched_build = matched_build_in
        if self.join_type is JoinType.LEFT_OUTER:
            # every stream row survives; unmatched rows take null builds.
            # Pad to the general path's post-concat capacity so lax.cond
            # sees identical output types.
            b_cols = [c.replace(validity=c.validity & pair_ok)
                      for c in b_cols]
            out = ColumnarBatch(stream.columns + tuple(b_cols),
                                stream.num_rows)
            target = bucket_capacity(out_cap + stream.capacity)
        else:
            out = compact(ColumnarBatch(stream.columns + tuple(b_cols),
                                        stream.num_rows), pair_ok)
            target = out_cap
        return self._pad_batch(out, target), matched_build

    @staticmethod
    def _pad_batch(batch: ColumnarBatch, cap: int) -> ColumnarBatch:
        if batch.capacity == cap:
            return batch
        from .aggregate import _pad_column
        return ColumnarBatch(
            tuple(_pad_column(c, cap) for c in batch.columns),
            batch.num_rows)

    def _expand_general(self, stream, build, lo,
                        counts, offsets, matched_build_in, out_cap: int,
                        base=None):
        s_cols, b_cols, pair_ok, probe_row, build_row = self._gather_pairs(
            stream, build, lo, counts, offsets, out_cap, base)

        # compact verified pairs to the front
        pairs = compact(ColumnarBatch(tuple(s_cols + b_cols),
                                      jnp.asarray(out_cap, jnp.int32)),
                        pair_ok)

        # per-stream-row verified match count (probe_row ascending)
        seg = jnp.where(pair_ok, probe_row, stream.capacity)
        stream_matches = jax.ops.segment_sum(
            pair_ok.astype(jnp.int32), seg, num_segments=stream.capacity + 1,
            indices_are_sorted=True)[: stream.capacity]
        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            matched_build = matched_build_in.at[
                jnp.where(pair_ok, build_row, build.capacity)].set(
                True, mode="drop")
        else:
            matched_build = matched_build_in

        if self.join_type in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER):
            unmatched = stream.row_mask() & (stream_matches == 0)
            u_cols = list(stream.columns) + _null_gather(build, stream.capacity)
            u_batch = compact(ColumnarBatch(
                tuple(u_cols), stream.num_rows), unmatched)
            out = concat_batches([pairs, u_batch],
                                 bucket_capacity(out_cap + stream.capacity))
        else:
            out = pairs
        return out, matched_build

    def _expand_masked(self, stream, build, lo, counts, offsets,
                       out_cap: int):
        """INNER-join expansion WITHOUT the compaction pass: the pair
        batch at out_cap slots (num_rows == capacity) plus a live-pair
        mask, for consumers that tolerate interleaved dead rows — a
        downstream aggregation key-sorts anyway, so fused join→agg skips
        an entire compact (cumsum + scatter + per-column gathers).
        Reference analogue: AST-fused filter feeding cudf groupby."""
        assert self.join_type is JoinType.INNER

        def unique_fn():
            b_cols, pair_ok = self._unique_probe_cols(stream, build, lo,
                                                      counts)
            if self.condition is not None:
                pb = ColumnarBatch(stream.columns + tuple(b_cols),
                                   stream.num_rows)
                c = self.condition.eval(pb, self.ctx)
                pair_ok = pair_ok & c.data & c.validity
            out = self._pad_batch(
                ColumnarBatch(stream.columns + tuple(b_cols),
                              jnp.asarray(stream.capacity, jnp.int32)),
                out_cap)
            mask = jnp.pad(pair_ok, (0, out_cap - stream.capacity))
            return out, mask

        def general_fn():
            s_cols, b_cols, pair_ok, _, _ = self._gather_pairs(
                stream, build, lo, counts, offsets, out_cap)
            return ColumnarBatch(tuple(s_cols + b_cols),
                                 jnp.asarray(out_cap, jnp.int32)), pair_ok

        if out_cap >= stream.capacity:
            unique = jnp.max(counts) <= 1
            return jax.lax.cond(unique, unique_fn, general_fn)
        return general_fn()

    def _expand_part_kernel(self, stream, build, lo_counts, base,
                            out_cap: int):
        """INNER join: candidates ``[base, base + out_cap)`` of a probe
        batch as one pair batch, verified pairs compacted to the front."""
        lo, counts, offsets = lo_counts
        return self._expand_general(stream, build, lo, counts, offsets,
                                    None, out_cap, base)[0]

    def _semi_kernel(self, stream, build, lo_counts, matched_build_in,
                     out_cap: int):
        lo, counts, offsets = lo_counts
        if self._exact_probe and self.condition is None:
            # candidate counts ARE verified match counts on the exact
            # path: no pair expansion at all
            stream_matches = counts
        else:
            _, _, pair_ok, probe_row, _ = self._gather_pairs(
                stream, build, lo, counts, offsets, out_cap)
            seg = jnp.where(pair_ok, probe_row, stream.capacity)
            stream_matches = jax.ops.segment_sum(
                pair_ok.astype(jnp.int32), seg,
                num_segments=stream.capacity + 1,
                indices_are_sorted=True)[: stream.capacity]
        if self.join_type is JoinType.LEFT_SEMI:
            keep = stream_matches > 0
        elif self.join_type is JoinType.LEFT_ANTI:
            keep = stream.row_mask() & (stream_matches == 0)
        else:   # EXISTENCE: no filtering, append the flag column
            exists = DeviceColumn((stream_matches > 0), stream.row_mask(),
                                  None, T.BOOLEAN)
            return ColumnarBatch(stream.columns + (exists,),
                                 stream.num_rows)
        return compact(stream, keep)

    def left_child_placeholder(self) -> ColumnarBatch:
        # a zero-row batch shaped like the left child, for null padding
        from ..batch import empty_batch
        return empty_batch(self.left.output_schema, 1)

    # ------------------------------------------------------------------

    def _maybe_coordinate(self) -> None:
        """Co-partitioned mode over two shuffle exchanges: plan BOTH
        reader layouts jointly (coalesce on combined stats + skew split).
        Without this, each adaptive exchange would coalesce by its own row
        counts and reader partition p would hold different keys on the two
        sides."""
        if self.broadcast_build or self._coordinated:
            return
        self._coordinated = True
        from ..shuffle.exchange import (ShuffleExchangeExec,
                                        coordinate_join_reads)
        l, r = self.left, self.right
        if not (isinstance(l, ShuffleExchangeExec) and
                isinstance(r, ShuffleExchangeExec)):
            return
        if self._maybe_broadcast_switch(r):
            return
        if not (l.adaptive or r.adaptive or self.skew_split_rows):
            return
        split = self.skew_split_rows
        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            # per-partition build tails stay correct only while each build
            # row is probed in exactly one reader partition
            split = None
        coordinate_join_reads(l, r, l.target_rows, split)

    def _maybe_broadcast_switch(self, build_ex) -> bool:
        """Runtime shuffled->broadcast switch: the build exchange has
        materialized (or is about to — reading its row counts forces
        it), so compare MEASURED build rows against the conf'd ceiling
        and replicate a small build instead of co-partition-probing it.
        Restricted to join types without build-side null tails
        (RIGHT/FULL outer fold to one partition under broadcast and are
        not worth re-planning into that shape at runtime). Bit-for-bit:
        a replicated build probes the same pairs per stream partition
        as the co-partitioned layout probes across partitions."""
        if self.broadcast_switch_rows is None or \
                self.join_type in (JoinType.RIGHT_OUTER,
                                   JoinType.FULL_OUTER):
            return False
        build_rows = sum(build_ex.partition_row_counts())
        if build_rows > self.broadcast_switch_rows:
            return False
        from ..plan.adaptive import record_decision
        record_decision(
            "broadcastSwitch",
            f"shuffled {self.join_type.name} join: build side measured "
            f"{build_rows} rows <= maxBuildRows="
            f"{self.broadcast_switch_rows} -> replicating build "
            f"(runtime broadcast)")
        self.broadcast_build = True
        return True

    def do_close(self) -> None:
        # the exchanges drop their materialization + reader specs on
        # close; a re-execute must re-coordinate or the two sides would
        # fall back to inconsistent solo layouts — and a runtime
        # broadcast switch must re-decide from fresh statistics
        self._coordinated = False
        self.broadcast_build = self._planned_broadcast
        self._switch_build_cache = None

    @property
    def num_partitions(self) -> int:
        self._maybe_coordinate()
        # With a replicated build side, RIGHT/FULL outer needs GLOBAL
        # matched-build state: a per-partition tail would both duplicate
        # unmatched build rows (once per stream partition) and null-pad
        # build rows matched in a different partition. Fold every stream
        # partition into one so the tail is emitted exactly once. The
        # co-partitioned (shuffled) path keeps per-partition tails — each
        # build row lives in exactly one partition there.
        if (self.broadcast_build and
                self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)):
            return 1
        return self.left.num_partitions

    @property
    def planned_partitions(self) -> int:
        """The same rule from plan facts: a PLANNED broadcast build (the
        run-time switch never takes RIGHT/FULL outer), the left child's
        planned count, and the skew split, which can cut even a single
        map-output partition into several reader partitions."""
        if (self._planned_broadcast and
                self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)):
            return 1
        n = self.left.planned_partitions
        return max(n, 2) if self.skew_split_rows else n

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        self._maybe_coordinate()
        if self.broadcast_build and not self._planned_broadcast:
            # Runtime switch: the build side is still a shuffle
            # exchange — read the whole relation exactly once (its
            # pieces close after their refcounted read) and reuse it
            # across stream partitions. Bounded: the switch only fires
            # at <= broadcastJoin.maxBuildRows measured rows. Partition
            # execution is sequential, so no synchronization needed.
            if self._switch_build_cache is None:
                self._switch_build_cache = [
                    b for cp in range(self.right.num_partitions)
                    for b in self.right.execute_partition(cp)]
            build_batches = self._switch_build_cache
        elif self.broadcast_build:
            build_batches = [b for cp in range(self.right.num_partitions)
                             for b in self.right.execute_partition(cp)]
        else:
            build_batches = list(self.right.execute_partition(p))
        if self.num_partitions == 1 and self.left.num_partitions > 1:
            stream_parts: Sequence[int] = range(self.left.num_partitions)
        else:
            stream_parts = (p,)
        stream_iter = (b for sp in stream_parts
                       for b in self.left.execute_partition(sp))

        build_rows = sum(int(n) for n in host_read(
            "buildRows", [b.num_rows for b in build_batches]))
        if build_rows > self.max_build_rows:
            yield from self._grace_join(build_batches, stream_iter)
        else:
            yield from self._probe(build_batches, stream_iter)

    def _probe(self, build_batches: List[ColumnarBatch],
               stream_iter: Iterator[ColumnarBatch]
               ) -> Iterator[ColumnarBatch]:
        """Core probe loop against ONE in-memory build table.

        Retry discipline: the build side is admitted to the spill catalog
        (SpillableColumnarBatch shape — held across the retry boundary as
        handles, not raw device arrays) and the concat+build runs under
        with_retry_no_split; each probe batch runs under with_retry with
        halving — a half-stream probes to the same pairs in the same
        stream-row order, so concatenated outputs are bit-for-bit."""
        from .. import trace as qtrace
        from ..batch import empty_batch
        from ..memory import (SpillableInput, admit_all, device_budget,
                              split_input_halves, with_retry,
                              with_retry_no_split)
        cat = device_budget()
        build_schema = self.right.output_schema
        build_inputs = admit_all(build_batches, build_schema, cat,
                                 name=f"{self.name}.build")

        def build_body():
            got: List[ColumnarBatch] = []
            try:
                for binp in build_inputs:
                    got.append(binp.acquire())
                if not got:
                    build = empty_batch(build_schema)
                elif len(got) == 1:
                    build = got[0]
                else:
                    cap = bucket_capacity(sum(b.capacity for b in got))
                    build = concat_batches(got, cap)
                return self._build_jit(build)
            finally:
                for j in range(len(got)):
                    build_inputs[j].release()

        try:
            table, sbuild, _ = with_retry_no_split(
                build_body, catalog=cat, name=f"{self.name}.build")
        finally:
            for binp in build_inputs:
                binp.close()
        matched_build = jnp.zeros(sbuild.capacity, bool)
        # what a search of the whole build would take (jnp.searchsorted's
        # scan: the bit length of the capacity), and what the directory
        # asks for, read once a build after its first probe's total
        plain_rounds = sbuild.capacity.bit_length()
        search_rounds = None

        semi = self.join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                                  JoinType.EXISTENCE)
        # the exact-probe semi join without a condition counts matches and
        # makes no pair batch: its program takes no pair capacity
        pairless = semi and self._exact_probe and self.condition is None
        stream_schema = self.left.output_schema

        def probe_body(item: SpillableInput):
            nonlocal search_rounds
            b = item.acquire()
            try:
                lo, counts, offsets, total = self._count_jit(b, table)
                total_i = int(host_read("joinTotal", total))
                if search_rounds is None:
                    dense, rounds = host_read("joinRounds", (table.dense,
                                                             table.rounds))
                    search_rounds = 0 if self._exact_probe and dense \
                        else int(rounds)
                if total_i > (1 << 31) - 1:
                    raise RuntimeError(
                        f"join candidate explosion: {total_i} pairs in "
                        f"one probe batch exceeds the int32 offset range; "
                        f"reduce the batch size or pre-aggregate the "
                        f"build side")
                out_cap = 0 if pairless else bucket_capacity(max(total_i, 1))
                # the slots of one pair-batch program, whose shapes choose
                # how its slots find their probe rows (``_maps_slots``)
                pair_cap = out_cap
                part = max(b.capacity, PAIR_PART_MIN_SLOTS)
                lo_counts = (lo, counts, offsets)
                if semi:
                    outs = [(self._semi_jit(b, sbuild, lo_counts,
                                            matched_build, out_cap), None)]
                elif self.join_type is JoinType.INNER and out_cap > part:
                    # a join whose pairs are many times its probe batch:
                    # pair batches of at most the probe batch's capacity,
                    # consecutive ranges of the candidates, in their order
                    outs = [(self._expand_part_jit(b, sbuild, lo_counts,
                                                   jnp.int32(base), part),
                             None) for base in range(0, total_i, part)]
                    out_cap = part * len(outs)
                    pair_cap = part
                else:
                    outs = [self._expand_jit(b, sbuild, lo_counts,
                                             matched_build, out_cap)]
                mapped = out_cap if out_cap and \
                    _maps_slots(b.capacity, pair_cap) else 0
                qtrace.count(joinProbeBatches=1, joinCandidates=total_i,
                             joinSlotsOut=out_cap, joinMappedSlots=mapped,
                             joinSearchRounds=search_rounds,
                             joinSearchRoundsPlain=plain_rounds)
                return outs
            finally:
                item.release()

        for stream in stream_iter:
            inp = SpillableInput.admit(stream, stream_schema, cat,
                                       name=self.name)
            # adaptive skew seam: a stream batch the shuffle statistics
            # already measured over the skew row target pre-splits
            # through the same split-and-retry machinery instead of
            # OOMing its way down to size
            for outs in with_retry(inp, probe_body,
                                   split=split_input_halves,
                                   catalog=cat, name=self.name,
                                   presplit_rows=self.skew_split_rows):
                for out, mb in outs:
                    if mb is not None:
                        matched_build = mb
                    yield out

        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            # matched state lives in SORTED build space; the tail reads
            # the sorted build batch (row order is not part of the
            # contract)
            unmatched = sbuild.row_mask() & ~matched_build
            null_left = _null_gather(self.left_child_placeholder(),
                                     sbuild.capacity)
            tail = ColumnarBatch(tuple(null_left) + sbuild.columns,
                                 sbuild.num_rows)
            yield compact(tail, unmatched)

    # ------------------------------------------------------------------
    # Grace-hash sub-partitioning (reference: GpuHashJoin.scala:811 /
    # GpuShuffledHashJoinExec oversized-build handling)
    # ------------------------------------------------------------------

    def _bucket_pids(self, batch: ColumnarBatch, keys, n_buckets: int):
        cols = [e.eval(batch, self.ctx) for e in keys]
        h = murmur3_batch(cols, 77)   # independent of the join's _hash64
        m = h % jnp.int32(n_buckets)
        return jnp.where(m < 0, m + n_buckets, m).astype(jnp.int32)

    def _grace_join(self, build_batches: List[ColumnarBatch],
                    stream_iter: Iterator[ColumnarBatch]
                    ) -> Iterator[ColumnarBatch]:
        """Split BOTH sides into murmur3(key) % S buckets, join each bucket
        pair independently with the normal probe loop. Stream buckets wait
        in the spill catalog, so peak device residency stays one bucket's
        build + one stream batch regardless of input size."""
        from ..memory import (SpillableBatch, acquire_with_retry,
                              device_budget, register_with_retry)
        cat = device_budget()
        build_rows = sum(int(n) for n in host_read(
            "buildRows", [b.num_rows for b in build_batches]))
        n_buckets = -(-build_rows // self.max_build_rows)
        from .. import trace as qtrace
        qtrace.count(graceBuckets=n_buckets)

        programs = KernelPrograms(self, ("left_keys", "right_keys"),
                                  also=[n_buckets])
        split_build = programs.jit(
            "splitBuild",
            lambda self, b, s: compact(
                b, self._bucket_pids(b, self.right_keys, n_buckets) == s),
            static_argnums=1)
        split_stream = programs.jit(
            "splitStream",
            lambda self, b, s: compact(
                b, self._bucket_pids(b, self.left_keys, n_buckets) == s),
            static_argnums=1)

        sub_builds: List[List[ColumnarBatch]] = [[] for _ in range(n_buckets)]
        for b in build_batches:
            for s in range(n_buckets):
                piece = split_build(b, s)
                if host_read("gracePieceRows", piece.num_rows) > 0:
                    sub_builds[s].append(piece)

        sub_stream: List[List[SpillableBatch]] = \
            [[] for _ in range(n_buckets)]
        stream_schema = self.left.output_schema
        for batch in stream_iter:
            for s in range(n_buckets):
                piece = split_stream(batch, s)
                if host_read("gracePieceRows", piece.num_rows) > 0:
                    sub_stream[s].append(register_with_retry(
                        piece, stream_schema, catalog=cat,
                        name=f"{self.name}.grace"))

        for s in range(n_buckets):
            def pieces(bucket=s):
                for sp in sub_stream[bucket]:
                    out = acquire_with_retry(sp, name=f"{self.name}.grace")
                    sp.done_with()
                    yield out
            try:
                yield from self._probe(sub_builds[s], pieces())
            finally:
                for sp in sub_stream[s]:
                    sp.close()


class BroadcastNestedLoopJoinExec(BinaryExec):
    """Cross / conditional nested-loop join (reference:
    GpuBroadcastNestedLoopJoinExec). Tiles the build side so each expansion
    stays inside a bounded capacity."""

    def coalesce_goal_for_child(self, i):
        # stream side wants sized batches; the build side is concatenated
        # whole (RequireSingleBatch — reference: GpuShuffledHashJoinExec
        # build-side single-batch contract)
        from .coalesce import RequireSingleBatch, TargetSize
        return TargetSize() if i == 0 else RequireSingleBatch()

    def __init__(self, join_type: JoinType, left: Exec, right: Exec,
                 condition: Optional[Expression] = None,
                 ctx: Optional[EvalContext] = None,
                 max_tile_rows: int = 1 << 20):
        super().__init__(left, right, ctx)
        self.join_type = join_type
        self.max_tile_rows = max_tile_rows
        lf, rf = left.output_schema.fields, right.output_schema.fields
        pair_schema = Schema(list(lf) + list(rf))
        l_nullable = join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)
        r_nullable = join_type in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
        if join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            self._schema = left.output_schema
        elif join_type is JoinType.EXISTENCE:
            self._schema = Schema(list(lf) + [Field("exists", T.BOOLEAN,
                                                    False)])
        else:
            self._schema = Schema(
                [Field(f.name, f.dtype, f.nullable or l_nullable)
                 for f in lf] +
                [Field(f.name, f.dtype, f.nullable or r_nullable)
                 for f in rf])
        # the condition sees the (left, right) PAIR row, whatever the
        # join type projects out (reference: AST closures in
        # GpuBroadcastNestedLoopJoinExec conditional variants)
        self.condition = condition.bind(pair_schema) if condition else None
        programs = KernelPrograms(self, ("join_type", "condition"))
        self._cross_jit = programs.jit("cross", type(self)._cross_kernel)
        self._count_jit = programs.jit("count", type(self)._count_kernel)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def _keep_mask(self, stream: ColumnarBatch, build: ColumnarBatch):
        s_cap, b_cap = stream.capacity, build.capacity
        out_cap = s_cap * b_cap
        j = jnp.arange(out_cap, dtype=jnp.int32)
        si, bi = j // b_cap, j % b_cap
        live = (si < stream.num_rows) & (bi < build.num_rows)
        s_cols = [gather_column(c, si, live) for c in stream.columns]
        b_cols = [gather_column(c, bi, live) for c in build.columns]
        out = ColumnarBatch(tuple(s_cols + b_cols),
                            jnp.asarray(out_cap, jnp.int32))
        keep = live
        if self.condition is not None:
            c = self.condition.eval(out, self.ctx)
            keep = keep & c.data & c.validity
        return out, keep, si, bi

    def _matches(self, keep, si, bi, s_cap: int, b_cap: int):
        # NOT indices_are_sorted: masking drops condition-failing slots to
        # the sentinel segment BETWEEN ascending si values, so the ids are
        # no longer monotone and the sorted-scatter lowering would be
        # unsound
        seg_s = jnp.where(keep, si, s_cap)
        s_m = jax.ops.segment_sum(keep.astype(jnp.int32), seg_s,
                                  num_segments=s_cap + 1)[:s_cap]
        seg_b = jnp.where(keep, bi, b_cap)
        b_m = jax.ops.segment_sum(keep.astype(jnp.int32), seg_b,
                                  num_segments=b_cap + 1)[:b_cap]
        return s_m, b_m

    def _cross_kernel(self, stream: ColumnarBatch, build: ColumnarBatch):
        out, keep, si, bi = self._keep_mask(stream, build)
        if self.join_type in (JoinType.INNER, JoinType.CROSS):
            # no tails -> no match bookkeeping; keep the kernel lean
            return compact(out, keep), None, None
        s_m, b_m = self._matches(keep, si, bi, stream.capacity,
                                 build.capacity)
        # live slots are interleaved (row-major tiles), so always compact
        return compact(out, keep), s_m, b_m

    def _count_kernel(self, stream: ColumnarBatch, build: ColumnarBatch):
        _, keep, si, bi = self._keep_mask(stream, build)
        return self._matches(keep, si, bi, stream.capacity, build.capacity)

    @property
    def num_partitions(self) -> int:
        # RIGHT/FULL outer emit the unmatched-build tail exactly once, so
        # every stream partition folds into one (broadcast build — same
        # policy as HashJoinExec)
        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            return 1
        return self.left.num_partitions

    @property
    def planned_partitions(self) -> int:
        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            return 1
        return self.left.planned_partitions

    def _build_tiles(self, build: ColumnarBatch, stream_cap: int):
        """(offset, piece) tiles of the build side bounded so one
        expansion stays under max_tile_rows output slots."""
        if stream_cap * build.capacity <= self.max_tile_rows:
            yield 0, build
            return
        tile = max(self.max_tile_rows // stream_cap, 1)
        tile_cap = bucket_capacity(tile)
        n_build = int(host_read("buildRows", build.num_rows))
        for off in range(0, max(n_build, 1), tile_cap):
            yield off, _slice_tile(build, jnp.int32(off),
                                   jnp.int32(tile_cap), tile_cap)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        build_batches = [b for cp in range(self.right.num_partitions)
                         for b in self.right.execute_partition(cp)]
        if not build_batches:
            from ..batch import empty_batch
            build = empty_batch(self.right.output_schema)
        elif len(build_batches) == 1:
            build = build_batches[0]
        elif self.join_type in (JoinType.INNER, JoinType.CROSS):
            # no cross-batch match bookkeeping: stream build batches one
            # at a time instead of materializing a padded concat (these
            # types never fold stream partitions, so read just p)
            for stream in self.left.execute_partition(p):
                for b in build_batches:
                    for _, piece in self._build_tiles(b, stream.capacity):
                        pairs, _, _ = self._cross_jit(stream, piece)
                        yield pairs
            return
        else:
            build = concat_batches(
                build_batches,
                bucket_capacity(sum(b.capacity for b in build_batches)))

        if self.num_partitions == 1 and self.left.num_partitions > 1:
            stream_parts: Sequence[int] = range(self.left.num_partitions)
        else:
            stream_parts = (p,)
        pair_out = self.join_type in (JoinType.INNER, JoinType.CROSS,
                                      JoinType.LEFT_OUTER,
                                      JoinType.RIGHT_OUTER,
                                      JoinType.FULL_OUTER)
        matched_build = jnp.zeros(build.capacity, jnp.int32)
        for sp in stream_parts:
            for stream in self.left.execute_partition(sp):
                s_matched = jnp.zeros(stream.capacity, jnp.int32)
                for off, piece in self._build_tiles(build,
                                                    stream.capacity):
                    if pair_out:
                        pairs, s_m, b_m = self._cross_jit(stream, piece)
                        yield pairs
                    else:
                        s_m, b_m = self._count_jit(stream, piece)
                    if s_m is not None:
                        s_matched = s_matched + s_m
                        matched_build = matched_build.at[
                            off:off + piece.capacity].add(
                            b_m[:min(piece.capacity,
                                     build.capacity - off)])
                yield from self._emit_stream_tail(stream, s_matched)

        if self.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            unmatched = build.row_mask() & (matched_build == 0)
            null_left = _null_gather(
                self._empty_like(self.left.output_schema), build.capacity)
            tail = ColumnarBatch(tuple(null_left) + build.columns,
                                 build.num_rows)
            yield compact(tail, unmatched)

    @staticmethod
    def _empty_like(schema: Schema) -> ColumnarBatch:
        from ..batch import empty_batch
        return empty_batch(schema, 1)

    def _emit_stream_tail(self, stream: ColumnarBatch,
                          s_matched) -> Iterator[ColumnarBatch]:
        jt = self.join_type
        if jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER):
            unmatched = stream.row_mask() & (s_matched == 0)
            null_right = _null_gather(
                self._empty_like(self.right.output_schema),
                stream.capacity)
            tail = ColumnarBatch(stream.columns + tuple(null_right),
                                 stream.num_rows)
            yield compact(tail, unmatched)
        elif jt is JoinType.LEFT_SEMI:
            yield compact(stream, s_matched > 0)
        elif jt is JoinType.LEFT_ANTI:
            yield compact(stream, stream.row_mask() & (s_matched == 0))
        elif jt is JoinType.EXISTENCE:
            exists = DeviceColumn((s_matched > 0), stream.row_mask(),
                                  None, T.BOOLEAN)
            yield ColumnarBatch(stream.columns + (exists,),
                                stream.num_rows)
