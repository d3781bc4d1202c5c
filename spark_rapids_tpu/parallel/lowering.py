"""Planner→mesh lowering: compile a PLANNED physical query onto one SPMD
XLA program over a device mesh.

Reference shape: GpuShuffleExchangeExecBase.scala:262 — the planner's
exchange nodes define the distributed dataflow; executors move the bytes.
Here the planner's output (Overrides.plan) is pattern-matched bottom-up and
each supported operator chain is fused into a single `shard_map` program:

    scan partitions          → per-device input shards (host-side split)
    Project/Filter           → per-device traced kernels
    ShuffleExchangeExec      → `mesh_exchange` (all_to_all over ICI)
    BroadcastExchangeExec    → `mesh_broadcast` (all_gather)
    HashAggregateExec P/F    → update / merge segment kernels
    HashJoinExec (broadcast) → sorted-hash join with STATIC output capacity

The whole query stage becomes ONE XLA program — no host round-trip between
operators, which is the TPU-native answer to the reference's per-task
iterator pipeline (SURVEY.md §3.3/§3.4).

Static shapes: a jitted program cannot host-sync to size join output the
way the host path does (exec/join.py two-phase sizing), so the mesh join
uses `join_expansion × stream_capacity` slots and returns an OVERFLOW flag;
the stage re-lowers with a doubled factor when it fires (the same
retry-on-capacity contract the bucketed batch design uses everywhere).
Unsupported plan shapes simply stay on the host path — lowering is an
optimization pass, never a correctness gate.
"""

from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..exec.aggregate import AggregateMode, HashAggregateExec
from ..exec.base import Exec, LeafExec
from ..exec.basic import FilterExec, InMemoryScanExec, ProjectExec
from ..exec.coalesce import CoalesceBatchesExec
from ..exec.common import compact, concat_batches, slice_batch
from ..exec.join import HashJoinExec, JoinType
from ..expressions.hashing import murmur3_batch
from ..shuffle.exchange import BroadcastExchangeExec, ShuffleExchangeExec
from ..shuffle.partitioning import (HashPartitioning, RoundRobinPartitioning,
                                    SinglePartitioning)
from .mesh import mesh_broadcast, mesh_exchange, stack_batches, \
    unstack_batches


class MeshUnsupported(Exception):
    """Plan shape outside the mesh-fusable subset (host path runs it)."""


class MeshCapacityError(RuntimeError):
    """Join expansion overflowed even after retries."""


_MESH_JOIN_TYPES = (JoinType.INNER, JoinType.LEFT_OUTER, JoinType.LEFT_SEMI,
                    JoinType.LEFT_ANTI, JoinType.EXISTENCE)


class MeshLowering:
    """Bottom-up pattern matcher producing a local-step function."""

    def __init__(self, mesh: Mesh, axis: str = "data",
                 join_expansion: int = 1):
        # join_expansion starts LEAN (output slots = stream capacity):
        # most planned equi-joins expand <= 1x after filters, and halving
        # the static output capacity halves every downstream kernel in
        # the fused program. A fan-out join overflows its flag and the
        # stage retraces at twice the factor (_run's retry loop).
        self.mesh = mesh
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self.join_expansion = join_expansion
        # chained hash exchanges must NOT compound capacity by n_dev each:
        # balanced routing receives ~cap rows, so bound the output at
        # exchange_factor*cap and flag overflow for the stage retry loop
        # (SinglePartitioning still gets the lossless n_dev*cap — ALL rows
        # genuinely land on one device there)
        self.exchange_factor = 2
        # partial-aggregate outputs keep their INPUT capacity (static
        # shapes), but carry only distinct-key rows — routing them at full
        # width makes the exchange and the final merge re-sort millions of
        # dead slots. Slice to this bucket before routing; the overflow
        # flag + stage retry (x4) covers genuinely high-cardinality keys.
        self.agg_bucket = 1 << 16
        self.inputs: List[Exec] = []
        self.lowered_names: List[str] = []
        self._trace_flags: List[jax.Array] = []

    def _bounded_exchange(self, b: ColumnarBatch, pids, lossless: bool
                          ) -> ColumnarBatch:
        if lossless or self.exchange_factor >= self.n_dev:
            return mesh_exchange(b, pids, self.n_dev, self.axis)
        out_cap = bucket_capacity(self.exchange_factor * b.capacity)
        routed = mesh_exchange(b, pids, self.n_dev, self.axis,
                               out_capacity=out_cap)
        self._trace_flags.append(routed.num_rows > out_cap)
        return routed

    # ------------------------------------------------------------------

    def lower(self, plan: Exec) -> "MeshStageExec":
        self.inputs = []
        self.lowered_names = []
        fn = self._lower_node(plan)
        return MeshStageExec(self, plan, fn)

    def build_local_step(self, plan: Exec) -> Callable:
        """(Re-)trace entry: rebuilds closures so a changed join_expansion
        takes effect (overflow retry)."""
        self.inputs = []
        self.lowered_names = []
        top = self._lower_node(plan)

        def local_step(*args):
            self._trace_flags = []
            out = top(list(args))
            flags = jnp.stack(self._trace_flags) if self._trace_flags \
                else jnp.zeros(1, bool)
            return out, flags

        return local_step

    # ------------------------------------------------------------------

    def _lower_node(self, node: Exec) -> Callable:
        self.lowered_names.append(node.name)
        if isinstance(node, (InMemoryScanExec, LeafExec)):
            from ..plan.overrides import CpuFallbackExec
            if isinstance(node, CpuFallbackExec):
                raise MeshUnsupported("CPU fallback island in plan")
            idx = len(self.inputs)
            self.inputs.append(node)
            return lambda args: args[idx]

        if isinstance(node, FilterExec):
            if node.ctx.ansi:
                raise MeshUnsupported("ANSI error channels need host sync")
            child = self._lower_node(node.child)
            cond = node.condition

            def filt(args):
                b = child(args)
                c = cond.eval(b, node.ctx)
                return compact(b, c.data & c.validity)
            return filt

        if isinstance(node, ProjectExec):
            if node.ctx.ansi:
                raise MeshUnsupported("ANSI error channels need host sync")
            child = self._lower_node(node.child)
            exprs = node.exprs

            def proj(args):
                b = child(args)
                cols = tuple(e.eval(b, node.ctx) for e in exprs)
                return ColumnarBatch(cols, b.num_rows)
            return proj

        if isinstance(node, CoalesceBatchesExec):
            # batch-size discipline is a host-path concern; inside one
            # program the stage is already a single computation
            return self._lower_node(node.child)

        if isinstance(node, HashAggregateExec):
            return self._lower_aggregate(node)

        if isinstance(node, HashJoinExec):
            return self._lower_join(node)

        if isinstance(node, ShuffleExchangeExec):
            return self._lower_exchange(node)

        from ..exec.sort import SortExec, TakeOrderedAndProjectExec
        if isinstance(node, SortExec):
            return self._lower_sort(node)
        if isinstance(node, TakeOrderedAndProjectExec):
            return self._lower_topn(node)

        raise MeshUnsupported(f"{node.name} has no mesh lowering")

    # ------------------------------------------------------------------

    def _lower_exchange(self, ex: ShuffleExchangeExec) -> Callable:
        """Generic hash/single exchange: the building block that lets
        MULTIPLE exchanges chain inside one stage (shuffled joins,
        join→agg pipelines — reference GpuShuffleExchangeExecBase:262).
        Routing is mesh-width (hash % n_dev), not conf shuffle-partition
        width: inside one SPMD program the device IS the partition."""
        part = ex.partitioning
        if not isinstance(part, (HashPartitioning, SinglePartitioning)):
            raise MeshUnsupported(f"{type(part).__name__} exchange")
        self.lowered_names.append("mesh_exchange(all_to_all)")
        child = self._lower_node(ex.child)
        n_dev, axis = self.n_dev, self.axis

        def exch(args):
            b = child(args)
            if isinstance(part, SinglePartitioning):
                pids = jnp.zeros(b.capacity, jnp.int32)
                return self._bounded_exchange(b, pids, lossless=True)
            from ..expressions.hashing import partition_ids
            cols = [e.eval(b) for e in part.exprs]
            pids = partition_ids(cols, n_dev).astype(jnp.int32)
            return self._bounded_exchange(b, pids, lossless=False)
        return exch

    def _lower_sort(self, node) -> Callable:
        """Global sort = splitter-routed range exchange + local sort.
        Splitters come from strided per-device samples of the FIRST key's
        sort operands (null-rank + orderable words), all_gathered and
        sorted so every device derives the same boundaries; rows equal on
        the first key always route together, so the cross-device order is
        total for ANY trailing keys (reference: GpuRangePartitioner's
        sampled bounds)."""
        from ..exec.common import lex_sort_permutation, sort_operands
        from ..exec.sort import sort_batch
        if not node.global_sort or self.n_dev == 1:
            child = self._lower_node(node.child)
            return lambda args: sort_batch(child(args), node.orders,
                                           node.ctx)
        self.lowered_names.append("mesh_exchange(all_to_all)")
        child = self._lower_node(node.child)
        n_dev, axis = self.n_dev, self.axis
        o0 = node.orders[0]
        S = 32   # samples per device

        def srt(args):
            b = child(args)
            k0 = o0.child.eval(b, node.ctx)
            lanes = sort_operands([k0], [o0.descending],
                                  [o0.effective_nulls_first], b.row_mask())
            # lanes[0] is the dead-row flag: dead rows sort greatest, so
            # including it keeps dead samples out of the splitter range
            n_live = jnp.maximum(b.num_rows, 1)
            pos = (jnp.arange(S, dtype=jnp.int32) * n_live) // S
            samp = [jnp.take(l, jnp.clip(pos, 0, b.capacity - 1))
                    for l in lanes]
            # dead devices contribute dead-flagged samples (sort last)
            gathered = [jax.lax.all_gather(s, axis).reshape(-1)
                        for s in samp]
            sperm = lex_sort_permutation(gathered)
            slanes = [jnp.take(g, sperm) for g in gathered]
            # n_dev-1 splitters at even quantiles of the sample pool
            total = n_dev * S
            cut = [(d + 1) * total // n_dev for d in range(n_dev - 1)]
            split = [jnp.stack([l[c] for c in cut]) for l in slanes]
            # pid = how many splitters are lexicographically <= the row
            pid = jnp.zeros(b.capacity, jnp.int32)
            for d in range(n_dev - 1):
                gt = jnp.zeros(b.capacity, bool)
                eq = jnp.ones(b.capacity, bool)
                for li, l in enumerate(lanes):
                    sv = split[li][d]
                    lt_here = eq & (sv < l)
                    gt = gt | lt_here
                    eq = eq & (l == sv)
                # splitter <= row  ⇔  NOT row < splitter
                pid = pid + (gt | eq).astype(jnp.int32)
            routed = self._bounded_exchange(b, pid, lossless=False)
            return sort_batch(routed, node.orders, node.ctx)
        return srt

    def _lower_topn(self, node) -> Callable:
        """TopN: local top-limit → all_gather → global top-limit, emitted
        once (device 0) — reference GpuTakeOrderedAndProjectExec."""
        from ..exec.sort import sort_batch
        self.lowered_names.append("mesh_broadcast(all_gather)")
        child = self._lower_node(node.child)
        n_dev, axis = self.n_dev, self.axis
        limit = node.limit

        def topn_local(b):
            s = sort_batch(b, node.orders, node.ctx)
            n = jnp.minimum(s.num_rows, jnp.int32(limit))
            cut = bucket_capacity(min(limit, b.capacity))
            return slice_batch(s, jnp.int32(0), n, cut)

        def topn(args):
            best = topn_local(child(args))
            gathered = mesh_broadcast(best, n_dev, axis)
            out = topn_local(gathered)
            if node.project:
                cols = tuple(e.eval(out, node.ctx) for e in node.project)
                out = ColumnarBatch(cols, out.num_rows)
            dev = jax.lax.axis_index(axis)
            return ColumnarBatch(out.columns,
                                 jnp.where(dev == 0, out.num_rows,
                                           jnp.int32(0)))
        return topn

    # ------------------------------------------------------------------

    def _lower_aggregate(self, final: HashAggregateExec) -> Callable:
        if final.mode is not AggregateMode.FINAL:
            raise MeshUnsupported(f"aggregate mode {final.mode}")
        # two planner shapes: FINAL(exchange(PARTIAL)) for multi-partition
        # children, FINAL(PARTIAL) when the host plan was single-partition.
        # On the mesh the input is ALWAYS sharded across devices, so both
        # lower to partial → all_to_all → final.
        ex = final.child
        part_kind = None
        if isinstance(ex, ShuffleExchangeExec):
            part_kind = ex.partitioning
            if not isinstance(part_kind,
                              (HashPartitioning, SinglePartitioning)):
                raise MeshUnsupported(f"{type(part_kind).__name__} exchange")
            self.lowered_names.append(ex.name)
            partial = ex.child
        else:
            partial = ex
        if not isinstance(partial, HashAggregateExec) or \
                partial.mode is not AggregateMode.PARTIAL or \
                partial.sort_sensitive:
            raise MeshUnsupported("FINAL child is not a PARTIAL agg")
        self.lowered_names.append(partial.name)
        self.lowered_names.append("mesh_exchange(all_to_all)")
        # join→agg mask fusion: an INNER join directly below the partial
        # aggregate emits its pair slots UNCOMPACTED with a live mask; the
        # aggregate's key sort pushes dead slots to the tail anyway, so a
        # whole compact pass (cumsum + scatter + per-column gathers)
        # disappears from the fused program
        inner = partial.child
        while isinstance(inner, CoalesceBatchesExec):
            inner = inner.child
        masked_join = None
        if isinstance(inner, HashJoinExec) and \
                inner.join_type is JoinType.INNER:
            masked_join = self._lower_join(inner, masked=True)
        else:
            child = self._lower_node(partial.child)
        nk = len(partial.key_fields)
        n_dev, axis = self.n_dev, self.axis

        def agg(args):
            if masked_join is not None:
                b, mask = masked_join(args)
                part = partial._update_kernel(b, mask)
            else:
                b = child(args)
                part = partial._update_kernel(b)
            shrink = bucket_capacity(min(part.capacity, self.agg_bucket))
            if shrink < part.capacity:
                self._trace_flags.append(part.num_rows > shrink)
                part = slice_batch(part, jnp.int32(0), part.num_rows,
                                   shrink)
            if nk == 0 or isinstance(part_kind, SinglePartitioning):
                pids = jnp.zeros(part.capacity, jnp.int32)
            else:
                # planner structure, mesh-width routing: keys land on
                # hash(key) % n_dev regardless of conf shuffle partitions
                h = murmur3_batch(list(part.columns[:nk]))
                m = h % jnp.int32(n_dev)
                pids = jnp.where(m < 0, m + n_dev, m).astype(jnp.int32)
            routed = mesh_exchange(part, pids, n_dev, axis)
            out = final._merge_kernel(routed, final=True)
            if nk == 0:
                dev = jax.lax.axis_index(axis)
                out = ColumnarBatch(
                    out.columns,
                    jnp.where(dev == 0, out.num_rows, jnp.int32(0)))
            return out
        return agg

    def _lower_join(self, join: HashJoinExec, masked: bool = False
                    ) -> Callable:
        if masked:
            self.lowered_names.append(join.name + "(masked)")
        if join.broadcast_build:
            if not isinstance(join.right, BroadcastExchangeExec):
                raise MeshUnsupported("broadcast join without broadcast "
                                      "exchange child")
            if join.join_type not in _MESH_JOIN_TYPES:
                raise MeshUnsupported(
                    f"{join.join_type} needs global matched-build state "
                    f"under a replicated build")
            self.lowered_names.append(join.right.name)
            self.lowered_names.append("mesh_broadcast(all_gather)")
            stream = self._lower_node(join.left)
            build = self._lower_node(join.right.child)
            n_dev, axis = self.n_dev, self.axis

            def jn(args):
                s = stream(args)
                full_build = mesh_broadcast(build(args), n_dev, axis)
                if masked:
                    return self._join_masked(join, s, full_build)
                return self._join_local(join, s, full_build)
            return jn

        # co-partitioned (shuffled) hash join: both children carry their
        # own hash exchanges on the join keys (lowered generically), so
        # equal keys are device-co-located and EVERY join type is correct
        # per device — including RIGHT/FULL outer tails, because each
        # build row lives on exactly one device (reference:
        # GpuShuffledHashJoinExec:85).
        def _hash_exchanged(side: Exec) -> bool:
            return (isinstance(side, ShuffleExchangeExec)
                    and isinstance(side.partitioning, HashPartitioning))
        if not (_hash_exchanged(join.left) and _hash_exchanged(join.right)):
            raise MeshUnsupported(
                "shuffled join children must both be hash exchanges")
        stream = self._lower_node(join.left)
        build = self._lower_node(join.right)

        def jn_shuffled(args):
            s = stream(args)
            b = build(args)
            if masked:
                return self._join_masked(join, s, b)
            return self._join_local(join, s, b)
        return jn_shuffled

    def _join_masked(self, join: HashJoinExec, s: ColumnarBatch,
                     build: ColumnarBatch):
        """INNER probe WITHOUT pair compaction: (pair batch, live mask)
        for the aggregate's fused-mask input."""
        sorted_h, sbuild, _ = join._build_kernel(build)
        lo, counts, offsets, total = join._count_kernel(s, sorted_h)
        out_cap = bucket_capacity(self.join_expansion * s.capacity)
        self._trace_flags.append(total > out_cap)
        return join._expand_masked(s, sbuild, lo, counts, offsets, out_cap)

    def _join_local(self, join: HashJoinExec, s: ColumnarBatch,
                    build: ColumnarBatch) -> ColumnarBatch:
        """Single-device probe incl. outer tails; static output capacity
        with an overflow trace-flag."""
        sorted_h, sbuild, _ = join._build_kernel(build)
        lo, counts, offsets, total = join._count_kernel(s, sorted_h)
        out_cap = bucket_capacity(self.join_expansion * s.capacity)
        matched0 = jnp.zeros(sbuild.capacity, bool)
        self._trace_flags.append(total > out_cap)
        semi = join.join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                                  JoinType.EXISTENCE)
        if semi:
            return join._semi_kernel(s, sbuild,
                                     (lo, counts, offsets), matched0,
                                     out_cap)
        out, matched = join._expand_kernel(s, sbuild,
                                           (lo, counts, offsets), matched0,
                                           out_cap)
        if join.join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            from ..exec.join import _null_gather
            unmatched = sbuild.row_mask() & ~matched
            null_left = _null_gather(join.left_child_placeholder(),
                                     sbuild.capacity)
            tail = compact(ColumnarBatch(tuple(null_left) + sbuild.columns,
                                         sbuild.num_rows), unmatched)
            out = concat_batches(
                [out, tail],
                bucket_capacity(out.capacity + sbuild.capacity))
        return out


# ---------------------------------------------------------------------------
# The stage exec the planner hands the rest of the plan
# ---------------------------------------------------------------------------

class MeshStageExec(LeafExec):
    """One fused SPMD stage; partitions = mesh devices.

    Owns input staging (host split → per-device shards), program execution,
    overflow retries, and unstacking. Inputs re-execute through their
    original exec subtrees, so scans/caches keep their own semantics.
    """

    def __init__(self, lowering: MeshLowering, plan: Exec, _fn):
        super().__init__()
        self.lowering = lowering
        self.plan = plan
        self._schema = plan.output_schema
        self._results: Optional[List[ColumnarBatch]] = None
        self.lowered = list(lowering.lowered_names)
        #: the executable that produced ``_results`` (``.as_text()`` is its
        #: HLO) and, per input, the devices it was staged on with the rows
        #: on each — what ran, not a program built like it
        self.executed = None
        self.staged: List[dict] = []

    @property
    def name(self) -> str:
        return "MeshStageExec"

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self.lowering.n_dev

    planned_partitions = num_partitions    # a plan fact

    # ------------------------------------------------------------------

    def _stack_input(self, e: Exec) -> ColumnarBatch:
        n_dev = self.lowering.n_dev
        batches = [b for p in range(e.num_partitions)
                   for b in e.execute_partition(p)]
        if not batches:
            from ..batch import empty_batch
            pieces = [empty_batch(e.output_schema) for _ in range(n_dev)]
            return stack_batches(pieces, self.lowering.mesh,
                                 self.lowering.axis)
        total = sum(int(b.num_rows) for b in batches)
        big = batches[0] if len(batches) == 1 else concat_batches(
            batches, bucket_capacity(max(total, 1)))
        per_dev = max(-(-total // n_dev), 1)
        cap = bucket_capacity(per_dev)
        sl = jax.jit(slice_batch, static_argnums=3)
        pieces = [sl(big, jnp.int32(d * per_dev), jnp.int32(per_dev), cap)
                  for d in range(n_dev)]
        return stack_batches(pieces, self.lowering.mesh, self.lowering.axis)

    def prepare(self):
        """Build (program, stacked_inputs) at the current join_expansion.
        Exposed so benchmarks can time steady-state program executions."""
        program = self.build_program()
        return program, [self._stack_input(e) for e in self.lowering.inputs]

    def build_program(self):
        """The stage's ONE jitted SPMD program over ``lowering.mesh``; takes
        one stacked batch per ``lowering.inputs`` entry. Needs no data, so
        tools/aot_compile.py compiles it for chips that are not attached."""
        low = self.lowering
        local_step = low.build_local_step(self.plan)
        spec = P(low.axis)

        def wrapped(*args):
            squeezed = [jax.tree.map(lambda x: x[0], a) for a in args]
            out, flags = local_step(*squeezed)
            return (jax.tree.map(lambda x: x[None], out),
                    flags[None])

        return jax.jit(shard_map(
            wrapped, mesh=low.mesh, in_specs=(spec,) * len(low.inputs),
            out_specs=(spec, spec), check_vma=False))

    def _run(self) -> List[ColumnarBatch]:
        if self._results is not None:
            return self._results
        low = self.lowering
        for attempt in range(5):
            program, stacked = self.prepare()
            compiled = program.lower(*stacked).compile()
            out, flags = compiled(*stacked)
            if not bool(np.any(np.asarray(jax.device_get(flags)))):
                self.executed = compiled
                self.staged = [_placement(b) for b in stacked]
                self._results = unstack_batches(out)
                return self._results
            # capacity flags don't say WHICH bucket lost; grow all —
            # retries are rare and the retrace is the expensive part
            low.join_expansion *= 2
            low.exchange_factor *= 2
            low.agg_bucket *= 4
        raise MeshCapacityError(
            f"mesh join overflowed at expansion {low.join_expansion}")

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        yield self._run()[p]


def _placement(stacked: ColumnarBatch) -> dict:
    """Where one stacked input sits: device ids of its shards, live rows on
    each."""
    leaf = jax.tree.leaves(stacked)[0]
    rows = np.asarray(jax.device_get(stacked.num_rows)).reshape(-1)
    return {"devices": sorted(s.device.id for s in leaf.addressable_shards),
            "rows_per_device": [int(r) for r in rows]}


# ---------------------------------------------------------------------------
# Session hook
# ---------------------------------------------------------------------------

def lower_to_mesh(plan: Exec, mesh: Mesh,
                  join_expansion: int = 1) -> MeshStageExec:
    """The fused mesh stage for ``plan``. Raises ``MeshUnsupported``, with
    the reason, when the plan shape (or any node in it) is outside the
    fusable subset — the caller decides what runs instead and says so
    (``Session._lower_to_mesh`` records the reason; no silent switch of
    data plane)."""
    return MeshLowering(mesh, join_expansion=join_expansion).lower(plan)
