"""Whole-stage fusion: one XLA program for a linear device-resident subplan.

The XLA twin of Spark's whole-stage codegen, and the single-chip sibling of
``parallel/lowering.lower_to_mesh``. The reference pipelines operators as
JVM iterators over per-op JNI kernel launches (SURVEY.md §3.3); here a whole
scan→filter→join→aggregate/sort stage traces into ONE jitted program, so a
stage execution is ONE dispatch with NO host round trips.

Two-phase join output sizing (the reference sizes gather maps with a device
count read back by the host — GpuHashJoin.scala:811 JoinGatherer sizing)
becomes OPTIMISTIC static sizing: the fused program sizes the join output at
the stream-side capacity bucket times a planner hint, and emits an overflow
FLAG alongside the result instead of forcing a mid-stage sync. The runner
validates flags at its single materialization point and re-executes with a
larger bucket when the guess lost (rare: FK joins produce at most one match
per probe row). ANSI/capacity error counters ride the same flag vector.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..batch import ColumnarBatch, Schema, bucket_capacity
from ..expressions.base import EvalContext
from .base import Exec, LeafExec
from .basic import (FilterExec, InMemoryScanExec, LocalLimitExec,
                    ProjectExec, _raise_ansi)
from .common import compact, jit_named, slice_batch
from .join import HashJoinExec, JoinType
from .sort import SortExec, TakeOrderedAndProjectExec, sort_batch

_FUSABLE_JOIN_TYPES = (JoinType.INNER, JoinType.LEFT_OUTER,
                       JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                       JoinType.EXISTENCE)


class FusionUnsupported(Exception):
    pass


class _Planner:
    """Static walk: validate every node is fusable and collect the leaf
    scans (each must supply exactly ONE device-resident batch)."""

    def __init__(self):
        self.scans: List[InMemoryScanExec] = []

    def walk(self, node: Exec) -> None:
        if isinstance(node, InMemoryScanExec):
            batches = list(node._all_batches())
            if len(batches) != 1:
                raise FusionUnsupported("scan must yield exactly one batch")
            self.scans.append(node)
            return
        if isinstance(node, (ProjectExec, FilterExec, SortExec,
                             TakeOrderedAndProjectExec, LocalLimitExec)):
            self.walk(node.children[0])
            return
        if isinstance(node, HashJoinExec):
            if node.join_type not in _FUSABLE_JOIN_TYPES:
                raise FusionUnsupported(
                    f"join type {node.join_type} needs cross-batch state")
            self.walk(node.left)
            self.walk(node.right)
            return
        from .aggregate import AggregateMode, HashAggregateExec
        if isinstance(node, HashAggregateExec):
            if node.mode not in (AggregateMode.COMPLETE,
                                 AggregateMode.PARTIAL):
                raise FusionUnsupported("merge-mode agg joins batches")
            if node.sort_sensitive:
                raise FusionUnsupported("sort-sensitive aggregate")
            self.walk(node.children[0])
            return
        raise FusionUnsupported(f"{type(node).__name__} not fusable")


class FusedStage:
    """A compiled whole-stage program plus its staged inputs."""

    def __init__(self, plan: Exec, expand_factor: int = 1):
        self.plan = plan
        self.expand_factor = expand_factor
        planner = _Planner()
        planner.walk(plan)
        self.scans = planner.scans
        self.inputs = [next(iter(s._all_batches())) for s in self.scans]
        self._program = jit_named("FusedStage_program", self._trace)

    # -- trace ---------------------------------------------------------

    def _trace(self, *batches: ColumnarBatch):
        by_scan: Dict[int, ColumnarBatch] = {
            id(s): b for s, b in zip(self.scans, batches)}
        # separate channels: ANSI/capacity error counters (raise as such)
        # vs join-bucket overflow (drives the exact-size retrace)
        self._err_kinds: List[str] = []
        self._err_vals: List[jax.Array] = []
        self._join_over: List[jax.Array] = []
        self._join_needs: List[jax.Array] = []
        out = self._emit(self.plan, by_scan, self._join_over)
        errs = (jnp.stack(self._err_vals) if self._err_vals
                else jnp.zeros(1, jnp.int64))
        over = (jnp.stack(self._join_over) if self._join_over
                else jnp.zeros(1, jnp.int64))
        needs = (jnp.stack(self._join_needs) if self._join_needs
                 else jnp.zeros(1, jnp.int64))
        return out, errs, over, needs

    def _emit(self, node: Exec, by_scan, flags) -> ColumnarBatch:
        if isinstance(node, InMemoryScanExec):
            return by_scan[id(node)]

        if isinstance(node, ProjectExec):
            b = self._emit(node.children[0], by_scan, flags)
            ctx = EvalContext(node.ctx.ansi, {})
            # raw_eval: identity projections keep dictionary-encoded
            # strings encoded through the fused stage (same contract as
            # the standalone ProjectExec kernel in basic.py)
            from ..expressions.base import raw_eval
            cols = tuple(raw_eval(e, b, ctx) for e in node.exprs)
            self._err_flags(ctx, flags)
            return ColumnarBatch(cols, b.num_rows)

        if isinstance(node, FilterExec):
            b = self._emit(node.children[0], by_scan, flags)
            ctx = EvalContext(node.ctx.ansi, {})
            c = node.condition.eval(b, ctx)
            self._err_flags(ctx, flags)
            return compact(b, c.data & c.validity)

        if isinstance(node, HashJoinExec):
            stream = self._emit(node.left, by_scan, flags)
            build = self._emit(node.right, by_scan, flags)
            return self._emit_join(node, stream, build, flags)

        if isinstance(node, SortExec):
            b = self._emit(node.children[0], by_scan, flags)
            return sort_batch(b, node.orders, node.ctx)

        if isinstance(node, TakeOrderedAndProjectExec):
            b = self._emit(node.children[0], by_scan, flags)
            s = sort_batch(b, node.orders, node.ctx)
            n = jnp.minimum(s.num_rows, jnp.int32(node.limit))
            cut = bucket_capacity(min(node.limit, b.capacity))
            out = slice_batch(s, jnp.int32(0), n, cut)
            if node.project:
                cols = tuple(e.eval(out, node.ctx) for e in node.project)
                out = ColumnarBatch(cols, out.num_rows)
            return out

        if isinstance(node, LocalLimitExec):
            b = self._emit(node.children[0], by_scan, flags)
            return slice_batch(b, jnp.int32(0), jnp.int32(node.limit))

        from .aggregate import AggregateMode, HashAggregateExec
        if isinstance(node, HashAggregateExec):
            b = self._emit(node.children[0], by_scan, flags)
            part = node._update_kernel(b)
            if node.mode is AggregateMode.COMPLETE:
                return node._merge_kernel(part, final=True)
            return part

        raise AssertionError(f"unplanned node {type(node).__name__}")

    def _emit_join(self, node: HashJoinExec, stream: ColumnarBatch,
                   build: ColumnarBatch, flags) -> ColumnarBatch:
        sorted_h, sbuild, _ = node._build_kernel(build)
        lo, counts, offsets, total = node._count_kernel(stream, sorted_h)
        out_cap = bucket_capacity(stream.capacity * self.expand_factor)
        matched = jnp.zeros(sbuild.capacity, bool)
        semi = node.join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                                  JoinType.EXISTENCE)
        # overflow: candidates that would not fit the optimistic bucket.
        # The needed/available ratio drives the single exact-size retrace.
        flags.append((total > out_cap).astype(jnp.int64))
        self._join_needs.append(
            ((total + out_cap - 1) // out_cap).astype(jnp.int64))
        if semi:
            return node._semi_kernel(stream, sbuild,
                                     (lo, counts, offsets), matched, out_cap)
        out, _ = node._expand_kernel(stream, sbuild,
                                     (lo, counts, offsets), matched, out_cap)
        return out

    def _err_flags(self, ctx: EvalContext, flags) -> None:
        for kind, v in ctx.errors.items():
            self._err_kinds.append(kind)
            self._err_vals.append(sum(v).astype(jnp.int64))

    # -- execution -----------------------------------------------------

    def prepare(self) -> Tuple[object, List[ColumnarBatch]]:
        """(jitted program, staged inputs) — for steady-state benching and
        callers that manage their own flag validation."""
        return self._program, self.inputs

    def run(self, max_retries: int = 3) -> ColumnarBatch:
        """Execute; validate flags at the single materialization sync.
        On join-bucket overflow the program's own needed/available ratios
        size ONE exact retrace (plus headroom for the pathological case
        where a bigger bucket uncovers more candidates downstream)."""
        stage = self
        for _ in range(max_retries):
            out, errs, over, needs = stage._program(*stage.inputs)
            ev = [int(x) for x in errs]
            if stage._err_kinds and any(ev):
                _raise_ansi(dict(zip(stage._err_kinds, ev)))
            if int(jnp.max(over)) == 0:
                return out
            grow = int(jnp.max(needs))
            factor = max(stage.expand_factor * max(grow, 2),
                         stage.expand_factor * 2)
            stage = FusedStage(self.plan, factor)
        raise RuntimeError("fused stage overflowed after retries; "
                           "join output exceeds retry buckets")


def try_fuse(plan: Exec, expand_factor: int = 1) -> Optional[FusedStage]:
    """Compile ``plan`` into one XLA program, or None if any node needs
    cross-batch state / host control flow."""
    try:
        return FusedStage(plan, expand_factor)
    except FusionUnsupported:
        return None


class FusedStageExec(LeafExec):
    """Planner wrapper: the fused program as a one-partition exec, so the
    session's collect path runs whole-stage programs transparently
    (Session.prepare wires this in under sql.fusion.enabled)."""

    def __init__(self, stage: FusedStage):
        super().__init__()
        self.stage = stage

    @property
    def name(self) -> str:
        return "FusedStageExec"

    @property
    def output_schema(self) -> Schema:
        return self.stage.plan.output_schema

    @property
    def num_partitions(self) -> int:
        return 1

    planned_partitions = num_partitions    # a plan fact

    def do_execute_partition(self, p: int):
        yield self.stage.run()


def try_fuse_exec(plan: Exec) -> Optional[FusedStageExec]:
    stage = try_fuse(plan)
    return FusedStageExec(stage) if stage is not None else None
