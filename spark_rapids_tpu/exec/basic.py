"""Basic physical operators: scan-from-memory, project, filter, limit, union,
range, sample, expand.

Reference: sql-plugin/.../basicPhysicalOperators.scala (GpuProjectExec:147,
GpuFilterExec:423, GpuRangeExec:644, GpuSampleExec), limit.scala,
GpuExpandExec. The TPU-first difference: FilterExec compacts with a cumsum
scatter (no host sync, no dynamic shape) and a project→filter chain traces
into one XLA computation.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T
from ..batch import (ColumnarBatch, DeviceColumn, Field, Schema,
                     bucket_capacity, from_arrow)
from ..expressions.base import Alias, EvalContext, Expression, raw_eval
from ..trace import host_read
from ..types import TypeKind
from .base import Exec, LeafExec, UnaryExec
from .common import KernelPrograms, compact, dec128_role, slice_batch


def output_name(e: Expression, i: int) -> str:
    if isinstance(e, Alias):
        return e.name
    name = getattr(e, "name", "")
    return name or f"col{i}"


def bind_all(exprs: Sequence[Expression], schema: Schema) -> List[Expression]:
    return [e.bind(schema) for e in exprs]


def schema_of(exprs: Sequence[Expression]) -> Schema:
    return Schema([Field(output_name(e, i), e.dtype, e.nullable)
                   for i, e in enumerate(exprs)])


class InMemoryScanExec(LeafExec):
    """Leaf feeding pre-loaded data; the H2D boundary for tests and caches
    (reference: GpuInMemoryTableScanExec)."""

    def __init__(self, data, schema: Optional[Schema] = None,
                 batch_rows: Optional[int] = None, num_slices: int = 1,
                 ctx: EvalContext = EvalContext(),
                 dict_conf: Optional[tuple] = None,
                 share: Optional[tuple] = None):
        super().__init__(ctx)
        self._num_slices = num_slices
        # (enabled, maxCardinality, maxCardinalityFraction) for the H2D
        # boundary; the planner threads the SESSION conf here so
        # dictEncoding.enabled=false is honored off the file-scan path
        # too. None = registry defaults (direct test construction).
        self._dict_conf = dict_conf
        # (ScanShareRegistry, key, digest, max_bytes) when cross-query
        # scan sharing is on (plan/sharing.py; the planner threads it) —
        # device batches are immutable, so concurrent queries over the
        # same table content ride one refcounted H2D upload. None = the
        # historical private-upload path, bit for bit.
        self._share = share
        self._share_entry = None
        if isinstance(data, pa.Table):
            self._tables = [data]
            self._batches = None
            if schema is None:
                from ..batch import schema_from_arrow
                schema = schema_from_arrow(data.schema)
        else:
            self._batches = list(data)
            self._tables = None
            assert schema is not None, "schema required for device batches"
        self._schema = schema
        self._batch_rows = batch_rows

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self._num_slices

    planned_partitions = num_partitions    # a plan fact

    def _upload_batches(self):
        from ..memory.retry import maybe_inject, with_retry_no_split
        from ..trace import span

        def h2d(chunk):
            maybe_inject("scan.h2d")
            with span("scan.h2d", kind="transfer") as sp:
                batch, _ = from_arrow(chunk, schema=self._schema,
                                      dict_conf=self._dict_conf)
                if sp is not None:
                    sp.attrs["hostBytes"] = chunk.nbytes
                    # padded to the capacity bucket
                    sp.attrs["deviceBytes"] = batch.size_bytes()
            return batch

        for table in self._tables:
            n = table.num_rows
            step = self._batch_rows or max(n, 1)
            for off in range(0, max(n, 1), step):
                chunk = table.slice(off, step)
                # H2D under the retry loop. NO split here: batch count
                # feeds the partition round-robin below and the fusion
                # planner's exactly-one-batch contract (fuse.py) — a
                # split would reshuffle rows across partitions / drop
                # the second half of a fused input. File scans split at
                # their H2D instead (io/scan.py).
                yield with_retry_no_split(lambda c=chunk: h2d(c),
                                          name=self.name)
                if n == 0:
                    break

    def _all_batches(self):
        if self._batches is not None:
            yield from self._batches
            return
        if self._share is None:
            yield from self._upload_batches()
            return
        yield from self._shared_batches()

    def _shared_batches(self):
        """Acquire (or perform) the one refcounted upload for this table
        content; the pin is released in do_close()."""
        if self._share_entry is not None:
            return list(self._share_entry.batches)
        from ..plan import sharing
        registry, key, digest, max_bytes = self._share
        entry, uploader = registry.acquire(key, digest,
                                           max_bytes=max_bytes)
        if uploader:
            try:
                batches = list(self._upload_batches())
            except BaseException:
                registry.abort(entry)   # a parked acquirer retries
                raise
            nbytes = sum(t.nbytes for t in self._tables)
            registry.publish(entry, batches, nbytes)
            sharing.metrics().note("scan_share_uploads")
        else:
            sharing.metrics().note("scan_share_hits")
        self._share_entry = entry
        return list(entry.batches)

    def do_close(self) -> None:
        entry = self._share_entry
        if entry is not None:
            self._share_entry = None
            self._share[0].release(entry)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        for i, b in enumerate(self._all_batches()):
            if i % self._num_slices == p:
                yield b


class ProjectExec(UnaryExec):
    """Reference: GpuProjectExec (basicPhysicalOperators.scala:147)."""

    def __init__(self, exprs: Sequence[Expression], child: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.exprs = bind_all(exprs, child.output_schema)
        self._schema = schema_of(self.exprs)

        def kernel(self, batch: ColumnarBatch, bseed):
            # errors dict is always live: ANSI rows report conditionally,
            # CAPACITY_* budget overflows report unconditionally. bseed is
            # a traced per-(partition, batch) scalar for stateless PRNG
            # expressions (Rand) — traced, so no per-batch retraces.
            ctx = EvalContext(self.ctx.ansi, {}, batch_seed=bseed)
            # raw_eval: a bare column reference passes the stored column
            # through VERBATIM — dictionary-encoded strings keep their
            # encoding across identity projections (select/reorder), the
            # common case; computed expressions decode at the choke point
            from ..expressions.base import raw_eval
            cols = tuple(raw_eval(e, batch, ctx) for e in self.exprs)
            return ColumnarBatch(cols, batch.num_rows), _sum_errors(ctx)

        self._kernel = KernelPrograms(self, ("exprs",)).jit(
            dec128_role("project", [f.dtype for f in self._schema]), kernel)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        for i, batch in enumerate(self.child.execute_partition(p)):
            # deterministic on re-execution: derived from position, not a
            # global counter
            out, errs = self._kernel(batch,
                                     jnp.uint32((p << 16) ^ (i & 0xFFFF)))
            _raise_ansi(errs)
            yield out


class ArithmeticException(ArithmeticError):
    """ANSI-mode evaluation error (Spark's ArithmeticException parity)."""


def _sum_errors(ctx) -> dict:
    return {k: sum(v) for k, v in ctx.errors.items()}


def _raise_ansi(errs: dict) -> None:
    """Raise for the first kind of error a batch's rows hit; ``errs``
    holds the device's counts, read here (none to read where the
    projection checks nothing)."""
    if errs:
        _raise_counted(host_read("ansiErrors", errs))


def _raise_counted(errs: dict) -> None:
    from ..batch import CapacityError
    for kind, count in errs.items():
        if int(count) > 0:
            if kind.startswith("CAPACITY"):
                raise CapacityError(
                    f"[{kind}] {int(count)} row(s) exceeded a fixed device "
                    f"budget; raise the budget or fall back to CPU")
            raise ArithmeticException(
                f"[{kind}] {int(count)} row(s) failed (ANSI mode)")


class FilterExec(UnaryExec):
    """Reference: GpuFilterExec (basicPhysicalOperators.scala:423).

    Null condition values drop the row (Spark semantics). Compaction is a
    cumsum scatter on device — no host round trip.
    """

    def __init__(self, condition: Expression, child: Exec,
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.condition = condition.bind(child.output_schema)
        if self.condition.dtype.kind is not TypeKind.BOOLEAN:
            raise TypeError(f"filter condition must be boolean, got "
                            f"{self.condition.dtype}")

        def kernel(self, batch: ColumnarBatch):
            ctx = EvalContext(self.ctx.ansi, {})
            c = self.condition.eval(batch, ctx)
            keep = c.data & c.validity
            return compact(batch, keep), _sum_errors(ctx)

        self._kernel = KernelPrograms(self, ("condition",)).jit(
            "filter", kernel)

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        for batch in self.child.execute_partition(p):
            out, errs = self._kernel(batch)
            _raise_ansi(errs)
            yield out


class LocalLimitExec(UnaryExec):
    """Reference: limit.scala GpuLocalLimitExec — cap rows per partition."""

    def __init__(self, limit: int, child: Exec):
        super().__init__(child)
        self.limit = limit
        self._kernel = KernelPrograms(self, ()).jit(
            "limit",
            lambda self, b, remaining: slice_batch(b, jnp.int32(0),
                                                   remaining))

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        for batch in self.child.execute_partition(p):
            if remaining <= 0:
                break
            out = self._kernel(batch, jnp.int32(remaining))
            # host sync: limits are control flow
            remaining -= int(host_read("limitRows", out.num_rows))
            yield out


class GlobalLimitExec(LocalLimitExec):
    """Reference: GpuGlobalLimitExec — drains all upstream partitions into
    one (the planner places it after a single-partition exchange)."""

    @property
    def num_partitions(self) -> int:
        return 1

    planned_partitions = num_partitions    # a plan fact

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        for cp in range(self.child.num_partitions):
            for batch in self.child.execute_partition(cp):
                if remaining <= 0:
                    return
                out = self._kernel(batch, jnp.int32(remaining))
                remaining -= int(host_read("limitRows", out.num_rows))
                yield out


class UnionExec(Exec):
    """Reference: GpuUnionExec — concatenation of children's partitions."""

    def __init__(self, children: Sequence[Exec]):
        super().__init__(children)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    @property
    def num_partitions(self) -> int:
        return sum(c.num_partitions for c in self.children)

    @property
    def planned_partitions(self) -> int:
        return sum(c.planned_partitions for c in self.children)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        for c in self.children:
            if p < c.num_partitions:
                yield from c.execute_partition(p)
                return
            p -= c.num_partitions
        raise IndexError(p)


class RangeExec(LeafExec):
    """Reference: GpuRangeExec (basicPhysicalOperators.scala:644)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 batch_rows: int = 1 << 20, name: str = "id"):
        super().__init__()
        if step == 0:
            raise ValueError("step must not be 0")
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows
        self._schema = Schema([Field(name, T.INT64, nullable=False)])

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        total = max(0, -(-(self.end - self.start) // self.step))
        emitted = 0
        while emitted < total or (total == 0 and emitted == 0):
            n = min(self.batch_rows, total - emitted)
            cap = bucket_capacity(max(n, 1))
            base = self.start + emitted * self.step
            data = (jnp.arange(cap, dtype=jnp.int64) * self.step + base)
            live = jnp.arange(cap, dtype=jnp.int32) < n
            col = DeviceColumn(jnp.where(live, data, 0), live, None, T.INT64)
            yield ColumnarBatch((col,), jnp.asarray(n, jnp.int32))
            emitted += n
            if total == 0:
                break


class SampleExec(UnaryExec):
    """Bernoulli row sample (reference: GpuSampleExec, GpuPoissonSampler)."""

    def __init__(self, fraction: float, seed: int, child: Exec):
        super().__init__(child)
        self.fraction, self.seed = fraction, seed

        def kernel(self, batch: ColumnarBatch, key) -> ColumnarBatch:
            u = jax.random.uniform(key, (batch.capacity,))
            return compact(batch, u < self.fraction)

        self._kernel = KernelPrograms(self, ("fraction",)).jit(
            "sample", kernel)

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        root = jax.random.fold_in(jax.random.PRNGKey(self.seed), p)
        for i, batch in enumerate(self.child.execute_partition(p)):
            yield self._kernel(batch, jax.random.fold_in(root, i))


class ExpandExec(UnaryExec):
    """Reference: GpuExpandExec — one output batch per projection per input
    batch (rollup/cube/grouping sets), all of a batch's projections made by
    ONE program, each at the input's capacity. A bare column reference
    hands on the stored column, dictionary codes included, so a string key
    stays one code lane for the aggregate above.

    ``emit``: the projections this exec makes (default: all). Under a
    rollup the planner asks for the finest alone and has the coarser levels
    made from the aggregate's partials (``aggregate.RollupExec``); the
    schema is that of all projections either way, so a key that a coarser
    level nulls is nullable from here on."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 child: Exec, ctx: Optional[EvalContext] = None,
                 emit: Optional[Sequence[int]] = None):
        super().__init__(child, ctx)
        self.projections = [bind_all(p, child.output_schema)
                            for p in projections]
        self.emit = tuple(range(len(self.projections))) if emit is None \
            else tuple(emit)
        first = schema_of(self.projections[0])
        # nullability is the union across projections
        self._schema = Schema([
            Field(f.name, f.dtype,
                  any(p[i].nullable for p in self.projections))
            for i, f in enumerate(first)])

        def kernel(self, batch: ColumnarBatch):
            return tuple(
                ColumnarBatch(tuple(raw_eval(e, batch, self.ctx)
                                    for e in self.projections[pi]),
                              batch.num_rows)
                for pi in self.emit)

        self._kernel = KernelPrograms(self, ("projections", "emit")).jit(
            "expand", kernel)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from .. import trace as qtrace
        qtrace.count(expandProjections=len(self.emit))
        for batch in self.child.execute_partition(p):
            out = self._kernel(batch)
            qtrace.count(expandBatchesOut=len(out),
                         expandSlotsOut=len(out) * int(batch.capacity))
            yield from out
