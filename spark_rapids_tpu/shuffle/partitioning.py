"""Partitionings — the engine's parallelism strategies.

Reference: GpuHashPartitioningBase.scala (murmur3 + Table.partition),
GpuRangePartitioner.scala:171 (sampled bounds + sort-based slicing),
GpuRoundRobinPartitioning.scala, GpuSinglePartitioning.scala; device-side
slicing in GpuPartitioning.scala:30-86.

Spark-compatibility matters here: HashPartitioning must produce
``pmod(murmur3(row, seed=42), n)`` bit-exactly, or a mixed CPU/TPU cluster
would route the same key to different reducers (the reference carries the
same constraint vs CPU Spark — HashFunctions.scala).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..batch import ColumnarBatch, DeviceColumn, Schema
from ..expressions.base import EvalContext, Expression
from ..expressions.hashing import murmur3_batch


class Partitioning:
    num_partitions: int

    def bind(self, schema: Schema) -> "Partitioning":
        return self

    def partition_ids(self, batch: ColumnarBatch,
                      ctx: EvalContext = EvalContext()) -> jnp.ndarray:
        """int32[cap] target partition per row (live rows only meaningful)."""
        raise NotImplementedError

    def program_key(self):
        """Everything ``partition_ids`` reads when it is traced, as values
        ``exec.common.program_key`` can encode, for the program table; None
        where that includes run-time state (sampled bounds), so that the
        exchange keeps a program of its own."""
        return None


@dataclass
class HashPartitioning(Partitioning):
    exprs: Sequence[Expression]
    num_partitions: int = 8

    def bind(self, schema: Schema) -> "HashPartitioning":
        return HashPartitioning([e.bind(schema) for e in self.exprs],
                                self.num_partitions)

    def partition_ids(self, batch, ctx=EvalContext()):
        # raw_eval keeps dict-encoded string keys in code form:
        # murmur3_batch hashes the dictionary entries once and gathers,
        # still bit-exact with Spark's pmod(murmur3(row, 42), n) routing
        from ..expressions.base import raw_eval
        cols = [raw_eval(e, batch, ctx) for e in self.exprs]
        h = murmur3_batch(cols)
        m = h % jnp.int32(self.num_partitions)
        return jnp.where(m < 0, m + self.num_partitions, m).astype(jnp.int32)

    def program_key(self):
        return [list(self.exprs), self.num_partitions]


@dataclass
class RoundRobinPartitioning(Partitioning):
    num_partitions: int = 8
    start: int = 0

    def partition_ids(self, batch, ctx=EvalContext()):
        cap = batch.capacity
        return ((jnp.arange(cap, dtype=jnp.int32) + self.start)
                % self.num_partitions)

    def program_key(self):
        return [self.num_partitions, self.start]


@dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids(self, batch, ctx=EvalContext()):
        return jnp.zeros(batch.capacity, jnp.int32)

    def program_key(self):
        return []


@dataclass
class RangePartitioning(Partitioning):
    """Range partitioning from sampled bounds.

    The exchange samples key rows across input batches (reference:
    SamplingUtils.scala reservoir sample), sorts them, and picks
    ``num_partitions - 1`` bound rows; each data row then binary-searches its
    target partition. Bounds are set once via ``set_bounds`` before use.
    """

    orders: Sequence  # List[SortOrder]
    num_partitions: int = 8

    def __post_init__(self):
        self._bound_words: Optional[List[jnp.ndarray]] = None
        self._descending = [o.descending for o in self.orders]
        self._nulls_first = [o.effective_nulls_first for o in self.orders]

    def bind(self, schema: Schema) -> "RangePartitioning":
        p = RangePartitioning([o.bind(schema) for o in self.orders],
                              self.num_partitions)
        return p

    def key_columns(self, batch: ColumnarBatch,
                    ctx: EvalContext = EvalContext()) -> List[DeviceColumn]:
        return [o.child.eval(batch, ctx) for o in self.orders]

    def _norm_words(self, key_cols: List[DeviceColumn],
                    live: jnp.ndarray) -> List[jnp.ndarray]:
        from ..exec.common import sort_operands
        # drop the leading liveness operand: bounds and rows share it
        return sort_operands(key_cols, self._descending, self._nulls_first,
                             live)[1:]

    def set_bounds(self, bound_cols: List[DeviceColumn], n_bounds) -> None:
        """``bound_cols`` hold the sorted bound rows (possibly fewer than
        num_partitions-1; n_bounds is traced-safe static int)."""
        live = jnp.arange(bound_cols[0].validity.shape[0]) < n_bounds
        self._bound_words = self._norm_words(bound_cols, live)
        self._n_bounds = n_bounds

    def partition_ids(self, batch, ctx=EvalContext()):
        assert self._bound_words is not None, "set_bounds first"
        keys = self.key_columns(batch, ctx)
        words = self._norm_words(keys, batch.row_mask())
        cap = batch.capacity
        pid = jnp.zeros(cap, jnp.int32)
        # row > bound lexicographically → row belongs to a later partition
        for b in range(self._n_bounds):
            gt = jnp.zeros(cap, bool)
            decided = jnp.zeros(cap, bool)
            for w, bw in zip(words, self._bound_words):
                bv = bw[b]
                gt = gt | (~decided & (w > bv))
                decided = decided | (w != bv)
            # Spark's RangePartitioner: keys <= bound stay in the earlier
            # partition (lteq in getPartition), so only strictly-greater
            # rows advance.
            pid = pid + gt.astype(jnp.int32)
        return jnp.minimum(pid, self.num_partitions - 1)
