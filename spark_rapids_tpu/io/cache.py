"""DataFrame cache serializer.

Reference: ParquetCachedBatchSerializer.scala:260 — df.cache() stores
compressed Parquet blobs on the host instead of Spark's row-based
DefaultCachedBatchSerializer, so re-reads decode straight to columnar.
Same design: cached partitions live as in-memory Parquet buffers (snappy),
rebuilt into device batches on demand.
"""

from __future__ import annotations

import io
from typing import Dict, List, Optional

import pyarrow as pa
import pyarrow.parquet as pq

from ..batch import ColumnarBatch, Schema, from_arrow, to_arrow
from ..exec.base import Exec, LeafExec


class CachedRelation:
    """Materialized, parquet-compressed cache of a plan's output."""

    def __init__(self, schema: Schema, partitions: List[bytes]):
        self.schema = schema
        self._partitions = partitions

    @classmethod
    def build(cls, plan: Exec) -> "CachedRelation":
        schema = plan.output_schema
        parts: List[bytes] = []
        for p in range(plan.num_partitions):
            tables = [to_arrow(b, schema) for b in plan.execute_partition(p)]
            buf = io.BytesIO()
            if tables:
                pq.write_table(pa.concat_tables(tables), buf,
                               compression="snappy")
            parts.append(buf.getvalue())
        return cls(schema, parts)

    def size_bytes(self) -> int:
        return sum(len(p) for p in self._partitions)

    @property
    def num_partitions(self) -> int:
        return len(self._partitions)

    def read_all(self) -> pa.Table:
        """Interpreter-side access (LogicalScan.source duck type)."""
        tabs = [self.read_partition(p) for p in range(self.num_partitions)]
        tabs = [t for t in tabs if t is not None]
        if not tabs:
            from .. import types as T
            return pa.table({f.name: pa.array([], T.to_arrow(f.dtype))
                             for f in self.schema})
        return pa.concat_tables(tabs)

    def read_partition(self, p: int) -> Optional[pa.Table]:
        blob = self._partitions[p]
        if not blob:
            return None
        return pq.read_table(io.BytesIO(blob))


class InMemoryRelationExec(LeafExec):
    """Scan over a CachedRelation (reference: GpuInMemoryTableScanExec)."""

    def __init__(self, cached: CachedRelation):
        super().__init__()
        self.cached = cached

    @property
    def output_schema(self) -> Schema:
        return self.cached.schema

    @property
    def num_partitions(self) -> int:
        return self.cached.num_partitions

    planned_partitions = num_partitions    # a plan fact

    def do_execute_partition(self, p: int):
        t = self.cached.read_partition(p)
        if t is None or t.num_rows == 0:
            return
        batch, _ = from_arrow(t, schema=self.cached.schema)
        yield batch
