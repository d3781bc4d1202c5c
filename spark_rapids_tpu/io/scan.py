"""File scan exec + user-facing read helpers.

Reference: GpuFileSourceScanExec.scala:67 — files are split across
partitions, each partition's reader streams host tables through the chosen
strategy and lands device batches at the H2D boundary.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

from ..batch import ColumnarBatch, Schema, from_arrow
from ..exec.base import LeafExec
from .source import FileSource


class FileSourceScanExec(LeafExec):
    def __init__(self, source: FileSource, num_slices: int = 1,
                 share: Optional[tuple] = None):
        super().__init__()
        from ..exec.base import DEBUG, MODERATE, Metric
        # prefetch pipeline visibility (reference: the multi-file reader's
        # bufferTime/filterTime metric split): overlapTime = decode work
        # hidden behind this exec's device_put/compute
        self.metrics["overlapTime"] = Metric("overlapTime", MODERATE)
        self.metrics["prefetchWaitTime"] = Metric("prefetchWaitTime", DEBUG)
        # (ScanShareRegistry, max_bytes) when cross-query scan sharing
        # is on: single-partition file scans publish their decoded +
        # uploaded device batches refcounted under the source's
        # stat-keyed share_key, so repeat queries ride one decode+H2D
        self._share = share
        self._share_entry = None
        self.source = source
        #: per-PLAN file list: DPP prunes THIS copy, never the shared
        #: FileSource (a pruned source would corrupt later queries)
        self.files = list(source.files)
        self.files_pruned = 0
        self._num_slices = max(1, min(num_slices, len(source.files)))
        self._schema = source.schema()

    def prune_partitions(self, name: str, allowed) -> int:
        """DPP entry: drop this plan's files whose hive partition value
        cannot join (reference: GpuSubqueryBroadcastExec feeding the
        scan's partition filters)."""
        values = getattr(self.source, "_pvalues", {}).get(name)
        if not values:
            return 0
        before = len(self.files)
        keep = [f for f in self.files if values[f] in allowed]
        self.files = keep or self.files[:1]
        pruned = before - len(self.files)
        self.files_pruned += pruned
        # surface the stat on the source for observability/tests
        self.source.files_pruned = getattr(
            self.source, "files_pruned", 0) + pruned
        self._num_slices = max(1, min(self._num_slices, len(self.files)))
        return pruned

    @property
    def name(self):
        return f"FileSourceScanExec[{self.source.format_name}]"

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self._num_slices

    planned_partitions = num_partitions    # a plan fact

    def _files_for(self, p: int) -> List[str]:
        return [f for i, f in enumerate(self.files)
                if i % self._num_slices == p]

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if self._share is not None and self._num_slices == 1:
            yield from self._shared_batches()
            return
        yield from self._stream_batches(p)

    def _shared_batches(self) -> Iterator[ColumnarBatch]:
        """Single-partition path through the scan-share registry: the
        first query decodes + uploads and publishes; concurrent and
        following queries over unchanged files replay the refcounted
        device batches (released in do_close)."""
        from ..plan import sharing
        registry, max_bytes = self._share
        key, digest = self.source.share_key(self.files)
        entry, uploader = registry.acquire(key, digest,
                                           max_bytes=max_bytes)
        if uploader:
            try:
                batches = list(self._stream_batches(0))
            except BaseException:
                registry.abort(entry)
                raise
            nbytes = sum(getattr(b, "nbytes", 0) or 0 for b in batches) \
                or (self.source.estimated_bytes() or 0)
            registry.publish(entry, batches, nbytes)
            sharing.metrics().note("scan_share_uploads")
        else:
            sharing.metrics().note("scan_share_hits")
        self._share_entry = entry
        yield from list(entry.batches)

    def do_close(self) -> None:
        entry = self._share_entry
        if entry is not None:
            self._share_entry = None
            self._share[0].release(entry)

    def _stream_batches(self, p: int) -> Iterator[ColumnarBatch]:
        from ..pipeline import close_iterator
        it = self.source.read_split(self._files_for(p),
                                    metrics=self.metrics)
        from ..memory.retry import (maybe_inject, split_host_table,
                                    with_retry)
        from ..trace import span
        try:
            dict_conf = getattr(self.source, "_dict_conf", None)

            def h2d(tbl):
                # dictionary-typed columns (RLE_DICTIONARY scan hand-off)
                # land as codes + dictionary; everything else pads as
                # before. dict_conf carries the session's cardinality
                # thresholds to the fallback decision.
                maybe_inject("scan.h2d")
                with span("scan.h2d", kind="transfer") as sp:
                    batch, _ = from_arrow(tbl, schema=self._schema,
                                          dict_conf=dict_conf)
                    if sp is not None:
                        sp.attrs["hostBytes"] = tbl.nbytes
                        # padded to the capacity bucket
                        sp.attrs["deviceBytes"] = batch.size_bytes()
                return batch

            for host_table in it:
                self.metrics["numOutputRows"].add(host_table.num_rows)
                # H2D under the retry loop: an OOM staging this table
                # halves it (host-side slice) and device_puts the halves —
                # downstream coalesce re-assembles them bit-for-bit
                yield from with_retry(host_table, h2d,
                                      split=split_host_table,
                                      name=self.name)
        finally:
            # consumer abort (limit early-exit) must cancel the prefetch
            # producer promptly — no decode running past the query
            close_iterator(it)


# ---------------------------------------------------------------------------
# read API (session.read.parquet(...) analogue)
# ---------------------------------------------------------------------------

def read_parquet(paths, columns=None, predicate=None, num_slices: int = 1,
                 **kw):
    from ..plan.logical import DataFrame, LogicalScan
    from .parquet import ParquetSource
    src = ParquetSource(paths, columns=columns, predicate=predicate, **kw)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema(),
                                 num_slices=num_slices))


def read_csv(paths, schema=None, header: bool = False, sep: str = ",",
             num_slices: int = 1, **kw):
    from ..plan.logical import DataFrame, LogicalScan
    from .csv import CsvSource
    src = CsvSource(paths, schema=schema, header=header, sep=sep, **kw)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema(),
                                 num_slices=num_slices))


def read_json(paths, schema=None, num_slices: int = 1, **kw):
    from ..plan.logical import DataFrame, LogicalScan
    from .json import JsonSource
    src = JsonSource(paths, schema=schema, **kw)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema(),
                                 num_slices=num_slices))


def read_avro(paths, columns=None, predicate=None, num_slices: int = 1,
              **kw):
    from ..plan.logical import DataFrame, LogicalScan
    from .avro import AvroSource
    src = AvroSource(paths, columns=columns, predicate=predicate, **kw)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema(),
                                 num_slices=num_slices))
