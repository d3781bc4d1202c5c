"""Arrow-exchange Python UDF execs.

Reference: SURVEY.md §2.11 / §3.5 — GpuArrowEvalPythonExec.scala:241
(device → Arrow IPC → python worker → Arrow → device), GpuMapInBatchExec,
GpuAggregateInPandasExec, gated by PythonWorkerSemaphore.scala:41. Here the
engine IS Python, so the "worker" is an in-process callable behind the same
Arrow columnar boundary (to_arrow/from_arrow is the exact exchange the
reference does over a socket), and the worker semaphore bounds concurrent
evaluation the same way.

Two shapes, mirroring the reference's exec family:
- ArrowEvalPythonExec: per-batch scalar pandas UDF — f(pd.Series...) ->
  pd.Series appended as new columns.
- MapInBatchExec: f(pd.DataFrame) -> pd.DataFrame with an arbitrary output
  schema (mapInPandas).
"""

from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa

from ..batch import ColumnarBatch, Field, Schema, from_arrow, to_arrow
from ..memory.semaphore import TpuSemaphore
from .base import Exec, UnaryExec

# reference: PythonWorkerSemaphore bounds concurrent GPU-using workers
_python_semaphore = TpuSemaphore(4)


# ---- forked-worker adapters (module-level: must pickle to the daemon;
# reference: python/rapids/worker.py applies the UDF inside the fork) ----

def _scalar_udf_on_table(table: pa.Table, fn, input_cols, out_names):
    pdf = table.to_pandas()
    args = [pdf[c] for c in input_cols]
    result = fn(*args)
    if not isinstance(result, (list, tuple)):
        result = [result]
    for name, series in zip(out_names, result):
        pdf[name] = series
    return pa.Table.from_pandas(pdf, preserve_index=False)


def _map_udf_on_table(table: pa.Table, fn):
    return pa.Table.from_pandas(fn(table.to_pandas()),
                                preserve_index=False)


class ArrowEvalPythonExec(UnaryExec):
    """Append columns computed by a scalar pandas UDF."""

    def __init__(self, fn: Callable, input_cols: Sequence[str],
                 output_fields: Sequence[Field], child: Exec,
                 use_daemon: bool = True):
        super().__init__(child)
        self.fn = fn
        self.input_cols = list(input_cols)
        self.output_fields = list(output_fields)
        self.use_daemon = use_daemon
        self._schema = Schema(list(child.output_schema.fields)
                              + self.output_fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        child_schema = self.child.output_schema
        from ..python_worker import worker_apply
        out_names = [f.name for f in self.output_fields]
        for batch in self.child.execute_partition(p):
            with _python_semaphore.task():
                table = to_arrow(batch, child_schema)     # D2H + Arrow
                # forked worker when the UDF pickles (process isolation —
                # a crashing UDF fails the query, not the executor);
                # closures downgrade to in-process
                out = worker_apply(_scalar_udf_on_table, table,
                                   (self.fn, self.input_cols, out_names),
                                   use_daemon=self.use_daemon,
                                   pool_size=getattr(
                                       self, "pool_size", None))
                # cast to the declared output schema (pandas widens types)
                from .. import types as T
                target = pa.schema(
                    [pa.field(f.name, T.to_arrow(f.dtype), f.nullable)
                     for f in self._schema])
                out = out.select(self._schema.names).cast(target)
            nb, _ = from_arrow(out, schema=self._schema)   # H2D
            yield nb


class MapInBatchExec(UnaryExec):
    """mapInPandas: df-in, df-out with a new schema (reference:
    GpuMapInBatchExec)."""

    def __init__(self, fn: Callable, output_schema: Schema, child: Exec,
                 use_daemon: bool = True):
        super().__init__(child)
        self.fn = fn
        self.use_daemon = use_daemon
        self._schema = output_schema

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        child_schema = self.child.output_schema
        from .. import types as T
        target = pa.schema([pa.field(f.name, T.to_arrow(f.dtype), f.nullable)
                            for f in self._schema])
        from ..python_worker import worker_apply
        for batch in self.child.execute_partition(p):
            with _python_semaphore.task():
                table = to_arrow(batch, child_schema)
                out = worker_apply(_map_udf_on_table, table, (self.fn,),
                                   use_daemon=self.use_daemon,
                                   pool_size=getattr(
                                       self, "pool_size", None))
                out = out.select(self._schema.names).cast(target)
            if out.num_rows == 0:
                continue
            nb, _ = from_arrow(out, schema=self._schema)
            yield nb


def _to_pandas(batches, schema):
    import pandas as pd
    frames = [to_arrow(b, schema).to_pandas() for b in batches]
    if not frames:
        import pyarrow as _pa
        from .. import types as T
        empty = _pa.table({f.name: _pa.array([], T.to_arrow(f.dtype))
                           for f in schema})
        return empty.to_pandas()
    return pd.concat(frames, ignore_index=True) if len(frames) > 1 \
        else frames[0]


def _emit(pdf, schema: Schema) -> Iterator[ColumnarBatch]:
    from .. import types as T
    target = pa.schema([pa.field(f.name, T.to_arrow(f.dtype), f.nullable)
                        for f in schema])
    out = pa.Table.from_pandas(pdf, preserve_index=False)
    out = out.select(schema.names).cast(target)
    if out.num_rows == 0:
        return
    nb, _ = from_arrow(out, schema=schema)
    yield nb


class AggregateInPandasExec(UnaryExec):
    """groupBy().agg(pandas_udf): one output row per group (reference:
    GpuAggregateInPandasExec — there the cudf groupby feeds per-group
    Arrow batches to the worker; here pandas groupby plays cudf's role).
    The planner co-locates groups with a hash exchange first, exactly as
    it does for native aggregates."""

    def __init__(self, keys: Sequence[str], fn: Callable,
                 input_cols: Sequence[str],
                 output_fields: Sequence[Field], child: Exec):
        super().__init__(child)
        self.keys = list(keys)
        self.fn = fn
        self.input_cols = list(input_cols)
        self.output_fields = list(output_fields)
        key_fields = [child.output_schema.field(k) for k in self.keys]
        self._schema = Schema(key_fields + self.output_fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        with _python_semaphore.task():
            pdf = _to_pandas(list(self.child.execute_partition(p)),
                             self.child.output_schema)
            rows = []
            if len(pdf):
                for key, grp in pdf.groupby(self.keys, dropna=False,
                                            sort=False):
                    if not isinstance(key, tuple):
                        key = (key,)
                    res = self.fn(*[grp[c] for c in self.input_cols])
                    if not isinstance(res, (list, tuple)):
                        res = [res]
                    rows.append(list(key) + list(res))
            import pandas as pd
            out = pd.DataFrame(rows, columns=self._schema.names)
        yield from _emit(out, self._schema)


class FlatMapGroupsInPandasExec(UnaryExec):
    """applyInPandas: f(group_df) -> df with an arbitrary schema
    (reference: GpuFlatMapGroupsInPandasExec)."""

    def __init__(self, keys: Sequence[str], fn: Callable,
                 output_schema: Schema, child: Exec):
        super().__init__(child)
        self.keys = list(keys)
        self.fn = fn
        self._schema = output_schema

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        import pandas as pd
        with _python_semaphore.task():
            pdf = _to_pandas(list(self.child.execute_partition(p)),
                             self.child.output_schema)
            outs = []
            if len(pdf):
                for _, grp in pdf.groupby(self.keys, dropna=False,
                                          sort=False):
                    outs.append(self.fn(grp.reset_index(drop=True)))
            out = pd.concat(outs, ignore_index=True) if outs else \
                pd.DataFrame(columns=self._schema.names)
        yield from _emit(out, self._schema)


class CoGroupInPandasExec(Exec):
    """cogroup().applyInPandas: f(left_group_df, right_group_df) -> df
    (reference: GpuFlatMapCoGroupsInPandasExec). Both sides must be
    co-partitioned on their keys (planner inserts the exchanges)."""

    def __init__(self, left_keys: Sequence[str],
                 right_keys: Sequence[str], fn: Callable,
                 output_schema: Schema, left: Exec, right: Exec):
        super().__init__((left, right))
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.fn = fn
        self._schema = output_schema

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @staticmethod
    def _norm_key(k) -> Tuple:
        """Group keys as dict keys: NaN objects are identity-hashed in
        CPython, so null keys normalize to None (Spark cogroups null keys
        as ONE group)."""
        if not isinstance(k, tuple):
            k = (k,)
        return tuple(None if (v is None or v != v) else v for v in k)

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        import pandas as pd
        left, right = self.children
        if left.num_partitions != right.num_partitions:
            raise ValueError(
                f"cogroup sides must be co-partitioned: "
                f"{left.num_partitions} vs {right.num_partitions} "
                f"partitions (insert matching hash exchanges)")
        with _python_semaphore.task():
            lf = _to_pandas(list(left.execute_partition(p)),
                            left.output_schema)
            rf = _to_pandas(list(right.execute_partition(p)),
                            right.output_schema)
            lgroups = {self._norm_key(k): g
                       for k, g in lf.groupby(self.left_keys, dropna=False,
                                              sort=False)} if len(lf) else {}
            rgroups = {self._norm_key(k): g
                       for k, g in rf.groupby(self.right_keys,
                                              dropna=False, sort=False)} \
                if len(rf) else {}
            outs = []
            for key in list(lgroups) + [k for k in rgroups
                                        if k not in lgroups]:
                lg = lgroups.get(key)
                rg = rgroups.get(key)
                if lg is None:
                    lg = lf.iloc[0:0]
                if rg is None:
                    rg = rf.iloc[0:0]
                outs.append(self.fn(lg.reset_index(drop=True),
                                    rg.reset_index(drop=True)))
            out = pd.concat(outs, ignore_index=True) if outs else \
                pd.DataFrame(columns=self._schema.names)
        yield from _emit(out, self._schema)


class WindowInPandasExec(UnaryExec):
    """Window pandas UDF over whole partitions (reference:
    GpuWindowInPandasExec — unbounded-frame shape): f(series...) returns
    a same-length series per partition group; results append as columns
    in the original row order."""

    def __init__(self, keys: Sequence[str], fn: Callable,
                 input_cols: Sequence[str],
                 output_fields: Sequence[Field], child: Exec):
        super().__init__(child)
        self.keys = list(keys)
        self.fn = fn
        self.input_cols = list(input_cols)
        self.output_fields = list(output_fields)
        self._schema = Schema(list(child.output_schema.fields)
                              + self.output_fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        import pandas as pd
        with _python_semaphore.task():
            pdf = _to_pandas(list(self.child.execute_partition(p)),
                             self.child.output_schema)
            for f in self.output_fields:
                pdf[f.name] = None
            if len(pdf):
                for _, grp in pdf.groupby(self.keys, dropna=False,
                                          sort=False):
                    res = self.fn(*[grp[c] for c in self.input_cols])
                    if not isinstance(res, (list, tuple)):
                        res = [res]
                    for f, series in zip(self.output_fields, res):
                        pdf.loc[grp.index, f.name] = \
                            series.values if hasattr(series, "values") \
                            else series
        yield from _emit(pdf, self._schema)
