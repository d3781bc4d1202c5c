"""The one place the benchmark touches the engine's plan-builder surface:
a query's ``scan(table)`` as a Parquet scan by path, pruned to the columns
the query reads (Spark's ReadSchema). Importing it initialises no JAX
backend."""

import sys

from . import loader

if loader.REPO not in sys.path:
    sys.path.insert(0, loader.REPO)


def scanner(written, query):
    def scan(table):
        from spark_rapids_tpu.io.parquet import ParquetSource
        from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
        src = ParquetSource(list(written[table]["paths"]),
                            columns=list(query.TABLES[table]))
        return DataFrame(LogicalScan((), source=src, _schema=src.schema()))
    return scan


def scanned_rows(written, query):
    """Rows of every table the query scans, a table scanned twice counted
    twice: what one completed query adds to ``rows_per_s``."""
    times = getattr(query, "SCANS", {})
    return sum(written[t]["rows"] * times.get(t, 1) for t in query.TABLES)
