"""Mean per query of ``FileSourceScanExec[parquet].opTime`` from the reply's
operator metrics: host seconds of Parquet decode, summed over the query's
scans. The engine counts it in nanoseconds (``exec/base.py`` times with
``perf_counter_ns``)."""

KEY = "FileSourceScanExec[parquet].opTime"


def read(run):
    values = [r.metrics[KEY] for r in run["done"] if KEY in r.metrics]
    if not values:
        return None
    return sum(values) / len(values) / 1e6
