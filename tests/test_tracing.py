"""The engine's one tracer (``spark_rapids_tpu.trace``) as a profiler
source: with a query trace open every operator pull is also a
``jax.profiler`` event of the operator's name, results stay the same, and
with no trace open a span site does nothing."""

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.plan import Session, table


def test_collect_under_tracing_matches():
    t = pa.table({"k": np.arange(64, dtype=np.int32) % 5,
                  "v": np.arange(64, dtype=np.int64)})

    def q():
        return (table(t).where(col("v") > lit(3))
                .group_by("k")
                .agg(Sum(col("v")).alias("s"), Count().alias("c")))
    base = Session().collect(q())
    ses = Session({"spark.rapids.tpu.trace.enabled": "true"})
    traced = ses.collect(q())
    assert traced.equals(base)
    # span names == metric name prefixes (docs/profiling.md contract):
    # what the profiler's trace calls a pull, the metrics call the exec
    metric_names = {k.split(".")[0] for k in ses.metrics()}
    assert any("Aggregate" in n for n in metric_names)
    spans = qtrace.flight_recorder().profiles(ses.last_query_id)[0]["spans"]
    operators = {s["name"] for s in spans if s["kind"] == "operator"}
    assert operators and operators <= metric_names


def test_span_site_is_a_noop_when_off():
    assert not qtrace.active()
    with qtrace.span("X") as sp:
        assert sp is None
    assert qtrace.open_operator("X", 0) is None
