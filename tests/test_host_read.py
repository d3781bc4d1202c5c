"""The engine's one door for a host read of a device value
(``trace.host_read``): free with tracing off, a counter and a profiler event
with it on, the only door in the modules on the query path, and a count that
matches the reads the sites made. Counts, not times: everything here runs on
the CPU."""

import ast
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.batch import from_arrow, schema_from_arrow
from spark_rapids_tpu.exec import HashJoinExec, InMemoryScanExec, JoinType, \
    collect
from spark_rapids_tpu.exec.base import LeafExec, UnaryExec
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.plan import table
from spark_rapids_tpu.plan.session import Session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ON = {"spark.rapids.tpu.trace.enabled": "true"}


class _OneBatch(LeafExec):
    def __init__(self):
        super().__init__()
        t = pa.table({"v": np.arange(8, dtype=np.int64)})
        self._schema = schema_from_arrow(t.schema)
        self._batch = from_arrow(t, schema=self._schema)[0]

    @property
    def output_schema(self):
        return self._schema

    def do_execute_partition(self, p):
        yield self._batch


class _ReadsRows(UnaryExec):
    """Reads each batch's row count on the host between its child's
    pulls: its own pull frame's read."""

    got = []

    @property
    def output_schema(self):
        return self.child.output_schema

    def do_execute_partition(self, p):
        for b in self.child.execute_partition(p):
            type(self).got.append(int(qtrace.host_read("testRows",
                                                       b.num_rows)))
            yield b


class _PassThrough(UnaryExec):
    @property
    def output_schema(self):
        return self.child.output_schema

    def do_execute_partition(self, p):
        yield from self.child.execute_partition(p)


def test_with_tracing_off_it_is_the_read_and_one_thread_local_read(
        monkeypatch):
    x = jnp.arange(5, dtype=jnp.int32).sum()
    before = qtrace.metrics().snapshot()["spanCount"]
    assert int(qtrace.host_read("off", x)) == 10
    got = qtrace.host_read("off", (x, [x + 1]))
    assert int(got[0]) == 10 and int(got[1][0]) == 11
    assert isinstance(got[0], np.ndarray)
    assert qtrace.metrics().snapshot()["spanCount"] == before

    class _Counting:
        reads = 0

        def __getattr__(self, name):
            type(self).reads += 1
            raise AttributeError(name)
    monkeypatch.setattr(qtrace, "_TLS", _Counting())
    assert int(qtrace.host_read("off", x)) == 10
    assert _Counting.reads == 1


def test_with_tracing_on_the_innermost_pull_frame_counts_it_and_no_span():
    def spans_of(plan):
        rec = qtrace.FlightRecorder()
        with qtrace.query_trace(recorder=rec):
            assert len(list(plan.execute())) == 1
        return rec.profiles()[0]["spans"]
    _ReadsRows.got = []
    plain = spans_of(_PassThrough(_OneBatch()))
    spans = spans_of(_ReadsRows(_OneBatch()))
    assert _ReadsRows.got == [8]
    assert len(spans) == len(plain)
    by = {s["name"]: s.get("attrs") or {} for s in spans}
    assert by["_ReadsRows"]["hostReads"] == 1
    assert by["_ReadsRows"]["hostReadUs"] >= 0
    # the child's pull was over when the parent read: not the child's
    assert "hostReads" not in by["_OneBatch"]
    assert not any("hostReads" in (s.get("attrs") or {}) for s in plain)


def test_past_the_span_cap_a_read_still_counts():
    x = jnp.arange(3).sum()
    with qtrace.query_trace(max_spans=2) as tr:
        with qtrace.span("only") as only:
            for _ in range(3):
                qtrace.host_read("capped", x)
            with qtrace.span("dropped"):
                qtrace.host_read("capped", x)
    assert len(tr.profile()["spans"]) == 2 and tr.dropped == 1
    assert only.attrs["hostReads"] == 4


#: the modules on the query path whose reads go through the door
DOOR_MODULES = [os.path.join("exec", n) for n in sorted(os.listdir(
    os.path.join(REPO, "spark_rapids_tpu", "exec"))) if n.endswith(".py")] \
    + [os.path.join("shuffle", "exchange.py"), os.path.join("io", "scan.py")]


def _bare_reads(tree):
    """Line numbers of host reads that bypass ``host_read``:
    ``int(<...>.num_rows)``, any ``device_get``, and ``.tolist()`` /
    ``.item()`` of anything that is not a ``host_read``'s result."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id == "int" and node.args \
                and isinstance(node.args[0], ast.Attribute) \
                and node.args[0].attr == "num_rows":
            out.append(node.lineno)
        elif (isinstance(f, ast.Attribute) and f.attr == "device_get") \
                or (isinstance(f, ast.Name) and f.id == "device_get"):
            out.append(node.lineno)
        elif isinstance(f, ast.Attribute) and f.attr in ("tolist", "item") \
                and not (isinstance(f.value, ast.Call)
                         and _called(f.value) == "host_read"):
            out.append(node.lineno)
    return out


def _called(call):
    f = call.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", None)


def test_every_host_read_on_the_query_path_goes_through_the_door():
    offenders = []
    for rel in DOOR_MODULES:
        with open(os.path.join(REPO, "spark_rapids_tpu", rel)) as f:
            tree = ast.parse(f.read())
        offenders += [f"{rel}:{n}" for n in _bare_reads(tree)]
    assert offenders == []


@pytest.mark.parametrize("source,found", [
    ("n = int(batch.num_rows)", 1),
    ("rows = jax.device_get(x)", 1),
    ("counts = counts.tolist()", 1),
    ("v = total.item()", 1),
    ("n = int(host_read('rows', batch.num_rows))", 0),
    ("counts = qtrace.host_read('splitCounts', counts).tolist()", 0),
    ("cap = int(batch.capacity)", 0),
], ids=["int_num_rows", "device_get", "tolist", "item", "door_int",
        "door_tolist", "capacity"])
def test_the_door_check_tells_a_bare_read(source, found):
    assert len(_bare_reads(ast.parse(source))) == found


@pytest.fixture
def counted_reads(monkeypatch):
    """Every read the door makes, by the device_get it calls."""
    calls = []

    def get(value):
        calls.append(value)
        return jax.device_get(value)
    monkeypatch.setattr(qtrace, "_DEVICE_GET", get)
    return calls


def _orders(n, seed, prefix=""):
    rng = np.random.default_rng(seed)
    return pa.table({prefix + "o": pa.array(rng.integers(0, n // 6, n)),
                     prefix + "v": pa.array(np.arange(n, dtype=np.int64))})


def _summed(spans, key, name=None):
    return sum((s.get("attrs") or {}).get(key, 0) for s in spans
               if name is None or s["name"] == name)


def test_a_traced_join_counts_one_read_per_probe_and_the_build_reads(
        counted_reads):
    left, right = _orders(1200, 1), _orders(400, 2, prefix="r_")

    def join():
        return HashJoinExec([col("o")], [col("r_o")], JoinType.INNER,
                            InMemoryScanExec(left, batch_rows=256),
                            InMemoryScanExec(right))
    want = collect(join())
    del counted_reads[:]
    rec = qtrace.FlightRecorder()
    with qtrace.query_trace(recorder=rec):
        got = collect(join())
    assert got.equals(want)
    spans = rec.profiles()[0]["spans"]
    probes = _summed(spans, "joinProbeBatches", "HashJoinExec")
    assert probes == 5
    # the build's rows, a total a probe batch, the build's rounds once
    assert _summed(spans, "hostReads", "HashJoinExec") == probes + 2 \
        >= probes
    assert _summed(spans, "hostReads") == len(counted_reads)
    assert _summed(spans, "hostReadUs") >= 0


def test_the_viewer_prints_the_reads_by_span(capsys):
    import importlib
    import sys
    left, right = _orders(1200, 5), _orders(400, 6, prefix="r_")
    join = HashJoinExec([col("o")], [col("r_o")], JoinType.INNER,
                        InMemoryScanExec(left, batch_rows=256),
                        InMemoryScanExec(right))
    rec = qtrace.FlightRecorder()
    with qtrace.query_trace(recorder=rec):
        collect(join)
    profile = rec.profiles()[0]
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        viewer = importlib.import_module("trace_viewer")
    finally:
        sys.path.pop(0)
    rows = {r["name"]: r for r in viewer.host_read_table(profile)}
    assert set(rows) == {"HashJoinExec"}
    join_row = rows["HashJoinExec"]
    assert join_row["hostReads"] == _summed(profile["spans"], "hostReads")
    assert join_row["insideUs"] == _summed(profile["spans"], "pullUs",
                                           "HashJoinExec")
    assert 0 <= join_row["hostReadUs"] <= join_row["insideUs"]
    viewer.print_tables([profile])
    printed = capsys.readouterr().out
    assert "hostReadUs" in printed and "of inside" in printed


def test_a_traced_aggregate_counts_every_read_its_sites_made(counted_reads):
    rng = np.random.default_rng(7)
    t = pa.table({"k": rng.integers(0, 5000, 20000).astype(np.int64),
                  "v": rng.integers(-9, 9, 20000).astype(np.int64)})
    df = (table(t, num_slices=2).where(col("v") > lit(-5)).group_by("k")
          .agg(Sum(col("v")).alias("s"), Count().alias("n")))
    want = Session().collect(df)
    del counted_reads[:]
    ses = Session(dict(TRACE_ON))
    got = ses.collect(df)
    assert got.sort_by("k").equals(want.sort_by("k"))
    spans = qtrace.flight_recorder().profiles(ses.last_query_id)[0]["spans"]
    reads = _summed(spans, "hostReads")
    assert reads == len(counted_reads) > 0
    assert _summed(spans, "hostReads", "HashAggregateExec") > 0


def test_each_read_is_a_profiler_event_named_by_its_purpose(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        from rtbench import xplane
    finally:
        sys.path.pop(0)
    left, right = _orders(600, 3), _orders(300, 4, prefix="r_")
    join = HashJoinExec([col("o")], [col("r_o")], JoinType.INNER,
                        InMemoryScanExec(left, batch_rows=256),
                        InMemoryScanExec(right))
    collect(join)                                   # compiles
    join = HashJoinExec([col("o")], [col("r_o")], JoinType.INNER,
                        InMemoryScanExec(left, batch_rows=256),
                        InMemoryScanExec(right))
    with jax.profiler.trace(str(tmp_path)):
        with qtrace.query_trace():
            collect(join)
    trace = xplane.load(xplane.find_trace(str(tmp_path)))
    names = Counter(e[0] for events in trace["host"].values()
                    for e in events if e[0].startswith("sync."))
    assert names == {"sync.buildRows": 1, "sync.joinTotal": 3,
                     "sync.joinRounds": 1}
