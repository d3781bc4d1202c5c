"""The span tree as something to attribute with (ISSUE 28): operator spans
scoped to their pulls, what JAX and the collector do charged to the exec
that caused it, work on pool threads under the query that asked for it, and
all of it in the profiler's trace under the same names. Counts, not times:
everything here runs on the CPU."""

import ast
import gc
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.batch import from_arrow, schema_from_arrow
from spark_rapids_tpu.exec.base import LeafExec, UnaryExec
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.plan.session import Session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ON = {"spark.rapids.tpu.trace.enabled": "true"}


class _TwoBatches(LeafExec):
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
        yield self._batch


class _JitsBetweenPulls(UnaryExec):
    """After each batch its child yields, a fresh ``jax.jit`` in THIS
    operator's code."""

    @property
    def output_schema(self):
        return self.child.output_schema

    def do_execute_partition(self, p):
        for b in self.child.execute_partition(p):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5))
            yield b


def _scan_frame(tmp_path, files, rows=4000):
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
    rng = np.random.default_rng(5)
    paths = []
    for i in range(files):
        paths.append(str(tmp_path / f"part-{i}.parquet"))
        pq.write_table(pa.table({
            "k": rng.integers(0, 7, rows).astype(np.int64),
            "v": rng.integers(-100, 100, rows).astype(np.int64)}),
            paths[-1])
    src = ParquetSource(paths)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema()))


def _aggregate(df):
    return (df.where(col("v") > lit(-50)).group_by("k")
            .agg(Sum(col("v")).alias("s"), Count().alias("n")))


def _profile_of(ses):
    return qtrace.flight_recorder().profiles(ses.last_query_id)[0]


@pytest.fixture
def no_programs_yet():
    """For a test that counts an exec's FIRST lowering: an earlier test of
    this process may have stated the same program, and then nothing of it
    is lowered again (``compile_cache.ProgramTable``)."""
    from spark_rapids_tpu.compile_cache import program_table
    program_table().clear()


def test_a_lowering_between_pulls_is_the_parents_not_the_open_childs():
    jnp.arange(5) * 3 + 1           # the eager ops of the operand: not ours
    plan = _JitsBetweenPulls(_TwoBatches())
    rec = qtrace.FlightRecorder()
    with qtrace.query_trace(recorder=rec):
        assert len(list(plan.execute())) == 2
    spans = rec.profiles()[0]["spans"]
    by_id = {s["id"]: s for s in spans}
    parent = next(s for s in spans if s["name"] == "_JitsBetweenPulls")
    child = next(s for s in spans if s["name"] == "_TwoBatches")
    assert child["parent"] == parent["id"]
    lowered = [s for s in spans if s["name"] == "jit.lower"
               and s["attrs"]["fun"] == "jit(<lambda>)"]
    assert len(lowered) == 2
    assert {by_id[s["parent"]]["name"] for s in lowered} \
        == {"_JitsBetweenPulls"}
    assert parent["attrs"]["lowerings"] == 2
    assert "lowerings" not in child["attrs"]
    # pull-scoped: two batches and the exhausting pull, rows at the end
    assert child["attrs"]["pulls"] == 3 and child["attrs"]["batches"] == 2
    assert child["attrs"]["rows"] == 16
    assert child["attrs"]["pullUs"] <= parent["attrs"]["pullUs"] \
        <= parent["durUs"]


def test_self_times_add_up_to_execute(tmp_path, no_programs_yet):
    ses = Session(dict(TRACE_ON))
    out = ses.collect(_aggregate(_scan_frame(tmp_path, 1)))
    assert out.num_rows == 7
    spans = _profile_of(ses)["spans"]
    own = qtrace.self_times(spans)
    execute = next(s for s in spans if s["name"] == "execute")
    by_id = {s["id"]: s for s in spans}

    def under_execute(s):
        while s["parent"] is not None:
            if s["parent"] == execute["id"]:
                return True
            s = by_id[s["parent"]]
        return False
    below = [s for s in spans if s["tid"] == execute["tid"]
             and under_execute(s)]
    assert {"scan.h2d", "result.d2h", "jit.lower"} \
        <= {s["name"] for s in below}
    explained = sum(own[s["id"]] for s in below)
    assert explained == pytest.approx(execute["durUs"], rel=0.05)
    # and nothing is explained twice: no span's children outlast it
    assert explained <= execute["durUs"]


def test_a_fresh_wrapper_lowers_once_and_its_second_call_never():
    x = jnp.arange(11, dtype=jnp.int32)
    x.block_until_ready()
    with qtrace.query_trace() as tr:
        with qtrace.span("first") as first:
            f = jax.jit(lambda v: v * 2 + 1)
            f(x)
        with qtrace.span("second") as second:
            f(x)
    assert first.attrs["lowerings"] == 1
    assert first.attrs["compiles"] == 1 and first.attrs["traces"] >= 1
    assert "lowerings" not in second.attrs
    names = [s["name"] for s in tr.profile()["spans"]]
    assert names.count("jit.lower") == 1
    # with no active trace the listeners record nothing
    before = qtrace.metrics().snapshot()["spanCount"]
    jax.jit(lambda v: v * 2 + 2)(x)
    assert qtrace.metrics().snapshot()["spanCount"] == before


def test_past_the_span_cap_the_lowerings_still_count():
    x = jnp.arange(13, dtype=jnp.int32)
    x.block_until_ready()
    with qtrace.query_trace(max_spans=2) as tr:
        with qtrace.span("only") as only:
            jax.jit(lambda v: v - 4)(x)
            jax.jit(lambda v: v - 5)(x)
    p = tr.profile()
    assert len(p["spans"]) == 2 and only.attrs["lowerings"] == 2
    assert p["overflow"]["jit.lower"][0] == 2
    assert p["overflow"]["jit.lower"][1] > 0


def test_with_tracing_off_one_thread_local_read_and_no_gc_callback(
        monkeypatch):
    assert not qtrace.active()
    assert qtrace._on_gc not in gc.callbacks
    with qtrace.query_trace():
        assert gc.callbacks.count(qtrace._on_gc) == 1
        with qtrace.query_trace():      # nested: still one
            assert gc.callbacks.count(qtrace._on_gc) == 1
    assert qtrace._on_gc not in gc.callbacks

    class _Counting:
        reads = 0

        def __getattr__(self, name):
            type(self).reads += 1
            raise AttributeError(name)
    monkeypatch.setattr(qtrace, "_TLS", _Counting())
    qtrace._on_jit_duration(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5, fun_name="f")
    assert _Counting.reads == 1
    qtrace._on_jit_event("/jax/compilation_cache/cache_hits")
    assert _Counting.reads == 2
    qtrace._on_gc("start", {"generation": 2})
    assert _Counting.reads == 3


def test_a_full_collection_is_a_span_of_the_collecting_thread():
    with qtrace.query_trace() as tr:
        with qtrace.span("busy"):
            gc.collect()
    spans = tr.profile()["spans"]
    pause = next(s for s in spans if s["name"] == "gc")
    busy = next(s for s in spans if s["name"] == "busy")
    assert pause["parent"] == busy["id"] and "collected" in pause["attrs"]


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="one core decodes inline")
def test_decode_on_pool_threads_is_the_querys_under_its_scan(tmp_path):
    ses = Session(dict(TRACE_ON))
    ses.collect(_aggregate(_scan_frame(tmp_path, 4)))
    profile = _profile_of(ses)
    assert profile["queryId"] == ses.last_query_id
    spans = profile["spans"]
    by_id = {s["id"]: s for s in spans}
    decodes = [s for s in spans if s["name"] == "scan.decode"]
    assert len(decodes) == 4
    for s in decodes:
        scan = by_id[s["parent"]]
        assert scan["name"] == "FileSourceScanExec[parquet]"
        assert s["tid"] != scan["tid"]
        assert s["attrs"]["rows"] == 4000 and s["attrs"]["bytes"] > 0
        assert s["attrs"]["file"].startswith("part-")
    h2d = [s for s in spans if s["name"] == "scan.h2d"]
    assert h2d and all(by_id[s["parent"]]["kind"] == "operator"
                       and s["attrs"]["deviceBytes"] > 0 for s in h2d)


def test_planning_runs_no_exchange_and_its_span_says_so():
    from spark_rapids_tpu.plan import table
    t = pa.table({"k": np.arange(600, dtype=np.int64) % 13,
                  "v": np.arange(600, dtype=np.int64)})
    ses = Session(dict(TRACE_ON, **{
        "spark.rapids.tpu.sql.adaptive.enabled": "true"}))
    # the outer aggregate's planning asks whether the inner one can have
    # more than one partition; the adaptive exchange under it could say
    # how many it HAS only once its map output exists, so the planner asks
    # what the plan states and runs nothing (ISSUE 33)
    df = (table(t, num_slices=3).group_by("k")
          .agg(Sum(col("v")).alias("s"))
          .group_by("s").agg(Sum(col("k")).alias("ks")))
    base = Session().collect(df)
    assert ses.collect(df).sort_by("s").equals(base.sort_by("s"))
    spans = _profile_of(ses)["spans"]
    names = [s["name"] for s in spans]
    assert {"plan.fingerprint", "plan.overrides"} <= set(names)
    by_id = {s["id"]: s for s in spans}
    asked = [s for s in spans if s["name"] == "plan.materialize"]
    assert asked, names
    for s in asked:
        # the planner's span, never the exec layer's guess
        assert by_id[s["parent"]]["name"] == "plan.overrides"
        assert s["attrs"]["exec"]
    prepare = next(s for s in spans if s["name"] == "plan.prepare")
    # the guard: nothing the planner asked had to run, so no span of any
    # kind lies under a question, and none of an operator, an exchange, a
    # scan or a lowering anywhere under ``plan.prepare``
    assert not any(_under(by_id, by_id.get(s["parent"]), a["id"])
                   for a in asked for s in spans)
    assert [s["name"] for s in spans if _under(by_id, s, prepare["id"])
            and (s["kind"] in ("operator", "shuffle")
                 or s["name"].startswith(("scan.", "jit.")))] == []
    # the exchange ran once, under ``execute``
    execute = next(s for s in spans if s["name"] == "execute")
    writes = [s for s in spans if s["name"] == "ShuffleExchangeExec.write"]
    assert writes and all(_under(by_id, s, execute["id"]) for s in writes)
    # ... and the outer one, planted on "maybe", stood aside: one exchange
    # ran (the one batch of the inner one), one is named
    assert len(writes) == 1
    assert ses.executed_exec_names().count("ShuffleExchangeExec") == 1


def _under(by_id, s, ancestor_id):
    while s is not None:
        if s["id"] == ancestor_id:
            return True
        s = by_id.get(s["parent"])
    return False


def _engine_lines(path, names):
    """``{line name: [event names]}`` of the host lines that hold an event
    called one of ``names``, and how many lines there were."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            held = {e.name for e in line.events} & names
            if held:
                lines.append((line.name, held))
    return lines


def test_a_served_query_is_in_the_profilers_trace_by_name(tmp_path):
    from spark_rapids_tpu.server import PlanClient
    from spark_rapids_tpu.server.server import PlanServer
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        from rtbench import xplane
    finally:
        sys.path.pop(0)
    df = _aggregate(_scan_frame(tmp_path, 4, rows=60000))
    log_dir = str(tmp_path / "profile")
    server = PlanServer(conf=dict(TRACE_ON)).start()
    try:
        with PlanClient("127.0.0.1", server.port) as c:
            want = c.collect(df)                    # compiles
            assert c.profile("start", log_dir) == {"profiling": True,
                                                   "dir": log_dir}
            assert c.profile("start", log_dir)["profiling"] is True
            got = c.collect(df)
            assert c.profile("stop") == {"profiling": False,
                                         "dir": log_dir}
            assert c.profile("stop") == {"profiling": False, "dir": None}
            tree = c.last_trace()
    finally:
        server.stop()
    assert got.equals(want)
    server_leg = next(p for p in tree["profiles"]
                      if p["component"] == "server")
    assert all("selfUs" in s for s in server_leg["spans"])
    path = xplane.find_trace(log_dir)
    assert path is not None
    engine = {"plan.prepare", "execute", "scan.decode", "scan.h2d",
              "HashAggregateExec", "FileSourceScanExec[parquet]"}
    lines = _engine_lines(path, engine)
    line_names = [n for n, _ in lines]
    assert len(set(line_names)) == len(line_names), line_names
    assert "python" not in line_names
    assert any(n.startswith("rtpu-q-") for n in line_names)
    assert any(n.startswith("rtpu-read-") for n in line_names)
    assert set().union(*(held for _, held in lines)) == engine
    # the benchmark's own reader names an interval inside the decode by
    # it, from the lines of the threads that decoded (what the handler
    # thread does meanwhile, a lowering say, may be shorter and win)
    trace = xplane.load(path)
    pool = {name: events for name, events in trace["host"].items()
            if name.startswith("rtpu-read-")}
    decode = max((e for events in pool.values() for e in events),
                 key=lambda e: e[2])
    name, start, dur = decode
    assert name == "scan.decode"
    assert xplane._covering(pool, start + 0.25 * dur,
                            start + 0.75 * dur) == "scan.decode"
    # and no event's name carries a query id
    qid = tree["queryId"]
    assert not any(qid in e[0] for events in trace["host"].values()
                   for e in events)


def test_thread_names_are_distinct_and_short():
    got = []
    threads = [threading.Thread(
        target=lambda: got.append(qtrace.name_thread("rtpu-test")))
        for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(got)) == 3 and all(len(n) <= 15 for n in got)
    long = []
    t = threading.Thread(target=lambda: (
        long.append(qtrace.name_thread("rtpu-a-long-prefix")),
        long.append(open(f"/proc/self/task/{threading.get_native_id()}"
                         "/comm").read().strip())))
    t.start()
    t.join()
    assert len(long[0]) == 15 and (sys.platform != "linux"
                                   or long[1] == long[0])


SUBPACKAGES = ("exec", "io", "shuffle", "memory")


def test_every_program_is_jitted_through_the_naming_helper():
    """No module of exec/, io/, shuffle/ or memory/ mentions ``jax.jit``
    (or imports ``jit``) but ``exec/common.jit_named``."""
    offenders = []
    for sub in SUBPACKAGES:
        root = os.path.join(REPO, "spark_rapids_tpu", sub)
        for name in sorted(os.listdir(root)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                tree = ast.parse(f.read())
            allowed = set()
            if (sub, name) == ("exec", "common.py"):
                helper = next(n for n in tree.body
                              if isinstance(n, ast.FunctionDef)
                              and n.name == "jit_named")
                allowed = {id(n) for n in ast.walk(helper)}
            for node in ast.walk(tree):
                if id(node) in allowed:
                    continue
                if isinstance(node, ast.Attribute) and node.attr == "jit" \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "jax":
                    offenders.append(f"{sub}/{name}:{node.lineno}")
                if isinstance(node, ast.ImportFrom) and node.module \
                        and node.module.split(".")[0] == "jax" \
                        and any(a.name in ("jit", "pjit")
                                for a in node.names):
                    offenders.append(f"{sub}/{name}:{node.lineno}")
    assert offenders == []


def test_a_named_program_keeps_its_name_and_a_named_function_its_cache():
    from spark_rapids_tpu.exec.common import jit_named, slice_batch
    f = jit_named("SomeExec_role", lambda v: v + 1)
    assert "jit_SomeExec_role" in f.lower(jnp.arange(3)).as_text()[:200]
    # a function already called that is jitted as it is: a second wrapper
    # of it re-traces nothing (JAX caches the trace by the function)
    assert jit_named("slice_batch", slice_batch,
                     static_argnums=3).__wrapped__ is slice_batch
    # with no key, a wrapper (and so a trace and a lowering) an instance
    assert jit_named("SomeExec_role", lambda v: v + 1) is not f


@pytest.mark.parametrize("other,same", [
    (dict(), True),
    (dict(key="k2"), False),
    (dict(name="SomeExec_other"), False),
    (dict(static_argnums=(1,)), False),
    (dict(static_argnums=1), False),
    (dict(key=None), False),
], ids=["equal", "key", "name", "jit_arguments", "jit_argument_form",
        "no_key"])
def test_a_keyed_program_is_one_object_a_name_key_and_jit_arguments(
        other, same):
    from spark_rapids_tpu.compile_cache import program_table
    from spark_rapids_tpu.exec.common import jit_named
    ran = []

    def kernel(tag):
        def fun(v, n=2):
            ran.append(tag)
            return v * n
        return fun
    one = dict(name="SomeExec_keyed", key="k1", static_argnums=())
    before = program_table().stats()
    f = jit_named(fun=kernel("first"), **one)
    g = jit_named(fun=kernel("second"), **dict(one, **other))
    assert (g is f) == same
    x = jnp.arange(3)
    if "static_argnums" in other:
        assert g(x, 3).tolist() == [0, 3, 6]
    assert f(x).tolist() == g(x).tolist() == [0, 2, 4]
    # the first to state a key made its program: a hit's own function is
    # dropped, its Python body never runs
    assert ("second" in ran) == (not same)
    after = program_table().stats()
    keyed = 1 if other.get("key", "k1") is None else 2
    assert after["hits"] + after["misses"] \
        == before["hits"] + before["misses"] + keyed
    assert after["unkeyed"] == before["unkeyed"] + 2 - keyed
    assert "jit_SomeExec_keyed" in f.lower(x).as_text()[:200]
