"""Planning plans, execution executes (ISSUE 33): ``Session.prepare`` pulls
no batch, launches no program and registers no buffer; the planner asks the
partition count the PLAN states (``Exec.planned_partitions``), plants its
exchange on that, and where the run says "one partition" after all the
exchange stands aside. Counts, not times: everything runs on the CPU."""

import os
import sys

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.compile_cache import program_table
from spark_rapids_tpu.exec.base import Exec, collect
from spark_rapids_tpu.exec.join import JoinType
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.expressions.window import WindowAgg, over
from spark_rapids_tpu.memory.catalog import device_budget
from spark_rapids_tpu.plan import table
from spark_rapids_tpu.plan.overrides import Overrides
from spark_rapids_tpu.plan.session import Session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
K = "spark.rapids.tpu."
TRACE_ON = {K + "trace.enabled": "true"}
SHUFFLED = {K + "sql.autoBroadcastJoinThreshold": 0}

_RNG = np.random.default_rng(33)
FACT = pa.table({"k": _RNG.integers(0, 40, 3000).astype(np.int64),
                 "g": _RNG.integers(0, 7, 3000).astype(np.int64),
                 "v": _RNG.integers(-100, 100, 3000).astype(np.int64)})
DIM = pa.table({"dk": np.arange(41, dtype=np.int64),
                "w": (np.arange(41) % 9).astype(np.int64)})


def _join_under_aggregate():
    return (table(FACT, num_slices=3, batch_rows=1000)
            .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
            .group_by("g").agg(Sum(col("w")).alias("sw"),
                               Count().alias("c")))


def _aggregate_over_adaptive_aggregate():
    return (table(FACT, num_slices=3, batch_rows=1000).group_by("k")
            .agg(Sum(col("v")).alias("s"))
            .group_by("s").agg(Sum(col("k")).alias("ks")))


def _window_over_join():
    return (table(FACT, num_slices=3, batch_rows=1000)
            .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
            .window(over(WindowAgg(Sum(col("v"))), [col("g")]).alias("sv")))


def _keyless_window_over_join():
    return (table(FACT, num_slices=3, batch_rows=1000)
            .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
            .window(over(WindowAgg(Sum(col("v"))), []).alias("sv")))


def _reference(df):
    got = Session({K + "sql.enabled": False}).collect(df)
    return _rows(got)


def _rows(t):
    return sorted(zip(*(t[c].to_pylist() for c in t.column_names)),
                  key=repr)


def _bench_query(cell, tmp_path):
    """``(conf, make_df, check)`` of one cell of BENCHMARK.json at 0.01
    scale, built the way ``benchmarks/run.py`` builds it (imported, as
    ``test_program_table.py`` does)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from rtbench import compare, data, loader, plans
    bench = loader.benchmark()
    w = loader.cell(bench, cell)
    config = loader.config(bench, w["config"])
    traffic = loader.traffic(w["traffic"])
    entry = traffic["queries"][0]
    q = loader.query(config["family"], entry["query"])
    params = loader.query_params(q, entry, rehearsal=True)
    written = data.write_tables(config, 0.01, 2 ** 31 + 33, list(q.TABLES),
                                str(tmp_path))
    # (at this scale every build side would be broadcast: the shuffled
    # joins of the SF1 plan are asked for)
    conf = dict(config.get("conf") or {}, **(traffic.get("conf") or {}),
                **SHUFFLED)
    want = q.reference(data.reader(written), params)

    def check(got):
        r = compare.compare(got, want, q.ORDERED)
        return r["exact_mismatches"] == 0 and r["double_rel_err"] < 1e-10
    return conf, lambda: q.plan(plans.scanner(written, q), params), check


def _case(which, tmp_path):
    if which.startswith("tpch_sf1."):
        return _bench_query(which, tmp_path)
    make = {"join_under_aggregate": _join_under_aggregate,
            "aggregate_over_adaptive_aggregate":
                _aggregate_over_adaptive_aggregate,
            "window_over_join": _window_over_join}[which]
    conf = {} if which.startswith("aggregate") else dict(SHUFFLED)
    want = _reference(make())
    return conf, make, lambda got: _rows(got) == want


class _Counted:
    """What ``jax.jit`` hands back while ``_launches`` is entered: the
    jitted function, with its calls counted."""

    def __init__(self, fn, calls):
        self._fn, self._calls = fn, calls

    def __call__(self, *args, **kwargs):
        self._calls.append(getattr(self._fn, "__name__", "?"))
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


@pytest.fixture
def launches(monkeypatch):
    """Every call of a program the engine jits from here on, by name. The
    program table is emptied first, so that every program of the test is
    built, and counted, here. (Stating a program is not launching it: an
    exec states its keyed programs when it is BUILT, so the table's
    ``hits`` and ``misses`` move while a plan is made; its calls may
    not.)"""
    calls = []
    real = jax.jit
    program_table().clear()
    monkeypatch.setattr(jax, "jit",
                        lambda fun, **kw: _Counted(real(fun, **kw), calls))
    yield calls
    program_table().clear()


@pytest.fixture
def pulls(monkeypatch):
    """Every partition iteration any exec starts, by exec name."""
    started = []
    real = Exec.execute_partition

    def execute_partition(self, p):
        started.append(self.name)
        return real(self, p)
    monkeypatch.setattr(Exec, "execute_partition", execute_partition)
    return started


def _under(by_id, s, name):
    s = by_id.get(s["parent"])
    while s is not None:
        if s["name"] == name:
            return True
        s = by_id.get(s["parent"])
    return False


@pytest.mark.parametrize("which", [
    "join_under_aggregate", "aggregate_over_adaptive_aggregate",
    "window_over_join", "tpch_sf1.q3", "tpch_sf1.q18"])
def test_prepare_runs_nothing_and_every_launch_is_under_execute(
        which, tmp_path, launches, pulls):
    conf, make_df, check = _case(which, tmp_path)
    ses = Session(dict(conf, **TRACE_ON))
    cat = device_budget()
    registered = (cat._next, len(cat._entries))
    kind, plan = ses.prepare(make_df())
    assert kind == "exec"
    assert launches == [] and pulls == []
    assert (cat._next, len(cat._entries)) == registered
    # ... and the collect's own planning, seen from its span tree
    got = ses.collect(make_df())
    assert check(got)
    assert launches and pulls
    spans = qtrace.flight_recorder().profiles(ses.last_query_id)[0]["spans"]
    by_id = {s["id"]: s for s in spans}
    planned = [s for s in spans if _under(by_id, s, "plan.prepare")]
    assert {s["name"] for s in planned} >= {"plan.overrides",
                                            "plan.materialize"}
    ran = [s["name"] for s in planned
           if s["kind"] in ("operator", "shuffle")
           or s["name"].startswith(("scan.", "jit."))]
    assert ran == []
    asked = [s for s in spans if s["name"] == "plan.materialize"]
    assert all(by_id[s["parent"]]["name"] == "plan.overrides"
               and s["attrs"]["exec"] for s in asked)
    writes = [s for s in spans if s["name"] == "ShuffleExchangeExec.write"]
    assert all(_under(by_id, s, "execute") for s in writes)
    assert writes


# ---------------------------------------------------------------------------
# the planned count: over 1 wherever the run-time count is
# ---------------------------------------------------------------------------

def _exec_classes():
    import spark_rapids_tpu.exec.fuse          # noqa: F401
    import spark_rapids_tpu.exec.python_exec   # noqa: F401
    import spark_rapids_tpu.io.cache           # noqa: F401
    import spark_rapids_tpu.io.scan            # noqa: F401
    import spark_rapids_tpu.parallel.lowering  # noqa: F401
    import spark_rapids_tpu.shuffle            # noqa: F401
    seen, todo = [], [Exec]
    while todo:
        c = todo.pop()
        for s in c.__subclasses__():
            if s not in seen and s.__module__.startswith("spark_rapids_tpu"):
                seen.append(s)
                todo.append(s)
    return seen


def test_every_exec_that_states_a_run_time_count_states_the_planned_one():
    """... beside it: an override of ``num_partitions`` (or of the hook
    the exchanges answer it through) without ``planned_partitions`` would
    inherit "the first child's", which may be wrong for it."""
    stated = [c for c in _exec_classes() if "num_partitions" in vars(c)]
    assert len(stated) >= 11, stated
    missing = [c.__name__ for c in stated
               if "planned_partitions" not in vars(c)]
    assert missing == []
    from spark_rapids_tpu.shuffle.exchange import PartitioningExchangeExec
    for c in _exec_classes():
        if "_reader_partitions" in vars(c):
            assert issubclass(c, PartitioningExchangeExec)


def _walk(plan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


_INVARIANT_CASES = {
    # name: (conf, DataFrame)
    "coordinated_join_coalesces_to_one": (
        SHUFFLED, _join_under_aggregate),
    "adaptive_aggregate_coalesces_to_one": (
        {}, _aggregate_over_adaptive_aggregate),
    "skew_split_of_a_single_map_partition": (
        dict(SHUFFLED, **{K + "shuffle.partitions": 1,
                          K + "sql.adaptive.broadcastJoin.enabled": "false",
                          K + "sql.adaptive.skewJoin.splitRows": 500}),
        _join_under_aggregate),
    "static_exchanges": (
        dict(SHUFFLED, **{K + "sql.adaptive.enabled": "false"}),
        _window_over_join),
    "planned_broadcast_full_outer": (
        {}, lambda: table(FACT, num_slices=3, batch_rows=1000).join(
            table(DIM), ["k"], ["dk"], JoinType.FULL_OUTER)),
    "union_sort_limit": (
        {}, lambda: table(FACT, num_slices=2, batch_rows=1000).union(
            table(FACT, num_slices=3, batch_rows=1000)).order_by("v").limit(5)),
    "keyless_right_outer": (
        {}, lambda: table(FACT, num_slices=3, batch_rows=1000).join(
            table(DIM), [], [], JoinType.RIGHT_OUTER,
            condition=col("k") < col("dk"))),
    "multithreaded": (
        dict(SHUFFLED, **{K + "shuffle.mode": "MULTITHREADED"}),
        _join_under_aggregate),
    "cached": (
        dict(SHUFFLED, **{K + "shuffle.mode": "CACHED"}),
        _join_under_aggregate),
}


@pytest.mark.parametrize("which", sorted(_INVARIANT_CASES))
def test_planned_count_is_over_one_wherever_the_run_time_count_is(
        which, pulls):
    from spark_rapids_tpu.memory.retry import apply_session_conf
    from spark_rapids_tpu.config import RapidsTpuConf
    conf, make = _INVARIANT_CASES[which]
    conf = RapidsTpuConf(dict(conf))
    plan = Overrides(conf).plan(make().plan)
    nodes = list(_walk(plan))
    planned = [n.planned_partitions for n in nodes]
    assert pulls == []          # the planned count ran nothing
    apply_session_conf(conf)
    try:
        collect(plan)
        ran = [n.num_partitions for n in nodes]
    finally:
        plan.close()
    # (the reader layout of a shuffled join's two exchanges is the JOIN's
    # to set, skew split included, and the join states that rule)
    joins_own = {id(c) for n in nodes if n.name == "HashJoinExec"
                 and not n._planned_broadcast for c in n.children}
    for n, p, r in zip(nodes, planned, ran):
        assert r <= 1 or p > 1 or id(n) in joins_own, (n.name, p, r)
    if which == "skew_split_of_a_single_map_partition":
        join = next(n for n in nodes if n.name == "HashJoinExec")
        assert ran[nodes.index(join)] > 1 == \
            join.left.partitioning.num_partitions


# ---------------------------------------------------------------------------
# the exchange that stands aside
# ---------------------------------------------------------------------------

def _without_exchanges_that_stood_aside(plan, ran):
    """``plan``, a fresh tree of the shape of ``ran``, with every exchange
    that stood aside when ``ran`` ran replaced by what it hands through:
    the parent commit's plan, where the planner had asked the run-time
    count and planted nothing there."""
    if getattr(ran, "stood_aside", False):
        return _without_exchanges_that_stood_aside(plan.aside, ran.aside)
    assert type(plan) is type(ran)
    plan.children = tuple(_without_exchanges_that_stood_aside(c, r)
                          for c, r in zip(plan.children, ran.children))
    return plan


@pytest.mark.parametrize("mode,make", [
    ("DEFAULT", _join_under_aggregate),
    ("DEFAULT", _aggregate_over_adaptive_aggregate),
    ("DEFAULT", _window_over_join),
    ("DEFAULT", _keyless_window_over_join),
    ("MULTITHREADED", _join_under_aggregate),
    ("CACHED", _join_under_aggregate),
], ids=["default-join", "default-adaptive", "default-window",
        "default-keyless-window", "multithreaded-join", "cached-join"])
def test_an_exchange_over_one_partition_stands_aside(mode, make, launches):
    from spark_rapids_tpu.config import RapidsTpuConf
    from spark_rapids_tpu.memory.retry import apply_session_conf
    # one shuffle partition: the plan still says "maybe two" of a join
    # whose skew split can cut one (HashJoinExec.planned_partitions)
    conf = dict(SHUFFLED, **{K + "shuffle.mode": mode})
    if make is not _aggregate_over_adaptive_aggregate:
        conf[K + "shuffle.partitions"] = 1
    ses = Session(conf)
    got = ses.collect(make())
    planted = [n for n in _walk(ses.last_plan)
               if getattr(n, "aside", None) is not None]
    aside = [n for n in planted if n.stood_aside]
    assert aside, [n.name for n in _walk(ses.last_plan)]
    # named only where it ran, and it launched nothing of its own
    names = ses.executed_exec_names()
    assert names.count(aside[0].name) == sum(
        1 for n in _walk(ses.last_plan)
        if n.name == aside[0].name and not getattr(n, "stood_aside", False))
    assert all(m.total() == 0 for n in aside for m in n.metrics.values())
    if make is _keyless_window_over_join:
        # the window asks for sized batches and a join fragments: what
        # the exchange hands through is the join under its coalesce
        assert [n.aside.name for n in aside] == ["CoalesceBatchesExec"]
        assert "CoalesceBatchesExec" in names
    # ... and the same batches in the same order as the tree without it
    apply_session_conf(RapidsTpuConf(conf))
    plain = _without_exchanges_that_stood_aside(
        Overrides(RapidsTpuConf(conf)).plan(make().plan), ses.last_plan)
    assert [n.name for n in _walk(plain)] == names
    try:
        want = collect(plain)
    finally:
        plain.close()
    assert got.equals(want)
    assert _rows(got) == _reference(make())


def test_a_stood_aside_exchange_asks_again_on_the_next_execution():
    """``close()`` forgets the decision, not the plan: the exchange under a
    re-executed plan asks its child again."""
    from spark_rapids_tpu.config import RapidsTpuConf
    conf = RapidsTpuConf({})
    plan = Overrides(conf).plan(_aggregate_over_adaptive_aggregate().plan)
    outer = plan.children[0]
    assert outer.aside is not None and not outer.stood_aside
    assert outer.planned_partitions == 8
    try:
        first = collect(plan)
        assert outer.stood_aside and outer.num_partitions == 1
    finally:
        plan.close()
    assert outer._standing is None and outer.stood_aside
    try:
        assert collect(plan).equals(first)
    finally:
        plan.close()


def test_explain_only_mode_plans_without_running(launches, pulls):
    """``sql.mode=explainonly`` plans as if a TPU were present and executes
    on the CPU: the plan it keeps must not have run its shuffled sides."""
    ses = Session(dict(SHUFFLED, **{K + "sql.mode": "explainonly"}))
    assert ses.prepare(_join_under_aggregate()) == ("interpret", None)
    assert ses.last_plan is not None
    assert launches == [] and pulls == []
