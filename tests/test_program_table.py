"""The program table behind ``exec/common.jit_named`` (ISSUE 29): a rebuilt
exec whose kernels are the same function of their inputs gets the SAME
jitted callable, so a warm query re-traces, re-lowers and re-loads nothing;
an exec that differs in anything its kernels read gets another; and the
table pins nothing of a query. Counts, not times: everything runs on the
CPU."""

import ast
import gc
import os
import re
import sys
import threading
import weakref
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import Field, Schema
from spark_rapids_tpu.compile_cache import ProgramTable, program_table
from spark_rapids_tpu.exec import (HashJoinExec, InMemoryScanExec, JoinType,
                                   collect)
from spark_rapids_tpu.exec.aggregate import AggregateMode, HashAggregateExec
from spark_rapids_tpu.exec.basic import (ArithmeticException, FilterExec,
                                         ProjectExec)
from spark_rapids_tpu.exec.common import KernelPrograms, program_key
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
from spark_rapids_tpu.expressions.base import EvalContext, Expression
from spark_rapids_tpu.plan import table as df_table
from spark_rapids_tpu.plan.session import Session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
TRACE_ON = {"spark.rapids.tpu.trace.enabled": "true"}


# ---------------------------------------------------------------------------
# (a), (c): a second run lowers nothing, and every hit is the same program
# ---------------------------------------------------------------------------

def _bench_query(cell, tmp_path):
    """``(conf, make_df, reference table)`` of one cell of BENCHMARK.json
    at 0.01 scale, built the way ``benchmarks/run.py`` builds it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from rtbench import data, loader, plans
    bench = loader.benchmark()
    w = loader.cell(bench, cell)
    config = loader.config(bench, w["config"])
    traffic = loader.traffic(w["traffic"])
    entry = traffic["queries"][0]
    q = loader.query(config["family"], entry["query"])
    params = loader.query_params(q, entry, rehearsal=True)
    written = data.write_tables(config, 0.01, 2 ** 31 + 29, list(q.TABLES),
                                str(tmp_path))
    conf = dict(config.get("conf") or {}, **(traffic.get("conf") or {}))
    want = q.reference(data.reader(written), params)
    return (conf, lambda: q.plan(plans.scanner(written, q), params),
            lambda got: _matches_reference(got, want, q.ORDERED))


def _matches_reference(got, want, ordered):
    from rtbench import compare
    r = compare.compare(got, want, ordered)
    return r["exact_mismatches"] == 0 and r["double_rel_err"] < 1e-10


def _join_agg_sort(tmp_path):
    rng = np.random.default_rng(29)
    fact = pa.table({"k": rng.integers(0, 40, 3000).astype(np.int64),
                     "v": rng.integers(-100, 100, 3000).astype(np.int64)})
    dim = pa.table({"k2": np.arange(50, dtype=np.int64),
                    "g": (np.arange(50) % 7).astype(np.int64)})

    def make():
        return (df_table(fact).join(df_table(dim), ["k"], ["k2"])
                .where(col("v") > lit(-90)).group_by("g")
                .agg(Sum(col("v")).alias("s"), Count().alias("n"))
                .order_by("g"))

    def check(got):
        keep = fact["v"].to_numpy() > -90
        g = dim["g"].to_numpy()[fact["k"].to_numpy()[keep]]
        v = fact["v"].to_numpy()[keep]
        want = [(int(x), int(v[g == x].sum()), int((g == x).sum()))
                for x in sorted(set(g.tolist()))]
        return list(zip(*(got[c].to_pylist() for c in "gsn"))) == want
    return {}, make, check


class _SamePrograms:
    """Test helper, not a product switch: while it is entered, every HIT of
    the program table is handed back as a callable that, before it calls
    the entry, traces both the entry's function and the fresh instance's
    (what a miss would have jitted) on the call's arguments and keeps the
    two jaxprs."""

    def __init__(self):
        self.pairs = []      # (name, entry's jaxpr, fresh instance's)

    def __enter__(self):
        table, real = program_table(), ProgramTable.get_or_build
        helper = self

        def get_or_build(self, key, build):
            built = []

            def counted():
                built.append(1)
                return build()
            entry, hit = real(self, key, counted)
            if built:
                return entry, hit
            fresh = build()
            static = dict(ast.literal_eval(key[2])).get("static_argnums", ())
            seen = set()

            def checked(*args):
                sig = str(jax.tree_util.tree_map(
                    lambda a: getattr(a, "aval", a), args))
                if sig not in seen:
                    seen.add(sig)
                    helper.pairs.append((key[0],) + tuple(
                        _ADDRESS.sub("", str(jax.make_jaxpr(
                            f.__wrapped__, static_argnums=static)(*args)))
                        for f in (entry, fresh)))
                return entry(*args)
            return checked, hit
        table.get_or_build = get_or_build.__get__(table)
        return self

    def __exit__(self, *exc):
        del program_table().get_or_build
        return False


_ADDRESS = re.compile(r" at 0x[0-9a-f]+")


def _second_run_profile(conf, make_df):
    ses = Session(dict(conf, **TRACE_ON))
    first = ses.collect(make_df())
    with _SamePrograms() as same:
        second = ses.collect(make_df())
    prof = qtrace.flight_recorder().profiles(ses.last_query_id)[0]
    return first, second, prof, same.pairs


@pytest.mark.parametrize("which", ["tpch_sf1.q1", "tpcds_sf1.q3",
                                   "tpch_sf1.q3", "join_agg_sort"])
def test_a_second_run_lowers_nothing_and_runs_the_same_programs(which,
                                                                tmp_path):
    conf, make_df, check = (_join_agg_sort(tmp_path)
                            if which == "join_agg_sort"
                            else _bench_query(which, tmp_path))
    first, second, prof, pairs = _second_run_profile(conf, make_df)
    spans = prof["spans"]
    assert [s["attrs"]["fun"] for s in spans
            if s["name"] == "jit.lower"] == []
    ops = [s for s in spans if s["kind"] == "operator"]
    assert sum(s["attrs"].get("programHits", 0) for s in ops) > 0
    assert sum(s["attrs"].get("programMisses", 0) for s in ops) == 0
    assert not any("lowerings" in s["attrs"] for s in ops)
    assert second.equals(first) and check(second)
    # (c) equal key => equal program, for every hit that was called
    assert pairs, "no keyed program was called"
    for name, entry, fresh in pairs:
        assert entry == fresh, name


# ---------------------------------------------------------------------------
# (b): whatever a kernel reads separates two execs
# ---------------------------------------------------------------------------

_T = pa.table({"k": np.array([1, 2, 2, 3, 3, 3, 9, 9], np.int64),
               "v": np.array([5, 1, 7, 2, 8, 4, 6, 3], np.int64)})
_DIM = pa.table({"k2": np.array([2, 3, 4], np.int64),
                 "w": np.array([20, 30, 40], np.int64)})


def _rows(t):
    return sorted(zip(*(t[c].to_pylist() for c in t.column_names)),
                  key=repr)


def _scan(t=_T, nullable=False):
    schema = Schema([Field(f.name, T.INT64, nullable) for f in t.schema])
    return InMemoryScanExec(t, schema=schema)


def _filter(n):
    return (FilterExec(col("v") > lit(n), _scan()), "_kernel",
            [r for r in _rows(_T) if r[1] > n])


def _overflowing(ansi):
    big = pa.table({"v": np.array([2 ** 63 - 1, 1], np.int64)})
    plan = ProjectExec([(col("v") + lit(1)).alias("x")], _scan(big),
                       ctx=EvalContext(ansi=ansi))
    return plan, "_kernel", ArithmeticException if ansi \
        else [(-2 ** 63,), (2,)]


def _grouped(nullable=False, mode=AggregateMode.COMPLETE, bucket=1 << 12):
    plan = HashAggregateExec([col("k")], [Sum(col("v")).alias("s"),
                                          Average(col("v")).alias("a")],
                             _scan(nullable=nullable), mode=mode,
                             small_groups_bucket=bucket)
    groups = {1: [5], 2: [1, 7], 3: [2, 8, 4], 9: [6, 3]}
    if mode is AggregateMode.PARTIAL:    # the buffers: sum, average
        want = [(k, sum(v), len(v), float(sum(v)), len(v))
                for k, v in groups.items()]
    else:
        want = [(k, sum(v), sum(v) / len(v)) for k, v in groups.items()]
    return plan, "_update_jit", want


def _joined(jt):
    plan = HashJoinExec([col("k")], [col("k2")], jt, _scan(), _scan(_DIM))
    dim = dict(zip(_DIM["k2"].to_pylist(), _DIM["w"].to_pylist()))
    want = [(k, v, k, dim[k]) for k, v in _rows(_T) if k in dim]
    if jt is JoinType.LEFT_OUTER:
        want += [(k, v, None, None) for k, v in _rows(_T) if k not in dim]
    return plan, "_expand_jit", sorted(want, key=repr)


@pytest.mark.parametrize("one,other", [
    (lambda: _filter(3), lambda: _filter(5)),
    (lambda: _overflowing(False), lambda: _overflowing(True)),
    (lambda: _grouped(nullable=False), lambda: _grouped(nullable=True)),
    (lambda: _joined(JoinType.INNER), lambda: _joined(JoinType.LEFT_OUTER)),
    (lambda: _grouped(mode=AggregateMode.COMPLETE),
     lambda: _grouped(mode=AggregateMode.PARTIAL)),
    (lambda: _grouped(bucket=1 << 12), lambda: _grouped(bucket=1 << 2)),
], ids=["literal", "ansi", "nullability", "join_type", "aggregate_mode",
        "small_groups_bucket"])
def test_what_a_kernel_reads_separates_two_execs(one, other):
    (a, attr, want_a), (b, _, want_b) = one(), other()
    again, _, _ = one()
    assert getattr(a, attr) is getattr(again, attr)
    assert getattr(a, attr) is not getattr(b, attr)
    for plan, want in ((a, want_a), (b, want_b), (again, want_a)):
        if isinstance(want, type):
            with pytest.raises(want):
                collect(plan)
        else:
            assert _rows(collect(plan)) == sorted(want, key=repr)


# ---------------------------------------------------------------------------
# (d): nothing pinned
# ---------------------------------------------------------------------------

def test_the_table_pins_no_exec_tree_and_no_batch(tmp_path):
    _, make_df, check = _join_agg_sort(tmp_path)

    def run():
        ses = Session({})
        out = ses.collect(make_df())
        tree = weakref.ref(ses.last_plan)
        assert check(out)
        del ses, out
        gc.collect()
        return tree, len(jax.live_arrays())

    tree, live_first = run()
    assert program_table().stats()["entries"] > 0
    assert tree() is None
    tree, live_second = run()
    assert tree() is None
    assert live_second <= live_first


def test_a_stand_in_has_the_stated_fields_and_nothing_else():
    plan = FilterExec(col("v") > lit(3), _scan())
    programs = KernelPrograms(plan, ("condition",))
    assert sorted(vars(programs.stand_in)) == ["condition", "ctx"]
    assert type(programs.stand_in) is FilterExec
    # a kernel that reads what its exec did not state fails when traced
    with pytest.raises(AttributeError):
        programs.jit("reads_children",
                     lambda self, b: self.child.output_schema)(1)


# ---------------------------------------------------------------------------
# (e): what cannot be written down stays per instance
# ---------------------------------------------------------------------------

@pytest.fixture
def py_fn():
    """An expression class over a Python callable: no encoding in the plan
    dialect. Made for the test and taken out of the registry again, which
    is what the wire codec and ``tools/lint_bridge.py`` walk."""
    @dataclass(frozen=True)
    class _PyFn(Expression):
        child: Expression
        fn: Callable

        @property
        def children(self):
            return (self.child,)

        def with_children(self, c):
            return _PyFn(c[0], self.fn)

        @property
        def dtype(self):
            return self.child.dtype

        def eval(self, batch, ctx=EvalContext()):
            c = self.child.eval(batch, ctx)
            return c.replace(data=self.fn(c.data))
    try:
        yield _PyFn
    finally:
        del Expression._registry["_PyFn"]


def test_an_unencodable_expression_passes_no_key_and_runs(py_fn):
    def build():
        return ProjectExec([py_fn(col("v"), lambda x: x * 2).alias("d")],
                           _scan())
    before = program_table().stats()
    a, b = build(), build()
    after = program_table().stats()
    assert KernelPrograms(a, ("exprs",)).key is None
    assert after["unkeyed"] == before["unkeyed"] + 2
    assert after["entries"] == before["entries"]
    assert a._kernel is not b._kernel
    assert a.program_hits == a.program_misses == 0
    assert collect(a)["d"].to_pylist() == [2 * v for v in
                                          _T["v"].to_pylist()]


def _loop_udfs():
    from spark_rapids_tpu.udf import compile_udf

    def triangle(y):
        acc = 0
        while y > 0:
            acc = acc + y
            y = y - 1
        return acc

    def squares(y):      # differs from triangle inside the loop alone
        acc = 0
        while y > 0:
            acc = acc + y * y
            y = y - 1
        return acc
    bound = col("v").bind(_scan().output_schema)
    return [(f, compile_udf(f, [bound])) for f in (triangle, squares)]


def test_two_loop_udfs_of_one_schema_each_keep_their_own_program():
    """The compiled UDF's loop nodes (``udf/compiler``) have hand-written
    constructors: their dataclass fields say nothing of the loop, so the
    plan dialect refuses them and neither exec states a key. Not fused:
    this is the ``ProjectExec._kernel`` a Parquet scan's or a multi-batch
    child's project runs."""
    from spark_rapids_tpu.server.plandoc import PlanDecodeError, encode_value
    before = program_table().stats()
    for f, expr in _loop_udfs():
        with pytest.raises(PlanDecodeError, match="do not state it"):
            encode_value(expr)
        plan = ProjectExec([expr.alias("r")], _scan())
        assert KernelPrograms(plan, ("exprs",)).key is None
        assert plan.program_hits == plan.program_misses == 0
        assert collect(plan)["r"].to_pylist() == [
            f(v) for v in _T["v"].to_pylist()]
    after = program_table().stats()
    assert after["unkeyed"] == before["unkeyed"] + 2
    assert after["entries"] == before["entries"]


def test_an_expression_is_stated_by_its_fields_or_refused():
    """Every expression class the package defines either takes its state
    through a dataclass-made constructor, so that ``astuple()`` lists it,
    or is refused by the plan dialect and with it by ``program_key``."""
    import spark_rapids_tpu.udf.compiler  # noqa: F401  (its loop nodes)
    from spark_rapids_tpu.expressions.aggregates import ApproxPercentile
    from spark_rapids_tpu.server.plandoc import (PlanDecodeError,
                                                 _refuse_unstated)

    def subclasses(c):
        for s in c.__subclasses__():
            yield s
            yield from subclasses(s)
    refused = set()
    for cls in set(subclasses(Expression)):
        if not cls.__module__.startswith("spark_rapids_tpu."):
            continue         # other test modules' own expression classes
        try:
            _refuse_unstated(cls)
        except PlanDecodeError:
            refused.add(cls.__name__)
    assert refused == {"_SlotRef", "_WhileOut", "_Memo", "_LoopBudgetCheck"}
    assert program_key(ApproxPercentile(col("v"), 0.5, 100)) \
        != program_key(ApproxPercentile(col("v"), 0.5, 1000))


def test_a_key_says_values_not_identities():
    e = col("v") > lit(3)
    assert program_key([e]) == program_key([col("v") > lit(3)])
    assert program_key([e]) != program_key([col("v") > lit(4)])
    assert program_key([e]) != program_key([col("v") > lit(3.0)])
    assert program_key(EvalContext(ansi=True)) \
        != program_key(EvalContext(ansi=False))
    assert program_key(EvalContext(errors={})) is None
    assert program_key(AggregateMode.FINAL) \
        != program_key(AggregateMode.PARTIAL_MERGE)
    assert program_key(Field("a", T.INT64, True)) \
        != program_key(Field("a", T.INT64, False))
    assert program_key(lambda: 0) is None


# ---------------------------------------------------------------------------
# (f), (g): the table itself
# ---------------------------------------------------------------------------

def test_the_lru_evicts_at_its_bound_and_a_reentry_rebuilds():
    t, built = ProgramTable(max_entries=2), []

    def build(k):
        def make():
            built.append(k)
            return object()
        return make
    a, hit = t.get_or_build("a", build("a"))
    assert not hit
    t.get_or_build("b", build("b"))
    assert t.get_or_build("a", build("a")) == (a, True)   # now the newest
    t.get_or_build("c", build("c"))                  # evicts b
    assert t.stats() == {"entries": 2, "hits": 1, "misses": 3,
                         "unkeyed": 0, "evictions": 1}
    assert t.get_or_build("a", build("a")) == (a, True)
    assert not t.get_or_build("b", build("b"))[1]    # rebuilt, evicts c
    assert built == ["a", "b", "c", "b"]
    assert t.stats()["evictions"] == 2 and t.stats()["entries"] == 2


def test_threads_stating_one_key_share_one_entry():
    t, built = ProgramTable(max_entries=64), []
    keys, threads, rounds = list(range(8)), 16, 200
    got = [[] for _ in range(threads)]
    start = threading.Barrier(threads)

    def work(i):
        start.wait(timeout=30)
        for r in range(rounds):
            k = keys[(i + r) % len(keys)]
            got[i].append((k, t.get_or_build(
                k, lambda k=k: built.append(k) or object())))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(i,))
              for i in range(threads)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in ts)
    finally:
        sys.setswitchinterval(old)
    assert sorted(built) == keys
    by_key, said_hit = {}, 0
    for g in got:
        for k, (fn, hit) in g:
            assert by_key.setdefault(k, fn) is fn
            said_hit += hit
    s = t.stats()
    assert (s["entries"], s["misses"], s["evictions"]) == (8, 8, 0)
    assert s["hits"] + s["misses"] == threads * rounds
    assert said_hit == s["hits"]


def test_two_threads_collecting_one_shape_leave_one_entry_a_program(
        tmp_path):
    _, make_df, check = _join_agg_sort(tmp_path)
    table = program_table()

    def entries_after(n_threads):
        table.clear()
        ok, start = [], threading.Barrier(n_threads)

        def work():
            start.wait(timeout=30)
            ok.append(check(Session({}).collect(make_df())))
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=300)
        assert not any(th.is_alive() for th in ts)
        assert ok == [True] * n_threads
        return table.stats()["entries"]

    alone = entries_after(1)
    assert alone > 0 and entries_after(2) == alone
