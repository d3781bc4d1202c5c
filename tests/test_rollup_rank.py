"""``GROUP BY ROLLUP`` ranked by a window, on the device: the DataFrame
surface plans Spark's Expand + aggregate, the planner merges the coarser
levels from the finest level's partials (``RollupExec``), and TPC-DS Q67 (the
benchmark's own configuration, generator, plan and plain reference,
``benchmarks/queries/tpcds/q67.py``) runs through ``Session.collect`` with
every operator on the device, equal to the reference cell for cell and in
order, also where ``KeyBatchingExec`` has to cut, and a second collect lowers
nothing."""

import decimal
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from rtbench import compare, data, loader, plans      # noqa: E402

from spark_rapids_tpu import compile_cache            # noqa: E402
from spark_rapids_tpu.exec.sort import asc, desc      # noqa: E402
from spark_rapids_tpu.expressions import col, lit     # noqa: E402
from spark_rapids_tpu.expressions.aggregates import (  # noqa: E402
    Average, Count, Max, Sum)
from spark_rapids_tpu.expressions.window import Rank, over   # noqa: E402
from spark_rapids_tpu.plan import Session, table      # noqa: E402
from spark_rapids_tpu.plan import logical as L        # noqa: E402
from spark_rapids_tpu.plan.overrides import Overrides  # noqa: E402

from harness.asserts import assert_tables_equal       # noqa: E402

WINDOW_ROWS = "spark.rapids.tpu.sql.window.batchRows"
KEYS = ["cat", "brand", "year"]


def _sales(n=3000, seed=35, nulls=True):
    rng = np.random.default_rng(seed)
    cats = np.array(["Books", "Home", "Music", "Shoes"])
    brands = np.array([f"brand #{i}" for i in range(12)])

    def maybe(values, share=0.05):
        return pa.array(values, mask=(rng.random(n) < share) if nulls
                        else None)
    cents = rng.integers(0, 5000, size=n)
    return pa.table({
        "cat": maybe(cats[rng.integers(0, len(cats), size=n)]),
        "brand": maybe(brands[rng.integers(0, len(brands), size=n)]),
        "year": maybe(rng.integers(1998, 2003, size=n).astype(np.int32)),
        "price": pa.array([decimal.Decimal(int(c)).scaleb(-2)
                           for c in cents.tolist()], pa.decimal128(7, 2),
                          mask=rng.random(n) < 0.05),
        "qty": pa.array(rng.integers(1, 100, size=n).astype(np.int32)),
    })


def _one_by_one(t, keys, num_slices=1):
    """The grouping sets of ``rollup(keys)``, each a plain ``group_by`` of
    its own, the dropped keys filled in as nulls."""
    ses = Session({})
    parts = []
    for kept in range(len(keys), -1, -1):
        got = ses.collect(
            table(t, num_slices=num_slices).group_by(*keys[:kept])
            .agg(Sum(col("price")).alias("total"),
                 Count().alias("n"), Max(col("qty")).alias("most")))
        for k in keys[kept:]:
            got = got.add_column(keys.index(k), k,
                                 pa.nulls(got.num_rows, t[k].type))
        parts.append(got)
    return pa.concat_tables(parts)


def _rollup(t, **kw):
    return table(t, **kw).rollup(*KEYS).agg(
        Sum(col("price")).alias("total"), Count().alias("n"),
        Max(col("qty")).alias("most"))


def test_rollup_plans_what_sparks_analyzer_plans():
    plan = _rollup(_sales(200)).plan
    assert isinstance(plan, L.LogicalProject)
    agg = plan.children[0]
    expand = agg.children[0]
    assert isinstance(agg, L.LogicalAggregate)
    assert isinstance(expand, L.LogicalExpand)
    assert len(expand.projections) == len(KEYS) + 1
    assert [g.name for g in agg.group_exprs] == KEYS + [L.GROUPING_ID]
    # trailing keys nulled level by level, the id counting the nulled bits
    ids = [p[-1].child.value for p in expand.projections]
    assert ids == [0, 1, 3, 7]
    for level, proj in enumerate(expand.projections):
        nulled = [e.child.value is None if hasattr(e.child, "value")
                  else False for e in proj[-1 - len(KEYS):-1]]
        assert nulled == [False] * (len(KEYS) - level) + [True] * level
    assert plan.schema().names == KEYS + ["total", "n", "most"]
    with pytest.raises(ValueError, match="also aggregated"):
        table(_sales(10)).rollup("year").agg(Sum(col("year")))


@pytest.mark.parametrize("num_slices", [1, 3], ids=["one_partition",
                                                    "exchanged"])
def test_rollup_equals_its_grouping_sets_one_by_one(num_slices):
    t = _sales()
    ses = Session({})
    got = ses.collect(_rollup(t, num_slices=num_slices))
    assert not ses.fell_back(), ses.fell_back()
    assert "RollupExec" in ses.executed_exec_names()
    want = _one_by_one(t, KEYS)
    assert got.schema == want.schema
    # (a key that is null in the data and a key the rollup nulled give two
    # rows with equal keys: the multiset compares them)
    assert_tables_equal(got, want, ignore_order=True, approx_float=False)


def test_rollup_equals_the_row_interpreter():
    t = _sales(800, seed=36)
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session({})
    df = lambda: table(t).rollup(*KEYS).agg(              # noqa: E731
        Sum(col("price")).alias("total"), Average(col("qty")).alias("avg"))
    assert_tables_equal(tpu.collect(df()), cpu.collect(df()),
                        ignore_order=True, approx_float=True)


def test_grouping_sets_that_do_not_nest_keep_the_plain_expand():
    """A cube's sets have no level to be merged from: the Expand makes every
    projection itself, as before."""
    t = _sales(500, seed=37, nulls=False)
    null = {k: lit(None, table(t).schema().field(k).dtype) for k in KEYS}

    def proj(kept, gid):
        return [col("qty")] + [(col(k) if k in kept else null[k]).alias(k)
                               for k in KEYS] + [lit(gid).alias("gid")]
    sets = [(KEYS, 0), (["cat"], 3), (["year"], 6)]
    df = L.DataFrame(L.LogicalAggregate(
        (L.LogicalExpand((table(t).plan,), [proj(k, g) for k, g in sets]),),
        [col(k) for k in KEYS + ["gid"]], [Sum(col("qty")).alias("q")]))
    ses = Session({})
    got = ses.collect(df)
    assert "RollupExec" not in ses.executed_exec_names()
    assert "ExpandExec" in ses.executed_exec_names()
    want = []
    for kept, gid in sets:
        g = ses.collect(table(t).group_by(*kept).agg(
            Sum(col("qty")).alias("q")))
        for row in g.to_pylist():
            want.append(tuple(row.get(k) for k in KEYS) + (gid, row["q"]))
    assert sorted(map(repr, (tuple(r.values()) for r in got.to_pylist()))) \
        == sorted(map(repr, want))


def test_ties_in_the_order_key_share_a_rank():
    # sums of whole dollars over few values: many groups tie
    n = 600
    rng = np.random.default_rng(38)
    t = pa.table({
        "cat": pa.array(np.array(["a", "b", "c"])[rng.integers(0, 3, n)]),
        "brand": pa.array(np.array([f"b{i}" for i in range(40)])[
            rng.integers(0, 40, n)]),
        "price": pa.array([decimal.Decimal(int(v)) for v in
                           rng.integers(1, 3, n).tolist()],
                          pa.decimal128(7, 2)),
    })
    ses = Session({})
    got = ses.collect(
        table(t).rollup("cat", "brand").agg(Sum(col("price")).alias("s"))
        .window(over(Rank(), [col("cat")], [desc(col("s"))]).alias("rk"))
        .order_by(asc(col("cat")), asc(col("rk")), asc(col("brand"))))
    assert not ses.fell_back(), ses.fell_back()
    assert got.schema.field("s").type == pa.decimal128(17, 2)
    assert got.schema.field("rk").type == pa.int32()
    rows = got.to_pylist()
    by_cat = {}
    for r in rows:
        by_cat.setdefault(r["cat"], []).append(r)
    tied = 0
    for part in by_cat.values():
        sums = [r["s"] for r in part]
        for r in part:
            # rank = 1 + the rows of the partition with a larger sum
            assert r["rk"] == 1 + sum(1 for s in sums if s > r["s"])
        tied += len(sums) - len(set(sums))
    assert tied > 10, "the table was meant to tie"
    assert set(by_cat) == {None, "a", "b", "c"}


# ---------------------------------------------------------------------------
# TPC-DS Q67 through the served plan's own pieces

@pytest.fixture(scope="module")
def q67(tmp_path_factory):
    bench = loader.benchmark()
    config = loader.config(bench, "tpcds_sf1_store")
    query = loader.query(config["family"], "q67")
    out = str(tmp_path_factory.mktemp("tpcds_sf1_store"))
    written = data.write_tables(config, 0.002, 3500000067,
                                sorted(query.TABLES), out)
    want = query.reference(data.reader(written), dict(query.PARAMS))
    return config, query, written, want


def _plan(q67):
    _, query, written, _ = q67
    return query.plan(plans.scanner(written, query), dict(query.PARAMS))


def test_configuration_states_its_guarantees(q67):
    config, query, written, _ = q67
    assert config["conf"] == {} and config["reduced"] == ["scale_factor"]
    assert config["guarantees"]["control_precision"] == "float32"
    assert config["guarantees"]["double_rel_err"] is None
    assert written["store"]["rows"] == 12
    assert set(query.TABLES) == {"store_sales", "date_dim", "store", "item"}


@pytest.mark.parametrize("window_rows", [None, 256],
                         ids=["one_window_batch", "key_batching_cuts"])
def test_q67_equals_the_reference_in_order(q67, window_rows):
    config, query, written, want = q67
    conf = dict(config["conf"])
    if window_rows:
        conf[WINDOW_ROWS] = window_rows
    ses = Session(conf)
    df = _plan(q67)
    explained = ses.explain(df)
    assert all(ln.lstrip().startswith("*") for ln in explained.splitlines()
               if ln.strip()), explained
    got = ses.collect(df)
    assert not ses.fell_back(), ses.fell_back()
    names = ses.executed_exec_names()
    for exec_name in ("ExpandExec", "RollupExec", "KeyBatchingExec",
                      "WindowExec", "HashAggregateExec"):
        assert exec_name in names, names
    assert got.schema == want.schema
    assert got.schema.field("sumsales").type == pa.decimal128(28, 2)
    assert got.schema.field("rk").type == pa.int32()
    r = compare.compare(got, want, query.ORDERED)
    assert r["exact_mismatches"] == 0, (got.slice(0, 5).to_pylist(),
                                        want.slice(0, 5).to_pylist())
    assert got.num_rows == query.LIMIT
    # the grand total first (every key null sorts first), never null
    first = got.slice(0, 1).to_pylist()[0]
    assert all(first[k] is None for k in query.KEYS) and first["rk"] == 1
    assert got.column("sumsales").null_count == 0


def test_key_batching_cuts_where_the_input_is_over_the_target(q67):
    """Beside the plan: the exec's own counters say it sorted and cut."""
    from spark_rapids_tpu import trace as qtrace
    config, _, _, want = q67
    ses = Session(dict(config["conf"], **{
        WINDOW_ROWS: 256, "spark.rapids.tpu.trace.enabled": True}))
    got = ses.collect(_plan(q67))
    assert compare.compare(got, want, True)["exact_mismatches"] == 0
    profile = qtrace.flight_recorder().profiles(ses.last_query_id)[0]
    spans = profile["spans"]
    kb = [s for s in spans if s["name"] == "KeyBatchingExec"]
    assert kb and sum(s["attrs"].get("keyBatchCuts", 0) for s in kb) >= 1
    assert sum(s["attrs"].get("keyBatchSlotsSorted", 0) for s in kb) > 0
    win = [s for s in spans if s["name"] == "WindowExec"]
    assert sum(s["attrs"].get("windowBatches", 0) for s in win) >= 2
    assert sum(s["attrs"].get("windowExprs", 0) for s in win) == 1
    ex = [s for s in spans if s["name"] == "ExpandExec"]
    assert sum(s["attrs"].get("expandProjections", 0) for s in ex) == 1
    assert sum(s["attrs"].get("expandSlotsOut", 0) for s in ex) > 0
    ru = [s for s in spans if s["name"] == "RollupExec"]
    assert sum(s["attrs"].get("rollupLevels", 0) for s in ru) == 8
    # tools/trace_viewer.py --table prints the new counters
    import importlib
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        viewer = importlib.import_module("trace_viewer")
    finally:
        sys.path.pop(0)
    rows = {r["name"]: r for r in viewer.self_time_table(profile)}
    assert rows["KeyBatchingExec"]["keyBatchCuts"] >= 1
    assert rows["WindowExec"]["windowBatches"] >= 2
    assert rows["ExpandExec"]["expandProjections"] == 1
    assert rows["RollupExec"]["rollupLevels"] == 8


def test_float32_control_gets_a_sum_wrong(q67):
    config, query, written, want = q67
    low = query.reference(data.reader(written), dict(query.PARAMS),
                          money=np.float32)
    # at this size the sums are small; the control has to differ at SF1
    # (PERF.md): here it only has to run and keep the shape
    assert low.schema == want.schema and low.num_rows == want.num_rows


def test_a_second_collect_lowers_nothing(q67):
    config = q67[0]
    ses = Session(dict(config["conf"]))
    ses.collect(_plan(q67))
    before = compile_cache.program_table().stats()
    got = Session(dict(config["conf"])).collect(_plan(q67))
    after = compile_cache.program_table().stats()
    assert compare.compare(got, q67[3], True)["exact_mismatches"] == 0
    assert after["misses"] == before["misses"], (before, after)
    assert after["unkeyed"] == before["unkeyed"], (before, after)
    assert after["hits"] > before["hits"]


def test_the_windows_program_is_stated_with_a_key():
    """Two execs built from equal plans share ONE window program, and its
    kernel reads nothing of the exec that its key does not state."""
    t = _sales(300, seed=39)

    def build():
        df = table(t).window(
            over(Rank(), [col("cat")], [desc(col("qty"))]).alias("rk"))
        plan = Overrides().plan(df.plan)
        return plan, [e for e in _walk(plan) if e.name == "WindowExec"][0]
    before = compile_cache.program_table().stats()["unkeyed"]
    (_, a), (_, b) = build(), build()
    assert a._kernel is b._kernel
    assert compile_cache.program_table().stats()["unkeyed"] == before
    assert not hasattr(a, "_range_batch")


def _walk(e):
    yield e
    for c in e.children:
        yield from _walk(c)


def test_the_plan_survives_the_wire(q67):
    """The served path writes the plan down and reads it back: the window
    expression, its spec and its frame come back as they went (a server
    imports ``expressions/window.py`` only when a plan names it)."""
    from spark_rapids_tpu.server.plandoc import doc_to_plan, plan_to_doc
    df = _plan(q67)
    doc, tables = plan_to_doc(df.plan)
    back = doc_to_plan(doc, tables)
    assert back.tree_string() == df.plan.tree_string()
    assert back.schema().names == df.plan.schema().names
    assert plan_to_doc(back)[0] == doc


def test_a_global_sort_runs_at_the_bucket_of_the_rows_it_holds():
    """What a selective filter leaves (a few rows in 2^15 slots) is sorted
    at the rows' capacity bucket, not at the filter's."""
    from spark_rapids_tpu.exec import FilterExec, InMemoryScanExec
    from spark_rapids_tpu.exec.sort import SortExec
    n = 1 << 15
    rng = np.random.default_rng(40)
    t = pa.table({"k": pa.array(rng.permutation(n).astype(np.int64)),
                  "s": pa.array([f"v{i % 7}" for i in range(n)])})
    keep = FilterExec(col("k") < lit(50), InMemoryScanExec(t))
    out = list(SortExec([desc(col("s")), asc(col("k"))], keep)
               .execute_partition(0))
    assert len(out) == 1 and int(out[0].num_rows) == 50
    assert out[0].capacity == 128 < n
    from spark_rapids_tpu.batch import to_arrow
    got = to_arrow(out[0], keep.output_schema).to_pylist()
    want = sorted(({"k": k, "s": f"v{i % 7}"} for i, k in
                   enumerate(t["k"].to_pylist()) if k < 50),
                  key=lambda r: (-int(r["s"][1:]), r["k"]))
    assert got == want


def test_a_lone_partial_is_handed_on_unmerged():
    """One update's partial holds each group once already: the Partial
    stage launches no merge for it, and the Final's answer is the same."""
    from spark_rapids_tpu.exec import (AggregateMode, HashAggregateExec,
                                       InMemoryScanExec)
    t = _sales(2000, seed=41)
    keys = [col("cat"), col("brand")]
    aggs = [Sum(col("price")).alias("total"), Count().alias("n")]
    partial = HashAggregateExec(keys, aggs, InMemoryScanExec(t),
                                AggregateMode.PARTIAL)

    def refuse(batch):
        raise AssertionError("a lone partial was merged")
    partial._merge_jit = refuse
    final = HashAggregateExec(keys, aggs, partial, AggregateMode.FINAL)
    from spark_rapids_tpu.batch import to_arrow
    got = pa.concat_tables(to_arrow(b, final.output_schema)
                           for b in final.execute_partition(0))
    want = Session({}).collect(
        table(t, batch_rows=512).group_by("cat", "brand").agg(*aggs))
    assert_tables_equal(got, want, ignore_order=True, approx_float=False)
