"""``count(DISTINCT x)`` on the DataFrame surface: planned as Spark 3.3
plans one distinct column (an aggregate on keys + ``x`` carrying the other
aggregates' partials, then one on the keys that counts the non-null
distinct values and merges the partials), every operator on the device,
equal to the row interpreter and to pyarrow's own distinct count, with
Spark's result types; a second distinct column set is refused at planning
and never answered wrongly."""

import decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu.exec.aggregate import AggregateMode, HashAggregateExec
from spark_rapids_tpu.expressions import col
from spark_rapids_tpu.expressions.aggregates import (Average, Count, Max,
                                                     Sum, count_distinct)
from spark_rapids_tpu.plan import Session, table
from spark_rapids_tpu.plan.overrides import Overrides

from harness.asserts import assert_tables_equal

CPU = {"spark.rapids.tpu.sql.enabled": False}


def _orders(n=4000, seed=37, null_share=0.1):
    rng = np.random.default_rng(seed)
    cents = rng.integers(-500000, 500000, size=n)
    return pa.table({
        "k": pa.array(rng.integers(0, 9, size=n).astype(np.int32),
                      mask=rng.random(n) < 0.05),
        "o": pa.array(rng.integers(0, 600, size=n).astype(np.int64),
                      mask=rng.random(n) < null_share),
        "s": pa.array([f"site {v}" for v in
                       rng.integers(0, 40, size=n).tolist()],
                      mask=rng.random(n) < 0.05),
        "m": pa.array([decimal.Decimal(int(c)).scaleb(-2)
                       for c in cents.tolist()], pa.decimal128(7, 2),
                      mask=rng.random(n) < 0.04),
    })


def _aggs(x="o"):
    return [count_distinct(col(x)).alias("n_distinct"),
            Sum(col("m")).alias("total"), Count(col("m")).alias("n"),
            Average(col("m")).alias("mean"), Max(col("k")).alias("top")]


def _both(df, keys=()):
    tpu = Session({})
    got = tpu.collect(df)
    assert not tpu.fell_back(), tpu.fell_back()
    want = Session(CPU).collect(df)
    assert_tables_equal(got, want, ignore_order=bool(keys),
                        approx_float=False)
    return got


@pytest.mark.parametrize("slices", [1, 4], ids=["one_partition", "shuffled"])
@pytest.mark.parametrize("keys", [(), ("k",)], ids=["global", "grouped"])
def test_equals_the_row_interpreter(keys, slices):
    t = _orders()
    df = table(t, num_slices=slices, batch_rows=512)
    grouped = df.group_by(*keys) if keys else df
    got = _both(grouped.agg(*_aggs()), keys)
    # Spark's types: bigint for the count, decimal(17,2) for the sum of a
    # decimal(7,2), never null where the group has rows
    assert got.schema.field("n_distinct").type == pa.int64()
    assert got.schema.field("total").type == pa.decimal128(17, 2)
    assert got.column("n_distinct").null_count == 0


@pytest.mark.parametrize("keys", [(), ("k",)], ids=["global", "grouped"])
def test_equals_pyarrows_distinct_count(keys):
    t = _orders(seed=38)
    df = table(t, num_slices=3, batch_rows=1024)
    grouped = df.group_by(*keys) if keys else df
    got = Session({}).collect(grouped.agg(
        count_distinct(col("o")).alias("n_distinct"),
        Sum(col("m")).alias("total")))
    if keys:
        want = t.group_by(list(keys)).aggregate(
            [("o", "count_distinct", pc.CountOptions(mode="only_valid")),
             ("m", "sum")])
        want = {r["k"]: (r["o_count_distinct"], r["m_sum"])
                for r in want.to_pylist()}
        rows = {r["k"]: (r["n_distinct"], r["total"])
                for r in got.to_pylist()}
        assert rows == want
    else:
        assert got.column("n_distinct")[0].as_py() == pc.count_distinct(
            t["o"], mode="only_valid").as_py()
        assert got.column("total")[0].as_py() == pc.sum(t["m"]).as_py()


def test_nulls_in_the_distinct_column_are_not_counted():
    t = pa.table({"k": pa.array([1, 1, 1, 2, 2, 3], pa.int32()),
                  "o": pa.array([5, None, 5, None, None, 7], pa.int64()),
                  "m": pa.array([decimal.Decimal("1.25")] * 6,
                                pa.decimal128(7, 2))})
    got = _both(table(t).group_by("k").agg(
        count_distinct(col("o")).alias("n"), Sum(col("m")).alias("total")),
        keys=("k",))
    rows = {r["k"]: (r["n"], r["total"]) for r in got.to_pylist()}
    assert rows == {1: (1, decimal.Decimal("3.75")),
                    2: (0, decimal.Decimal("2.50")),
                    3: (1, decimal.Decimal("1.25"))}


def test_a_distinct_count_alone_and_of_a_string():
    t = _orders(seed=39)
    got = _both(table(t, num_slices=2).agg(
        count_distinct(col("s")).alias("sites")))
    assert got.column("sites")[0].as_py() == pc.count_distinct(
        t["s"], mode="only_valid").as_py()


def test_no_rows_give_zero_and_null_sums():
    t = _orders(n=50).slice(0, 0)
    got = _both(table(t).agg(count_distinct(col("o")).alias("n"),
                             Sum(col("m")).alias("total")))
    assert got.to_pylist() == [{"n": 0, "total": None}]


def test_plans_sparks_one_distinct_form():
    """Partial and PartialMerge on keys + x, then PartialMerge and Final on
    the keys: four of today's aggregates, an exchange between each pair."""
    t = _orders()
    plan = Overrides().plan(table(t, num_slices=4).group_by("k").agg(
        *_aggs()).plan)
    aggs, exchanges = [], 0

    def walk(e):
        nonlocal exchanges
        if isinstance(e, HashAggregateExec):
            aggs.append(e)
        if "Exchange" in e.name:
            exchanges += 1
        for c in e.children:
            walk(c)
    walk(plan)
    assert [a.mode for a in reversed(aggs)] == [
        AggregateMode.PARTIAL, AggregateMode.PARTIAL_MERGE,
        AggregateMode.PARTIAL_MERGE, AggregateMode.FINAL]
    first, second = aggs[-1], aggs[1]
    assert len(first.key_fields) == 2 and len(second.key_fields) == 1
    assert exchanges == 2


def test_a_second_distinct_column_set_is_refused_at_planning():
    t = _orders()
    df = table(t).agg(count_distinct(col("o")).alias("a"),
                      count_distinct(col("k")).alias("b"))
    with pytest.raises(NotImplementedError, match="not supported"):
        Session({}).collect(df)
    # the same column twice is one set, and both counts agree
    got = _both(table(t).agg(count_distinct(col("o")).alias("a"),
                             count_distinct(col("o")).alias("b")))
    assert got.column("a")[0].as_py() == got.column("b")[0].as_py()


def test_the_distinct_aggregate_survives_the_wire():
    from spark_rapids_tpu.server.plandoc import doc_to_plan, plan_to_doc
    df = table(_orders(n=100)).group_by("k").agg(*_aggs())
    doc, tables = plan_to_doc(df.plan)
    back = doc_to_plan(doc, tables)
    assert back.schema().names == df.plan.schema().names
    assert plan_to_doc(back)[0] == doc
