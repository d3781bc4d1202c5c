"""avg and sum over decimals on the device: Spark's buffer (a
decimal(p+10, s) sum, in limbs past 18 digits, and an int64 count) and
result (sum x 10^4 / count rounded HALF_UP to decimal(p+4, s+4)), against
Python's ``decimal`` and the row interpreter: exact halves, negative sums,
all-null and empty groups, a multi-batch merge, sums that overflow, and the
fused lanes of the aggregate (one exec program, no operator on the CPU)."""

import decimal as d
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import (Average, Count, Max,
                                                     Min, Sum)
from spark_rapids_tpu.plan import Session, table

from harness.asserts import assert_tpu_and_cpu_are_equal_collect


def py_avg(values, p, s):
    nn = [v for v in values if v is not None]
    if not nn:
        return None
    rs = min(s + 4, 38)
    with d.localcontext() as cx:
        cx.prec = 120
        q = (sum(nn) / len(nn)).quantize(d.Decimal(1).scaleb(-rs),
                                         rounding=d.ROUND_HALF_UP)
        return None if abs(q.scaleb(rs)) >= 10 ** min(p + 4, 38) else q


def py_sum(values, p, s):
    nn = [v for v in values if v is not None]
    if not nn:
        return None
    with d.localcontext() as cx:
        cx.prec = 120
        t = sum(nn)
        return None if abs(t.scaleb(s)) >= 10 ** min(p + 10, 38) else t


def grouped(t, name):
    out = {}
    for k, v in zip(t.column("k").to_pylist(), t.column(name).to_pylist()):
        out.setdefault(k, []).append(v)
    return out


def check(t, p, s, conf=None):
    def q():
        return table(t).group_by("k").agg(
            Average(col("x")).alias("a"), Sum(col("x")).alias("s"),
            Count(col("x")).alias("c")).order_by("k")
    ses = Session(conf or {})
    got = ses.collect(q())
    assert not ses.fell_back(), ses.fell_back()
    assert got.schema.field("a").type == pa.decimal128(
        min(p + 4, 38), min(s + 4, 38))
    assert got.schema.field("s").type == pa.decimal128(min(p + 10, 38), s)
    groups = grouped(t, "x")
    keys = got.column("k").to_pylist()
    assert keys == sorted(groups, key=lambda k: (k is None, k))
    assert got.column("a").to_pylist() == [py_avg(groups[k], p, s)
                                           for k in keys]
    assert got.column("s").to_pylist() == [py_sum(groups[k], p, s)
                                           for k in keys]
    cpu = Session({"spark.rapids.tpu.sql.enabled": False}).collect(q())
    assert cpu.to_pylist() == got.to_pylist()
    assert cpu.schema == got.schema
    return got


def random_table(p, s, n, seed, groups=5):
    rng = random.Random(seed)
    with d.localcontext() as cx:
        cx.prec = 60
        xs = []
        for i in range(n):
            if i % 6 == 5:
                xs.append(None)
                continue
            v = rng.randrange(10 ** rng.randrange(1, p + 1))
            xs.append(d.Decimal(-v if rng.random() < 0.5 else v).scaleb(-s))
    return pa.table({"k": pa.array([rng.randrange(groups)
                                    for _ in range(n)], pa.int32()),
                     "x": pa.array(xs, pa.decimal128(p, s))})


@pytest.mark.parametrize("p,s", [(4, 2), (7, 2), (8, 0), (9, 9), (15, 2),
                                 (18, 6), (18, 18), (19, 0), (28, 10),
                                 (34, 4), (38, 6), (38, 36)])
def test_avg_and_sum_match_python_decimal(p, s):
    check(random_table(p, s, 700, seed=p * 40 + s), p, s)


def test_half_up_at_exact_halves_and_negative_sums():
    # sums over counts whose quotient ends in exactly 5 at the 7th digit
    D = d.Decimal
    rows = [(0, "0.01"), (0, "0.02"),              # 0.015     -> 0.015000
            (1, "0.000001"), (1, "0.000002"),      # scale 6 input below
            (2, "-0.01"), (2, "-0.02"),
            (3, "1.00"), (3, "2.00"), (3, "2.00"),  # 5/3 = 1.666666|6
            (4, "-1.00"), (4, "-2.00"), (4, "-2.00")]
    t = pa.table({"k": pa.array([k for k, _ in rows], pa.int32()),
                  "x": pa.array([D(v).quantize(D("0.01")) if k != 1
                                 else D("0.00") for k, v in rows],
                                pa.decimal128(15, 2))})
    got = check(t, 15, 2)
    assert got.column("a").to_pylist()[3] == D("1.666667")
    assert got.column("a").to_pylist()[4] == D("-1.666667")
    # scale 6 -> result scale 10: (1 + 2) / 2 at the last digit
    # 0.0000000001 x 3 / 2 = ...00015 -> exact half at digit 11
    t6 = pa.table({"k": pa.array([0, 0, 1, 1], pa.int32()),
                   "x": pa.array([D("0.000001"), D("0.000002"),
                                  D("-0.000001"), D("-0.000002")],
                                 pa.decimal128(12, 6))})
    got = check(t6, 12, 6)
    assert got.column("a").to_pylist() == [D("0.0000015000"),
                                           D("-0.0000015000")]
    # an exact half past the result's last digit: 1 / 2 at scale 38
    th = pa.table({"k": pa.array([0, 0, 1, 1], pa.int32()),
                   "x": pa.array([D(1).scaleb(-36), D(0), D(-1).scaleb(-36),
                                  D(0)], pa.decimal128(38, 36))})
    got = check(th, 38, 36)
    assert got.column("a").to_pylist() == [D(5).scaleb(-37),
                                           D(-5).scaleb(-37)]
    thh = pa.table({"k": pa.array([0, 0, 1, 1], pa.int32()),
                    "x": pa.array([D(1).scaleb(-38), D(0),
                                   D(-1).scaleb(-38), D(0)],
                                  pa.decimal128(38, 38))})
    got = check(thh, 38, 38)    # 0.5 ulp rounds AWAY from zero
    assert got.column("a").to_pylist() == [D(1).scaleb(-38),
                                           D(-1).scaleb(-38)]


def test_all_null_group_and_empty_input():
    t = pa.table({"k": pa.array([0, 0, 1], pa.int32()),
                  "x": pa.array([None, None, d.Decimal("2.50")],
                                pa.decimal128(15, 2))})
    got = check(t, 15, 2)
    assert got.column("a").to_pylist() == [None, d.Decimal("2.500000")]
    assert got.column("c").to_pylist() == [0, 1]
    empty = t.slice(0, 0)
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(empty).group_by("k").agg(
            Average(col("x")).alias("a")))
    got = assert_tpu_and_cpu_are_equal_collect(      # keyless: one row
        lambda: table(empty).agg(Average(col("x")).alias("a"),
                                 Sum(col("x")).alias("s")))
    assert got.to_pylist() == [{"a": None, "s": None}]


@pytest.mark.parametrize("p,s", [(15, 2), (7, 2), (30, 4)])
def test_multi_batch_merge(p, s):
    """Partials of several scan batches merged: the limb buffers ride the
    merge kernel's fused lanes too."""
    t = random_table(p, s, 5000, seed=p, groups=3)
    check(t, p, s, conf={"spark.rapids.tpu.sql.batchRowCapacity": 1024})


def test_keyless_and_high_cardinality_take_the_general_path():
    t = random_table(15, 2, 600, seed=9, groups=200)
    check(t, 15, 2)
    got = assert_tpu_and_cpu_are_equal_collect(
        lambda: table(t).agg(Average(col("x")).alias("a"),
                             Sum(col("x")).alias("s"),
                             Min(col("x")).alias("mn"),
                             Max(col("x")).alias("mx")))
    xs = t.column("x").to_pylist()
    assert got.column("a").to_pylist() == [py_avg(xs, 15, 2)]


def test_sum_overflow_nulls_sum_and_average():
    top = d.Decimal(10 ** 38 - 1)
    t = pa.table({"k": pa.array([0] * 12 + [1] * 2, pa.int32()),
                  "x": pa.array([top] * 12 + [top, d.Decimal(1 - 10 ** 38)],
                                pa.decimal128(38, 0))})
    got = check(t, 38, 0)
    assert got.column("s").to_pylist() == [None, d.Decimal(0)]
    assert got.column("a").to_pylist() == [None, d.Decimal(0)]


def test_aggregate_over_limb_products_stays_in_one_program():
    """Q1's shape: sums of limb products and decimal averages, string
    keys; the partial aggregate is ONE exec with every aggregate on the
    fused lanes (no generic update under it)."""
    rng = random.Random(4)
    n = 4000
    D = d.Decimal
    t = pa.table({
        "f": pa.array([rng.choice("ANR") for _ in range(n)]),
        "p": pa.array([D(rng.randrange(90000, 10500000)).scaleb(-2)
                       for _ in range(n)], pa.decimal128(15, 2)),
        "dsc": pa.array([D(rng.randrange(0, 11)).scaleb(-2)
                         for _ in range(n)], pa.decimal128(15, 2)),
        "tax": pa.array([D(rng.randrange(0, 9)).scaleb(-2)
                         for _ in range(n)], pa.decimal128(15, 2))})
    one = lit(D("1"))
    dp = col("p") * (one - col("dsc"))

    def q():
        return (table(t).select(col("f"), col("p"), dp.alias("dp"),
                                (dp * (one + col("tax"))).alias("ch"))
                .group_by("f").agg(Sum(col("dp")).alias("sdp"),
                                   Sum(col("ch")).alias("sch"),
                                   Average(col("p")).alias("ap"))
                .order_by("f"))
    ses = Session()
    got = ses.collect(q())
    assert not ses.fell_back()
    want = {}
    for f, p, ds, tx in zip(*(t.column(c).to_pylist() for c in t.column_names)):
        w = want.setdefault(f, [D(0), D(0), []])
        with d.localcontext() as cx:
            cx.prec = 80
            w[0] += p * (1 - ds)
            w[1] += p * (1 - ds) * (1 + tx)
            w[2].append(p)
    assert got.column("sdp").to_pylist() == [want[f][0] for f in "ANR"]
    assert got.column("sch").to_pylist() == [want[f][1] for f in "ANR"]
    assert got.column("ap").to_pylist() == [py_avg(want[f][2], 15, 2)
                                            for f in "ANR"]
    assert got.schema.field("sdp").type == pa.decimal128(38, 4)
    assert got.schema.field("sch").type == pa.decimal128(38, 6)
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.expressions.aggregates import FastLanes
    aggs = [n_ for n_ in _walk(ses.last_plan)
            if isinstance(n_, HashAggregateExec)]
    assert aggs and all(a._fast_update or a._fast_merge for a in aggs)
    live = np.ones(8, bool)
    for a in aggs:          # every aggregate registers lanes: none generic
        for agg in a.aggs:
            assert type(agg).fast_update is not \
                __import__("spark_rapids_tpu.expressions.aggregates",
                           fromlist=["AggregateFunction"]) \
                .AggregateFunction.fast_update


def _walk(node):
    yield node
    for c in getattr(node, "children", ()):
        yield from _walk(c)
