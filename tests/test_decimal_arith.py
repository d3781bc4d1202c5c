"""Decimal +, -, * at Spark 3.3's types (DecimalPrecision with
allowPrecisionLoss=true), on the device path, against the row interpreter
AND against Python's ``decimal`` computed here from the typing rules:
across scales and precisions 1-38, full-range 18-digit operands, negatives,
nulls, products and sums past the precision (null; reported in ANSI mode),
and results whose type cuts the scale (CPU fallback with a reason)."""

import decimal as d
import random

import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.plan import Session, table

from harness.asserts import (assert_tpu_and_cpu_are_equal_collect,
                             assert_tpu_and_cpu_error)

ANSI = {"spark.rapids.tpu.sql.ansi.enabled": True}
OPS = {"+": lambda a, b: a + b, "-": lambda a, b: a - b,
       "*": lambda a, b: a * b}


def adjust(p, s):
    if p <= 38:
        return p, s
    return 38, max(38 - (p - s), min(s, 6))


def spark_type(op, p1, s1, p2, s2):
    if op == "*":
        return adjust(p1 + p2 + 1, s1 + s2)
    return adjust(max(s1, s2) + max(p1 - s1, p2 - s2) + 1, max(s1, s2))


def to_type(v, p, s):
    """Spark's cast / CheckOverflow: HALF_UP at the scale, None past p."""
    with d.localcontext() as cx:
        cx.prec = 120
        q = v.quantize(d.Decimal(1).scaleb(-s), rounding=d.ROUND_HALF_UP)
        return None if abs(q.scaleb(s)) >= 10 ** p else q


def expected(op, a, b, p1, s1, p2, s2):
    if a is None or b is None:
        return None
    p, s = spark_type(op, p1, s1, p2, s2)
    with d.localcontext() as cx:
        cx.prec = 120
        if op != "*":       # promotePrecision: operands cast first
            a, b = to_type(a, p, s), to_type(b, p, s)
            if a is None or b is None:
                return None
        return to_type(OPS[op](a, b), p, s)


def column(rng, p, s, n, full=False):
    with d.localcontext() as cx:
        cx.prec = 60
        out = []
        for i in range(n):
            if i % 9 == 4:
                out.append(None)
                continue
            digits = p if full or i % 5 == 0 else rng.randrange(1, p + 1)
            x = rng.randrange(10 ** (digits - 1), 10 ** digits)
            if i % 7 == 0:
                x = 10 ** p - 1             # the type's extreme
            out.append(d.Decimal(-x if rng.random() < 0.45 else x)
                       .scaleb(-s))
        return out


# (p1, s1, p2, s2): int64 x int64 results, int64 -> limb results, limb
# operands, capped precisions that can overflow, and 18-digit extremes
SHAPES = [(1, 0, 1, 0), (5, 2, 7, 3), (8, 8, 9, 0), (9, 4, 8, 4),
          (15, 2, 16, 2), (18, 0, 18, 0), (18, 18, 18, 0), (18, 9, 18, 9),
          (32, 4, 16, 2), (20, 0, 10, 5), (25, 2, 12, 4), (38, 0, 1, 0),
          (38, 6, 38, 6), (30, 6, 30, 0), (38, 2, 18, 2), (1, 0, 15, 2)]


def cuts_scale(op, p1, s1, p2, s2):
    _, s = spark_type(op, p1, s1, p2, s2)
    return s < (s1 + s2 if op == "*" else max(s1, s2))


def frame(p1, s1, p2, s2, seed, n=120):
    rng = random.Random(seed)
    return pa.table({
        "a": pa.array(column(rng, p1, s1, n, full=seed % 2 == 0),
                      pa.decimal128(p1, s1)),
        "b": pa.array(column(rng, p2, s2, n, full=seed % 3 == 0),
                      pa.decimal128(p2, s2))})


@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_device_matches_python_decimal_and_interpreter(op, shape):
    p1, s1, p2, s2 = shape
    t = frame(*shape, seed=sum(shape) + len(op))

    def q():
        return table(t).select(OPS[op](col("a"), col("b")).alias("r"))
    p, s = spark_type(op, *shape)
    want = [expected(op, a, b, *shape) for a, b in
            zip(t.column("a").to_pylist(), t.column("b").to_pylist())]
    dev = Session()
    got = dev.collect(q())
    assert got.schema.field("r").type == pa.decimal128(p, s)
    assert got.column("r").to_pylist() == want
    if cuts_scale(op, *shape):
        assert dev.fell_back(), "a scale-cutting result ran on the device"
        assert "scale is cut" in Session().explain(q())
    else:
        assert not dev.fell_back(), dev.fell_back()
    cpu = Session({"spark.rapids.tpu.sql.enabled": False}).collect(q())
    assert cpu.schema == got.schema
    assert cpu.column("r").to_pylist() == want


def test_overflow_is_null_and_reported_in_ansi_mode():
    big = d.Decimal(10 ** 20 - 1)
    t = pa.table({"a": pa.array([big, d.Decimal(2), None],
                                pa.decimal128(20, 0)),
                  "b": pa.array([big, d.Decimal(3), big],
                                pa.decimal128(20, 0))})

    def q():    # decimal(41,0) -> decimal(38,0): about 10^40 does not fit
        return table(t).select((col("a") * col("b")).alias("m"))
    got = assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)
    assert got.column("m").to_pylist() == [None, d.Decimal(6), None]
    assert_tpu_and_cpu_error(q, "ARITHMETIC_OVERFLOW", conf=ANSI)


def test_sum_past_38_digits_is_null_and_reported():
    top, bot = d.Decimal(10 ** 38 - 1), d.Decimal(1 - 10 ** 38)
    t = pa.table({"a": pa.array([top, bot, top], pa.decimal128(38, 0)),
                  "b": pa.array([top, bot, bot], pa.decimal128(38, 0))})

    def q():
        return table(t).select((col("a") + col("b")).alias("s"),
                               (col("a") - col("b")).alias("m"))
    got = assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)
    assert got.column("s").to_pylist() == [None, None, d.Decimal(0)]
    assert got.column("m").to_pylist() == [d.Decimal(0), d.Decimal(0), None]
    assert_tpu_and_cpu_error(q, "ARITHMETIC_OVERFLOW", conf=ANSI)


MONEY = pa.table({
    "p": pa.array([d.Decimal("100.00"), d.Decimal("-3.25"),
                   d.Decimal("9999999999999.99"), None],
                  pa.decimal128(15, 2)),
    "d": pa.array([d.Decimal("0.05"), d.Decimal("0.10"),
                   d.Decimal("0.00"), d.Decimal("0.01")],
                  pa.decimal128(15, 2))})
ONE = d.Decimal("1")


def test_fault_one_minus_discount_aligns_scales():
    """ISSUE 31, first fault: ``lit(1) - d`` answered -0.04 for 0.95 typed
    decimal(15,2): storage cast, scales never aligned, no +1."""
    ses = Session()
    got = ses.collect(table(MONEY).select((lit(ONE) - col("d")).alias("r")))
    assert got.schema.field("r").type == pa.decimal128(16, 2)
    assert got.column("r").to_pylist() == [
        d.Decimal("0.95"), d.Decimal("0.90"), d.Decimal("1.00"),
        d.Decimal("0.99")]
    assert not ses.fell_back()


def test_fault_price_times_one_minus_discount_is_wide():
    """ISSUE 31, second fault: the product, typed decimal(31,4), passed
    the planner, multiplied in one int64 lane and died in ``to_arrow``."""
    ses = Session()
    disc_price = col("p") * (lit(ONE) - col("d"))
    got = ses.collect(table(MONEY).select(
        disc_price.alias("dp"),
        (disc_price * (lit(ONE) + col("d"))).alias("ch")))
    assert got.schema.field("dp").type == pa.decimal128(32, 4)
    assert got.schema.field("ch").type == pa.decimal128(38, 6)
    assert got.column("dp").to_pylist() == [
        d.Decimal("95.0000"), d.Decimal("-2.9250"),
        d.Decimal("9999999999999.9900"), None]
    assert got.column("ch").to_pylist() == [
        d.Decimal("99.750000"), d.Decimal("-3.217500"),
        d.Decimal("9999999999999.990000"), None]
    assert not ses.fell_back()


@pytest.mark.parametrize("kind,digits", [("int8", 3), ("int16", 5),
                                         ("int32", 10), ("int64", 20)])
def test_integral_operand_enters_as_its_decimal(kind, digits):
    t = pa.table({"i": pa.array([3, -7, None], getattr(pa, kind)()),
                  "x": pa.array([d.Decimal("1.25"), d.Decimal("-0.75"),
                                 d.Decimal("2.00")], pa.decimal128(9, 2))})
    ses = Session()
    got = ses.collect(table(t).select((col("i") * col("x")).alias("m"),
                                      (col("x") + col("i")).alias("s")))
    assert got.schema.field("m").type == pa.decimal128(digits + 10, 2)
    assert got.schema.field("s").type == pa.decimal128(
        max(digits, 7) + 3, 2)
    assert got.column("m").to_pylist() == [d.Decimal("3.75"),
                                           d.Decimal("5.25"), None]
    assert got.column("s").to_pylist() == [d.Decimal("4.25"),
                                           d.Decimal("-7.75"), None]
    assert not ses.fell_back()


def test_decimal_with_double_computes_in_double():
    t = pa.table({"x": pa.array([d.Decimal("1.25"), None],
                                pa.decimal128(9, 2)),
                  "f": pa.array([2.0, 1.0])})
    got = assert_tpu_and_cpu_are_equal_collect(
        lambda: table(t).select((col("x") * col("f")).alias("m")),
        ignore_order=False)
    assert got.column("m").to_pylist() == [2.5, None]


def test_negate_and_abs_of_limbs():
    t = frame(30, 6, 30, 0, seed=11)
    from spark_rapids_tpu.expressions.arithmetic import Abs, UnaryMinus
    got = assert_tpu_and_cpu_are_equal_collect(
        lambda: table(t).select(UnaryMinus(col("a")).alias("n"),
                                Abs(col("a")).alias("m")),
        ignore_order=False)
    want = t.column("a").to_pylist()
    assert got.column("n").to_pylist() == [
        None if v is None else v.copy_negate() for v in want]
    assert got.column("m").to_pylist() == [
        None if v is None else v.copy_abs() for v in want]


@pytest.mark.parametrize("a,b,add,mul", [
    ((15, 2), (1, 0), (16, 2), (17, 2)),
    ((15, 2), (16, 2), (17, 2), (32, 4)),
    ((32, 4), (16, 2), (33, 4), (38, 6)),
    ((38, 10), (38, 10), (38, 9), (38, 6)),
    ((38, 0), (10, 6), (38, 6), (38, 6)),
    ((20, 0), (9, 2), (23, 2), (30, 2)),
])
def test_spark_result_types(a, b, add, mul):
    ta, tb = T.decimal(*a), T.decimal(*b)
    r = T.decimal_add_type(ta, tb)
    assert (r.precision, r.scale) == add
    r = T.decimal_multiply_type(ta, tb)
    assert (r.precision, r.scale) == mul


def test_comparison_across_a_wide_scale_gap_runs_on_device():
    """A scale gap of 14 digits between limb operands: the rescale steps
    by 10^9 at a time (it was gated to the CPU past 9)."""
    with d.localcontext() as cx:
        cx.prec = 60
        t = pa.table({
            "a": pa.array([d.Decimal("12345678901234567890.5"),
                           d.Decimal("-1.5"), None], pa.decimal128(22, 1)),
            "b": pa.array([d.Decimal("12345678901234567890.500000000000001"),
                           d.Decimal("-1.500000000000000"), d.Decimal(0)],
                          pa.decimal128(36, 15))})
    ses = Session()
    got = ses.collect(table(t).select((col("a") < col("b")).alias("lt"),
                                      (col("a") == col("b")).alias("eq")))
    assert not ses.fell_back(), ses.fell_back()
    assert got.column("lt").to_pylist() == [True, False, None]
    assert got.column("eq").to_pylist() == [False, True, None]


def test_windowed_decimal_average_falls_back_with_a_reason():
    from spark_rapids_tpu.expressions.aggregates import Average
    from spark_rapids_tpu.expressions.window import WindowAgg, over
    t = pa.table({"k": pa.array([0, 0, 1], pa.int32()),
                  "x": pa.array([d.Decimal("1.00"), d.Decimal("2.00"),
                                 d.Decimal("5.00")], pa.decimal128(9, 2))})
    w = over(WindowAgg(Average(col("x"))), [col("k")])
    ses = Session()
    got = ses.collect(table(t).window(w.alias("a")))
    assert ses.fell_back(), "a windowed decimal avg has no device kernel"
    assert "no decimal128 kernel" in ses.explain(
        table(t).window(w.alias("a")))
    assert sorted(got.column("a").to_pylist()) == [
        d.Decimal("1.500000"), d.Decimal("1.500000"), d.Decimal("5.000000")]
