"""DECIMAL128 limb-storage tests (reference: the cudf __int128 column
path in GpuCast.scala/DecimalUtil.scala; here expressions/decimal128.py).
"""

import decimal as d
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.batch import from_arrow, to_arrow
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import (Count, First, Last, Max,
                                                     Min, Sum)
from spark_rapids_tpu.plan import Session, table

from harness.asserts import (assert_tpu_and_cpu_are_equal_collect,
                             assert_tpu_fallback_collect)


def wide_table(seed=7, n=200):
    rng = random.Random(seed)
    vals, ks = [], []
    for i in range(n):
        ks.append(rng.randrange(6))
        if i % 11 == 0:
            vals.append(None)
        else:
            digits = rng.randrange(1, 35)
            x = rng.randrange(10 ** digits)
            if rng.random() < 0.5:
                x = -x
            vals.append(d.Decimal(x).scaleb(-4))
    return pa.table({
        "k": pa.array(ks, pa.int32()),
        "w": pa.array(vals, pa.decimal128(38, 4)),
    })


def test_roundtrip():
    t = wide_table()
    batch, schema = from_arrow(t)
    assert to_arrow(batch, schema).column("w").to_pylist() == \
        t.column("w").to_pylist()


def test_groupby_sum_min_max():
    """The VERDICT acceptance shape: decimal(38,x) group-by aggregate."""
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).group_by("k").agg(
            Sum(col("w")).alias("s"), Min(col("w")).alias("mn"),
            Max(col("w")).alias("mx"), Count(col("w")).alias("c")))


def test_groupby_runs_on_device():
    s = Session()
    s.collect(table(wide_table()).group_by("k").agg(
        Sum(col("w")).alias("s")))
    assert not s.fell_back(), s.fell_back()


def test_filter_compare():
    bound = d.Decimal("1000000000000000000.0001")   # > int64 range
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).where(
            col("w") > lit(bound, __import__(
                "spark_rapids_tpu.types", fromlist=["types"]
            ).decimal(38, 4))))


def test_sort():
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).order_by("w"), ignore_order=False)


def test_dec64_sum_widens_on_device():
    """sum(decimal(12,2)) → Spark decimal(22,2): the accumulator must hold
    >18 digits; round 1 gated this to CPU, now lift64 widening covers it."""
    rng = random.Random(3)
    t = pa.table({
        "k": pa.array([rng.randrange(3) for _ in range(300)], pa.int32()),
        "x": pa.array([d.Decimal(rng.randrange(-10**11, 10**11))
                       .scaleb(-2) for _ in range(300)],
                      pa.decimal128(12, 2)),
    })
    s = Session()
    got = s.collect(table(t).group_by("k").agg(Sum(col("x")).alias("s")))
    assert not s.fell_back()
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    exp = cpu.collect(table(t).group_by("k").agg(Sum(col("x")).alias("s")))
    assert sorted(zip(got.column("k").to_pylist(),
                      got.column("s").to_pylist())) == \
        sorted(zip(exp.column("k").to_pylist(), exp.column("s").to_pylist()))


def test_first_last():
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).group_by("k").agg(
            Min(col("w")).alias("mn"), Max(col("w")).alias("mx"),
            Count().alias("c")))


def test_dec128_murmur3_vs_oracle():
    """Bit-exactness of the 128-bit murmur3 path (VERDICT r4 Next #5)
    against the scalar Java-faithful oracle, across byte-length edges."""
    import jax.numpy as jnp
    from spark_rapids_tpu.expressions.hashing import murmur3_batch
    from harness.murmur3_oracle import hash_decimal

    edge = [0, 1, -1, 127, 128, -128, -129, 255, 256, -256,
            2**31 - 1, 2**31, -(2**31), 2**32 - 1, 2**32, -(2**32),
            2**63 - 1, 2**63, -(2**63), 10**37, -(10**37),
            3 * 10**37, -(3 * 10**37), 2**96 + 12345, -(2**96) - 99]
    rng = random.Random(11)
    vals = edge + [rng.randrange(-(10**37), 10**37) for _ in range(200)]
    with d.localcontext() as lctx:
        lctx.prec = 60      # the default 28-digit context ROUNDS scaleb
        decs = [d.Decimal(v).scaleb(-4) for v in vals]
    t = pa.table({"w": pa.array(decs, pa.decimal128(38, 4))})
    batch, schema = from_arrow(t)
    got = np.asarray(murmur3_batch(
        [batch.columns[0]])[:t.num_rows]).tolist()
    expected = [_i32(hash_decimal(v, 38, 42)) for v in vals]
    assert got == expected


def _i32(x):
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x >= (1 << 31) else x


def test_dec128_group_key_on_device():
    """dec128 GROUP KEYS run on device via limb order keys + the 128-bit
    hash exchange path (the r4 fallback tag is gone)."""
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).group_by("w").agg(Count().alias("c")),
        ignore_order=True)
    s = Session()
    s.collect(table(wide_table()).group_by("w").agg(Count().alias("c")))
    assert not s.fell_back(), s.fell_back()


def test_dec128_join_key_on_device():
    def q():
        left = table(wide_table(seed=7))
        right = table(wide_table(seed=7)).group_by("w").agg(
            Count().alias("n"))
        return left.join(right, [col("w")], [col("w")],
                         __import__("spark_rapids_tpu.exec.join",
                                    fromlist=["JoinType"]).JoinType.INNER)
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_dec128_key_through_exchange():
    """multi-slice scan → hash exchange routes dec128 keys by the
    Spark-bit-exact 128-bit murmur3 (shuffle placement compatibility)."""
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table(), num_slices=3).group_by("w").agg(
            Count().alias("c"), Min(col("w")).alias("mn")),
        ignore_order=True)


def test_dec128_arithmetic_falls_back():
    """A product whose Spark type cuts the scale (decimal(77,8) adjusted
    to decimal(38,6)) rounds inside the arithmetic: no device kernel."""
    assert_tpu_fallback_collect(
        lambda: table(wide_table()).select(
            (col("w") * col("w")).alias("squared")),
        "Project")


def test_dec128_addition_runs_on_device():
    """decimal(38,4) + decimal(38,4) keeps its scale (decimal(38,4)): the
    limb kernel adds it, a sum past 38 digits null (the gate is gone)."""
    s = Session()
    t = wide_table()
    got = s.collect(table(t).select((col("w") + col("w")).alias("twice")))
    assert not s.fell_back(), s.fell_back()
    with d.localcontext() as cx:
        cx.prec = 60
        want = [None if v is None else v + v
                for v in t.column("w").to_pylist()]
    assert got.column("twice").to_pylist() == want
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(wide_table()).select(
            (col("w") + col("w")).alias("twice")))


def test_sum_overflow_nulls():
    """Sum exceeding 38 digits nulls the group (Spark non-ANSI), device
    and interpreter alike (review finding)."""
    big = d.Decimal(10 ** 37)
    t = pa.table({"k": pa.array([0] * 45 + [1], pa.int32()),
                  "w": pa.array([big] * 45 + [d.Decimal(7)],
                                pa.decimal128(38, 0))})
    s = Session()
    got = s.collect(table(t).group_by("k").agg(Sum(col("w")).alias("s")))
    assert not s.fell_back()
    res = dict(zip(got.column("k").to_pylist(), got.column("s").to_pylist()))
    assert res[0] is None and res[1] == d.Decimal(7)
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    exp = cpu.collect(table(t).group_by("k").agg(Sum(col("w")).alias("s")))
    eres = dict(zip(exp.column("k").to_pylist(), exp.column("s").to_pylist()))
    assert eres == res


def test_mixed_scale_compare():
    """decimal(10,2) vs decimal(25,3) comparison rescales on device
    (review finding: raw unscaled compare gave wrong answers)."""
    t = pa.table({
        "a": pa.array([d.Decimal("5.00"), d.Decimal("-1.25"),
                       d.Decimal("4.00")], pa.decimal128(10, 2)),
        "b": pa.array([d.Decimal("4.000"), d.Decimal("-1.250"),
                       d.Decimal("4.001")], pa.decimal128(25, 3)),
    })
    assert_tpu_and_cpu_are_equal_collect(
        lambda: table(t).select(
            (col("a") > col("b")).alias("gt"),
            (col("a") == col("b")).alias("eq"),
            (col("a") <= col("b")).alias("le")))


def test_size_of_map_stays_on_device():
    from spark_rapids_tpu.expressions.collections import Size
    maps = [[(1, 2)], [], None]
    t = pa.table({"m": pa.array(maps, pa.map_(pa.int32(), pa.int64()))})
    s = Session()
    out = s.collect(table(t).select(Size(col("m")).alias("n")))
    assert not s.fell_back(), s.fell_back()
    assert out.column("n").to_pylist() == [1, 0, -1]
