"""TPC-H Q1, pricing summary report (specification §2.4.1), validation
parameter DELTA = 90, at the schema's published ``decimal(15,2)`` money and
quantity (§1.4), typed as Spark 3.3 types decimal arithmetic
(``spark.sql.decimalOperations.allowPrecisionLoss=true``, non-ANSI):

    1 - l_discount, 1 + l_tax            decimal(16,2)  (the literal 1 is decimal(1,0))
    l_extendedprice * (1 - l_discount)   decimal(32,4)
    ... * (1 + l_tax)                    decimal(38,6)  (decimal(49,6) adjusted)
    sum(decimal(p,s))                    decimal(min(p+10, 38), s)
    avg(decimal(15,2))                   decimal(19,6), rounded HALF_UP

scan -> filter -> project -> 8 aggregates over 4 groups -> sort. The plan
side imports the engine's plan builder; the reference side is exact integer
arithmetic on cents (numpy int64 where a bound shows it cannot overflow,
Python ints otherwise) on the same Parquet files and imports nothing of the
engine.
"""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

# table -> the columns the plan reads (Spark's ReadSchema: the scan is pruned
# to them); also what the touched-bytes function counts
TABLES = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                       "l_returnflag", "l_linestatus", "l_shipdate"]}
PARAMS = {"delta": 90}
ORDERED = True

# result column -> (precision, scale), Spark's
SUM_TYPES = {"sum_qty": (25, 2), "sum_base_price": (25, 2),
             "sum_disc_price": (38, 4), "sum_charge": (38, 6)}
AVG_TYPE = (19, 6)


def _cutoff(params):
    return datetime.date(1998, 12, 1) - datetime.timedelta(
        days=int(params["delta"]))


def plan(scan, params):
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
    one = lit(decimal.Decimal("1"))
    disc_price = col("l_extendedprice") * (one - col("l_discount"))
    return (scan("lineitem")
            .where(col("l_shipdate") <= lit(_cutoff(params)))
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"), disc_price.alias("disc_price"),
                    (disc_price * (one + col("l_tax"))).alias("charge"))
            .group_by("l_returnflag", "l_linestatus")
            .agg(Sum(col("l_quantity")).alias("sum_qty"),
                 Sum(col("l_extendedprice")).alias("sum_base_price"),
                 Sum(col("disc_price")).alias("sum_disc_price"),
                 Sum(col("charge")).alias("sum_charge"),
                 Average(col("l_quantity")).alias("avg_qty"),
                 Average(col("l_extendedprice")).alias("avg_price"),
                 Average(col("l_discount")).alias("avg_disc"),
                 Count().alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def _cents(column):
    """A ``decimal(15,2)`` column as int64 cents."""
    col = column.combine_chunks()
    assert pa.types.is_decimal(col.type) and col.type.scale == 2, col.type
    assert col.null_count == 0
    return pc.multiply(col, pa.scalar(decimal.Decimal(100),
                                      pa.decimal128(3, 0))) \
        .cast(pa.int64()).to_numpy()


def _exact_sum(v):
    """Σ v as a Python int: in int64 where the largest value times the
    count stays under 2^62, in Python ints otherwise."""
    if v.size == 0:
        return 0
    if int(np.abs(v).max()) * int(v.size) < 1 << 62:
        return int(v.sum(dtype=np.int64))
    return int(v.astype(object).sum())


def _half_up(num, den):
    """num / den rounded HALF_UP (away from zero at a half), den > 0."""
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return -q if num < 0 else q


def _decimal(unscaled, precision, scale):
    """Spark's non-ANSI result: null past the precision."""
    if unscaled is None or abs(unscaled) >= 10 ** precision:
        return None
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        return decimal.Decimal(unscaled).scaleb(-scale)


def _rounded(value, scale):
    """A float result as the unscaled integer of its nearest decimal at
    ``scale`` (HALF_UP): what a float engine would hand back."""
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        q = decimal.Decimal(repr(float(value))).quantize(
            decimal.Decimal(1).scaleb(-scale),
            rounding=decimal.ROUND_HALF_UP)
        return int(q.scaleb(scale))


def reference(read, params, money=np.int64):
    """``money`` is the type the arithmetic runs in: int64 cents, exact, as
    the configuration states; a numpy float type in the control, which
    carries dollars, sums in that type and rounds to the result's scale."""
    t = read("lineitem", TABLES["lineitem"])
    cutoff = (_cutoff(params) - datetime.date(1970, 1, 1)).days
    keep = t["l_shipdate"].to_numpy().astype(np.int64) <= cutoff
    flag = t["l_returnflag"].to_numpy(zero_copy_only=False)[keep]
    status = t["l_linestatus"].to_numpy(zero_copy_only=False)[keep]
    qty, price, disc, tax = (
        _cents(t[c])[keep]
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    exact = np.issubdtype(money, np.integer)
    if exact:
        # unscaled integers at scale 2, 2, 4, 6: price and (100 - disc)
        # are under 2^31 at any scale factor; the bound is checked
        assert int(price.max(initial=0)) * 110 * 108 < 1 << 62
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
    else:
        qty, price, disc, tax = ((v / 100.0).astype(money)
                                 for v in (qty, price, disc, tax))
        one = money(1.0)
        disc_price = price * (one - disc)
        charge = disc_price * (one + tax)
    rows = []
    for f, s in sorted(set(zip(flag.tolist(), status.tolist()))):
        g = (flag == f) & (status == s)
        n = int(g.sum())
        row = [f, s]
        for (name, (p, sc)), v in zip(SUM_TYPES.items(),
                                      (qty, price, disc_price, charge)):
            total = _exact_sum(v[g]) if exact \
                else _rounded(v[g].sum(dtype=money), sc)
            row.append(_decimal(total, p, sc))
        for v in (qty, price, disc):
            avg = _half_up(_exact_sum(v[g]) * 10 ** 4, n) if exact \
                else _rounded(v[g].sum(dtype=money) / money(n), AVG_TYPE[1])
            row.append(_decimal(avg, *AVG_TYPE))
        rows.append(row + [n])
    names = ["l_returnflag", "l_linestatus", *SUM_TYPES, "avg_qty",
             "avg_price", "avg_disc", "count_order"]
    types = [pa.string()] * 2 \
        + [pa.decimal128(p, sc) for p, sc in SUM_TYPES.values()] \
        + [pa.decimal128(*AVG_TYPE)] * 3 + [pa.int64()]
    return pa.table([pa.array([r[i] for r in rows], ty)
                     for i, ty in enumerate(types)], names=names)
