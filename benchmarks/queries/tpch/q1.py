"""TPC-H Q1, pricing summary report (specification §2.4.1), validation
parameter DELTA = 90.

scan -> filter -> project -> 8 aggregates over 4 groups -> sort. The plan
side imports the engine's plan builder; the reference side is numpy and
pyarrow on the same Parquet files and imports nothing of the engine.
"""

import datetime

import numpy as np
import pyarrow as pa

# table -> the columns the plan reads (Spark's ReadSchema: the scan is pruned
# to them); also what the touched-bytes function counts
TABLES = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                       "l_returnflag", "l_linestatus", "l_shipdate"]}
PARAMS = {"delta": 90}
ORDERED = True


def _cutoff(params):
    return datetime.date(1998, 12, 1) - datetime.timedelta(
        days=int(params["delta"]))


def plan(scan, params):
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (scan("lineitem")
            .where(col("l_shipdate") <= lit(_cutoff(params)))
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"), disc_price.alias("disc_price"),
                    (disc_price * (lit(1.0) + col("l_tax"))).alias("charge"))
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


def reference(read, params, money=np.float64):
    """``money`` is the type the arithmetic runs in: float64 as the
    configuration states, float32 in the control."""
    t = read("lineitem", TABLES["lineitem"])
    cutoff = (_cutoff(params) - datetime.date(1970, 1, 1)).days
    keep = t["l_shipdate"].to_numpy().astype(np.int64) <= cutoff
    flag = t["l_returnflag"].to_numpy(zero_copy_only=False)[keep]
    status = t["l_linestatus"].to_numpy(zero_copy_only=False)[keep]
    qty, price, disc, tax = (
        t[c].to_numpy()[keep].astype(money)
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = money(1.0)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for f, s in sorted(set(zip(flag.tolist(), status.tolist()))):
        g = (flag == f) & (status == s)
        n = int(g.sum())
        sums = [float(v[g].sum(dtype=money))
                for v in (qty, price, disc_price, charge)]
        avgs = [float(v[g].sum(dtype=money) / money(n))
                for v in (qty, price, disc)]
        rows.append([f, s] + sums + avgs + [n])
    names = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
             "avg_disc", "count_order"]
    types = [pa.string()] * 2 + [pa.float64()] * 7 + [pa.int64()]
    return pa.table([pa.array([r[i] for r in rows], ty)
                     for i, ty in enumerate(types)], names=names)
