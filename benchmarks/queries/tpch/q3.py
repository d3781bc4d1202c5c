"""TPC-H Q3, shipping priority (specification §2.4.3), validation parameters
SEGMENT = BUILDING, DATE = 1995-03-15.

customer (filtered on a string) joins orders joins lineitem -> aggregate of
some ten thousand groups -> top 10 by revenue. The reference is numpy and
pyarrow on the same Parquet files and imports nothing of the engine.
"""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"customer": ["c_custkey", "c_mktsegment"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"],
          "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate"]}
PARAMS = {"segment": "BUILDING", "date": "1995-03-15"}
ORDERED = True
LIMIT = 10


def plan(scan, params):
    from spark_rapids_tpu.exec.sort import asc, desc
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum
    day = datetime.date.fromisoformat(params["date"])
    cust = scan("customer").where(
        col("c_mktsegment") == lit(params["segment"])).select("c_custkey")
    orders = scan("orders").where(col("o_orderdate") < lit(day))
    lines = scan("lineitem").where(col("l_shipdate") > lit(day)).select(
        col("l_orderkey"),
        (col("l_extendedprice") * (lit(1.0) - col("l_discount")))
        .alias("volume"))
    return (lines
            .join(orders.join(cust, ["o_custkey"], ["c_custkey"]),
                  ["l_orderkey"], ["o_orderkey"])
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(Sum(col("volume")).alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .order_by(desc(col("revenue")), asc(col("o_orderdate")))
            .limit(LIMIT))


def reference(read, params, money=np.float64):
    day = (datetime.date.fromisoformat(params["date"])
           - datetime.date(1970, 1, 1)).days
    c = read("customer", TABLES["customer"])
    o = read("orders", TABLES["orders"])
    li = read("lineitem", TABLES["lineitem"])
    seg = c["c_mktsegment"].to_numpy(zero_copy_only=False) == params["segment"]
    custs = c["c_custkey"].to_numpy()[seg]
    okey = o["o_orderkey"].to_numpy()
    odate = o["o_orderdate"].to_numpy().astype(np.int64)
    oprio = o["o_shippriority"].to_numpy()
    keep_o = (odate < day) & np.isin(o["o_custkey"].to_numpy(), custs)
    okey, odate, oprio = okey[keep_o], odate[keep_o], oprio[keep_o]
    order = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[order], odate[order], oprio[order]

    lkey = li["l_orderkey"].to_numpy()
    keep_l = li["l_shipdate"].to_numpy().astype(np.int64) > day
    lkey = lkey[keep_l]
    volume = li["l_extendedprice"].to_numpy()[keep_l].astype(money) * (
        money(1.0) - li["l_discount"].to_numpy()[keep_l].astype(money))
    pos = np.searchsorted(okey, lkey)
    pos[pos == len(okey)] = 0
    hit = okey[pos] == lkey if len(okey) else np.zeros(len(lkey), bool)
    pos, volume = pos[hit], volume[hit]
    # one group per order: the date and priority are functions of the key
    groups, inverse = np.unique(pos, return_inverse=True)
    revenue = np.zeros(len(groups), dtype=money)
    np.add.at(revenue, inverse, volume)
    revenue = revenue.astype(np.float64)
    top = np.lexsort((odate[groups], -revenue))[:LIMIT]
    g = groups[top]
    return pa.table({
        "l_orderkey": pa.array(okey[g], pa.int64()),
        "revenue": pa.array(revenue[top], pa.float64()),
        "o_orderdate": pa.array(odate[g].astype(np.int32), pa.date32()),
        "o_shippriority": pa.array(oprio[g], pa.int32()),
    })
