"""TPC-H Q18, large volume customer (specification §2.4.18), validation
parameter QUANTITY = 300.

lineitem -> aggregate of one group an order (1.5M groups of 6.0M rows) ->
HAVING; orders joins that result, then customer; lineitem, scanned a second
time, joins the orders kept -> aggregate -> top 100 by total price. The
reference is numpy and pyarrow on the same Parquet files and imports nothing
of the engine.
"""

import numpy as np
import pyarrow as pa

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders": ["o_orderkey", "o_custkey", "o_totalprice",
                     "o_orderdate"],
          "lineitem": ["l_orderkey", "l_quantity"]}
SCANS = {"lineitem": 2}         # the subquery and the outer join
PARAMS = {"quantity": 300}
ORDERED = True
LIMIT = 100


def plan(scan, params):
    from spark_rapids_tpu.exec.join import JoinType
    from spark_rapids_tpu.exec.sort import asc, desc
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum
    large = (scan("lineitem")
             .group_by("l_orderkey")
             .agg(Sum(col("l_quantity")).alias("order_qty"))
             .where(col("order_qty") > lit(float(params["quantity"])))
             .select(col("l_orderkey").alias("large_orderkey")))
    kept = (scan("orders")
            .join(large, ["o_orderkey"], ["large_orderkey"],
                  JoinType.LEFT_SEMI)
            .join(scan("customer"), ["o_custkey"], ["c_custkey"]))
    return (scan("lineitem")
            .join(kept, ["l_orderkey"], ["o_orderkey"])
            .group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice")
            .agg(Sum(col("l_quantity")).alias("sum_qty"))
            .order_by(desc(col("o_totalprice")), asc(col("o_orderdate")))
            .limit(LIMIT))


def reference(read, params, money=np.float64):
    """``money`` is the type the double columns are carried and summed in:
    float64 as the configuration states, float32 in the control."""
    c = read("customer", TABLES["customer"])
    o = read("orders", TABLES["orders"])
    li = read("lineitem", TABLES["lineitem"])
    lkey = li["l_orderkey"].to_numpy()
    qty = li["l_quantity"].to_numpy().astype(money)
    by_key = np.argsort(lkey, kind="stable")
    keys, starts = np.unique(lkey[by_key], return_index=True)
    order_qty = np.add.reduceat(qty[by_key], starts)     # in ``money``
    large = keys[order_qty > money(params["quantity"])]

    okey = o["o_orderkey"].to_numpy()
    keep = np.isin(okey, large)
    okey = okey[keep]
    ocust = o["o_custkey"].to_numpy()[keep]
    odate = o["o_orderdate"].to_numpy().astype(np.int64)[keep]
    price = o["o_totalprice"].to_numpy()[keep].astype(money)
    ckey = c["c_custkey"].to_numpy()
    by_cust = np.argsort(ckey, kind="stable")
    pos = by_cust[np.minimum(np.searchsorted(ckey[by_cust], ocust),
                             len(ckey) - 1)]
    have = ckey[pos] == ocust           # an order without its customer goes
    okey, odate, price, pos = okey[have], odate[have], price[have], pos[have]
    # the second scan's sum: the lines of the orders kept, one group an
    # order (name, customer, date and price are functions of its key)
    at = np.minimum(np.searchsorted(keys, okey), len(keys) - 1)
    lines = keys[at] == okey
    okey, odate, price, pos, at = (okey[lines], odate[lines], price[lines],
                                   pos[lines], at[lines])
    sum_qty = order_qty[at].astype(np.float64)
    price = price.astype(np.float64)
    top = np.lexsort((odate, -price))[:LIMIT]
    return pa.table({
        "c_name": c["c_name"].take(pa.array(pos[top])),
        "c_custkey": pa.array(ckey[pos[top]], pa.int64()),
        "o_orderkey": pa.array(okey[top], pa.int64()),
        "o_orderdate": pa.array(odate[top].astype(np.int32), pa.date32()),
        "o_totalprice": pa.array(price[top], pa.float64()),
        "sum_qty": pa.array(sum_qty[top], pa.float64()),
    })
