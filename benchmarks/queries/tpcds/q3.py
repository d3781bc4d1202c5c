"""TPC-DS Q3 (specification v3, query template query3.tpl), with
MANUFACT = 128 and MONTH = 11.

store_sales joins date_dim (d_moy = 11) and item (i_manufact_id = 128), both
small enough to broadcast -> decimal sum by year and brand (groups of about
four rows) -> top 100.
The reference is numpy and pyarrow on the same Parquet files, sums integer
cents, and imports nothing of the engine.
"""

import decimal

import numpy as np
import pyarrow as pa

TABLES = {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                          "ss_ext_sales_price"],
          "date_dim": ["d_date_sk", "d_year", "d_moy"],
          "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"]}
PARAMS = {"manufact": 128, "month": 11}
ORDERED = True
LIMIT = 100


def plan(scan, params):
    from spark_rapids_tpu.exec.sort import asc, desc
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum
    dt = scan("date_dim").where(col("d_moy") == lit(int(params["month"]))) \
        .select("d_date_sk", "d_year")
    it = scan("item").where(
        col("i_manufact_id") == lit(int(params["manufact"]))) \
        .select("i_item_sk", "i_brand_id", "i_brand")
    return (scan("store_sales")
            .join(dt, ["ss_sold_date_sk"], ["d_date_sk"])
            .join(it, ["ss_item_sk"], ["i_item_sk"])
            .group_by("d_year", "i_brand", "i_brand_id")
            .agg(Sum(col("ss_ext_sales_price")).alias("sum_agg"))
            .select(col("d_year"), col("i_brand_id").alias("brand_id"),
                    col("i_brand").alias("brand"), col("sum_agg"))
            .order_by(asc(col("d_year")), desc(col("sum_agg")),
                      asc(col("brand_id")))
            .limit(LIMIT))


def _cents(column):
    """decimal(p,2) column -> (int64 cents, valid)."""
    valid = ~column.is_null().to_numpy(zero_copy_only=False)
    whole = pa.compute.multiply(
        column.fill_null(decimal.Decimal(0)),
        pa.scalar(decimal.Decimal(100), pa.decimal128(3, 0)))
    return whole.cast(pa.int64()).to_numpy(), valid


def reference(read, params, money=np.int64):
    """``money`` int64 sums exact cents, as the configuration states; the
    control sums float32 dollars and rounds to cents."""
    ss = read("store_sales", TABLES["store_sales"])
    d = read("date_dim", TABLES["date_dim"])
    it = read("item", TABLES["item"])
    dsel = d["d_moy"].to_numpy() == params["month"]
    dsk, dyear = d["d_date_sk"].to_numpy()[dsel], d["d_year"].to_numpy()[dsel]
    isel = it["i_manufact_id"].to_numpy() == params["manufact"]
    isk = it["i_item_sk"].to_numpy()[isel]
    bid = it["i_brand_id"].to_numpy()[isel]
    bname = it["i_brand"].to_numpy(zero_copy_only=False)[isel]

    date = ss["ss_sold_date_sk"].fill_null(-1).to_numpy()
    item = ss["ss_item_sk"].to_numpy()
    cents, valid = _cents(ss["ss_ext_sales_price"].combine_chunks())
    dorder, iorder = np.argsort(dsk), np.argsort(isk)
    dpos = np.searchsorted(dsk[dorder], date)
    ipos = np.searchsorted(isk[iorder], item)
    dpos[dpos == len(dsk)] = 0
    ipos[ipos == len(isk)] = 0
    hit = (dsk[dorder][dpos] == date) & (isk[iorder][ipos] == item) \
        if len(dsk) and len(isk) else np.zeros(len(date), bool)
    year = dyear[dorder][dpos[hit]]
    ii = iorder[ipos[hit]]
    cents, valid = cents[hit], valid[hit]
    # group by (year, brand name, brand id)
    names, name_code = np.unique(bname, return_inverse=True)
    key = np.stack([year, name_code[ii], bid[ii]], axis=1)
    groups, inverse = np.unique(key, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    if money is np.int64:
        total = np.zeros(len(groups), dtype=np.int64)
        np.add.at(total, inverse[valid], cents[valid])
    else:
        acc = np.zeros(len(groups), dtype=money)
        np.add.at(acc, inverse[valid], (cents[valid] / 100.0).astype(money))
        total = np.rint(acc.astype(np.float64) * 100).astype(np.int64)
    any_valid = np.zeros(len(groups), dtype=bool)
    any_valid[inverse[valid]] = True
    # SQL sorts NULL first ascending, so last under DESC
    order = np.lexsort((groups[:, 2], -total, ~any_valid, groups[:, 0]))
    order = order[:LIMIT]
    sums = [decimal.Decimal(int(total[i])).scaleb(-2) if any_valid[i] else None
            for i in order]
    return pa.table({
        "d_year": pa.array(groups[order, 0], pa.int32()),
        "brand_id": pa.array(groups[order, 2], pa.int32()),
        "brand": pa.array(names[groups[order, 1]], pa.string()),
        "sum_agg": pa.array(sums, pa.decimal128(17, 2)),
    })
