"""TPC-DS Q67 (specification v3.2.0, query template query67.tpl), with its
qualification value DMS = 1200: twelve months from January 2000.

    select * from (
      select i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
             d_moy, s_store_id, sumsales,
             rank() over (partition by i_category
                          order by sumsales desc) rk
      from (select ..., sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales
            from store_sales, date_dim, store, item
            where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
              and ss_store_sk = s_store_sk
              and d_month_seq between DMS and DMS + 11
            group by rollup(i_category, i_class, i_brand, i_product_name,
                            d_year, d_qoy, d_moy, s_store_id)) dw1) dw2
    where rk <= 100
    order by i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
             d_moy, s_store_id, sumsales, rk
    limit 100

store_sales joins date_dim (one year), store and item, all three small
enough to broadcast -> a rollup over eight keys, five of them strings: nine
grouping sets, some 0.72M groups of 0.53M joined rows at SF1 -> rank within
each category (ten, and the rollup's null) -> the first hundred of each ->
top 100 on ten keys, nulls first.

Typed as Spark 3.3 types it (allowPrecisionLoss=true, non-ANSI):
``ss_sales_price * ss_quantity`` is ``decimal(7,2) * decimal(10,0)`` =
``decimal(18,2)``; ``coalesce(.., 0)`` keeps that type and is never null; its
sum is ``decimal(28,2)``; ``rank()`` is ``int``.

The reference is numpy and pyarrow on the same Parquet files: integer cents,
the nine grouping sets built one by one (no Expand), and it imports nothing
of the engine.
"""

import decimal

import numpy as np
import pyarrow as pa

TABLES = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                          "ss_quantity", "ss_sales_price"],
          "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_qoy",
                       "d_moy"],
          "store": ["s_store_sk", "s_store_id"],
          "item": ["i_item_sk", "i_category", "i_class", "i_brand",
                   "i_product_name"]}
PARAMS = {"dms": 1200}
ORDERED = True
LIMIT = 100
TOP = 100                   # rk <= 100

KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
STRING_KEYS = {"i_category", "i_class", "i_brand", "i_product_name",
               "s_store_id"}
SUM_TYPE = pa.decimal128(28, 2)


def plan(scan, params):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.sort import asc, desc
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum
    from spark_rapids_tpu.expressions.conditional import Coalesce
    from spark_rapids_tpu.expressions.window import Rank, over
    dms = int(params["dms"])
    dt = (scan("date_dim")
          .where((col("d_month_seq") >= lit(dms))
                 & (col("d_month_seq") <= lit(dms + 11)))
          .select("d_date_sk", "d_year", "d_qoy", "d_moy"))
    zero = lit(decimal.Decimal("0.00"), T.decimal(18, 2))
    sales = Coalesce((col("ss_sales_price") * col("ss_quantity"), zero))
    dw1 = (scan("store_sales")
           .join(dt, ["ss_sold_date_sk"], ["d_date_sk"])
           .join(scan("store"), ["ss_store_sk"], ["s_store_sk"])
           .join(scan("item"), ["ss_item_sk"], ["i_item_sk"])
           .select(*[col(k) for k in KEYS], sales.alias("sales"))
           .rollup(*KEYS)
           .agg(Sum(col("sales")).alias("sumsales")))
    rk = over(Rank(), partition_by=[col("i_category")],
              order_by=[desc(col("sumsales"))])
    return (dw1.window(rk.alias("rk"))
            .where(col("rk") <= lit(TOP))
            .order_by(*[asc(col(c)) for c in KEYS + ["sumsales", "rk"]])
            .limit(LIMIT))


# ---------------------------------------------------------------------------
# the plain reference

def _cents(column):
    """decimal(p,2) column -> (int64 cents, valid)."""
    column = column.combine_chunks()
    valid = ~column.is_null().to_numpy(zero_copy_only=False)
    whole = pa.compute.multiply(
        column.fill_null(decimal.Decimal(0)),
        pa.scalar(decimal.Decimal(100), pa.decimal128(3, 0)))
    return whole.cast(pa.int64()).to_numpy(), valid


def _lookup(keys, probe, probe_valid):
    """Inner join on an integer key: for each probe row the position of its
    key in ``keys`` and whether it has one (a null key has none)."""
    if len(keys) == 0:
        return np.zeros(len(probe), np.int64), np.zeros(len(probe), bool)
    order = np.argsort(keys, kind="stable")
    pos = np.minimum(np.searchsorted(keys[order], probe), len(keys) - 1)
    return order[pos], probe_valid & (keys[order][pos] == probe)


def _ints(column):
    valid = ~column.is_null().to_numpy(zero_copy_only=False)
    return column.fill_null(0).to_numpy().astype(np.int64), valid


def reference(read, params, money=np.int64):
    """``money`` int64 sums exact cents, as the configuration states; the
    control carries and sums float32 dollars and rounds to cents."""
    ss = read("store_sales", TABLES["store_sales"])
    d = read("date_dim", TABLES["date_dim"])
    st = read("store", TABLES["store"])
    it = read("item", TABLES["item"])
    dms = int(params["dms"])

    seq = d["d_month_seq"].to_numpy()
    dsel = (seq >= dms) & (seq <= dms + 11)
    dsk = d["d_date_sk"].to_numpy()[dsel].astype(np.int64)
    date, date_ok = _ints(ss["ss_sold_date_sk"])
    dpos, dhit = _lookup(dsk, date, date_ok)
    store, store_ok = _ints(ss["ss_store_sk"])
    spos, shit = _lookup(st["s_store_sk"].to_numpy().astype(np.int64),
                         store, store_ok)
    item, item_ok = _ints(ss["ss_item_sk"])
    ipos, ihit = _lookup(it["i_item_sk"].to_numpy().astype(np.int64),
                         item, item_ok)
    hit = dhit & shit & ihit
    dpos, spos, ipos = dpos[hit], spos[hit], ipos[hit]

    cents, price_ok = _cents(ss["ss_sales_price"])
    qty, qty_ok = _ints(ss["ss_quantity"])
    ok = (price_ok & qty_ok)[hit]
    if money is np.int64:
        # coalesce(price * quantity, 0): a null factor counts as 0
        amount = np.where(ok, cents[hit] * qty[hit], 0)
    else:
        amount = np.where(ok, (cents[hit] / 100.0).astype(money)
                          * qty[hit].astype(money), money(0))

    # every key as a code whose order is the key's, worked out on its
    # dimension; strings order by their UTF-8 bytes
    values, codes = {}, []
    joined = {"d": (d, dsel, dpos), "s": (st, None, spos),
              "i": (it, None, ipos)}
    for k in KEYS:
        dim, selected, pos = joined[k[0]]
        raw = dim[k].to_numpy(zero_copy_only=False)
        if selected is not None:
            raw = raw[selected]
        if k in STRING_KEYS:
            raw = np.array([v.encode() for v in raw.tolist()], dtype=object)
        if len(raw) == 0:
            values[k], code = raw, np.zeros(0, np.int64)
        else:
            values[k], code = np.unique(raw, return_inverse=True)
        codes.append(code.reshape(-1).astype(np.int64)[pos])
    codes = np.stack(codes, axis=1)
    cards = [max(len(values[k]), 1) for k in KEYS]

    def groups_of(level):
        """The distinct tuples of the first ``level`` codes, in order, and
        each row's group: through one mixed-radix number a tuple where that
        fits 62 bits, else row-wise."""
        kept = codes[:, :level]
        if level == 0:
            return np.zeros((1, 0), np.int64), np.zeros(len(kept), np.int64)
        if int(np.prod([float(c) for c in cards[:level]])) < 2 ** 62:
            number = np.zeros(len(kept), np.int64)
            for i in range(level):
                number = number * cards[i] + kept[:, i]
            distinct, inverse = np.unique(number, return_inverse=True)
            groups = np.empty((len(distinct), level), np.int64)
            for i in range(level - 1, -1, -1):
                groups[:, i] = distinct % cards[i]
                distinct = distinct // cards[i]
            return groups, inverse.reshape(-1)
        groups, inverse = np.unique(kept, axis=0, return_inverse=True)
        return groups, inverse.reshape(-1)

    # the nine grouping sets, one by one: level L keeps the first L keys
    # (no joined row: no group at any level, the grand total included)
    rows_code = [np.zeros((0, len(KEYS)), np.int64)]
    rows_sum = [np.zeros(0, np.int64)]
    for level in range(len(KEYS), -1, -1):
        if len(amount) == 0:
            break
        groups, inverse = groups_of(level)
        if money is np.int64:
            total = np.zeros(len(groups), np.int64)
            np.add.at(total, inverse, amount)
        else:
            acc = np.zeros(len(groups), money)
            np.add.at(acc, inverse, amount)
            total = np.rint(acc.astype(np.float64) * 100).astype(np.int64)
        full = np.full((len(groups), len(KEYS)), -1, np.int64)   # -1: null
        full[:, :level] = groups
        rows_code.append(full)
        rows_sum.append(total)
    code = np.concatenate(rows_code)
    total = np.concatenate(rows_sum)

    # rank() over (partition by i_category order by sumsales desc): ties
    # share a rank, the next rank skips them
    order = np.lexsort((-total, code[:, 0]))
    cat, tot = code[order, 0], total[order]
    first_of_part = np.r_[True, cat[1:] != cat[:-1]]
    first_of_peer = first_of_part | np.r_[True, tot[1:] != tot[:-1]]
    idx = np.arange(len(order))
    part_start = np.maximum.accumulate(np.where(first_of_part, idx, 0))
    peer_start = np.maximum.accumulate(np.where(first_of_peer, idx, 0))
    rank = np.empty(len(order), np.int64)
    rank[order] = peer_start - part_start + 1

    keep = rank <= TOP
    code, total, rank = code[keep], total[keep], rank[keep]
    # nulls first ascending: the null's code -1 sorts before every value
    top = np.lexsort((rank, total) + tuple(
        code[:, i] for i in range(len(KEYS) - 1, -1, -1)))[:LIMIT]
    out = {}
    for i, k in enumerate(KEYS):
        c = code[top, i]
        vals = [None if v < 0 else values[k][v] for v in c.tolist()]
        if k in STRING_KEYS:
            out[k] = pa.array([None if v is None else v.decode()
                               for v in vals], pa.string())
        else:
            out[k] = pa.array(vals, pa.int32())
    out["sumsales"] = pa.array(
        [decimal.Decimal(int(v)).scaleb(-2) for v in total[top].tolist()],
        SUM_TYPE)
    out["rk"] = pa.array(rank[top], pa.int32())
    return pa.table(out)
