"""TPC-DS Q95 (specification v3.2.0, query template query95.tpl), at its
qualification values YEAR = 1999, MONTH = 2, STATE = IL and the template's
fixed web_company_name = 'pri'.

    with ws_wh as (select ws1.ws_order_number, ws1.ws_warehouse_sk wh1,
                          ws2.ws_warehouse_sk wh2
                   from web_sales ws1, web_sales ws2
                   where ws1.ws_order_number = ws2.ws_order_number
                     and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
    select count(distinct ws_order_number) as "order count",
           sum(ws_ext_ship_cost) as "total shipping cost",
           sum(ws_net_profit) as "total net profit"
    from web_sales ws1, date_dim, customer_address, web_site
    where d_date between '1999-02-01' and (cast('1999-02-01' as date) + 60 days)
      and ws1.ws_ship_date_sk = d_date_sk
      and ws1.ws_ship_addr_sk = ca_address_sk and ca_state = 'IL'
      and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
      and ws1.ws_order_number in (select ws_order_number from ws_wh)
      and ws1.ws_order_number in (select wr_order_number
                                  from web_returns, ws_wh
                                  where wr_order_number = ws_wh.ws_order_number)
    order by count(distinct ws_order_number)
    limit 100

Planned as Spark 3.3 optimizes it: both IN predicates become left-semi
joins directly over ws1's scan; ``ws_wh`` (a self-join of web_sales on the
order, a line paired with every line of its order, kept where the
warehouses differ) is computed for each of them; the three dimension joins
(61 dates, the IL addresses, the 'pri' sites) come after the semi joins;
the one-distinct aggregate comes last. ``web_sales`` is scanned five times
(``SCANS``): ws1, and ws1 and ws2 of each ``ws_wh``.

``days`` is the template's fixed 60; a traffic file's ``rehearsal_params``
may widen it, so that a rehearsal's tenth of the rows still qualifies
hundreds of lines.

Typed as Spark 3.3 types it: ``count(distinct)`` is ``bigint`` (0 where no
row qualifies), each sum of ``decimal(7,2)`` is ``decimal(17,2)``.

The reference is numpy and pyarrow on the same Parquet files and imports
nothing of the engine: the pairs per order counted directly (an order is in
``ws_wh`` where some two of its lines have different, non-null warehouses),
sets of order numbers, integer cents.
"""

import datetime
import decimal

import numpy as np
import pyarrow as pa

TABLES = {"web_sales": ["ws_ship_date_sk", "ws_ship_addr_sk",
                        "ws_web_site_sk", "ws_warehouse_sk",
                        "ws_order_number", "ws_ext_ship_cost",
                        "ws_net_profit"],
          "web_returns": ["wr_order_number"],
          "date_dim": ["d_date_sk", "d_date"],
          "customer_address": ["ca_address_sk", "ca_state"],
          "web_site": ["web_site_sk", "web_company_name"]}
SCANS = {"web_sales": 5}        # ws1, and ws1 and ws2 of each ws_wh
PARAMS = {"year": 1999, "month": 2, "state": "IL", "days": 60}
ORDERED = True
LIMIT = 100
COMPANY = "pri"                 # fixed in the template
SUM_TYPE = pa.decimal128(17, 2)
NAMES = ["order count", "total shipping cost", "total net profit"]


def _window(params):
    first = datetime.date(int(params["year"]), int(params["month"]), 1)
    return first, first + datetime.timedelta(days=int(params["days"]))


def plan(scan, params):
    from spark_rapids_tpu.exec.join import JoinType
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum, count_distinct
    first, last = _window(params)

    def ws_wh():
        ws1 = scan("web_sales").select(
            col("ws_order_number").alias("wh_order_number"),
            col("ws_warehouse_sk").alias("wh1"))
        ws2 = scan("web_sales").select(
            col("ws_order_number").alias("wh_order_number2"),
            col("ws_warehouse_sk").alias("wh2"))
        return (ws1.join(ws2, ["wh_order_number"], ["wh_order_number2"],
                         condition=col("wh1") != col("wh2"))
                .select("wh_order_number"))

    returned = (scan("web_returns")
                .join(ws_wh(), ["wr_order_number"], ["wh_order_number"])
                .select("wr_order_number"))
    dates = (scan("date_dim")
             .where((col("d_date") >= lit(first))
                    & (col("d_date") <= lit(last)))
             .select("d_date_sk"))
    addresses = (scan("customer_address")
                 .where(col("ca_state") == lit(str(params["state"])))
                 .select("ca_address_sk"))
    sites = (scan("web_site")
             .where(col("web_company_name") == lit(COMPANY))
             .select("web_site_sk"))
    ws1 = (scan("web_sales")
           .join(ws_wh(), ["ws_order_number"], ["wh_order_number"],
                 JoinType.LEFT_SEMI)
           .join(returned, ["ws_order_number"], ["wr_order_number"],
                 JoinType.LEFT_SEMI)
           .join(dates, ["ws_ship_date_sk"], ["d_date_sk"])
           .join(addresses, ["ws_ship_addr_sk"], ["ca_address_sk"])
           .join(sites, ["ws_web_site_sk"], ["web_site_sk"]))
    return (ws1.agg(count_distinct(col("ws_order_number")).alias(NAMES[0]),
                    Sum(col("ws_ext_ship_cost")).alias(NAMES[1]),
                    Sum(col("ws_net_profit")).alias(NAMES[2]))
            .order_by(col(NAMES[0]))
            .limit(LIMIT))


# ---------------------------------------------------------------------------
# the plain reference

def _ints(column):
    valid = ~column.is_null().to_numpy(zero_copy_only=False)
    return column.fill_null(0).to_numpy().astype(np.int64), valid


def _cents(column):
    """decimal(p,2) column -> (int64 cents, valid)."""
    column = column.combine_chunks()
    valid = ~column.is_null().to_numpy(zero_copy_only=False)
    whole = pa.compute.multiply(
        column.fill_null(decimal.Decimal(0)),
        pa.scalar(decimal.Decimal(100), pa.decimal128(3, 0)))
    return whole.cast(pa.int64()).to_numpy(), valid


def pairs_by_order(ws):
    """For each distinct ``ws_order_number`` (ascending): the lines paired
    with the lines of their order (the self-join's candidates, a line with
    itself included) and the pairs whose warehouses differ (``ws_wh``'s
    rows; a null warehouse differs from nothing)."""
    order = ws["ws_order_number"].to_numpy()
    wh, wh_ok = _ints(ws["ws_warehouse_sk"])
    orders, inverse, lines = np.unique(order, return_inverse=True,
                                       return_counts=True)
    inverse = inverse.reshape(-1)
    # lines of each (order, warehouse), non-null warehouses only
    cell = inverse[wh_ok] * (int(wh.max(initial=0)) + 1) + wh[wh_ok]
    cells, per = np.unique(cell, return_counts=True)
    of = cells // (int(wh.max(initial=0)) + 1)
    known = np.bincount(of, weights=per, minlength=len(orders))
    same = np.bincount(of, weights=per * per, minlength=len(orders))
    candidates = lines.astype(np.int64) ** 2
    differ = (known * known - same).astype(np.int64)
    return orders, candidates, differ


def reference(read, params, money=np.int64):
    """``money`` int64 sums exact cents, as the configuration states; the
    control carries and sums float32 dollars and rounds to cents."""
    ws = read("web_sales", TABLES["web_sales"])
    wr = read("web_returns", TABLES["web_returns"])
    d = read("date_dim", TABLES["date_dim"])
    ca = read("customer_address", TABLES["customer_address"])
    site = read("web_site", TABLES["web_site"])

    orders, _, differ = pairs_by_order(ws)
    in_ws_wh = orders[differ > 0]
    returned = wr["wr_order_number"].drop_null().to_numpy()
    in_returns = np.intersect1d(np.unique(returned), in_ws_wh)

    first, last = _window(params)
    day = d["d_date"].cast(pa.int32()).to_numpy()
    lo = (first - datetime.date(1970, 1, 1)).days
    hi = (last - datetime.date(1970, 1, 1)).days
    dates = d["d_date_sk"].to_numpy()[(day >= lo) & (day <= hi)]
    state = ca["ca_state"].to_numpy(zero_copy_only=False)
    addresses = ca["ca_address_sk"].to_numpy()[state == str(params["state"])]
    company = site["web_company_name"].to_numpy(zero_copy_only=False)
    sites = site["web_site_sk"].to_numpy()[company == COMPANY]

    order = ws["ws_order_number"].to_numpy()
    ship, ship_ok = _ints(ws["ws_ship_date_sk"])
    addr, addr_ok = _ints(ws["ws_ship_addr_sk"])
    web, web_ok = _ints(ws["ws_web_site_sk"])
    keep = (np.isin(order, in_ws_wh) & np.isin(order, in_returns)
            & ship_ok & np.isin(ship, dates) & addr_ok
            & np.isin(addr, addresses) & web_ok & np.isin(web, sites))

    def total(name):
        cents, ok = _cents(ws[name])
        ok = ok & keep
        if not ok.any():
            return None
        if money is np.int64:
            s = int(cents[ok].sum())
        else:
            dollars = np.cumsum((cents[ok] / 100.0).astype(money),
                                dtype=money)[-1]
            s = int(np.rint(np.float64(dollars) * 100))
        return decimal.Decimal(s).scaleb(-2)
    return pa.table({
        NAMES[0]: pa.array([len(np.unique(order[keep]))], pa.int64()),
        NAMES[1]: pa.array([total("ws_ext_ship_cost")], SUM_TYPE),
        NAMES[2]: pa.array([total("ws_net_profit")], SUM_TYPE),
    })
