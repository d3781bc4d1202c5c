"""TPC-DS tables from a seed for the web channel: ``web_sales`` and
``web_returns`` with the columns, order and types of the specification's
§2.3 (v3.2.0), ``customer_address`` and ``web_site`` with those of §2.4,
and ``date_dim`` exactly as generator ``tpcds`` makes it (loaded by name,
byte for byte). Value rules follow dsdgen as far as they are known offline;
what is replaced is listed under ``assumed`` in
``configs/tpcds_sf1_web.json``.

``generate(config, scale, seed, tables)`` returns ``{table: pyarrow.Table}``.
``scale`` multiplies the two fact tables' row counts; the dimensions are
never scaled.

- ``web_sales``: orders of 8 to 16 lines. An order shares its sold date and
  time, its bill and ship customer, demographics and address, its web page,
  web site and ship mode; each line draws its item, its warehouse (1 to 5),
  its promotion and its ship date (1 to 120 days after the sale). 4 % of
  each nullable key is null, and 4 % of lines have no price, which nulls
  every amount worked out from it (as generator ``tpcds``).
- ``web_returns``: lines drawn from the sold ones without repeats, so
  ``wr_order_number`` / ``wr_item_sk`` are a real sale's; returned 1 to 60
  days after the ship date, at most the quantity sold.
- ``customer_address``: ``ca_state`` drawn in proportion to each state's
  count of counties (dsdgen draws a county, then takes its state).
- ``web_site``: sites of 1, 2, 3 revisions in turn sharing a business key,
  as generator ``tpcds_store``'s ``store``; ``web_company_name`` is the
  syllable of the company, companies 1 to 6 in turn over the sites, so
  ``pri`` is always among them.

numpy and pyarrow only.
"""

import numpy as np
import pyarrow as pa

from rtbench import loader

_tpcds = loader.generator("tpcds")
_store = loader.generator("tpcds_store")

MONEY = _tpcds.MONEY
RATE = pa.decimal128(5, 2)
NULL_SHARE = _tpcds.NULL_SHARE
_STREAMS = {"web_sales": 104, "web_returns": 105, "customer_address": 106,
            "web_site": 107}      # beside tpcds's 101, 102 and store's 103

WAREHOUSES = 5
ITEMS = 18000                         # item's rows at SF1 (tpcds_sf1)
WEB_PAGES = 60
SHIP_MODES = 20
PROMOTIONS = 300
CUSTOMERS = 100000
CDEMOS = 1920800
HDEMOS = 7200
REASONS = 35
TIME_SECONDS = 86400
SHIP_LAG_DAYS = (1, 120)
RETURN_LAG_DAYS = (1, 60)

# dsdgen's company syllables: company k is the k-th (1-based)
COMPANIES = ["ought", "able", "pri", "ese", "anti", "cally"]
# counties by state (US Census county equivalents), dsdgen's fips weights
STATE_COUNTIES = {
    "TX": 254, "GA": 159, "VA": 133, "KY": 120, "MO": 115, "KS": 105,
    "IL": 102, "NC": 100, "IA": 99, "TN": 95, "NE": 93, "IN": 92, "OH": 88,
    "MN": 87, "MI": 83, "MS": 82, "OK": 77, "AR": 75, "WI": 72, "AL": 67,
    "FL": 67, "PA": 67, "SD": 66, "CO": 64, "LA": 64, "NY": 62, "CA": 58,
    "MT": 56, "WV": 55, "ND": 53, "SC": 46, "ID": 44, "WA": 39, "OR": 36,
    "NM": 33, "AK": 29, "UT": 29, "MD": 24, "WY": 23, "NJ": 21, "NV": 17,
    "ME": 16, "AZ": 15, "MA": 14, "VT": 14, "NH": 10, "CT": 8, "HI": 5,
    "RI": 5, "DE": 3, "DC": 1}
# hours west of UTC by state, for ca_gmt_offset
_OFFSET = {"CA": -8, "OR": -8, "WA": -8, "NV": -8, "AK": -9, "HI": -10,
           "MT": -7, "ID": -7, "WY": -7, "UT": -7, "CO": -7, "AZ": -7,
           "NM": -7, "TX": -6, "OK": -6, "KS": -6, "NE": -6, "SD": -6,
           "ND": -6, "MN": -6, "IA": -6, "MO": -6, "AR": -6, "LA": -6,
           "WI": -6, "IL": -6, "MS": -6, "AL": -6, "TN": -6}
STREETS = _store.STREETS + ["Main", "Church", "Walnut", "Maple", "Pine",
                            "Washington", "Lincoln", "Jackson", "Franklin",
                            "Highland", "Railroad", "Johnson", "Adams"]
STREET_TYPES = _store.STREET_TYPES
CITIES = ["Fairview", "Midway", "Oak Grove", "Five Points", "Pleasant Hill",
          "Centerville", "Riverside", "Mount Pleasant", "Greenwood",
          "Union", "Bethel", "Salem", "Springfield", "Franklin", "Clinton",
          "Georgetown", "Marion", "Oakland", "Glendale", "Shiloh"]
COUNTY_NAMES = ["Williamson", "Franklin", "Jefferson", "Washington",
                "Jackson", "Lincoln", "Madison", "Montgomery", "Marion",
                "Clay", "Monroe", "Union", "Wayne", "Greene", "Warren"]
LOCATION_TYPES = ["apartment", "condo", "single family"]
SITE_CLASSES = ["Unknown"]
MANAGERS = _store.MANAGERS


def _rng(seed, table):
    return np.random.default_rng([int(seed), _STREAMS[table]])


def _rows(config, table, scale):
    return int(config["tables"][table]["rows"] * scale)


def _rate(hundredths):
    """decimal(5,2) from integer hundredths, exactly."""
    lo = np.ascontiguousarray(hundredths, dtype=np.int64)
    words = np.empty((len(lo), 2), dtype=np.int64)
    words[:, 0] = lo
    words[:, 1] = lo >> 63
    return pa.Array.from_buffers(RATE, len(lo), [None, pa.py_buffer(words)])


def _text(codes, values):
    return pa.array(np.asarray(values, dtype=object)[np.asarray(codes)],
                    pa.string())


def _sales_days(rng, n):
    """Sale days since 1900-01-02, weighted as generator ``tpcds``'s."""
    first = int((_tpcds.SALES_FIRST - _tpcds.FIRST_DATE).astype(int))
    last = int((_tpcds.SALES_LAST - _tpcds.FIRST_DATE).astype(int))
    days = np.arange(first, last + 1)
    moy = (_tpcds.FIRST_DATE + days).astype("datetime64[M]").astype(int) \
        % 12 + 1
    weight = np.where(moy >= 11, 2.0, np.where(moy >= 8, 1.3, 1.0))
    return rng.choice(days, size=n, p=weight / weight.sum())


def _sales(config, scale, seed):
    """The sold lines as numpy columns (``web_returns`` draws from them)."""
    rng = _rng(seed, "web_sales")
    n = max(_rows(config, "web_sales", scale), 2000)
    n_orders = n // 8 + 1
    per = rng.integers(8, 17, size=n_orders)
    order = np.repeat(np.arange(1, n_orders + 1, dtype=np.int64), per)[:n]
    o = order - 1
    sold = _sales_days(rng, n_orders)[o]
    ship = sold + rng.integers(SHIP_LAG_DAYS[0], SHIP_LAG_DAYS[1] + 1,
                               size=n)

    def per_order(hi, lo=1):
        return rng.integers(lo, hi + 1, size=n_orders)[o]

    quantity = rng.integers(1, 101, size=n)
    wholesale = rng.integers(100, 10001, size=n)             # cents a unit
    list_price = wholesale + (wholesale * rng.integers(0, 201, size=n)) // 100
    sales_price = (list_price * rng.integers(0, 101, size=n)) // 100
    ext_sales = sales_price * quantity
    ext_list = list_price * quantity
    coupon = np.where(rng.random(n) < 0.2,
                      (ext_sales * rng.integers(0, 101, size=n)) // 100, 0)
    net_paid = ext_sales - coupon
    return {
        "n": n, "order": order, "sold": sold, "ship": ship,
        "sold_time": per_order(TIME_SECONDS - 1, 0),
        "bill_customer": per_order(CUSTOMERS),
        "bill_cdemo": per_order(CDEMOS), "bill_hdemo": per_order(HDEMOS),
        "bill_addr": per_order(config["tables"]["customer_address"]["rows"]),
        "ship_customer": per_order(CUSTOMERS),
        "ship_cdemo": per_order(CDEMOS), "ship_hdemo": per_order(HDEMOS),
        "ship_addr": per_order(config["tables"]["customer_address"]["rows"]),
        "web_page": per_order(WEB_PAGES),
        "web_site": per_order(config["tables"]["web_site"]["rows"]),
        "ship_mode": per_order(SHIP_MODES),
        "item": rng.integers(1, ITEMS + 1, size=n),
        "warehouse": rng.integers(1, WAREHOUSES + 1, size=n),
        "promo": rng.integers(1, PROMOTIONS + 1, size=n),
        "quantity": quantity, "wholesale": wholesale,
        "list_price": list_price, "sales_price": sales_price,
        "ext_sales": ext_sales, "ext_list": ext_list,
        "ext_wholesale": wholesale * quantity,
        "coupon": coupon, "net_paid": net_paid,
        "ext_tax": (net_paid * rng.integers(0, 10, size=n)) // 100,
        "ext_ship": (ext_list * rng.integers(0, 51, size=n)) // 100,
        # a null price nulls every amount worked out from it, as in dsdgen
        "no_price": rng.random(n) < NULL_SHARE,
        "null_draw": rng,
    }


def web_sales(config, scale, seed):
    s = _sales(config, scale, seed)
    n, rng = s["n"], s["null_draw"]

    def key(values):
        return pa.array(np.asarray(values, dtype=np.int32),
                        mask=rng.random(n) < NULL_SHARE)

    def amount(cents):
        return _tpcds._money(cents, null=s["no_price"])
    julian = _tpcds.JULIAN_1900_01_02
    paid, ship, tax = s["net_paid"], s["ext_ship"], s["ext_tax"]
    return pa.table({
        "ws_sold_date_sk": key(julian + s["sold"]),
        "ws_sold_time_sk": key(s["sold_time"]),
        "ws_ship_date_sk": key(julian + s["ship"]),
        "ws_item_sk": pa.array(s["item"].astype(np.int32)),
        "ws_bill_customer_sk": key(s["bill_customer"]),
        "ws_bill_cdemo_sk": key(s["bill_cdemo"]),
        "ws_bill_hdemo_sk": key(s["bill_hdemo"]),
        "ws_bill_addr_sk": key(s["bill_addr"]),
        "ws_ship_customer_sk": key(s["ship_customer"]),
        "ws_ship_cdemo_sk": key(s["ship_cdemo"]),
        "ws_ship_hdemo_sk": key(s["ship_hdemo"]),
        "ws_ship_addr_sk": key(s["ship_addr"]),
        "ws_web_page_sk": key(s["web_page"]),
        "ws_web_site_sk": key(s["web_site"]),
        "ws_ship_mode_sk": key(s["ship_mode"]),
        "ws_warehouse_sk": key(s["warehouse"]),
        "ws_promo_sk": key(s["promo"]),
        "ws_order_number": pa.array(s["order"]),
        "ws_quantity": key(s["quantity"]),
        "ws_wholesale_cost": amount(s["wholesale"]),
        "ws_list_price": amount(s["list_price"]),
        "ws_sales_price": amount(s["sales_price"]),
        "ws_ext_discount_amt": amount(s["ext_list"] - s["ext_sales"]),
        "ws_ext_sales_price": amount(s["ext_sales"]),
        "ws_ext_wholesale_cost": amount(s["ext_wholesale"]),
        "ws_ext_list_price": amount(s["ext_list"]),
        "ws_ext_tax": amount(tax),
        "ws_coupon_amt": amount(s["coupon"]),
        "ws_ext_ship_cost": amount(ship),
        "ws_net_paid": amount(paid),
        "ws_net_paid_inc_tax": amount(paid + tax),
        "ws_net_paid_inc_ship": amount(paid + ship),
        "ws_net_paid_inc_ship_tax": amount(paid + ship + tax),
        "ws_net_profit": amount(paid - s["ext_wholesale"]),
    })


def web_returns(config, scale, seed):
    s = _sales(config, scale, seed)
    rng = _rng(seed, "web_returns")
    n = min(max(_rows(config, "web_returns", scale), 200), s["n"])
    line = np.sort(rng.choice(s["n"], size=n, replace=False))

    def key(values):
        return pa.array(np.asarray(values, dtype=np.int32),
                        mask=rng.random(n) < NULL_SHARE)

    def draw(hi, lo=1):
        return rng.integers(lo, hi + 1, size=n)
    julian = _tpcds.JULIAN_1900_01_02
    returned = s["ship"][line] + draw(RETURN_LAG_DAYS[1], RETURN_LAG_DAYS[0])
    qty = np.minimum(draw(100), s["quantity"][line])
    amt = s["sales_price"][line] * qty
    tax = (amt * draw(9, 0)) // 100
    fee = draw(10000, 50)
    ship = (s["list_price"][line] * qty * draw(50, 0)) // 100
    # the refund is split between cash, a reversed charge and credit
    cash = (amt * draw(100, 0)) // 100
    reversed_charge = ((amt - cash) * draw(100, 0)) // 100
    credit = amt - cash - reversed_charge
    no_price = rng.random(n) < NULL_SHARE

    def amount(cents):
        return _tpcds._money(cents, null=no_price)
    addr = config["tables"]["customer_address"]["rows"]
    return pa.table({
        "wr_returned_date_sk": key(julian + returned),
        "wr_returned_time_sk": key(draw(TIME_SECONDS - 1, 0)),
        "wr_item_sk": pa.array(s["item"][line].astype(np.int32)),
        "wr_refunded_customer_sk": key(s["bill_customer"][line]),
        "wr_refunded_cdemo_sk": key(s["bill_cdemo"][line]),
        "wr_refunded_hdemo_sk": key(s["bill_hdemo"][line]),
        "wr_refunded_addr_sk": key(s["bill_addr"][line]),
        "wr_returning_customer_sk": key(draw(CUSTOMERS)),
        "wr_returning_cdemo_sk": key(draw(CDEMOS)),
        "wr_returning_hdemo_sk": key(draw(HDEMOS)),
        "wr_returning_addr_sk": key(draw(addr)),
        "wr_web_page_sk": key(s["web_page"][line]),
        "wr_reason_sk": key(draw(REASONS)),
        "wr_order_number": pa.array(s["order"][line]),
        "wr_return_quantity": key(qty),
        "wr_return_amt": amount(amt),
        "wr_return_tax": amount(tax),
        "wr_return_amt_inc_tax": amount(amt + tax),
        "wr_fee": amount(fee),
        "wr_return_ship_cost": amount(ship),
        "wr_refunded_cash": amount(cash),
        "wr_reversed_charge": amount(reversed_charge),
        "wr_account_credit": amount(credit),
        "wr_net_loss": amount(amt + tax + fee + ship - cash),
    })


def customer_address(config, scale, seed):
    rng = _rng(seed, "customer_address")
    n = config["tables"]["customer_address"]["rows"]
    states = sorted(STATE_COUNTIES)
    weight = np.array([STATE_COUNTIES[s] for s in states], dtype=np.float64)
    state = rng.choice(len(states), size=n, p=weight / weight.sum())
    sk = np.arange(1, n + 1, dtype=np.int32)
    offset = np.array([_OFFSET.get(s, -5) for s in states])[state] * 100
    return pa.table({
        "ca_address_sk": pa.array(sk),
        "ca_address_id": pa.array([_store.business_key(int(k))
                                   for k in sk.tolist()], pa.string()),
        "ca_street_number": pa.array(
            [str(v) for v in rng.integers(1, 1000, size=n).tolist()],
            pa.string()),
        "ca_street_name": _text(rng.integers(0, len(STREETS), size=n),
                                STREETS),
        "ca_street_type": _text(rng.integers(0, len(STREET_TYPES), size=n),
                                STREET_TYPES),
        "ca_suite_number": pa.array(
            [f"Suite {v}" for v in (rng.integers(0, 50, size=n) * 10)
             .tolist()], pa.string()),
        "ca_city": _text(rng.integers(0, len(CITIES), size=n), CITIES),
        "ca_county": pa.array(
            [f"{COUNTY_NAMES[c]} County"
             for c in rng.integers(0, len(COUNTY_NAMES), size=n).tolist()],
            pa.string()),
        "ca_state": _text(state, states),
        "ca_zip": pa.array([f"{v:05d}" for v in
                            rng.integers(10000, 99999, size=n).tolist()],
                           pa.string()),
        "ca_country": _text(np.zeros(n, int), ["United States"]),
        "ca_gmt_offset": _rate(offset),
        "ca_location_type": _text(rng.integers(0, len(LOCATION_TYPES),
                                               size=n), LOCATION_TYPES),
    })


def web_site(config, scale, seed):
    n = config["tables"]["web_site"]["rows"]
    rng = _rng(seed, "web_site")
    sk = np.arange(1, n + 1, dtype=np.int32)
    first, number, of = _store._revisions(n)
    a_site = {f: i for i, f in enumerate(np.unique(first).tolist())}
    which = np.array([a_site[f] for f in first.tolist()])
    n_sites = len(a_site)
    starts = _store.REVISION_STARTS
    start = np.array([starts[r] for r in number.tolist()],
                     dtype="datetime64[D]")
    last = number == of - 1
    end = np.array([starts[min(r + 1, len(starts) - 1)]
                    for r in number.tolist()], dtype="datetime64[D]") - 1
    company = which % len(COMPANIES)            # 0-based: 2 is "pri"

    def per_site(values):
        return np.asarray(values)[which]
    i32 = lambda a: pa.array(np.asarray(a, dtype=np.int32))   # noqa: E731
    market = rng.integers(1, 7, size=n)
    julian = _tpcds.JULIAN_1900_01_02
    first_day = int((np.datetime64("1997-08-16")
                     - _tpcds.FIRST_DATE).astype(int))
    return pa.table({
        "web_site_sk": pa.array(sk),
        "web_site_id": pa.array([_store.business_key(int(f))
                                 for f in first.tolist()], pa.string()),
        "web_rec_start_date": pa.array(start.astype(np.int32), pa.date32()),
        "web_rec_end_date": pa.array(end.astype(np.int32), pa.date32(),
                                     mask=last),
        "web_name": pa.array([f"site_{w}" for w in which.tolist()],
                             pa.string()),
        "web_open_date_sk": i32(julian + first_day
                                + per_site(rng.integers(0, 700,
                                                        size=n_sites))),
        "web_close_date_sk": pa.array(
            (julian + first_day + 1000
             + rng.integers(0, 1500, size=n)).astype(np.int32),
            mask=rng.random(n) < 0.7),
        "web_class": _text(np.zeros(n, int), SITE_CLASSES),
        "web_manager": _text(rng.integers(0, len(MANAGERS), size=n),
                             MANAGERS),
        "web_mkt_id": i32(market),
        "web_mkt_class": pa.array([f"class {m} of web sites"
                                   for m in market.tolist()], pa.string()),
        "web_mkt_desc": pa.array([f"market {m} of {COMPANIES[c]} sites"
                                  for m, c in zip(market.tolist(),
                                                  company.tolist())],
                                 pa.string()),
        "web_market_manager": _text(rng.integers(0, len(MANAGERS), size=n),
                                    MANAGERS),
        "web_company_id": i32(company + 1),
        "web_company_name": _text(company, COMPANIES),
        "web_street_number": pa.array(
            [str(v) for v in per_site(rng.integers(1, 1000, size=n_sites))
             .tolist()], pa.string()),
        "web_street_name": _text(per_site(rng.integers(0, len(STREETS),
                                                       size=n_sites)),
                                 STREETS),
        "web_street_type": _text(per_site(rng.integers(
            0, len(STREET_TYPES), size=n_sites)), STREET_TYPES),
        "web_suite_number": pa.array(
            [f"Suite {v}" for v in per_site(
                rng.integers(0, 50, size=n_sites) * 10).tolist()],
            pa.string()),
        "web_city": _text(per_site(rng.integers(0, len(CITIES),
                                                size=n_sites)), CITIES),
        "web_county": _text(np.zeros(n, int), ["Williamson County"]),
        "web_state": _text(np.zeros(n, int), ["TN"]),
        "web_zip": pa.array([f"{v:05d}" for v in per_site(
            rng.integers(30000, 40000, size=n_sites)).tolist()],
            pa.string()),
        "web_country": _text(np.zeros(n, int), ["United States"]),
        "web_gmt_offset": _rate(np.full(n, -500)),
        "web_tax_percentage": _rate(rng.integers(0, 13, size=n)),
    })


_TABLES = {"web_sales": web_sales, "web_returns": web_returns,
           "customer_address": customer_address, "web_site": web_site}


def generate(config, scale, seed, tables):
    theirs = [t for t in tables if t not in _TABLES]
    out = _tpcds.generate(config, scale, seed, theirs) if theirs else {}
    for t in tables:
        if t in _TABLES:
            out[t] = _TABLES[t](config, scale, seed)
    return {t: out[t] for t in tables}
