"""TPC-DS tables from a seed: ``store_sales``, ``date_dim`` and ``item`` with
the columns, order and types of the specification's §2 (v3), and the value
rules of dsdgen as far as they are known offline; what is replaced is listed
under ``assumed`` in ``configs/tpcds_sf1.json``.

``generate(config, scale, seed, tables)`` returns ``{table: pyarrow.Table}``.
``scale`` multiplies the fact table's row count; the dimensions (``item``,
and ``date_dim``, which is the calendar) are always whole. Money is exact
``decimal(7,2)``, built from integer cents. numpy and pyarrow only.
"""

import numpy as np
import pyarrow as pa

MONEY = pa.decimal128(7, 2)
JULIAN_1900_01_02 = 2415022           # d_date_sk of date_dim's first row
FIRST_DATE = np.datetime64("1900-01-02")
SALES_FIRST = np.datetime64("1998-01-02")
SALES_LAST = np.datetime64("2003-01-02")
NULL_SHARE = 0.04                     # dsdgen leaves ~4% of nullable FKs null

CATEGORIES = ["Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics"]
CLASSES = ["dresses", "pants", "shirts", "accessories", "athletic",
           "classical", "pop", "rock", "country", "bedding", "lighting",
           "rugs", "camping", "fishing", "fiction", "history"]
BRAND_SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar",
                   "corp", "univ", "brand", "maxi", "nameless"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "burnished", "chartreuse", "chiffon", "chocolate", "coral"]
SIZES = ["petite", "small", "medium", "large", "extra large", "economy", "N/A"]
UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
         "Box", "Bunch", "Bundle", "Cup", "Dram", "Gram", "Lb", "N/A", "Oz",
         "Ounce", "Pound", "Tbl", "Ton", "Tsp"]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]

_STREAMS = {"store_sales": 1, "item": 2}


def _rng(seed, table):
    return np.random.default_rng([int(seed), 100 + _STREAMS[table]])


def _money(cents, null=None):
    """decimal(7,2) from integer cents, exactly: the 128-bit unscaled value
    is the sign-extended int64."""
    lo = np.ascontiguousarray(cents, dtype=np.int64)
    words = np.empty((len(lo), 2), dtype=np.int64)
    words[:, 0] = lo
    words[:, 1] = lo >> 63
    validity = None
    if null is not None:
        validity = pa.array(~np.asarray(null)).buffers()[1]
    return pa.Array.from_buffers(MONEY, len(lo),
                                 [validity, pa.py_buffer(words)])


def _strings(codes, values):
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(values, pa.string())).cast(pa.string())


def _ids(keys):
    """The 16-character business key of a surrogate key."""
    base = np.char.zfill(keys.astype(str), 8)
    return pa.array(np.char.add("AAAAAAAA", base), pa.string())


def n_items(config, scale):
    return config["tables"]["item"]["rows"]


# ---------------------------------------------------------------------------

def date_dim(config, scale, seed):
    n = config["tables"]["date_dim"]["rows"]
    day = FIRST_DATE + np.arange(n)
    sk = JULIAN_1900_01_02 + np.arange(n, dtype=np.int32)
    year = day.astype("datetime64[Y]").astype(int) + 1970
    month0 = day.astype("datetime64[M]").astype(int)          # since 1970-01
    moy = month0 % 12 + 1
    dom = (day - day.astype("datetime64[M]")).astype(int) + 1
    dow = ((day.astype("datetime64[D]").astype(int) + 4) % 7)  # 0 = Sunday
    qoy = (moy - 1) // 3 + 1
    month_seq = month0 + 70 * 12                               # since 1900-01
    week_seq = (np.arange(n) + 1) // 7 + 1
    quarter_seq = (year - 1900) * 4 + qoy
    first_dom = sk - (dom - 1)
    next_month = (day.astype("datetime64[M]") + 1).astype("datetime64[D]")
    last_dom = sk + (next_month - day).astype(int) - 1
    holiday = ((moy == 12) & (dom == 25)) | ((moy == 1) & (dom == 1)) \
        | ((moy == 7) & (dom == 4))
    weekend = (dow == 0) | (dow == 6)
    yn = ["N", "Y"]
    i32 = lambda a: pa.array(np.asarray(a, dtype=np.int32))   # noqa: E731
    quarter_name = [f"{y}Q{q}" for y, q in zip(year.tolist(), qoy.tolist())]
    return pa.table({
        "d_date_sk": i32(sk),
        "d_date_id": _ids(sk),
        "d_date": pa.array(day.astype("datetime64[D]").astype(np.int32),
                           pa.date32()),
        "d_month_seq": i32(month_seq), "d_week_seq": i32(week_seq),
        "d_quarter_seq": i32(quarter_seq), "d_year": i32(year),
        "d_dow": i32(dow), "d_moy": i32(moy), "d_dom": i32(dom),
        "d_qoy": i32(qoy), "d_fy_year": i32(year),
        "d_fy_quarter_seq": i32(quarter_seq), "d_fy_week_seq": i32(week_seq),
        "d_day_name": _strings(dow, DAY_NAMES),
        "d_quarter_name": pa.array(quarter_name, pa.string()),
        "d_holiday": _strings(holiday, yn),
        "d_weekend": _strings(weekend, yn),
        "d_following_holiday": _strings(np.roll(holiday, 1), yn),
        "d_first_dom": i32(first_dom), "d_last_dom": i32(last_dom),
        "d_same_day_ly": i32(sk - 365), "d_same_day_lq": i32(sk - 91),
        "d_current_day": _strings(np.zeros(n), yn),
        "d_current_week": _strings(np.zeros(n), yn),
        "d_current_month": _strings(np.zeros(n), yn),
        "d_current_quarter": _strings(np.zeros(n), yn),
        "d_current_year": _strings(np.zeros(n), yn),
    })


def item(config, scale, seed):
    rng = _rng(seed, "item")
    n = n_items(config, scale)
    sk = np.arange(1, n + 1, dtype=np.int32)
    category = rng.integers(0, len(CATEGORIES), size=n)
    klass = rng.integers(0, len(CLASSES), size=n)
    brand_no = rng.integers(1, 11, size=n)
    # the brand's name is a function of its id, as in dsdgen
    brand_id = (category + 1) * 1000000 + (klass + 1) * 1000 + brand_no
    s = BRAND_SYLLABLES
    brand = [f"{s[c]}{s[k % len(s)]} #{b}" for c, k, b in zip(
        category.tolist(), klass.tolist(), brand_no.tolist())]
    manufact_id = rng.integers(1, 1001, size=n)
    wholesale = rng.integers(100, 8000, size=n)
    price = wholesale + (wholesale * rng.integers(10, 200, size=n)) // 100
    start = (np.datetime64("1997-10-27")
             + rng.integers(0, 3, size=n) * 365).astype("datetime64[D]")
    ended = rng.random(n) < 0.5
    desc_pool = [" ".join(rng.choice(COLORS + CLASSES + CATEGORIES,
                                     size=int(k)).tolist())[:200]
                 for k in rng.integers(3, 30, size=1024)]
    return pa.table({
        "i_item_sk": pa.array(sk),
        "i_item_id": _ids(sk),
        "i_rec_start_date": pa.array(start.astype(np.int32), pa.date32()),
        "i_rec_end_date": pa.array((start + 730).astype(np.int32),
                                   pa.date32(), mask=~ended),
        "i_item_desc": _strings(rng.integers(0, 1024, size=n), desc_pool),
        "i_current_price": _money(price),
        "i_wholesale_cost": _money(wholesale),
        "i_brand_id": pa.array(brand_id.astype(np.int32)),
        "i_brand": pa.array(brand, pa.string()),
        "i_class_id": pa.array((klass + 1).astype(np.int32)),
        "i_class": _strings(klass, CLASSES),
        "i_category_id": pa.array((category + 1).astype(np.int32)),
        "i_category": _strings(category, CATEGORIES),
        "i_manufact_id": pa.array(manufact_id.astype(np.int32)),
        "i_manufact": pa.array([f"{s[m % 10]}{s[m // 10 % 10]}"
                                for m in manufact_id.tolist()], pa.string()),
        "i_size": _strings(rng.integers(0, len(SIZES), size=n), SIZES),
        "i_formulation": pa.array(
            [f"{v:020d}" for v in rng.integers(0, 10 ** 18, size=n).tolist()],
            pa.string()),
        "i_color": _strings(rng.integers(0, len(COLORS), size=n), COLORS),
        "i_units": _strings(rng.integers(0, len(UNITS), size=n), UNITS),
        "i_container": _strings(np.zeros(n), ["Unknown"]),
        "i_manager_id": pa.array(rng.integers(1, 101, size=n)
                                 .astype(np.int32)),
        "i_product_name": pa.array(
            [f"{s[v % 10]}{s[v // 10 % 10]}{s[v // 100 % 10]}"
             for v in sk.tolist()], pa.string()),
    })


def store_sales(config, scale, seed):
    rng = _rng(seed, "store_sales")
    n = max(int(config["tables"]["store_sales"]["rows"] * scale), 2000)
    # a ticket is 8 to 16 items sold to one customer at one time and store
    n_tickets = n // 8 + 1
    per = rng.integers(8, 17, size=n_tickets)
    ticket = np.repeat(np.arange(1, n_tickets + 1, dtype=np.int64), per)[:n]
    t = ticket - 1

    first = int((SALES_FIRST - FIRST_DATE).astype(int))
    days = np.arange(first, int((SALES_LAST - FIRST_DATE).astype(int)) + 1)
    moy = (FIRST_DATE + days).astype("datetime64[M]").astype(int) % 12 + 1
    weight = np.where(moy >= 11, 2.0, np.where(moy >= 8, 1.3, 1.0))
    date_of_ticket = rng.choice(days, size=n_tickets, p=weight / weight.sum())

    def per_ticket(hi, lo=1):
        return rng.integers(lo, hi + 1, size=n_tickets)[t].astype(np.int32)

    def nullable(values):
        return pa.array(values, mask=rng.random(n) < NULL_SHARE)

    n_item = n_items(config, scale)
    quantity = rng.integers(1, 101, size=n)
    wholesale = rng.integers(100, 10001, size=n)             # cents a unit
    list_price = wholesale + (wholesale * rng.integers(0, 201, size=n)) // 100
    sales_price = (list_price * rng.integers(0, 101, size=n)) // 100
    ext_sales = sales_price * quantity
    ext_wholesale = wholesale * quantity
    ext_list = list_price * quantity
    coupon = np.where(rng.random(n) < 0.2,
                      (ext_sales * rng.integers(0, 101, size=n)) // 100, 0)
    net_paid = ext_sales - coupon
    ext_tax = (net_paid * rng.integers(0, 10, size=n)) // 100
    # a null price nulls every amount worked out from it, as in dsdgen
    no_price = rng.random(n) < NULL_SHARE

    def amount(cents):
        return _money(cents, null=no_price)

    return pa.table({
        "ss_sold_date_sk": nullable(
            (JULIAN_1900_01_02 + date_of_ticket[t]).astype(np.int32)),
        "ss_sold_time_sk": nullable(per_ticket(75599, 28800)),
        "ss_item_sk": pa.array(rng.integers(1, n_item + 1, size=n)
                               .astype(np.int32)),
        "ss_customer_sk": nullable(per_ticket(100000)),
        "ss_cdemo_sk": nullable(per_ticket(1920800)),
        "ss_hdemo_sk": nullable(per_ticket(7200)),
        "ss_addr_sk": nullable(per_ticket(50000)),
        "ss_store_sk": nullable(per_ticket(12)),
        "ss_promo_sk": nullable(rng.integers(1, 301, size=n)
                                .astype(np.int32)),
        "ss_ticket_number": pa.array(ticket),
        "ss_quantity": nullable(quantity.astype(np.int32)),
        "ss_wholesale_cost": amount(wholesale),
        "ss_list_price": amount(list_price),
        "ss_sales_price": amount(sales_price),
        "ss_ext_discount_amt": amount(ext_list - ext_sales),
        "ss_ext_sales_price": amount(ext_sales),
        "ss_ext_wholesale_cost": amount(ext_wholesale),
        "ss_ext_list_price": amount(ext_list),
        "ss_ext_tax": amount(ext_tax),
        "ss_coupon_amt": amount(coupon),
        "ss_net_paid": amount(net_paid),
        "ss_net_paid_inc_tax": amount(net_paid + ext_tax),
        "ss_net_profit": amount(net_paid - ext_wholesale),
    })


_TABLES = {"store_sales": store_sales, "date_dim": date_dim, "item": item}


def generate(config, scale, seed, tables):
    return {t: _TABLES[t](config, scale, seed) for t in tables}
