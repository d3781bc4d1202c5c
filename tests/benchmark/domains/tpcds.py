"""What generator ``tpcds`` has to make, table by table: the value rules
(``DOMAINS``), the row counts (``ROWS``) and the tables no seed changes
(``SEEDLESS``), found by the generator's name (``test_generators.py``)."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def _np(t, name):
    return t[name].to_numpy(zero_copy_only=False)


def _domain_store_sales(t, all_tables, cfg):
    date = t["ss_sold_date_sk"].drop_null().to_numpy()
    assert date.min() >= 2450816 and date.max() <= 2452642
    nulls = t["ss_sold_date_sk"].null_count / t.num_rows
    assert 0.02 < nulls < 0.06
    assert t["ss_item_sk"].null_count == 0
    assert t["ss_ticket_number"].null_count == 0
    qty = t["ss_quantity"].drop_null().to_numpy()
    assert qty.min() >= 1 and qty.max() <= 100
    # a ticket holds 8 to 16 items
    _, per = np.unique(_np(t, "ss_ticket_number"), return_counts=True)
    assert per[:-1].min() >= 8 and per.max() <= 16
    # ext_sales = sales_price x quantity, exactly, where neither is null
    both = pc.and_(pc.is_valid(t["ss_quantity"]),
                   pc.is_valid(t["ss_sales_price"]))
    f = t.filter(both)
    product = pc.multiply(f["ss_sales_price"], f["ss_quantity"])
    assert pc.all(pc.equal(product.cast(pa.decimal128(18, 2)),
                           f["ss_ext_sales_price"].cast(
                               pa.decimal128(18, 2)))).as_py()
    net = pc.subtract(f["ss_ext_sales_price"], f["ss_coupon_amt"])
    assert pc.all(pc.equal(net.cast(pa.decimal128(18, 2)),
                           f["ss_net_paid"].cast(
                               pa.decimal128(18, 2)))).as_py()


def _domain_date_dim(t, all_tables, cfg):
    assert t["d_date_sk"][0].as_py() == 2415022
    assert str(t["d_date"][0].as_py()) == "1900-01-02"
    assert str(t["d_date"][t.num_rows - 1].as_py()) == "2100-01-01"
    moy, dom, year = _np(t, "d_moy"), _np(t, "d_dom"), _np(t, "d_year")
    days = _np(t, "d_date").astype("datetime64[D]")
    assert (moy == days.astype("datetime64[M]").astype(int) % 12 + 1).all()
    assert (year == days.astype("datetime64[Y]").astype(int) + 1970).all()
    assert dom.min() == 1 and dom.max() == 31
    assert (np.diff(_np(t, "d_date_sk")) == 1).all()
    # 2000-01-01 was a Saturday
    i = int(np.flatnonzero(days == np.datetime64("2000-01-01"))[0])
    assert t["d_day_name"][i].as_py() == "Saturday"
    assert t["d_weekend"][i].as_py() == "Y"


def _domain_item(t, all_tables, cfg):
    assert (_np(t, "i_item_sk") == np.arange(1, t.num_rows + 1)).all()
    m = _np(t, "i_manufact_id")
    assert m.min() >= 1 and m.max() <= 1000
    assert set(_np(t, "i_category")) <= {
        "Women", "Men", "Children", "Shoes", "Music", "Jewelry", "Home",
        "Sports", "Books", "Electronics"}
    # a brand's name is a function of its id
    pairs = set(zip(_np(t, "i_brand_id").tolist(),
                    _np(t, "i_brand").tolist()))
    assert len(pairs) == len({b for b, _ in pairs})
    assert all("#" in name for _, name in pairs)
    assert pc.max(pc.binary_length(t["i_item_desc"])).as_py() <= 200


DOMAINS = {"store_sales": _domain_store_sales, "date_dim": _domain_date_dim,
           "item": _domain_item}
SEEDLESS = {"date_dim"}         # the calendar has no seed


def _rows_stated(table):
    def rule(cfg, scale, tables):       # dimensions are never scaled
        assert tables[table].num_rows == cfg["tables"][table]["rows"]
    return rule


def _rows_store_sales(cfg, scale, tables):
    assert tables["store_sales"].num_rows == int(
        cfg["tables"]["store_sales"]["rows"] * scale)


ROWS = {"store_sales": _rows_store_sales, "date_dim": _rows_stated("date_dim"),
        "item": _rows_stated("item")}
