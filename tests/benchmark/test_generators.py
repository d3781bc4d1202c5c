"""The generators against their configuration files and the specs' value
rules, one case per table."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from benchlib import BENCH
from rtbench import loader

SCALE = 0.02
SEED = 2 ** 31 + 12345          # seeds are larger than 32 signed bits hold


def _configs():
    out = []
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            cfg = json.load(f)
        out += [(cfg, t) for t in cfg["tables"]]
    return out


CASES = _configs()
IDS = [f"{c['name']}.{t}" for c, t in CASES]


@pytest.fixture(scope="module")
def made():
    cache = {}

    def get(cfg):
        if cfg["name"] not in cache:
            gen = loader.generator(cfg["generator"])
            cache[cfg["name"]] = gen.generate(cfg, SCALE, SEED,
                                              sorted(cfg["tables"]))
        return cache[cfg["name"]]
    return get


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_schema_is_the_configurations(cfg, table, made):
    t = made(cfg)[table]
    want = [(c["name"], c["type"]) for c in cfg["tables"][table]["columns"]]
    assert [(f.name, str(f.type)) for f in t.schema] == want


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_row_count(cfg, table, made):
    t = made(cfg)[table]
    stated = cfg["tables"][table]["rows"]
    if table in ("date_dim", "item"):
        assert t.num_rows == stated         # dimensions are never scaled
    elif table == "lineitem":
        # 1 to 7 lines an order, uniform: 4 an order on average
        orders = made(cfg)["orders"].num_rows
        assert abs(t.num_rows - 4 * orders) < 0.03 * 4 * orders
    else:
        assert t.num_rows == int(stated * SCALE)


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_same_seed_same_bytes(cfg, table):
    gen = loader.generator(cfg["generator"])

    def ipc(seed):
        t = gen.generate(cfg, SCALE / 4, seed, [table])[table]
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        return sink.getvalue().to_pybytes()
    assert ipc(SEED) == ipc(SEED)
    if table != "date_dim":             # the calendar has no seed
        assert ipc(SEED) != ipc(SEED + 1)


def _np(t, name):
    return t[name].to_numpy(zero_copy_only=False)


def _days(iso):
    return int((np.datetime64(iso) - np.datetime64("1970-01-01")).astype(int))


def _domain_lineitem(t, all_tables):
    qty, disc, tax = _np(t, "l_quantity"), _np(t, "l_discount"), \
        _np(t, "l_tax")
    assert qty.min() >= 1 and qty.max() <= 50 and (qty == qty.round()).all()
    assert disc.min() >= 0.0 and disc.max() <= 0.10 + 1e-12
    assert tax.min() >= 0.0 and tax.max() <= 0.08 + 1e-12
    ship = _np(t, "l_shipdate").astype("datetime64[D]").astype(int)
    receipt = _np(t, "l_receiptdate").astype("datetime64[D]").astype(int)
    current = _days("1995-06-17")
    flag, status = _np(t, "l_returnflag"), _np(t, "l_linestatus")
    # the flag and status rule against the dates (spec 4.2.3)
    assert set(flag[receipt > current]) == {"N"}
    assert set(flag[receipt <= current]) <= {"R", "A"}
    assert (status == np.where(ship > current, "O", "F")).all()
    assert ((receipt - ship) >= 1).all() and ((receipt - ship) <= 30).all()
    # 1 to 7 lines an order, numbered from 1
    _, counts = np.unique(_np(t, "l_orderkey"), return_counts=True)
    assert counts.min() >= 1 and counts.max() <= 7
    assert set(np.unique(counts)) == set(range(1, 8))
    assert _np(t, "l_linenumber").max() <= 7
    # extended price = quantity x the part's retail price
    part = _np(t, "l_partkey")
    retail = (90000 + (part // 10) % 20001 + 100 * (part % 1000)) / 100.0
    assert np.allclose(_np(t, "l_extendedprice"), qty * retail, rtol=1e-12)
    assert set(_np(t, "l_shipmode")) <= {"REG AIR", "AIR", "RAIL", "SHIP",
                                         "TRUCK", "MAIL", "FOB"}
    assert pc.max(pc.binary_length(t["l_comment"])).as_py() <= 44


def _domain_orders(t, all_tables):
    key = _np(t, "o_orderkey")
    assert ((key - 1) % 32 < 8).all()           # sparse keys
    assert (_np(t, "o_custkey") % 3 != 0).all()
    date = _np(t, "o_orderdate").astype("datetime64[D]").astype(int)
    assert date.min() >= _days("1992-01-01")
    assert date.max() <= _days("1998-12-31") - 151
    assert set(_np(t, "o_orderstatus")) <= {"F", "O", "P"}
    assert set(_np(t, "o_orderpriority")) == {
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"}
    # o_totalprice is the sum over the order's lines
    li = all_tables["lineitem"]
    line = np.round(_np(li, "l_extendedprice") * (1 + _np(li, "l_tax"))
                    * (1 - _np(li, "l_discount")), 2)
    order = np.searchsorted(key, _np(li, "l_orderkey"))
    total = np.bincount(order, weights=line, minlength=len(key))
    assert np.allclose(_np(t, "o_totalprice"), total, atol=0.011)


def _domain_customer(t, all_tables):
    assert (_np(t, "c_custkey") == np.arange(1, t.num_rows + 1)).all()
    assert set(_np(t, "c_mktsegment")) == {
        "AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"}
    bal = _np(t, "c_acctbal")
    assert bal.min() >= -999.99 and bal.max() <= 9999.99
    assert t["c_name"][0].as_py() == "Customer#000000001"
    nation = _np(t, "c_nationkey")
    assert nation.min() >= 0 and nation.max() <= 24
    assert t["c_phone"][0].as_py().startswith(f"{nation[0] + 10}-")


def _domain_store_sales(t, all_tables):
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


def _domain_date_dim(t, all_tables):
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


def _domain_item(t, all_tables):
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


DOMAINS = {"lineitem": _domain_lineitem, "orders": _domain_orders,
           "customer": _domain_customer, "store_sales": _domain_store_sales,
           "date_dim": _domain_date_dim, "item": _domain_item}


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_value_domains(cfg, table, made):
    tables = made(cfg)
    DOMAINS[table](tables[table], tables)
