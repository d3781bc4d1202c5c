"""What generator ``tpch`` has to make, table by table: the value rules of
the specification's §4.2.3 (``DOMAINS``) and the row counts (``ROWS``), found
by the generator's name (``test_generators.py``). Money is read as integer
cents from either form of ``money_type``, so the spec's identities are
asserted exactly for doubles and decimals alike."""

import decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def _np(t, name):
    return t[name].to_numpy(zero_copy_only=False)


def _days(iso):
    return int((np.datetime64(iso) - np.datetime64("1970-01-01")).astype(int))


def cents(t, name):
    """A money or quantity column as int64 cents: a double has to hold
    cents / 100.0 to the bit, a decimal has to have scale 2."""
    col = t[name].combine_chunks()
    if pa.types.is_floating(col.type):
        x = col.to_numpy()
        c = np.rint(x * 100).astype(np.int64)
        assert (c / 100.0 == x).all(), f"{name} holds more than cents"
        return c
    assert pa.types.is_decimal(col.type) and col.type.scale == 2, col.type
    assert col.null_count == 0
    return pc.multiply(col, pa.scalar(decimal.Decimal(100),
                                      pa.decimal128(3, 0))) \
        .cast(pa.int64()).to_numpy()


def _line_totals(li):
    """An order line's price in dollars, rounded to the cent, as the
    generator's double arithmetic has it (``assumed``)."""
    return np.round((cents(li, "l_extendedprice") / 100.0)
                    * (1 + cents(li, "l_tax") / 100.0)
                    * (1 - cents(li, "l_discount") / 100.0), 2)


def _domain_lineitem(t, all_tables, cfg):
    qty, disc, tax = cents(t, "l_quantity"), cents(t, "l_discount"), \
        cents(t, "l_tax")
    assert qty.min() >= 100 and qty.max() <= 5000 and (qty % 100 == 0).all()
    assert disc.min() >= 0 and disc.max() <= 10
    assert tax.min() >= 0 and tax.max() <= 8
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
    # extended price = quantity x the part's retail price, to the cent
    part = _np(t, "l_partkey")
    retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    assert (cents(t, "l_extendedprice") == qty // 100 * retail_cents).all()
    assert set(_np(t, "l_shipmode")) <= {"REG AIR", "AIR", "RAIL", "SHIP",
                                         "TRUCK", "MAIL", "FOB"}
    assert pc.max(pc.binary_length(t["l_comment"])).as_py() <= 44


def _domain_orders(t, all_tables, cfg):
    key = _np(t, "o_orderkey")
    assert ((key - 1) % 32 < 8).all()           # sparse keys
    assert (_np(t, "o_custkey") % 3 != 0).all()
    date = _np(t, "o_orderdate").astype("datetime64[D]").astype(int)
    assert date.min() >= _days("1992-01-01")
    assert date.max() <= _days("1998-12-31") - 151
    assert set(_np(t, "o_orderstatus")) <= {"F", "O", "P"}
    assert set(_np(t, "o_orderpriority")) == {
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"}
    # o_totalprice is the sum over the order's lines, to the cent
    li = all_tables["lineitem"]
    order = np.searchsorted(key, _np(li, "l_orderkey"))
    total = np.round(np.bincount(order, weights=_line_totals(li),
                                 minlength=len(key)), 2)
    assert (cents(t, "o_totalprice")
            == np.rint(total * 100).astype(np.int64)).all()


def _domain_customer(t, all_tables, cfg):
    assert (_np(t, "c_custkey") == np.arange(1, t.num_rows + 1)).all()
    assert set(_np(t, "c_mktsegment")) == {
        "AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"}
    bal = cents(t, "c_acctbal")
    assert bal.min() >= -99999 and bal.max() <= 999999
    assert t["c_name"][0].as_py() == "Customer#000000001"
    nation = _np(t, "c_nationkey")
    assert nation.min() >= 0 and nation.max() <= 24
    assert t["c_phone"][0].as_py().startswith(f"{nation[0] + 10}-")


DOMAINS = {"lineitem": _domain_lineitem, "orders": _domain_orders,
           "customer": _domain_customer}


def _rows_scaled(table):
    def rule(cfg, scale, tables):
        assert tables[table].num_rows == int(cfg["tables"][table]["rows"]
                                             * scale)
    return rule


def _rows_lineitem(cfg, scale, tables):
    # 1 to 7 lines an order, uniform: 4 an order on average
    orders = tables["orders"].num_rows
    assert abs(tables["lineitem"].num_rows - 4 * orders) < 0.03 * 4 * orders


ROWS = {"lineitem": _rows_lineitem, "orders": _rows_scaled("orders"),
        "customer": _rows_scaled("customer")}
