"""What generator ``tpcds_web`` has to make, table by table: ``date_dim`` is
held to generator ``tpcds``'s rules (imported from ``domains/tpcds.py``);
``web_sales`` to orders of 8 to 16 lines that share their order's keys, a
warehouse 1 to 5 a line and ship dates after the sale; ``web_returns`` to
lines of real sales; ``customer_address`` to a county-weighted state and
``web_site`` to 30 revisions of 15 sites among which ``pri`` is a company.
Beside them, Q95's answer is held to be about something: at SF1 the plain
reference counts between 10 and 2,000 orders on three seeds."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from rtbench import loader

_theirs = loader._module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpcds.py"),
    "domains_tpcds_for_web")

ANSWER_SEEDS = (3700000001, 3700000002, 3700000003)
ANSWER_ORDERS = (10, 2000)


def _np(t, name):
    return t[name].to_numpy(zero_copy_only=False)


def _domain_web_sales(t, all_tables, cfg):
    n = t.num_rows
    order = _np(t, "ws_order_number")
    assert t["ws_order_number"].null_count == 0
    assert t["ws_item_sk"].null_count == 0
    assert (np.diff(order) >= 0).all() and order[0] == 1
    # orders of 8 to 16 lines (the last one may be cut by the row count)
    _, per = np.unique(order, return_counts=True)
    assert per[:-1].min() >= 8 and per.max() <= 16
    # a warehouse a line, 1 to 5, and 4 % of each nullable key null
    wh = t["ws_warehouse_sk"].drop_null().to_numpy()
    assert wh.min() == 1 and wh.max() == 5
    for key in ("ws_warehouse_sk", "ws_ship_date_sk", "ws_ship_addr_sk",
                "ws_web_site_sk"):
        assert 0.02 < t[key].null_count / n < 0.06, key
    # within an order the warehouses differ: the self-join has work
    first = np.r_[True, order[1:] != order[:-1]]
    wh_all = t["ws_warehouse_sk"].fill_null(0).to_numpy()
    assert (wh_all[~first] != wh_all[np.flatnonzero(~first) - 1]).mean() > 0.5
    # an order's keys are the order's: one site, one ship address
    for key in ("ws_web_site_sk", "ws_ship_addr_sk", "ws_sold_date_sk"):
        v = t[key].to_numpy(zero_copy_only=False)
        ok = ~np.isnan(v.astype(np.float64))
        seen = {}
        for o, x in zip(order[ok][:5000].tolist(), v[ok][:5000].tolist()):
            assert seen.setdefault(o, x) == x, key
    site = t["ws_web_site_sk"].drop_null().to_numpy()
    assert site.min() >= 1 and site.max() <= cfg["tables"]["web_site"]["rows"]
    addr = t["ws_ship_addr_sk"].drop_null().to_numpy()
    assert addr.min() >= 1 \
        and addr.max() <= cfg["tables"]["customer_address"]["rows"]
    # shipped 1 to 120 days after the sale
    both = pc.and_(pc.is_valid(t["ws_sold_date_sk"]),
                   pc.is_valid(t["ws_ship_date_sk"]))
    f = t.filter(both)
    lag = _np(f, "ws_ship_date_sk").astype(np.int64) \
        - _np(f, "ws_sold_date_sk")
    assert lag.min() >= 1 and lag.max() <= 120
    # money: decimal(7,2), the inc_ship_tax column sums its parts
    priced = t.filter(pc.is_valid(t["ws_net_paid"]))
    assert pc.all(pc.equal(
        pc.add(pc.add(priced["ws_net_paid"], priced["ws_ext_ship_cost"]),
               priced["ws_ext_tax"]).cast(pa.decimal128(9, 2)),
        priced["ws_net_paid_inc_ship_tax"].cast(pa.decimal128(9, 2)))).as_py()
    _answer_is_about_something(cfg)


def _answer_is_about_something(cfg):
    """At SF1, on three seeds, the reference's order count is inside
    ``ANSWER_ORDERS``: ``correct`` compares a non-trivial reply."""
    gen = loader.generator(cfg["generator"])
    query = loader.query(cfg["family"], "q95")
    for seed in ANSWER_SEEDS:
        tables = gen.generate(cfg, 1.0, seed, sorted(query.TABLES))
        answer = query.reference(lambda name, cols: tables[name].select(cols),
                                 dict(query.PARAMS))
        orders = answer.column("order count")[0].as_py()
        assert ANSWER_ORDERS[0] <= orders <= ANSWER_ORDERS[1], (seed, orders)
        assert answer.column("total shipping cost")[0].as_py() is not None


def _domain_web_returns(t, all_tables, cfg):
    sales = all_tables["web_sales"]
    assert t["wr_order_number"].null_count == 0
    assert t["wr_item_sk"].null_count == 0
    # each return is a sold line, and no line comes back twice
    sold = set(zip(_np(sales, "ws_order_number").tolist(),
                   _np(sales, "ws_item_sk").tolist()))
    got = list(zip(_np(t, "wr_order_number").tolist(),
                   _np(t, "wr_item_sk").tolist()))
    assert all(g in sold for g in got)
    assert np.isin(_np(t, "wr_order_number"),
                   _np(sales, "ws_order_number")).all()
    qty = t["wr_return_quantity"].drop_null().to_numpy()
    assert qty.min() >= 1 and qty.max() <= 100
    assert 0.08 < t.num_rows / sales.num_rows < 0.12


def _domain_customer_address(t, all_tables, cfg):
    n = t.num_rows
    assert (_np(t, "ca_address_sk") == np.arange(1, n + 1)).all()
    ids = t["ca_address_id"].to_pylist()
    assert all(len(i) == 16 for i in ids) and len(set(ids)) == n
    states = _np(t, "ca_state")
    assert all(len(s) == 2 for s in set(states.tolist()))
    # weighted by counties: Texas (254) well above Illinois (102), which is
    # about 3 % of the addresses
    share = {s: (states == s).mean() for s in ("TX", "IL", "DE")}
    assert share["TX"] > 2 * share["IL"] > 4 * share["DE"]
    assert 0.02 < share["IL"] < 0.045


def _domain_web_site(t, all_tables, cfg):
    n = t.num_rows
    assert n == 30
    assert (_np(t, "web_site_sk") == np.arange(1, n + 1)).all()
    ids = t["web_site_id"].to_pylist()
    # 15 sites, the revisions of one site neighbours sharing its key
    runs = 1 + sum(a != b for a, b in zip(ids, ids[1:]))
    assert len(set(ids)) == runs == 15
    names = set(_np(t, "web_company_name").tolist())
    assert "pri" in names and names <= {"ought", "able", "pri", "ese",
                                        "anti", "cally"}
    # a site's revisions share its company
    by_id = {}
    for i, c in zip(ids, _np(t, "web_company_name").tolist()):
        assert by_id.setdefault(i, c) == c
    end = t["web_rec_end_date"].to_pylist()
    for i in range(n):
        last = i == n - 1 or ids[i + 1] != ids[i]
        assert (end[i] is None) == last


DOMAINS = {"web_sales": _domain_web_sales, "web_returns": _domain_web_returns,
           "customer_address": _domain_customer_address,
           "web_site": _domain_web_site,
           "date_dim": _theirs.DOMAINS["date_dim"]}
SEEDLESS = set(_theirs.SEEDLESS)


def _rows_fact(table):
    def rule(cfg, scale, tables):
        assert tables[table].num_rows == int(
            cfg["tables"][table]["rows"] * scale)
    return rule


def _rows_stated(table):
    def rule(cfg, scale, tables):       # dimensions are never scaled
        assert tables[table].num_rows == cfg["tables"][table]["rows"]
    return rule


ROWS = {"web_sales": _rows_fact("web_sales"),
        "web_returns": _rows_fact("web_returns"),
        "customer_address": _rows_stated("customer_address"),
        "web_site": _rows_stated("web_site"),
        "date_dim": _theirs.ROWS["date_dim"]}
