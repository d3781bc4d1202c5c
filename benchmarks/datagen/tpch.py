"""TPC-H tables from a seed, to the column rules of the specification's
§4.2.3 (dbgen) as far as they are known offline; what is replaced is listed
under ``assumed`` in ``configs/tpch_sf1.json``.

``generate(config, scale, seed, tables)`` returns ``{table: pyarrow.Table}``
for the tables asked for. ``scale`` multiplies the configuration's row
counts (1.0 on the chip, a small fraction in the CPU rehearsal). Money and
quantity come in the type the configuration's ``money_type`` states:
``double`` (the ``--floats`` form), or ``decimal(15,2)`` (the published
type) as ``decimal128(15, 2)`` holding the same cents. Keys are ``int64``,
dates ``date32``, ``char``/``varchar`` Arrow strings. numpy and pyarrow
only: the client process never touches JAX.
"""

import re

import numpy as np
import pyarrow as pa

EPOCH = np.datetime64("1970-01-01")
START_DATE = int((np.datetime64("1992-01-01") - EPOCH).astype(int))
END_DATE = int((np.datetime64("1998-12-31") - EPOCH).astype(int))
CURRENT_DATE = int((np.datetime64("1995-06-17") - EPOCH).astype(int))

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
WORDS = ("furiously sly carefully blithely quickly fluffily slyly quietly "
         "ruthlessly thinly closely doggedly daringly bravely stealthily "
         "permanently enticingly idly busily regular special express final "
         "pending ironic even bold silent unusual packages requests accounts "
         "deposits foxes ideas theodolites pinto beans instructions "
         "dependencies excuses platelets asymptotes courts dolphins "
         "multipliers sauternes warthogs frets dinos attainments somas "
         "tithes sleep wake are cajole haggle nag use boost affix detect "
         "integrate maintain nod was lose sublate solve thrash promise "
         "engage hinder print above the according to").split()

_STREAMS = {"customer": 1, "orders": 2, "lineitem": 3}
_DECIMAL = re.compile(r"^decimal\((\d+), *2\)$")


def money(config, cents, dollars=None):
    """A money or quantity column from integer cents, in the configuration's
    ``money_type``. ``dollars`` is the double form where the caller already
    holds it; a decimal's 128-bit unscaled value is the sign-extended
    int64."""
    kind = config["money_type"]
    if kind == "double":
        return cents / 100.0 if dollars is None else dollars
    m = _DECIMAL.match(kind)
    if m is None or int(m.group(1)) > 18:
        raise ValueError(f"money_type {kind!r}: this generator makes "
                         f"'double' or 'decimal(p,2)' with p <= 18")
    lo = np.ascontiguousarray(cents, dtype=np.int64)
    words = np.empty((len(lo), 2), dtype=np.int64)
    words[:, 0] = lo
    words[:, 1] = lo >> 63
    return pa.Array.from_buffers(pa.decimal128(int(m.group(1)), 2), len(lo),
                                 [None, pa.py_buffer(words)])


def _rng(seed, table):
    return np.random.default_rng([int(seed), _STREAMS[table]])


def _pool_strings(rng, n, pool):
    idx = rng.integers(0, len(pool), size=n, dtype=np.int32)
    return pa.DictionaryArray.from_arrays(
        pa.array(idx), pa.array(pool, pa.string())).cast(pa.string())


def _text_pool(rng, lo, hi, size=4096):
    """``size`` sentences of the word list, lengths uniform in [lo, hi]
    (stands in for dbgen's text grammar: see ``assumed``)."""
    out = []
    for want in rng.integers(lo, hi + 1, size=size):
        s = ""
        while len(s) < want:
            s += WORDS[int(rng.integers(0, len(WORDS)))] + " "
        out.append(s[:int(want)].rstrip() or "x")
    return out


def _text(rng, n, lo, hi):
    return _pool_strings(rng, n, _text_pool(rng, lo, hi))


def _numbered(prefix, keys, width=9):
    return pa.array(np.char.add(prefix, np.char.zfill(
        keys.astype(str), width)), pa.string())


def n_customers(config, scale):
    return max(int(config["tables"]["customer"]["rows"] * scale), 30)


def n_orders(config, scale):
    return max(int(config["tables"]["orders"]["rows"] * scale), 100)


def customer(config, scale, seed, shared):
    rng = _rng(seed, "customer")
    n = n_customers(config, scale)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, size=n, dtype=np.int64)
    local = rng.integers(0, 10 ** 10, size=n)
    phone = [f"{c + 10}-{p // 10 ** 7:03d}-{p // 10 ** 4 % 1000:03d}-"
             f"{p % 10 ** 4:04d}" for c, p in zip(nation.tolist(),
                                                   local.tolist())]
    return pa.table({
        "c_custkey": key,
        "c_name": _numbered("Customer#", key),
        "c_address": _text(rng, n, 10, 40),
        "c_nationkey": nation,
        "c_phone": pa.array(phone, pa.string()),
        "c_acctbal": money(config, rng.integers(-99999, 999999 + 1, size=n)),
        "c_mktsegment": _pool_strings(rng, n, SEGMENTS),
        "c_comment": _text(rng, n, 29, 116),
    })


def _order_keys(n):
    # sparse keys: the first 8 of every 32 are used
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def _orders_core(config, scale, seed):
    """What ``orders`` and ``lineitem`` share, from the orders stream only,
    so that either table can be made without the other."""
    rng = _rng(seed, "orders")
    n = n_orders(config, scale)
    n_cust = n_customers(config, scale)
    # customers whose key is divisible by 3 place no orders
    cust = rng.integers(1, n_cust + 1, size=n, dtype=np.int64)
    cust = np.where(cust % 3 == 0, np.maximum(cust - 1, 1), cust)
    date = rng.integers(START_DATE, END_DATE - 151 + 1, size=n,
                        dtype=np.int32)
    lines = rng.integers(1, 8, size=n, dtype=np.int64)
    return rng, n, cust, date, lines


def _lineitem_columns(config, scale, seed):
    core = _orders_core(config, scale, seed)
    _, n, _, odate, lines = core
    rng = _rng(seed, "lineitem")
    total = int(lines.sum())
    order_idx = np.repeat(np.arange(n, dtype=np.int64), lines)
    first = np.cumsum(lines) - lines
    linenumber = np.arange(total, dtype=np.int64) - first[order_idx] + 1
    n_part = max(int(200000 * scale), 200)
    n_supp = max(int(10000 * scale), 10)
    partkey = rng.integers(1, n_part + 1, size=total, dtype=np.int64)
    hop = rng.integers(0, 4, size=total, dtype=np.int64)
    suppkey = (partkey + hop * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = rng.integers(1, 51, size=total, dtype=np.int64)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    odate_l = odate[order_idx]
    shipdate = odate_l + rng.integers(1, 122, size=total, dtype=np.int32)
    commitdate = odate_l + rng.integers(30, 91, size=total, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, size=total, dtype=np.int32)
    returned = rng.integers(0, 2, size=total, dtype=np.int8)
    flag = np.where(receiptdate <= CURRENT_DATE, returned, 2).astype(np.int32)
    status = (shipdate > CURRENT_DATE).astype(np.int32)
    discount = rng.integers(0, 11, size=total)
    tax = rng.integers(0, 9, size=total)
    return {
        "rng": rng, "core": core, "order_idx": order_idx,
        "l_orderkey": _order_keys(n)[order_idx],
        "l_partkey": partkey, "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        # the double forms, which orders' totals are computed from, and the
        # integer cents each holds
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": (quantity * retail_cents) / 100.0,
        "l_discount": discount / 100.0, "l_tax": tax / 100.0,
        "cents": {"l_quantity": quantity * 100,
                  "l_extendedprice": quantity * retail_cents,
                  "l_discount": discount, "l_tax": tax},
        "flag": flag, "status": status,
        "l_shipdate": shipdate, "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
    }


def _codes(codes, values):
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)),
        pa.array(values, pa.string())).cast(pa.string())


def lineitem(config, scale, seed, shared):
    c = shared()
    rng, total = c["rng"], len(c["l_orderkey"])
    return pa.table({
        "l_orderkey": c["l_orderkey"], "l_partkey": c["l_partkey"],
        "l_suppkey": c["l_suppkey"], "l_linenumber": c["l_linenumber"],
        **{name: money(config, cents, c[name])
           for name, cents in c["cents"].items()},
        "l_returnflag": _codes(c["flag"], ["R", "A", "N"]),
        "l_linestatus": _codes(c["status"], ["F", "O"]),
        "l_shipdate": pa.array(c["l_shipdate"], pa.date32()),
        "l_commitdate": pa.array(c["l_commitdate"], pa.date32()),
        "l_receiptdate": pa.array(c["l_receiptdate"], pa.date32()),
        "l_shipinstruct": _pool_strings(rng, total, INSTRUCTIONS),
        "l_shipmode": _pool_strings(rng, total, MODES),
        "l_comment": _text(rng, total, 10, 43),
    })


def orders(config, scale, seed, shared):
    c = shared()
    rng, n, cust, date, _ = c["core"]
    # o_totalprice and o_orderstatus are functions of the order's lines
    line_total = np.round(c["l_extendedprice"] * (1 + c["l_tax"])
                          * (1 - c["l_discount"]), 2)
    total = np.round(np.bincount(c["order_idx"], weights=line_total,
                                 minlength=n), 2)
    open_lines = np.bincount(c["order_idx"], weights=c["status"],
                             minlength=n)
    all_lines = np.bincount(c["order_idx"], minlength=n)
    status = np.where(open_lines == 0, 0,
                      np.where(open_lines == all_lines, 1, 2))
    n_clerk = max(int(1000 * scale), 10)
    clerks = [f"Clerk#{k:09d}" for k in range(1, n_clerk + 1)]
    return pa.table({
        "o_orderkey": _order_keys(n),
        "o_custkey": cust,
        "o_orderstatus": _codes(status, ["F", "O", "P"]),
        "o_totalprice": money(config, np.rint(total * 100).astype(np.int64),
                              total),
        "o_orderdate": pa.array(date, pa.date32()),
        "o_orderpriority": _pool_strings(rng, n, PRIORITIES),
        "o_clerk": _pool_strings(rng, n, clerks),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _text(rng, n, 19, 78),
    })


_TABLES = {"customer": customer, "orders": orders, "lineitem": lineitem}


def generate(config, scale, seed, tables):
    memo = []

    def shared():
        # the numeric lineitem columns, which orders' totals and status
        # are functions of: made once for both tables
        if not memo:
            memo.append(_lineitem_columns(config, scale, seed))
        return memo[0]

    return {t: _TABLES[t](config, scale, seed, shared) for t in tables}
