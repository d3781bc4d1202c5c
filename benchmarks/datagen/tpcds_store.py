"""TPC-DS tables from a seed for the store channel: ``store_sales``,
``date_dim`` and ``item`` exactly as generator ``tpcds`` makes them (loaded
by name, byte for byte), plus ``store`` with the 29 columns, order and types
of the specification's §2.4 (v3.2.0) and dsdgen's rules as far as they are
known offline; what is replaced is listed under ``assumed`` in
``configs/tpcds_sf1_store.json``.

``generate(config, scale, seed, tables)`` returns ``{table: pyarrow.Table}``.
``store`` is a dimension and is never scaled: ``s_store_sk`` 1 to 12, the
keys ``ss_store_sk`` draws. It is a history-keeping dimension: a store has
one, two or three revisions in turn (surrogate keys 1 | 2-3 | 4-6 | 7 | 8-9
| 10-12), the revisions of one store share its 16-character business key
``s_store_id`` (six distinct at SF1) and split the sales years between them.
The other columns draw from the seed. numpy and pyarrow only.
"""

import numpy as np
import pyarrow as pa

from rtbench import loader

_tpcds = loader.generator("tpcds")

RATE = pa.decimal128(5, 2)
_STREAM = 103                         # beside tpcds's 101 (sales), 102 (item)

NAMES = ["ought", "able", "pri", "ese", "anti", "cally", "ation", "eing",
         "n st", "bar"]
HOURS = ["8AM-4PM", "8AM-8AM", "8AM-12AM"]
MANAGERS = ["William Ward", "Scott Smith", "Edwin Adams", "David Thomas",
            "Brett Yates", "Raymond Jacobs", "Robert Thompson",
            "Thomas Pollack"]
GEOGRAPHY = ["Unknown"]
STREETS = ["Spring", "Main", "Oak", "Park", "Elm", "Lake", "Hill", "Cedar",
           "Ridge", "Sunset"]
STREET_TYPES = ["Dr", "Ave", "Way", "Blvd", "Ct", "Ln", "Pkwy", "RD", "ST",
                "Cir", "Wy", "Boulevard"]
CITIES = ["Fairview", "Midway"]
COUNTIES = ["Williamson County"]
STATES = ["TN"]
REVISIONS = (1, 2, 3)                 # of successive stores, in turn
# a store's revisions split the sales years (1997-03-13 on) between them
REVISION_STARTS = [np.datetime64("1997-03-13"), np.datetime64("2000-03-13"),
                   np.datetime64("2001-03-13")]


def business_key(n):
    """dsdgen's 16-character key of the number ``n``: eight ``A`` then the
    32 low bits as eight letters ``A`` to ``P``, four bits each, least
    significant first (1 -> ``AAAAAAAABAAAAAAA``)."""
    return "AAAAAAAA" + "".join(chr(ord("A") + ((n >> (4 * i)) & 15))
                                for i in range(8))


def _revisions(n):
    """For surrogate keys 1..n: (first key of the store, revision number,
    revisions of the store)."""
    first, number, of = [], [], []
    sk, turn = 1, 0
    while sk <= n:
        k = min(REVISIONS[turn % len(REVISIONS)], n - sk + 1)
        for r in range(k):
            first.append(sk)
            number.append(r)
            of.append(k)
        sk += k
        turn += 1
    return np.array(first), np.array(number), np.array(of)


def _rate(hundredths):
    """decimal(5,2) from integer hundredths, exactly."""
    lo = np.ascontiguousarray(hundredths, dtype=np.int64)
    words = np.empty((len(lo), 2), dtype=np.int64)
    words[:, 0] = lo
    words[:, 1] = lo >> 63
    return pa.Array.from_buffers(RATE, len(lo), [None, pa.py_buffer(words)])


def store(config, scale, seed):
    n = config["tables"]["store"]["rows"]
    rng = np.random.default_rng([int(seed), _STREAM])
    sk = np.arange(1, n + 1, dtype=np.int32)
    first, number, of = _revisions(n)
    # what belongs to the store is drawn once a store, what a revision may
    # change once a row
    a_store = {f: i for i, f in enumerate(np.unique(first).tolist())}
    which = np.array([a_store[f] for f in first.tolist()])
    n_stores = len(a_store)

    def per_store(values):
        return np.asarray(values)[which]

    def text(codes, values):
        return pa.array([values[c % len(values)] for c in
                         np.asarray(codes).tolist()], pa.string())

    start = np.array([REVISION_STARTS[r] for r in number.tolist()],
                     dtype="datetime64[D]")
    last = number == of - 1
    end = np.array([REVISION_STARTS[min(r + 1, len(REVISION_STARTS) - 1)]
                    for r in number.tolist()], dtype="datetime64[D]") - 1
    i32 = lambda a: pa.array(np.asarray(a, dtype=np.int32))   # noqa: E731
    market = rng.integers(1, 11, size=n)
    return pa.table({
        "s_store_sk": pa.array(sk),
        "s_store_id": pa.array([business_key(f) for f in first.tolist()],
                               pa.string()),
        "s_rec_start_date": pa.array(start.astype(np.int32), pa.date32()),
        "s_rec_end_date": pa.array(end.astype(np.int32), pa.date32(),
                                   mask=last),
        "s_closed_date_sk": pa.array(
            (2450820 + rng.integers(0, 1000, size=n)).astype(np.int32),
            mask=rng.random(n) < 0.7),
        "s_store_name": text(per_store(np.arange(n_stores)), NAMES),
        "s_number_employees": i32(rng.integers(200, 301, size=n)),
        "s_floor_space": i32(rng.integers(5000000, 10000001, size=n)),
        "s_hours": text(rng.integers(0, len(HOURS), size=n), HOURS),
        "s_manager": text(rng.integers(0, len(MANAGERS), size=n), MANAGERS),
        "s_market_id": i32(market),
        "s_geography_class": text(np.zeros(n, int), GEOGRAPHY),
        "s_market_desc": pa.array(
            [f"market {m} of the {NAMES[w % len(NAMES)]} store"
             for m, w in zip(market.tolist(), which.tolist())], pa.string()),
        "s_market_manager": text(rng.integers(0, len(MANAGERS), size=n),
                                 MANAGERS),
        "s_division_id": i32(np.ones(n)),
        "s_division_name": text(np.zeros(n, int), ["Unknown"]),
        "s_company_id": i32(np.ones(n)),
        "s_company_name": text(np.zeros(n, int), ["Unknown"]),
        "s_street_number": pa.array(
            [str(v) for v in per_store(rng.integers(1, 1000, size=n_stores))
             .tolist()], pa.string()),
        "s_street_name": text(per_store(rng.integers(0, len(STREETS),
                                                     size=n_stores)), STREETS),
        "s_street_type": text(per_store(rng.integers(0, len(STREET_TYPES),
                                                     size=n_stores)),
                              STREET_TYPES),
        "s_suite_number": pa.array(
            [f"Suite {v}" for v in per_store(
                rng.integers(0, 50, size=n_stores) * 10).tolist()],
            pa.string()),
        "s_city": text(per_store(rng.integers(0, len(CITIES),
                                              size=n_stores)), CITIES),
        "s_county": text(np.zeros(n, int), COUNTIES),
        "s_state": text(np.zeros(n, int), STATES),
        "s_zip": pa.array(
            [f"{v:05d}" for v in per_store(
                rng.integers(30000, 40000, size=n_stores)).tolist()],
            pa.string()),
        "s_country": text(np.zeros(n, int), ["United States"]),
        "s_gmt_offset": _rate(np.full(n, -500)),
        "s_tax_precentage": _rate(rng.integers(0, 12, size=n)),
    })


def generate(config, scale, seed, tables):
    theirs = [t for t in tables if t != "store"]
    out = _tpcds.generate(config, scale, seed, theirs) if theirs else {}
    if "store" in tables:
        out["store"] = store(config, scale, seed)
    return {t: out[t] for t in tables}
