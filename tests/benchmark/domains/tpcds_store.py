"""What generator ``tpcds_store`` has to make, table by table: the three
tables it takes from generator ``tpcds`` are held to that generator's rules
(imported from ``domains/tpcds.py``), ``store`` to its own: 12 rows, keys 1
to 12 in order, a 16-character business key shared by the revisions of one
store (six distinct), and every non-null ``ss_store_sk`` finds its store."""

import os

import numpy as np

from rtbench import loader

_theirs = loader._module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpcds.py"),
    "domains_tpcds_for_store")


def _domain_store(t, all_tables, cfg):
    n = t.num_rows
    assert n == 12
    sk = t["s_store_sk"].to_numpy()
    assert (sk == np.arange(1, n + 1)).all()
    ids = t["s_store_id"].to_pylist()
    assert all(len(i) == 16 and i.startswith("AAAAAAAA") for i in ids)
    assert len(set(ids)) == 6
    # revisions of one store are neighbours and share its key; the key is
    # dsdgen's of the first revision's number
    assert ids == sorted(ids)
    assert ids[0] == "AAAAAAAABAAAAAAA" and ids[1] == ids[2] \
        == "AAAAAAAACAAAAAAA" and ids[9] == "AAAAAAAAKAAAAAAA"
    # one open revision a store: the last, with no end date
    end = t["s_rec_end_date"].to_pylist()
    start = t["s_rec_start_date"].to_pylist()
    for i in range(n):
        last = i == n - 1 or ids[i + 1] != ids[i]
        assert (end[i] is None) == last
        if not last:
            assert start[i] <= end[i] < start[i + 1]
    tax = t["s_tax_precentage"].to_pylist()
    assert all(0 <= float(v) <= 0.11 for v in tax)
    assert t["s_store_sk"].null_count == 0 and t["s_store_id"].null_count == 0
    # every sale at a store finds it
    sold = all_tables["store_sales"]["ss_store_sk"].drop_null().to_numpy()
    assert sold.min() >= 1 and sold.max() <= n
    assert np.isin(sold, sk).all()


DOMAINS = dict(_theirs.DOMAINS, store=_domain_store)
SEEDLESS = set(_theirs.SEEDLESS)


def _rows_store(cfg, scale, tables):        # a dimension is never scaled
    assert tables["store"].num_rows == cfg["tables"]["store"]["rows"] == 12


ROWS = dict(_theirs.ROWS, store=_rows_store)
