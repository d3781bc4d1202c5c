"""Sort / TopN differential tests. Oracle: Python sorted() with Spark key
semantics (asc nulls first / desc nulls last by default, NaN greatest)."""

import math

import pytest

from spark_rapids_tpu.exec import (InMemoryScanExec, SortExec,
                                   TakeOrderedAndProjectExec, collect)
from spark_rapids_tpu.exec.sort import SortOrder, asc, desc
from spark_rapids_tpu.expressions import col

from harness.asserts import assert_rows_equal, rows_of
from harness.data_gen import (DoubleGen, IntegerGen, LongGen, StringGen,
                              gen_table)


def scan(t, batch_rows=None):
    return InMemoryScanExec(t, batch_rows=batch_rows)


def spark_key(v, descending, nulls_first):
    # (null_rank, value_rank); NaN sorts greater than any double
    if v is None:
        return (0 if nulls_first else 2, 0)
    if isinstance(v, float):
        if math.isnan(v):
            r = (1, math.inf)
        else:
            r = (1, v)
        if descending:
            return (r[0], _neg(r[1]))
        return r
    if isinstance(v, str):
        b = v.encode("utf-8")
        key = tuple(b)
        return (1, tuple(-x for x in key) + (math.inf,)) if descending \
            else (1, key)
    return (1, -v if descending else v)


def _neg(x):
    return -x if x != math.inf else -math.inf


def oracle_sort(rows, specs):
    # specs: list of (col_idx, descending, nulls_first)
    def key(row):
        parts = []
        for i, d, nf in specs:
            parts.append(spark_key(row[i], d, nf))
        return tuple(parts)
    return sorted(rows, key=key)


@pytest.mark.parametrize("descending", [False, True])
def test_sort_ints(descending):
    t = gen_table([("a", IntegerGen()), ("b", LongGen())], n=900, seed=20)
    order = [SortOrder(col("a"), descending)]
    plan = SortExec(order, scan(t, batch_rows=200))
    got = rows_of(collect(plan))
    rows = list(zip(t.column("a").to_pylist(), t.column("b").to_pylist()))
    exp = oracle_sort(rows, [(0, descending, not descending)])
    # stable only per sort key; compare full rows but allow ties any order:
    assert [r[0] for r in got] == [r[0] for r in exp]
    assert_rows_equal(got, exp, ignore_order=True)


def test_sort_multi_key_with_doubles():
    t = gen_table([("a", IntegerGen(min_val=0, max_val=5)),
                   ("d", DoubleGen())], n=600, seed=21)
    plan = SortExec([asc(col("a")), desc(col("d"))], scan(t, batch_rows=128))
    got = rows_of(collect(plan))
    rows = list(zip(t.column("a").to_pylist(), t.column("d").to_pylist()))
    exp = oracle_sort(rows, [(0, False, True), (1, True, False)])
    for g, e in zip(got, exp):
        assert (g[0] is None) == (e[0] is None) and \
            (g[0] == e[0] or g[0] is None)
        ga, ea = g[1], e[1]
        if ea is None or ga is None:
            assert ga is None and ea is None
        elif math.isnan(ea):
            assert math.isnan(ga)
        else:
            assert ga == ea


def test_sort_strings():
    t = gen_table([("s", StringGen(max_len=10))], n=500, seed=22)
    plan = SortExec([asc(col("s"))], scan(t, batch_rows=100))
    got = [r[0] for r in rows_of(collect(plan))]
    vals = t.column("s").to_pylist()
    nones = [v for v in vals if v is None]
    rest = sorted([v for v in vals if v is not None],
                  key=lambda s: s.encode("utf-8"))
    assert got == [None] * len(nones) + rest


def test_top_n():
    t = gen_table([("a", IntegerGen()), ("b", IntegerGen())], n=2000, seed=23)
    plan = TakeOrderedAndProjectExec(25, [asc(col("a"))],
                                     [col("a"), col("b")],
                                     scan(t, batch_rows=256))
    got = rows_of(collect(plan))
    rows = list(zip(t.column("a").to_pylist(), t.column("b").to_pylist()))
    exp = oracle_sort(rows, [(0, False, True)])[:25]
    assert [r[0] for r in got] == [r[0] for r in exp]
    assert len(got) == 25


# ---------------------------------------------------------------------------
# exec/common.lex_sort_permutation — the one place a key sort is built
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtypes", [
    ("uint8",),
    ("uint8", "uint32", "uint32"),
    ("uint8", "uint8", "uint64"),
    ("uint32", "uint16", "uint8", "uint64", "uint8"),
], ids=lambda d: "-".join(d))
def test_lex_sort_permutation_matches_numpy_lexsort(dtypes):
    """Any mix of unsigned key words, most significant first, stably —
    however they are packed into i32 lanes and passes underneath."""
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.common import lex_sort_permutation
    rng = np.random.default_rng(5)
    n = 4096
    cols = []
    for dt in dtypes:
        info = np.iinfo(dt)
        # few distinct values (ties reach the later keys) incl. the extremes
        vals = np.array([0, 1, info.max // 2, info.max // 2 + 1,
                         info.max - 1, info.max], dtype=dt)
        cols.append(vals[rng.integers(0, len(vals), n)])
    got = np.asarray(lex_sort_permutation([jnp.asarray(c) for c in cols]))
    want = np.lexsort(tuple(reversed(cols)))      # lexsort: LAST key primary
    assert (got == want).all()


def test_float64_orderable_words_order_like_spark():
    """f64 keys sort through two u32 words built without a 64-bit bitcast:
    -inf < negatives < -0.0 == 0.0 < positives < inf < NaN. (Subnormals
    are left out: XLA flushes them to zero in the arithmetic that builds
    the words, as the TPU does when they are transferred.)"""
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import DeviceColumn
    from spark_rapids_tpu.exec.common import orderable_words
    vals = np.array([float("nan"), float("inf"), 1.7976931348623157e308,
                     1e300, 1.0000000000000002, 1.0, 2.2250738585072014e-308,
                     0.0, -0.0, -2.2250738585072014e-308, -1.0,
                     -1.0000000000000002, -1e300, float("-inf")])
    rng = np.random.default_rng(1)
    vals = np.concatenate([vals, rng.normal(0, 1e6, 200), rng.normal(0, 1e-6,
                                                                     200)])
    col = DeviceColumn(jnp.asarray(vals), jnp.ones(len(vals), bool), None,
                       T.FLOAT64)
    hi, lo = (np.asarray(w).astype(np.uint64) for w in orderable_words(col))
    word = (hi << np.uint64(32)) | lo
    order = np.argsort(word, kind="stable")
    s = vals[order]
    finite = s[~np.isnan(s)]
    assert (np.diff(finite) >= 0).all()
    assert np.isnan(s[-1]) and np.isinf(s[-2]) and s[-2] > 0
    assert word[7] == word[8]                     # 0.0 and -0.0: one key
    # distinct doubles get distinct words (np.unique folds -0.0 into 0.0
    # too, and counts the NaN once)
    assert len(np.unique(word)) == len(np.unique(vals))
