"""The vectorised decimal read and write of ``batch.py`` (numpy views of
Arrow's 16-byte little-endian values) against the per-value conversion it
replaced, kept here as the oracle: same values, same nulls, for every
precision 1-38, negatives, sliced, chunked and nullable arrays; and the
``scan.h2d.decimal`` span with the ``dec128Columns`` / ``dec128Bytes``
counters."""

import decimal as d
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (_col_to_arrow, _scalar_storage,
                                    from_arrow, to_arrow)
from spark_rapids_tpu.expressions import decimal128 as D128

MASK32 = (1 << 32) - 1


def old_scalar_storage(arr, dtype):
    """batch._scalar_storage's decimal branch before this change."""
    with d.localcontext() as lctx:
        lctx.prec = 60
        ints = [int(v.scaleb(dtype.scale)) if v is not None else 0
                for v in arr.to_pylist()]
    if dtype.precision > 18:
        out = np.zeros((len(ints), 4), np.int64)
        for i, v in enumerate(ints):
            u = v & ((1 << 128) - 1)
            for j in range(4):
                out[i, j] = (u >> (32 * j)) & MASK32
        return out
    return np.array(ints, dtype=np.int64)


def old_to_arrow(data, validity, dtype):
    """batch._col_to_arrow's decimal branch before this change."""
    with d.localcontext() as lctx:
        lctx.prec = 60
        if dtype.precision > 18:
            ints = []
            for row in data:
                u = 0
                for j in range(4):
                    u |= (int(row[j]) & MASK32) << (32 * j)
                ints.append(u - (1 << 128) if u >= 1 << 127 else u)
        else:
            ints = [int(v) for v in data]
        vals = [d.Decimal(v).scaleb(-dtype.scale) if ok else None
                for v, ok in zip(ints, validity)]
    return pa.array(vals, type=T.to_arrow(dtype))


def values(p, s, n, seed, nulls=True):
    rng = random.Random(seed)
    with d.localcontext() as cx:
        cx.prec = 60
        out = [d.Decimal(10 ** p - 1).scaleb(-s),
               d.Decimal(1 - 10 ** p).scaleb(-s), d.Decimal(0).scaleb(-s),
               d.Decimal(-1).scaleb(-s)]
        for i in range(n):
            if nulls and i % 5 == 3:
                out.append(None)
                continue
            v = rng.randrange(10 ** rng.randrange(1, p + 1))
            out.append(d.Decimal(-v if rng.random() < 0.5 else v).scaleb(-s))
    return out


@pytest.mark.parametrize("p", range(1, 39))
def test_read_matches_the_per_value_conversion(p):
    s = (p * 7) % (p + 1)
    dtype = T.decimal(p, s)
    arr = pa.array(values(p, s, 150, seed=p), pa.decimal128(p, s))
    forms = {"whole": arr, "sliced": arr.slice(7, 90),
             "tail": arr.slice(len(arr) - 5),
             "empty": arr.slice(3, 0),
             "no_nulls": pa.array(values(p, s, 40, p, nulls=False),
                                  pa.decimal128(p, s))}
    for name, a in forms.items():
        validity = np.asarray(a.is_valid()) if a.null_count \
            else np.ones(len(a), bool)
        got = _scalar_storage(a, dtype, validity)
        want = old_scalar_storage(a, dtype)
        assert got.dtype == want.dtype == np.int64, name
        assert got.shape == want.shape, name
        assert np.array_equal(got, want), name


@pytest.mark.parametrize("p,s", [(1, 0), (9, 2), (18, 18), (19, 0),
                                 (25, 2), (38, 6), (38, 38)])
def test_write_matches_the_per_value_conversion(p, s):
    dtype = T.decimal(p, s)
    arr = pa.array(values(p, s, 200, seed=100 + p), pa.decimal128(p, s))
    chunked = pa.chunked_array([arr.slice(0, 50), arr.slice(50, 1),
                                arr.slice(51)])
    for column in (arr, arr.slice(11, 120), chunked):
        t = pa.table({"w": column})
        batch, schema = from_arrow(t)
        n = t.num_rows
        col = batch.columns[0]
        got = _col_to_arrow(col, dtype, "w", n)
        want = old_to_arrow(np.asarray(col.data[:n]),
                            np.asarray(col.validity[:n]), dtype)
        assert got.type == want.type == pa.decimal128(p, s)
        assert got.equals(want)
        assert to_arrow(batch, schema).column("w").to_pylist() == \
            t.column("w").to_pylist()


def test_limb_helpers_match_python_ints():
    rng = random.Random(8)
    ints = [0, -1, 1, (1 << 127) - 1, -(1 << 127)] + [
        rng.randrange(-(1 << 126), 1 << 126) >> rng.randrange(0, 120)
        for _ in range(500)]
    limbs = D128.to_limbs_np(ints)
    assert limbs.dtype == np.int64 and limbs.shape == (len(ints), 4)
    assert ((limbs >= 0) & (limbs <= MASK32)).all()
    assert D128.from_limbs_np(limbs) == ints
    small = np.array([0, -1, 5, -(1 << 62), (1 << 62) - 3], np.int64)
    assert D128.from_limbs_np(D128.to_limbs_np(small)) == small.tolist()


def test_nested_decimal_elements_round_trip():
    D = d.Decimal
    t = pa.table({"a": pa.array([[D("1.50"), D("-2.25")], [], None,
                                 [D("99999.99")]],
                                pa.list_(pa.decimal128(7, 2)))})
    batch, schema = from_arrow(t)
    assert to_arrow(batch, schema).column("a").to_pylist() == \
        t.column("a").to_pylist()


def test_scan_span_and_dec128_counters(tmp_path, capsys):
    """A traced scan of decimal columns opens ``scan.h2d.decimal`` inside
    ``scan.h2d`` (values, columns, bytes), and an operator that emits limb
    matrices counts them (``dec128Columns``, ``dec128Bytes``)."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Sum
    from spark_rapids_tpu import trace as qtrace
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan import Session
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
    D = d.Decimal
    n = 300
    t = pa.table({"p": pa.array([D(i).scaleb(-2) for i in range(n)],
                                pa.decimal128(15, 2)),
                  "q": pa.array([D(7 * i).scaleb(-2) for i in range(n)],
                                pa.decimal128(15, 2)),
                  "k": pa.array(list(range(n)), pa.int64())})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    src = ParquetSource([path])
    df = DataFrame(LogicalScan((), source=src, _schema=src.schema()))
    ses = Session({"spark.rapids.tpu.trace.enabled": "true"})
    got = ses.collect(df.select(
        (col("p") * (lit(D("1")) - col("q"))).alias("m"), col("k")))
    assert got.schema.field("m").type == pa.decimal128(32, 4)
    profile = qtrace.flight_recorder().profiles(ses.last_query_id)[0]
    spans = profile["spans"]
    by_id = {s["id"]: s for s in spans}
    dec = [s for s in spans if s["name"] == "scan.h2d.decimal"]
    assert dec, sorted({s["name"] for s in spans})
    for s in dec:
        assert by_id[s["parent"]]["name"] == "scan.h2d"
        assert s["attrs"]["columns"] == 2
        assert s["attrs"]["values"] == n
        assert s["attrs"]["bytes"] >= 2 * 16 * n
    proj = [s for s in spans if s["name"].startswith("ProjectExec")]
    assert proj and proj[0]["attrs"]["dec128Columns"] == 1
    cap = 512
    assert proj[0]["attrs"]["dec128Bytes"] == cap * 4 * 8
    scan = [s for s in spans if s["name"].startswith("FileSourceScan")]
    assert scan and "dec128Columns" not in scan[0]["attrs"]
    # tools/trace_viewer.py --table prints both counters per span name
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        viewer = importlib.import_module("trace_viewer")
    finally:
        sys.path.pop(0)
    rows = {r["name"]: r for r in viewer.self_time_table(profile)}
    name = proj[0]["name"]
    assert rows[name]["dec128Columns"] == 1
    assert rows[name]["dec128Bytes"] == cap * 4 * 8
    # ... and an aggregate's partials: how many were cut to their groups'
    # bucket, and the capacities before and after (4 groups of one 512-row
    # batch: the partial aggregate cuts its partial to 128 rows, and the
    # final one, handed those 128, has nothing to cut)
    ses.collect(df.select((col("k") % lit(4)).alias("g"), col("p"))
                .group_by("g").agg(Sum(col("p")).alias("s")))
    profile = qtrace.flight_recorder().profiles(ses.last_query_id)[0]
    rows = {r["name"]: r for r in viewer.self_time_table(profile)}
    agg = rows["HashAggregateExec"]
    assert (agg["spans"], agg["partialsCut"]) == (2, 1)
    assert (agg["partialRowsMade"], agg["partialRowsKept"]) == \
        (cap + 128, 128 + 128)
    viewer.print_tables([profile])
    header, *lines = capsys.readouterr().out.splitlines()[1:]
    assert header.split()[-6:] == ["partials", "cut", "rows", "made",
                                   "rows", "kept"]
    line = [ln for ln in lines if ln.startswith("HashAggregateExec")][0]
    assert line.split()[-3:] == ["1", str(cap + 128), "256"]


def test_row_group_reader_keeps_the_codes_hand_off_beside_decimals(
        tmp_path, monkeypatch):
    """More than two files take the MULTITHREADED row-group reader; a
    decimal column puts a file outside the native decoder's subset, and
    its pyarrow fallback has to hand string columns over as dictionary
    codes all the same (they arrived as plain strings: 3.7 s of
    ``scan.h2d`` a 2^20-row batch of TPC-H Q1 on the chip, PR 31)."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.expressions.aggregates import Sum
    from spark_rapids_tpu.io import scan
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan import Session
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
    D = d.Decimal
    paths = []
    for i in range(4):
        t = pa.table({"f": pa.array(["A", "N", "R", "N"] * 50),
                      "p": pa.array([D(i * 100 + j).scaleb(-2)
                                     for j in range(200)],
                                    pa.decimal128(15, 2))})
        paths.append(str(tmp_path / f"part-{i}.parquet"))
        pq.write_table(t, paths[-1])
    seen = []
    real = scan.from_arrow

    def spy(tbl, *a, **k):
        seen.append([str(ty) for ty in tbl.schema.types])
        return real(tbl, *a, **k)
    monkeypatch.setattr(scan, "from_arrow", spy)
    src = ParquetSource(paths)
    df = DataFrame(LogicalScan((), source=src, _schema=src.schema()))
    ses = Session()
    got = ses.collect(df.group_by("f").agg(Sum(col("p")).alias("s")))
    assert not ses.fell_back()
    assert got.num_rows == 3
    assert seen and all(types[0].startswith("dictionary<values=string")
                        for types in seen), seen
    assert all(types[1] == "decimal128(15, 2)" for types in seen)
