"""TPC-H Q1 at the schema's published ``decimal(15,2)`` end to end at a
tiny scale: the benchmark's own configuration, generator, plan and plain
reference (``benchmarks/queries/tpchdec/q1.py``), every operator on the
device with an empty conf, Spark's result types, exact against the
reference; once as one scan batch, once as several (the merge path)."""

import os
import sys

import pyarrow as pa
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from rtbench import compare, data, loader, plans      # noqa: E402

from spark_rapids_tpu.plan import Session             # noqa: E402

TYPES = {"sum_qty": (25, 2), "sum_base_price": (25, 2),
         "sum_disc_price": (38, 4), "sum_charge": (38, 6),
         "avg_qty": (19, 6), "avg_price": (19, 6), "avg_disc": (19, 6)}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    bench = loader.benchmark()
    config = loader.config(bench, "tpch_sf1dec")
    query = loader.query(config["family"], "q1")
    out = str(tmp_path_factory.mktemp("tpch_sf1dec"))
    written = data.write_tables(config, 0.004, 3100000041,
                                sorted(query.TABLES), out)
    return config, query, written


def test_configuration_states_the_published_types(cell):
    config, query, _ = cell
    assert config["conf"] == {} and config["reduced"] == ["scale_factor"]
    assert config["money_type"] == "decimal(15,2)"
    assert config["guarantees"]["double_rel_err"] is None
    assert config["guarantees"]["control_precision"] == "float32"
    money = [c["name"] for t in config["tables"].values()
             for c in t["columns"] if c["type"] == "decimal128(15, 2)"]
    assert sorted(money) == ["c_acctbal", "l_discount", "l_extendedprice",
                             "l_quantity", "l_tax", "o_totalprice"]


@pytest.mark.parametrize("batch_rows", [None, 4096],
                         ids=["one_batch", "merged_batches"])
def test_q1_decimal_runs_wholly_on_the_device(cell, batch_rows):
    config, query, written = cell
    conf = dict(config["conf"])
    if batch_rows:
        conf["spark.rapids.tpu.sql.batchRowCapacity"] = batch_rows
    ses = Session(conf)
    df = query.plan(plans.scanner(written, query), dict(query.PARAMS))
    explained = ses.explain(df)
    assert all(ln.lstrip().startswith("*") for ln in explained.splitlines()
               if ln.strip()), explained
    got = ses.collect(df)
    assert not ses.fell_back(), ses.fell_back()
    for name, (p, s) in TYPES.items():
        assert got.schema.field(name).type == pa.decimal128(p, s), name
    assert got.schema.field("count_order").type == pa.int64()
    want = query.reference(data.reader(written), dict(query.PARAMS))
    assert want.schema == got.schema
    r = compare.compare(got, want, query.ORDERED)
    assert r["exact_mismatches"] == 0, (got.to_pylist(), want.to_pylist())
    assert got.num_rows == 4 and sum(
        got.column("count_order").to_pylist()) > 20000


def test_float32_control_gets_exact_values_wrong(cell):
    import numpy as np
    config, query, written = cell
    read = data.reader(written)
    want = query.reference(read, dict(query.PARAMS))
    low = query.reference(read, dict(query.PARAMS), money=np.float32)
    assert low.schema == want.schema
    assert compare.compare(low, want, True)["exact_mismatches"] > 0
