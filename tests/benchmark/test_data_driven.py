"""A later PR adds files and entries and edits no file that is there. Two
such PRs, each made in a copy of the benchmark: a new configuration, traffic
mix, query and per-layer metric over a table the benchmark has; and a new
generator with a table and a decimal type of its own, its checks, its
configuration with a stated precision control, a query and a traffic mix.
The same ``run.py``, ``control.py`` and ``test_generators.py`` pick them
up."""

import json
import os
import shutil

from benchlib import REPO, run_cli

NEW_QUERY = '''
"""Rows of item by category: a new query, as a later PR would add it."""
import pyarrow as pa

TABLES = {"item": ["i_category", "i_item_sk"]}
PARAMS = {}
ORDERED = True


def plan(scan, params):
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.expressions.aggregates import Count
    return (scan("item").group_by("i_category")
            .agg(Count().alias("n")).order_by("i_category"))


def reference(read, params, money=None):
    t = read("item", TABLES["item"])
    g = t.group_by("i_category").aggregate([("i_item_sk", "count")])
    g = g.sort_by("i_category")
    return pa.table({"i_category": g["i_category"],
                     "n": g["i_item_sk_count"].cast(pa.int64())})
'''

NEW_METRIC = '''
"""Rows the window's replies returned: a new per-layer metric."""


def read(run):
    return sum(r.table.num_rows for r in run["done"]) or None
'''


def _checkout(tmp_path):
    """A copy of the benchmark's own directories beside the system under
    test; returns its root, every copied file's bytes, and BENCHMARK.json
    parsed."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    root = tmp_path / "checkout"
    before = {}
    for path in bench["paths"]:
        shutil.copytree(os.path.join(REPO, path), root / path,
                        ignore=shutil.ignore_patterns("__pycache__", ".work"))
        for d, _, files in os.walk(root / path):
            for f in files:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    before[p] = fh.read()
    for name in ("spark_rapids_tpu", "native"):
        os.symlink(os.path.join(REPO, name), root / name)
    return root, before, bench


def _no_file_was_edited(before):
    for p, content in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == content, f"{p} was edited"


def test_new_files_are_picked_up_with_no_edit(tmp_path):
    root, before, bench = _checkout(tmp_path)
    with open(root / "benchmarks" / "configs" / "tpcds_sf1.json") as f:
        config = json.load(f)
    config["name"] = "tpcds_sf1_items"
    config["source"] += " (item only)"
    (root / "benchmarks" / "configs" / "tpcds_sf1_items.json").write_text(
        json.dumps(config))
    (root / "benchmarks" / "traffic" / "by_category.json").write_text(
        json.dumps({"clients": 1, "timeout_s": 60,
                    "rehearsal_scale": 0.05,
                    "queries": [{"query": "by_category", "weight": 1}]}))
    (root / "benchmarks" / "queries" / "tpcds" / "by_category.py") \
        .write_text(NEW_QUERY)
    (root / "benchmarks" / "metrics" / "reply_rows.py") \
        .write_text(NEW_METRIC)
    bench["configs"].append({
        "name": "tpcds_sf1_items", "source": config["source"],
        "file": "benchmarks/configs/tpcds_sf1_items.json",
        "reduced": ["scale_factor"], "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": "tpcds_sf1_items.by_category", "config": "tpcds_sf1_items",
        "traffic": "by_category", "chips": 1, "why": "a later PR's cell"})
    bench["per_layer"].append({
        "name": "reply_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "client",
        "moves": "rows_per_s",
        "workloads": ["tpcds_sf1_items.by_category"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    script = str(root / "benchmarks" / "run.py")
    common = ["--workload", "tpcds_sf1_items.by_category", "--seed", 77,
              "--rehearsal", "--work-dir", tmp_path / "work"]
    rc, last, out, err = run_cli(script, common + [
        "--seconds", 1, "--trace", 0], cwd=str(root))
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["attempted"] >= 1
    assert set(last["metrics"]) == {"rows_per_s", "query_s", "setup_s"}

    rc, last, out, err = run_cli(script, common + [
        "--seconds", 3, "--trace", 1], cwd=str(root))
    assert rc == 0, err[-3000:]
    assert last["metrics"]["reply_rows"]["value"] \
        == 10 * last["attempted"]           # ten categories a reply
    assert last["metrics"]["reply_rows"]["unit"] == "rows"

    _no_file_was_edited(before)


NEW_GENERATOR = '''
"""A later PR's generator: one table of its own, ``payments``, with a money
column in the configuration's ``money_type`` (made as TPC-H's is)."""
import numpy as np
import pyarrow as pa

from rtbench import loader

REGIONS = ["north", "south", "east", "west", "centre", "coast", "hills"]


def generate(config, scale, seed, tables):
    tpch = loader.generator("tpch")
    rng = np.random.default_rng([int(seed), 41])
    n = max(int(config["tables"]["payments"]["rows"] * scale), 1000)
    # up to a thousand million a payment: sums need more than 18 digits
    cents = rng.integers(-10 ** 9, 10 ** 11, size=n)
    made = {"payments": pa.table({
        "p_id": np.arange(1, n + 1, dtype=np.int64),
        "p_region": tpch._pool_strings(rng, n, REGIONS),
        "p_amount": tpch.money(config, cents)})}
    return {t: made[t] for t in tables}
'''

NEW_DOMAINS = '''
"""What generator ``ledger`` has to make."""
import pyarrow as pa


def _domain_payments(t, all_tables, cfg):
    assert t["p_amount"].type == pa.decimal128(15, 2)
    assert t["p_amount"].null_count == 0
    assert len(set(t["p_region"].to_pylist())) == 7
    assert t["p_id"].to_pylist() == list(range(1, t.num_rows + 1))
    print("checked payments:", t.num_rows, "rows")


def _rows_payments(cfg, scale, tables):
    assert tables["payments"].num_rows == max(
        int(cfg["tables"]["payments"]["rows"] * scale), 1000)


DOMAINS = {"payments": _domain_payments}
ROWS = {"payments": _rows_payments}
'''

NEW_FAMILY_QUERY = '''
"""Payments summed by region: an exact decimal sum by a string key."""
import decimal

import numpy as np
import pyarrow as pa

TABLES = {"payments": ["p_region", "p_amount"]}
PARAMS = {}
ORDERED = True


def plan(scan, params):
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.expressions.aggregates import Sum
    return (scan("payments").group_by("p_region")
            .agg(Sum(col("p_amount")).alias("total")).order_by("p_region"))


def reference(read, params, money=np.int64):
    """``money`` int64 sums exact cents, as the configuration states; the
    control sums dollars in the float type it names and rounds to cents."""
    t = read("payments", TABLES["payments"])
    cents = pa.compute.multiply(
        t["p_amount"], pa.scalar(decimal.Decimal(100), pa.decimal128(3, 0))
    ).cast(pa.int64()).to_numpy()
    regions, inverse = np.unique(
        t["p_region"].to_numpy(zero_copy_only=False), return_inverse=True)
    if money is np.int64:
        total = np.zeros(len(regions), dtype=np.int64)
        np.add.at(total, inverse, cents)
    else:
        acc = np.zeros(len(regions), dtype=money)
        np.add.at(acc, inverse, (cents / 100.0).astype(money))
        total = np.rint(acc.astype(np.float64) * 100).astype(np.int64)
    return pa.table({
        "p_region": pa.array(regions, pa.string()),
        "total": pa.array([decimal.Decimal(int(c)).scaleb(-2) for c in total],
                          pa.decimal128(25, 2))})
'''

NEW_CONFIG = {
    "name": "ledger_small",
    "source": "a later PR's deployment, standing in for one with a public "
              "source",
    "family": "ledger", "generator": "ledger", "chips": 1,
    "money_type": "decimal(15,2)",
    "tables": {"payments": {"rows": 400000, "columns": [
        {"name": "p_id", "type": "int64"},
        {"name": "p_region", "type": "string", "avg_bytes": 5.0},
        {"name": "p_amount", "type": "decimal128(15, 2)"}]}},
    "conf": {},
    "guarantees": {
        "exact": "every column exact, decimal(15,2) sums as decimal(25,2)",
        "double_rel_err": None, "double_precision": "none",
        "control_precision": "float32",
        "control_precision_why": "sums of tens of thousands of payments: "
                                 "float32 dollars miss the cent"},
    "reduced": [], "assumed": []}


def test_a_new_generator_table_type_and_control_come_as_files(tmp_path):
    """What the next ``model_config`` PR does (``tpch_sf1dec``: tables and
    types of its own), done to a copy: nothing that was there changes."""
    root, before, bench = _checkout(tmp_path)
    b = root / "benchmarks"
    new = {
        b / "datagen" / "ledger.py": NEW_GENERATOR,
        root / "tests" / "benchmark" / "domains" / "ledger.py": NEW_DOMAINS,
        b / "configs" / "ledger_small.json": json.dumps(NEW_CONFIG),
        b / "queries" / "ledger" / "by_region.py": NEW_FAMILY_QUERY,
        b / "traffic" / "by_region.json": json.dumps({
            "clients": 1, "timeout_s": 60, "rehearsal_scale": 0.1,
            "queries": [{"query": "by_region", "weight": 1}]})}
    for path, text in new.items():
        assert not path.exists()
        path.parent.mkdir(exist_ok=True)
        path.write_text(text)
    bench["configs"].append({
        "name": "ledger_small", "source": NEW_CONFIG["source"],
        "file": "benchmarks/configs/ledger_small.json", "reduced": [],
        "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": "ledger_small.by_region", "config": "ledger_small",
        "traffic": "by_region", "chips": 1, "why": "a later PR's cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    # the rehearsal is correct, on the device path, with exact decimals
    rc, last, out, err = run_cli(str(b / "run.py"), [
        "--workload", "ledger_small.by_region", "--seed", 2 ** 31 + 30,
        "--seconds", 1, "--trace", 0, "--rehearsal", "--work-dir",
        tmp_path / "work"], cwd=str(root))
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["attempted"] >= 1
    assert last["compared"]["exact_mismatches"] == {"value": 0, "limit": 0}
    assert "double_rel_err" not in last["compared"]
    assert all(ln.strip().startswith("*")
               for plan in last["plans"] for ln in plan.splitlines())

    # both controls come out not correct, the stated precision by a cent
    rc, _, out, err = run_cli(str(b / "control.py"), [
        "--workload", "ledger_small.by_region", "--seeds", 2 ** 31 + 31,
        "--scale", 0.1], cwd=str(root))
    assert rc == 0, err[-3000:]
    controls = {r["control"]: r for r in map(json.loads, out.splitlines())}
    assert set(controls) == {"lower_precision", "lost_batch"}
    for r in controls.values():
        assert r["correct"] is False and r["exact_mismatches"] > 0

    # the generator tests run their checks on the new table, and on no
    # stand-in: the check prints what it saw
    rc, _, out, err = run_cli("-m", [
        "pytest", root / "tests" / "benchmark" / "test_generators.py", "-q",
        "-s", "-k", "ledger_small", "-p", "no:cacheprovider"], cwd=str(root))
    assert rc == 0, out[-3000:] + err[-3000:]
    assert "4 passed" in out and "checked payments: 8000 rows" in out
    # without its checks the same configuration fails, and says what to add
    os.remove(root / "tests" / "benchmark" / "domains" / "ledger.py")
    rc, _, out, err = run_cli("-m", [
        "pytest", root / "tests" / "benchmark" / "test_generators.py", "-q",
        "-k", "ledger_small", "-p", "no:cacheprovider"], cwd=str(root))
    assert rc != 0 and "3 failed, 1 passed" in out
    assert "domains/ledger.py exporting DOMAINS" in out

    _no_file_was_edited(before)
