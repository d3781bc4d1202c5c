"""A later PR adds files and entries and edits no file that is there: a new
configuration, traffic mix, query and per-layer metric, added as new files
to a copy of the benchmark (and as entries of BENCHMARK.json), are picked up
by the same ``run.py``."""

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


def test_new_files_are_picked_up_with_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = {}
    for d, _, files in os.walk(root / "benchmarks"):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    # the system under test, as the checkout has it
    for name in ("spark_rapids_tpu", "native"):
        os.symlink(os.path.join(REPO, name), root / name)

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
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

    # an existing cell does not report the metric that lists other cells
    for p, content in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == content, f"{p} was edited"
