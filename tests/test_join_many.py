"""Joins whose output is many times their input, on the device: a self-join
of orders with a ``<>`` condition whose candidates pass one probe batch's
capacity many times over, a left-semi join on a build where each key
repeats a hundred times, the grace path with a condition, the hash join's
four counters on inputs whose answers are known, and TPC-DS Q95 (the
benchmark's own configuration, generator, plan and plain reference,
``benchmarks/queries/tpcds/q95.py``) through ``Session.collect``."""

import importlib
import os
import sys
from collections import Counter, defaultdict

import numpy as np
import pyarrow as pa
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from rtbench import compare, data, loader, plans      # noqa: E402

from spark_rapids_tpu import trace as qtrace          # noqa: E402
from spark_rapids_tpu.batch import bucket_capacity    # noqa: E402
from spark_rapids_tpu.exec import (HashJoinExec, InMemoryScanExec,  # noqa
                                   JoinType, collect)
from spark_rapids_tpu.exec import join as join_module  # noqa: E402
from spark_rapids_tpu.expressions import col          # noqa: E402
from spark_rapids_tpu.plan import Session             # noqa: E402

BATCH = 256


def _orders(n=2000, seed=95, prefix=""):
    """Orders of 8 to 16 lines, a warehouse 1 to 5 a line (4 % null)."""
    rng = np.random.default_rng(seed)
    per = rng.integers(8, 17, size=n // 8 + 1)
    order = np.repeat(np.arange(1, len(per) + 1, dtype=np.int64), per)[:n]
    return pa.table({
        prefix + "o": pa.array(order),
        prefix + "wh": pa.array(rng.integers(1, 6, size=n).astype(np.int32),
                                mask=rng.random(n) < 0.04),
        prefix + "v": pa.array(np.arange(n, dtype=np.int64)),
    })


def _profile(run):
    rec = qtrace.FlightRecorder()
    with qtrace.query_trace(recorder=rec):
        out = run()
    return out, rec.profiles()[-1]


def _counters(profile, name="HashJoinExec"):
    got = Counter()
    for s in profile["spans"]:
        if s["name"] == name:
            for k, v in (s.get("attrs") or {}).items():
                if k.startswith("join") or k == "graceBuckets":
                    got[k] += v
    return got


def _pairs_oracle(left, right):
    """Self-join on the order, kept where both warehouses are known and
    differ: the (left v, right v) pairs, and the candidates of each probe
    batch of ``BATCH`` left rows (every right row of the same order)."""
    by_order = defaultdict(list)
    for o, wh, v in zip(*(right.column(i).to_pylist() for i in range(3))):
        by_order[o].append((wh, v))
    pairs, per_batch = Counter(), []
    lo, lwh, lv = (left.column(i).to_pylist() for i in range(3))
    for start in range(0, len(lo), BATCH):
        total = 0
        for o, wh, v in zip(lo[start:start + BATCH], lwh[start:start + BATCH],
                            lv[start:start + BATCH]):
            total += len(by_order[o])
            for rwh, rv in by_order[o]:
                if wh is not None and rwh is not None and wh != rwh:
                    pairs[(v, rv)] += 1
        per_batch.append(total)
    return pairs, per_batch


def _self_join(**kw):
    left, right = _orders(), _orders(prefix="r_")
    join = HashJoinExec([col("o")], [col("r_o")], JoinType.INNER,
                        InMemoryScanExec(left, batch_rows=BATCH),
                        InMemoryScanExec(right),
                        condition=col("wh") != col("r_wh"), **kw)
    return left, right, join


@pytest.mark.parametrize("part", [None, 1024], ids=["one_pair_batch",
                                                    "pair_batches_cut"])
def test_a_self_join_whose_pairs_pass_the_probe_batch_many_times(
        part, monkeypatch):
    """Where the candidates pass the bound of a pair batch (the probe
    batch's capacity, at least ``PAIR_PART_MIN_SLOTS``), the pairs come in
    parts of that bound, in the same order; here the bound is lowered so
    that the parts show at this size."""
    if part is not None:
        monkeypatch.setattr(join_module, "PAIR_PART_MIN_SLOTS", part)
    left, right, join = _self_join()
    got, profile = _profile(lambda: collect(join))
    want, per_batch = _pairs_oracle(left, right)
    assert Counter(zip(got.column("v").to_pylist(),
                       got.column("r_v").to_pylist())) == want
    # each probe batch's candidates are many times its capacity
    assert min(per_batch) > 8 * BATCH
    c = _counters(profile)
    assert c["joinProbeBatches"] == len(per_batch)
    assert c["joinCandidates"] == sum(per_batch)
    if part is None:
        assert c["joinSlotsOut"] == sum(bucket_capacity(t)
                                        for t in per_batch)
    else:
        assert c["joinSlotsOut"] == sum(-(-t // part) * part
                                        for t in per_batch)
    assert "graceBuckets" not in c


def test_pair_batches_cut_keep_the_pairs_in_order(monkeypatch):
    left, right, join = _self_join()
    whole = collect(join)
    monkeypatch.setattr(join_module, "PAIR_PART_MIN_SLOTS", 512)
    _, _, join = _self_join()
    assert collect(join).equals(whole)


def test_the_grace_path_with_a_condition():
    left, right, join = _self_join(max_build_rows=500)
    got, profile = _profile(lambda: collect(join))
    want, per_batch = _pairs_oracle(left, right)
    assert Counter(zip(got.column("v").to_pylist(),
                       got.column("r_v").to_pylist())) == want
    c = _counters(profile)
    assert c["graceBuckets"] == -(-right.num_rows // 500)
    # every candidate of the whole join is found in some bucket
    assert c["joinCandidates"] == sum(per_batch)


def _semi_tables(seed=96):
    rng = np.random.default_rng(seed)
    keys = np.arange(0, 100, 2, dtype=np.int64)          # 50 keys
    build = pa.table({"b": pa.array(np.repeat(keys, 100)),
                      "bw": pa.array(np.tile(np.arange(100, dtype=np.int32),
                                             len(keys)))})
    stream = pa.table({"a": pa.array(rng.integers(0, 100, size=3000)
                                     .astype(np.int64),
                                     mask=rng.random(3000) < 0.05),
                       "aw": pa.array(rng.integers(0, 200, size=3000)
                                      .astype(np.int32))})
    return stream, build


@pytest.mark.parametrize("condition", [False, True],
                         ids=["exact_counts_only", "condition_pairs"])
def test_a_semi_join_on_keys_repeated_a_hundred_times(condition):
    stream, build = _semi_tables()
    cond = (col("aw") < col("bw")) if condition else None
    join = HashJoinExec([col("a")], [col("b")], JoinType.LEFT_SEMI,
                        InMemoryScanExec(stream, batch_rows=1024),
                        InMemoryScanExec(build), condition=cond)
    got, profile = _profile(lambda: collect(join))
    a, aw = stream.column("a").to_pylist(), stream.column("aw").to_pylist()
    keys = set(build.column("b").to_pylist())
    keep = [i for i, k in enumerate(a)
            if k in keys and (not condition or aw[i] < 99)]
    assert got.column("a").to_pylist() == [a[i] for i in keep]
    assert got.column("aw").to_pylist() == [aw[i] for i in keep]
    c = _counters(profile)
    assert c["joinProbeBatches"] == 3
    matched = sum(1 for k in a if k in keys)
    assert c["joinCandidates"] == 100 * matched
    if condition:
        assert c["joinSlotsOut"] >= c["joinCandidates"]
    else:
        # counted, never expanded: no pair batch at all
        assert c["joinSlotsOut"] == 0


def test_a_semi_join_on_a_string_key_expands_and_verifies():
    stream, build = _semi_tables(seed=97)
    stream = stream.append_column("s", pa.array(
        [None if v is None else f"k{v}" for v in
         stream.column("a").to_pylist()]))
    build = build.append_column("t", pa.array(
        [f"k{v}" for v in build.column("b").to_pylist()]))
    join = HashJoinExec([col("s")], [col("t")], JoinType.LEFT_SEMI,
                        InMemoryScanExec(stream, batch_rows=1024),
                        InMemoryScanExec(build))
    got, profile = _profile(lambda: collect(join))
    keys = set(build.column("t").to_pylist())
    want = [s for s in stream.column("s").to_pylist() if s in keys]
    assert got.column("s").to_pylist() == want
    assert _counters(profile)["joinSlotsOut"] > 0


def test_the_viewer_prints_the_join_counters():
    _, _, join = _self_join()
    _, profile = _profile(lambda: collect(join))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        viewer = importlib.import_module("trace_viewer")
    finally:
        sys.path.pop(0)
    rows = {r["name"]: r for r in viewer.self_time_table(profile)}
    assert rows["HashJoinExec"]["joinCandidates"] \
        == _counters(profile)["joinCandidates"] > 0


# ---------------------------------------------------------------------------
# TPC-DS Q95 through the served plan's own pieces

SHUFFLED = {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 200_000}


@pytest.fixture(scope="module")
def q95(tmp_path_factory):
    bench = loader.benchmark()
    config = loader.config(bench, "tpcds_sf1_web")
    query = loader.query(config["family"], "q95")
    params = dict(query.PARAMS, days=730)
    out = str(tmp_path_factory.mktemp("tpcds_sf1_web"))
    written = data.write_tables(config, 0.03, 3700000095,
                                sorted(query.TABLES), out)
    want = query.reference(data.reader(written), params)
    return config, query, written, params, want


@pytest.mark.parametrize("conf", [{}, SHUFFLED],
                         ids=["broadcast", "shuffled"])
def test_q95_equals_the_reference(q95, conf):
    config, query, written, params, want = q95
    assert config["conf"] == {} and config["reduced"] == ["scale_factor"]
    ses = Session(dict(config["conf"], **conf,
                       **{"spark.rapids.tpu.trace.enabled": True}))
    df = query.plan(plans.scanner(written, query), params)
    explained = ses.explain(df)
    assert all(ln.lstrip().startswith("*") for ln in explained.splitlines()
               if ln.strip()), explained
    got = ses.collect(df)
    assert not ses.fell_back(), ses.fell_back()
    assert got.schema == want.schema
    assert got.schema.field("total net profit").type \
        == pa.decimal128(17, 2)
    assert compare.compare(got, want, query.ORDERED)["exact_mismatches"] \
        == 0, (got.to_pylist(), want.to_pylist())
    assert want.column("order count")[0].as_py() > 0
    names = ses.executed_exec_names()
    assert names.count("HashAggregateExec") == 4
    assert names.count("HashJoinExec") == 8
    if conf:
        assert "ShuffleExchangeExec" in names
    profile = qtrace.flight_recorder().profiles(ses.last_query_id)[0]
    c = _counters(profile)
    # the two self-joins alone make sum(lines^2) candidates each
    _, candidates, _ = query.pairs_by_order(
        data.reader(written)("web_sales", query.TABLES["web_sales"]))
    assert c["joinCandidates"] > 2 * int(candidates.sum())
    assert c["joinSlotsOut"] >= 2 * int(candidates.sum())


def test_q95_equals_the_row_interpreter(q95):
    """The same plan on the row interpreter (a nested loop a join, so on a
    slice of the data): 400 lines of ``web_sales`` and the returns of their
    orders, every sale date in the window, and the state most of them ship
    to, so that the answer holds orders."""
    import pyarrow.compute as pc
    from collections import Counter as Tally
    from spark_rapids_tpu.plan import table
    config, query, written, _, _ = q95
    read = data.reader(written)
    tables = {t: read(t, cols) for t, cols in query.TABLES.items()}
    ws = tables["web_sales"].slice(0, 400)
    wr = tables["web_returns"]
    tables["web_sales"] = ws
    tables["web_returns"] = wr.filter(pc.is_in(
        wr["wr_order_number"], ws["ws_order_number"]))
    ca = tables["customer_address"]
    state_of = dict(zip(ca["ca_address_sk"].to_pylist(),
                        ca["ca_state"].to_pylist()))
    state = Tally(state_of.get(a) for a in ws["ws_ship_addr_sk"].to_pylist()
                  if a is not None).most_common(1)[0][0]
    params = dict(query.PARAMS, year=1998, month=1, days=3650, state=state)
    df = query.plan(lambda t: table(tables[t]), params)
    tpu = Session({})
    got = tpu.collect(df)
    assert not tpu.fell_back(), tpu.fell_back()
    want = Session({"spark.rapids.tpu.sql.enabled": False}).collect(df)
    assert got.equals(want), (got.to_pylist(), want.to_pylist())
    assert got.equals(query.reference(
        lambda t, cols: tables[t].select(cols), params))
    assert got.column("order count")[0].as_py() > 0
