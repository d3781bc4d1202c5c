"""Concurrent-client serving differential suite (ISSUE 10 acceptance).

The five bench shapes driven by N threaded ``PlanClient``s against one
embedded ``PlanServer``, result cache ON vs OFF:

  1. bit-for-bit: every (client, shape, round) result equals the
     cache-off oracle for the same query;
  2. nonzero hit counters on repeats (plan cache always; result cache
     for every digest-keyed shape — the file-backed scan is
     result-uncacheable by design and must still be bit-for-bit);
  3. zero leaks at close: no admitted sessions, no catalog pins.

Plus the mini load smoke job (<2 min, ``-m "serving and smoke"``)
driving tools/server_loadbench.py with small parameters.
"""

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.sort import asc
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.memory.catalog import device_budget
from spark_rapids_tpu.plan import table
from spark_rapids_tpu.server import PlanClient, PlanServer

pytestmark = pytest.mark.serving

N = 3000


def _rng(seed):
    return np.random.default_rng(seed)


@pytest.fixture(scope="module")
def tabs(tmp_path_factory):
    import pyarrow.parquet as pq
    rng = _rng(3)
    lineitem = pa.table({
        "k": rng.integers(0, 3, N).astype(np.int32),
        "l_quantity": rng.integers(1, 51, N).astype(np.int64),
        "l_extendedprice": rng.uniform(1.0, 1e5, N),
    })
    sales = pa.table({
        "k": rng.integers(0, 256, N).astype(np.int64),
        "ss_quantity": rng.integers(1, 100, N).astype(np.int64),
    })
    facts = pa.table({
        "k": rng.integers(0, 64, N).astype(np.int64),
        "v": rng.integers(-1000, 1000, N).astype(np.int64),
    })
    dims = pa.table({
        "k": np.arange(64, dtype=np.int64),
        "w": (np.arange(64) % 10).astype(np.int64),
    })
    pdir = tmp_path_factory.mktemp("serving_pq")
    ppath = str(pdir / "part-0.parquet")
    pq.write_table(pa.table({
        "k": rng.integers(0, 100, N).astype(np.int64),
        "v": rng.uniform(-10.0, 10.0, N),
    }), ppath)
    return {"lineitem": lineitem, "sales": sales, "facts": facts,
            "dims": dims, "parquet_path": ppath}


def _shapes(tabs):
    """(name, builder(literal)) for the five bench shapes."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan

    def q1(v):
        return (table(tabs["lineitem"])
                .where(col("l_quantity") > lit(int(v)))
                .group_by("k")
                .agg(Sum(col("l_extendedprice")).alias("rev"),
                     Count().alias("n")))

    def hash_agg(v):
        return (table(tabs["sales"])
                .where(col("ss_quantity") > lit(int(v)))
                .group_by("k").agg(Sum(col("ss_quantity")).alias("q")))

    def join_sort(v):
        return (table(tabs["facts"])
                .where(col("v") > lit(int(v)))
                .join(table(tabs["dims"]), ["k"], ["k"])
                .group_by("w").agg(Sum(col("v")).alias("s"))
                .order_by(asc(col("w"))))

    def parquet_scan(v):
        src = ParquetSource([tabs["parquet_path"]])
        df = DataFrame(LogicalScan((), source=src,
                                   _schema=src.schema()))
        return (df.where(col("k") > lit(int(v)))
                .group_by("k").agg(Count().alias("n")))

    def exchange(v):
        return (table(tabs["facts"], num_slices=4)
                .where(col("v") > lit(int(v)))
                .group_by("k").agg(Sum(col("v")).alias("s")))

    return [("q1_stage", q1), ("hash_agg", hash_agg),
            ("join_sort", join_sort), ("parquet_scan", parquet_scan),
            ("exchange", exchange)]


def _drive(tabs, conf, n_clients=4, rounds=3):
    """Each client collects every shape every round (literal varies per
    round, repeats across clients). Returns (results, stats, leaked)."""
    server = PlanServer(conf=conf).start()
    shapes = _shapes(tabs)
    results = {}
    caches = []
    errors = []
    lock = threading.Lock()

    def worker(ci):
        try:
            with PlanClient("127.0.0.1", server.port) as c:
                for r in range(rounds):
                    for name, build in shapes:
                        t = c.collect(build(10 + r * 7))
                        with lock:
                            results[(ci, name, r)] = t
                            caches.append((name, dict(c.last_cache),
                                           c.last_cached))
        except Exception as e:
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # a deterministic repeat pass: everything the fleet computed is now
    # stored, so a sequential client MUST hit every digest-keyed shape
    worker("verify")
    import time
    deadline = time.monotonic() + 5.0
    while server.active_sessions and time.monotonic() < deadline:
        time.sleep(0.02)     # closed clients drain on their next recv
    stats = server.serving_stats()
    leaked = server.active_sessions
    server.stop()
    assert errors == []
    return results, caches, stats, leaked


def test_concurrent_differential_cache_on_vs_off(tabs):
    pins0 = device_budget().total_pinned()
    on_conf = {
        "spark.rapids.tpu.server.planCache.enabled": "true",
        "spark.rapids.tpu.server.resultCache.enabled": "true",
        "spark.rapids.tpu.server.concurrentCollects": "3",
    }
    off_conf = {
        "spark.rapids.tpu.server.planCache.enabled": "false",
        "spark.rapids.tpu.server.resultCache.enabled": "false",
    }
    res_on, caches, stats, leaked_on = _drive(tabs, on_conf)
    res_off, _, _, leaked_off = _drive(tabs, off_conf, n_clients=1)

    # 1) bit-for-bit: every cached-path result equals the uncached
    #    oracle for the same (shape, round) query
    for (ci, name, r), t in res_on.items():
        oracle = res_off[(0, name, r)]
        assert t.equals(oracle), \
            f"client {ci} shape {name} round {r} diverged under caching"

    # 2) repeats hit: plan cache counters moved, and EVERY shape —
    #    file-backed scans included, stat-keyed on (path, mtime_ns,
    #    size) since ISSUE 18 — served repeats from the result cache
    counters = stats["counters"]
    assert counters["planCacheHitCount"] > 0
    assert counters["resultCacheHitCount"] > 0
    served = {name for (name, info, cached) in caches if cached}
    assert {"q1_stage", "hash_agg", "join_sort",
            "exchange", "parquet_scan"} <= served
    # no shape ever answers from the loud-refusal path anymore
    assert not any(str(i.get("result", "")).startswith("uncacheable")
                   for (_, i, _) in caches)

    # 3) zero leaks: no admitted sessions, no catalog pins beyond the
    #    suite's pre-existing ones
    assert leaked_on == 0 and leaked_off == 0
    assert device_budget().total_pinned() == pins0
    assert stats["admission"]["inFlight"] == 0


def test_admission_serializes_past_concurrent_collects(tabs):
    """concurrentCollects=1 forces strictly serialized collects; the
    admission wait counter proves queries actually queued there."""
    conf = {
        "spark.rapids.tpu.server.planCache.enabled": "true",
        "spark.rapids.tpu.server.resultCache.enabled": "false",
        "spark.rapids.tpu.server.concurrentCollects": "1",
        "spark.rapids.tpu.server.test.collectDelayMs": "150",
    }
    server = PlanServer(conf=conf).start()
    try:
        shapes = dict(_shapes(tabs))
        done = []

        def one(ci):
            with PlanClient("127.0.0.1", server.port) as c:
                done.append(c.collect(shapes["hash_agg"](5)))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        import time
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = server.serving_stats()
        # 3 collects x 150ms delay through ONE slot cannot overlap
        assert wall >= 0.44, f"serialized collects overlapped: {wall}"
        assert stats["admission"]["waitTimeNs"] > 0
        assert stats["admission"]["admitted"] == 3
        assert len(done) == 3 and all(d.equals(done[0]) for d in done)
    finally:
        server.stop()


@pytest.mark.smoke
def test_mini_loadbench_smoke():
    """The <2-min smoke-tier load job (README test tiers): a small
    fleet through tools/server_loadbench.py — caches on, repeats must
    hit, nothing may leak."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import server_loadbench
    finally:
        sys.path.pop(0)
    rep = server_loadbench.run_load(
        clients=4, rounds=3, rows=1000,
        plan_cache=True, result_cache=True, concurrent_collects=2)
    assert rep["queries"] == 4 * 3 * 4
    assert rep["server"]["counters"]["planCacheHitCount"] > 0
    assert rep["result_cache_served"] > 0
    assert rep["leaked_sessions"] == 0
    assert rep["server"]["admission"]["inFlight"] == 0


def test_query_admission_cancel_and_cap_unit():
    """Direct QueryAdmission coverage: cancellation while waiting for a
    held slot raises (and leaks nothing), an impossible reservation is
    capped to the device budget instead of spinning forever, and
    cancelled waits still land in the wait-time metric."""
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.memory.semaphore import (
        AdmissionCancelledError, QueryAdmission)
    cat = BufferCatalog(device_limit=1 << 20, host_limit=1 << 20,
                        spill_dir="/tmp/rtpu_admission_test")
    adm = QueryAdmission(1, catalog=cat)
    entered = threading.Event()
    release = threading.Event()

    def holder():
        with adm.admit(1024):
            entered.set()
            release.wait(10)

    th = threading.Thread(target=holder, daemon=True)
    th.start()
    assert entered.wait(5)
    with pytest.raises(AdmissionCancelledError):
        with adm.admit(1024, cancelled=lambda: True):
            raise AssertionError("admitted past a held slot")
    assert adm.wait_time_ns > 0          # the aborted wait was counted
    release.set()
    th.join(5)
    # the slot was not leaked by the cancelled waiter
    with adm.admit(0):
        pass
    # a reservation larger than the device budget is capped, not spun on
    with adm.admit(reserve_bytes=(1 << 30)):
        assert cat.device_used <= cat.device_limit
    assert cat.device_used == 0
    assert adm.in_flight == 0


# ---------------------------------------------------------------------------
# What covers a query's exchanges now that planning runs none (ISSUE 33):
# admission, the unwind, the breaker's classification. The query is the
# shuffled ``join_sort`` shape: until ISSUE 33 planning its group-by ran
# both of the join's exchanges, before any of the three applied.
# ---------------------------------------------------------------------------

_K = "spark.rapids.tpu."
_SHUFFLED_JOIN = {
    _K + "sql.autoBroadcastJoinThreshold": 0,
    _K + "server.resultCache.enabled": "false",
    _K + "server.concurrentCollects": "1",
}


def _hold_the_slot(server, tabs, delay_ms):
    """Start a query whose ``server.test.collectDelayMs`` keeps it in the
    one collect slot, and return once it sits there."""
    import time

    def hold():
        with PlanClient("127.0.0.1", server.port, conf={
                _K + "server.test.collectDelayMs": str(delay_ms)}) as c:
            c.collect(dict(_shapes(tabs))["hash_agg"](3))
    th = threading.Thread(target=hold)
    th.start()
    deadline = time.monotonic() + 5
    while server.serving_stats()["admission"]["inFlight"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    return th


def test_a_queued_querys_first_exchange_write_waits_for_its_admission(tabs):
    server = PlanServer(conf=dict(
        _SHUFFLED_JOIN, **{_K + "trace.enabled": "true"})).start()
    try:
        holder = _hold_the_slot(server, tabs, 500)
        with PlanClient("127.0.0.1", server.port) as c:
            got = c.collect(dict(_shapes(tabs))["join_sort"](11))
            assert got.num_rows == 10
            assert "ShuffleExchangeExec" in c.last_execs
            spans = next(p["spans"] for p in c.last_trace()["profiles"]
                         if p["component"] == "server")
        holder.join(10)
        wait = next(s for s in spans if s["name"] == "admission.wait")
        writes = [s for s in spans
                  if s["name"] == "ShuffleExchangeExec.write"]
        assert wait["durUs"] > 100_000      # it did queue behind the slot
        assert writes and min(s["tsUs"] for s in writes) >= \
            wait["tsUs"] + wait["durUs"]
    finally:
        server.stop()


def test_a_query_cancelled_waiting_for_admission_registered_nothing(tabs):
    from spark_rapids_tpu.server.client import PlanServerError
    server = PlanServer(conf=dict(_SHUFFLED_JOIN)).start()
    cat = device_budget()
    try:
        holder = _hold_the_slot(server, tabs, 1200)
        # (the holder sleeps in its slot: nothing else registers meanwhile)
        found = (cat._next, len(cat._entries))
        with PlanClient("127.0.0.1", server.port) as c:
            with pytest.raises(PlanServerError) as ei:
                c.collect(dict(_shapes(tabs))["join_sort"](12),
                          timeout_ms=300)
            assert ei.value.timeout and ei.value.retryable
        assert (cat._next, len(cat._entries)) == found
        holder.join(10)
        import time
        deadline = time.monotonic() + 5
        while server.active_query_count and time.monotonic() < deadline:
            time.sleep(0.02)
        assert server.active_query_count == 0
        assert len(cat._entries) == found[1]
    finally:
        server.stop()


def test_a_final_oom_in_an_exchange_write_is_an_exec_phase_failure(tabs):
    """... for the circuit breaker: only failures tagged where the plan ran
    on the device reach its classification, and the exchange's write used
    to run, and fail, under ``prepare`` as a bind error (with whatever
    injection the previous collect had installed, not this session's)."""
    from spark_rapids_tpu.server.client import PlanServerError
    server = PlanServer(conf=dict(_SHUFFLED_JOIN)).start()
    breaker = server._server.breaker
    seen, record = [], breaker.record_failure
    breaker.record_failure = lambda e: (seen.append(e), record(e))[1]
    try:
        # every allocation check after the two scans' uploads fails, more
        # often than the retry loop tries: the first exchange write's
        with PlanClient("127.0.0.1", server.port, conf={
                _K + "test.injectOOM.mode": "every-1",
                _K + "test.injectOOM.skipCount": "2",
                _K + "test.injectOOM.oomCount": "100"}) as c:
            with pytest.raises(PlanServerError, match="ShuffleExchangeExec: "
                               "device OOM survived"):
                c.collect(dict(_shapes(tabs))["join_sort"](13))
        assert [type(e).__name__ for e in seen] == ["FinalOOMError"]
        assert getattr(seen[0], "_rtpu_exec_phase", False)
        # a final OOM fails the query and leaves the executor healthy
        with PlanClient("127.0.0.1", server.port) as c:
            assert c.collect(
                dict(_shapes(tabs))["join_sort"](13)).num_rows == 10
    finally:
        from spark_rapids_tpu.memory.retry import oom_injection
        with oom_injection(""):
            pass
        server.stop()
