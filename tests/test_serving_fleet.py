"""Serving-fleet differential suite (ISSUE 12 acceptance).

A real Router in front of real plan-server worker SUBPROCESSES (each a
full engine: own planning cache, own XLA compile cache, shared
persistent result tier), driven by threaded ``PlanClient``s:

  1. bit-for-bit: every (client, shape, round) result through the fleet
     equals the in-process single-engine oracle;
  2. failover: a worker SIGKILLed mid-query is promoted suspect→dead
     and the plan completes on the surviving worker — zero failed
     queries;
  3. rolling restart under load: every worker drained + replaced while
     clients keep querying — zero dropped queries, nonzero
     persistent-tier rehydration hits on the replacements;
  4. invalidation: drop_table through the router empties every tier
     (the stale-serve-after-drop regression: drop reaching worker A
     must also kill the entry worker B could rehydrate from disk);
  5. zero leaks: no sessions, no catalog pins, no worker processes left.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.sort import asc
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.memory.catalog import device_budget
from spark_rapids_tpu.plan import table
from spark_rapids_tpu.plan.session import Session
from spark_rapids_tpu.server import PlanClient
from spark_rapids_tpu.server.client import PlanServerError
from spark_rapids_tpu.server.router import Router

pytestmark = pytest.mark.serving

N = 2000


@pytest.fixture(scope="module")
def tabs(tmp_path_factory):
    import pyarrow.parquet as pq
    rng = np.random.default_rng(11)
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
    pdir = tmp_path_factory.mktemp("fleet_pq")
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


def _facts_query(tabs, v=5):
    return (table(tabs["facts"]).where(col("v") > lit(int(v)))
            .group_by("k").agg(Sum(col("v")).alias("s")))


def _assert_no_worker_leak(router):
    for w in router.workers.values():
        assert not w.alive(), f"worker {w.wid} outlived router.stop()"


# ---------------------------------------------------------------------------
# 1. bit-for-bit differential, threaded clients x five shapes x 2 workers
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_differential_bit_for_bit(tabs):
    pins0 = device_budget().total_pinned()
    router = Router(workers=2).start()
    shapes = _shapes(tabs)
    results = {}
    errors = []
    lock = threading.Lock()

    def worker(ci):
        try:
            with PlanClient("127.0.0.1", router.port,
                            unavailable_retries=3) as c:
                for r in range(2):
                    for name, build in shapes:
                        t = c.collect(build(10 + r * 7))
                        with lock:
                            results[(ci, name, r)] = t
        except Exception as e:
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")

    try:
        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []

        # oracle: the in-process single engine, caches off
        ses = Session({"spark.rapids.tpu.server.planCache.enabled":
                       "false"})
        for r in range(2):
            for name, build in shapes:
                oracle = ses.collect(build(10 + r * 7))
                for ci in range(3):
                    got = results[(ci, name, r)]
                    assert got.equals(oracle), \
                        f"client {ci} shape {name} round {r} diverged " \
                        f"through the fleet"

        # routing is shape-affine: each shape's plans all landed on ONE
        # worker (the warm-cache pinning claim), and the fleet spread
        # at least two shapes across two workers
        stats = router.serving_stats()
        per_worker = stats["routing"]["perWorkerPlans"]
        assert sum(per_worker.values()) == 3 * 2 * len(shapes)
        assert stats["routing"]["failovers"] == 0

        deadline = time.monotonic() + 5.0
        while router.active_sessions and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.active_sessions == 0
    finally:
        router.stop(grace_s=5)
    _assert_no_worker_leak(router)
    assert device_budget().total_pinned() == pins0


def test_routing_is_deterministic_per_shape(tabs):
    """Same shape (different literals) → same worker; the repeat run
    hits the home worker's result/planning caches."""
    router = Router(workers=2).start()
    try:
        with PlanClient("127.0.0.1", router.port) as c:
            workers_seen = set()
            for v in (5, 15, 25, 5):
                c.collect(_facts_query(tabs, v))
            st = c.stats()
            per = {k: v for k, v in
                   st["routing"]["perWorkerPlans"].items() if v}
            workers_seen = set(per)
            assert len(workers_seen) == 1, \
                f"one shape spread over workers: {per}"
            # the literal-repeat (v=5 twice) was served from the home
            # worker's result cache
            assert c.last_cached
    finally:
        router.stop(grace_s=5)


# ---------------------------------------------------------------------------
# 2. kill a worker mid-query: suspect/dead + transparent failover
# ---------------------------------------------------------------------------


def test_kill_worker_mid_query_failover(tabs):
    router = Router(
        workers=2,
        worker_conf={
            "spark.rapids.tpu.server.test.collectDelayMs": "600",
            "spark.rapids.tpu.server.resultCache.enabled": "false",
        }).start()
    try:
        with PlanClient("127.0.0.1", router.port) as c:
            oracle = c.collect(_facts_query(tabs))
            st = router.serving_stats()
            home = max(st["routing"]["perWorkerPlans"],
                       key=st["routing"]["perWorkerPlans"].get)

            def killer():
                time.sleep(0.25)      # lands inside the delayed collect
                router.workers[home].proc.kill()

            th = threading.Thread(target=killer, daemon=True)
            th.start()
            got = c.collect(_facts_query(tabs))   # must NOT raise
            th.join()
            assert got.equals(oracle)
        st = router.serving_stats()
        assert st["routing"]["failovers"] >= 1
        states = {w["id"]: w["state"] for w in st["fleet"]["workers"]}
        assert states[home] == "dead"       # promoted, not suspect
        # a replacement resurrects the slot and serves again
        router.replace_worker(home)
        with PlanClient("127.0.0.1", router.port) as c:
            assert c.collect(_facts_query(tabs)).equals(oracle)
    finally:
        router.stop(grace_s=5)
    _assert_no_worker_leak(router)


class _DyingProc:
    """A worker process whose sockets are already closed and which the
    kernel lets be reaped only ``after`` seconds from now (never, for
    None): what a killed process is to the peer that saw the reset."""

    def __init__(self, after):
        self.dead_at = None if after is None else time.monotonic() + after

    def poll(self):
        gone = self.dead_at is not None and time.monotonic() >= self.dead_at
        return -9 if gone else None

    def wait(self, timeout):
        import subprocess
        left = float("inf") if self.dead_at is None \
            else self.dead_at - time.monotonic()
        if left > timeout:
            time.sleep(timeout)
            raise subprocess.TimeoutExpired("worker", timeout)
        time.sleep(max(left, 0.0))
        return -9


@pytest.mark.parametrize("error,reapable_after,state,at_least,under", [
    # the reset outruns the reaping: the corpse must not stay in the ring
    (ConnectionResetError(), 0.15, "dead", 0.1, 0.45),
    # a live worker that dropped one connection: suspect, after the wait
    (ConnectionResetError(), None, "suspect", 0.45, 2.0),
    # alive but slow: read as it is, no wait on the failover path
    (TimeoutError(), None, "suspect", 0.0, 0.1),
    (None, 0.15, "suspect", 0.0, 0.1),
])
def test_note_failure_waits_for_the_reaping_only_after_a_reset(
        tmp_path, error, reapable_after, state, at_least, under):
    from spark_rapids_tpu.server.router import WorkerHandle
    router = Router(workers=0, worker_conf={      # never started
        "spark.rapids.tpu.server.fleet.resultStore.path": str(tmp_path)})
    w = WorkerHandle("w0", {}, "127.0.0.1")
    w.proc = _DyingProc(reapable_after)
    t0 = time.monotonic()
    router.note_failure(w, error)
    took = time.monotonic() - t0
    assert w.state == state and w.failures == 1
    assert at_least <= took < under, took


# ---------------------------------------------------------------------------
# 3. rolling restart under load: zero dropped queries + rehydration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_rolling_restart_under_load_zero_drops(tabs):
    router = Router(workers=2).start()
    stop = threading.Event()
    errors = []
    counts = [0] * 4
    lock = threading.Lock()
    oracle = Session({"spark.rapids.tpu.server.planCache.enabled":
                      "false"}).collect(_facts_query(tabs))

    def client_loop(ci):
        try:
            with PlanClient("127.0.0.1", router.port,
                            unavailable_retries=8,
                            retry_budget_ms=60000) as c:
                while not stop.is_set():
                    got = c.collect(_facts_query(tabs))
                    if not got.equals(oracle):
                        raise AssertionError("diverged under restart")
                    with lock:
                        counts[ci] += 1
                    time.sleep(0.01)
        except Exception as e:
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")

    try:
        threads = [threading.Thread(target=client_loop, args=(i,),
                                    daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        # let the cache warm, then restart the whole fleet under load
        time.sleep(1.0)
        report = router.rolling_restart(grace_s=10)
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert errors == [], errors
        assert all(c > 0 for c in counts), counts
        assert report["drained"] == 2 and report["died_mid_drain"] == 0
        assert all(w["generation"] == 2 for w in report["workers"])
        # rehydration: a replacement served at least one result straight
        # from the persistent tier (its memory cache started empty)
        st = router.serving_stats()
        rehydrated = sum(
            (ws or {}).get("counters", {}).get("resultStoreHitCount", 0)
            for ws in st["workers"].values())
        assert rehydrated > 0, \
            f"no persistent-tier rehydration after restart: {st}"
    finally:
        stop.set()
        router.stop(grace_s=5)
    _assert_no_worker_leak(router)


# ---------------------------------------------------------------------------
# 4. invalidation across tiers and workers (the stale-drop regression)
# ---------------------------------------------------------------------------


def test_drop_table_invalidates_every_tier(tabs):
    """Drop through the router: the ack aggregates per-worker memory
    invalidations PLUS the shared persistent tier, and afterwards NO
    tier still holds the entry — a restarted worker B must not be able
    to rehydrate a result whose table worker A saw dropped."""
    router = Router(workers=2).start()
    try:
        with PlanClient("127.0.0.1", router.port) as c:
            # register the scan table under an explicit name: the plan's
            # scan dedupes against it by identity, so the drop below
            # names exactly the table the cached result depends on
            c.register_table("fleet_drop_t", tabs["facts"])
            df = (table(tabs["facts"]).where(col("v") > lit(5))
                  .group_by("k").agg(Sum(col("v")).alias("s")))
            r1 = c.collect(df)
            r2 = c.collect(df)
            assert c.last_cached and r2.equals(r1)
            # the entry exists in the home worker's memory AND on disk
            st = c.stats()
            persisted = [
                (ws or {}).get("resultCache", {})
                .get("persistent", {}).get("entries", 0)
                for ws in st["workers"].values()]
            assert max(persisted) >= 1
            ack = c.drop_table("fleet_drop_t")
            assert ack["invalidated"] >= 2, ack   # memory + disk at least
            assert ack["workers"] == 2
            # every tier is now empty: nothing to rehydrate anywhere
            st = c.stats()
            for wid, ws in st["workers"].items():
                assert ws["resultCache"]["entries"] == 0, (wid, ws)
                assert ws["resultCache"]["persistent"]["entries"] == 0
            # the same query (table re-ships transparently) recomputes
            # and still matches
            r3 = c.collect(df)
            assert r3.equals(r1)
            assert not c.last_cached
    finally:
        router.stop(grace_s=5)


def test_direct_worker_drop_ack_covers_persistent_tier(tabs):
    """The satellite fix at the single-server level: a drop_table sent
    to ONE worker directly still reports (and performs) the persistent
    tier's invalidation — the ack is authoritative beyond its own
    memory."""
    router = Router(workers=2).start()
    try:
        with PlanClient("127.0.0.1", router.port) as c:
            df = _facts_query(tabs, 7)
            c.collect(df)
            c.collect(df)
            assert c.last_cached
            st = router.serving_stats()
            home = max(st["routing"]["perWorkerPlans"],
                       key=st["routing"]["perWorkerPlans"].get)
        # now talk to the OTHER worker directly (its memory never saw
        # this query): its drop must still clear the shared disk tier
        other = next(w for w in router.workers.values()
                     if w.wid != home)
        with PlanClient("127.0.0.1", other.port) as direct:
            ack = direct.register_table("t0", tabs["facts"])
            ack = direct.drop_table("t0")
            assert ack["invalidated"] >= 1, ack     # the disk entry
        from spark_rapids_tpu.plan.resultstore import \
            PersistentResultStore
        store = PersistentResultStore(router.store_path)
        assert store.stats()["entries"] == 0
    finally:
        router.stop(grace_s=5)


# ---------------------------------------------------------------------------
# 5. tenant admission through the fleet
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tenant_quota_structured_unavailable_and_retry(tabs):
    router = Router(
        workers=1,
        conf={"spark.rapids.tpu.server.fleet.tenant.maxConcurrent": "1"},
        worker_conf={
            "spark.rapids.tpu.server.test.collectDelayMs": "400",
            "spark.rapids.tpu.server.resultCache.enabled": "false",
        }).start()
    tconf = {"spark.rapids.tpu.server.fleet.tenantId": "acme"}
    df = _facts_query(tabs)
    try:
        # burst WITHOUT retries: over-quota plans get the structured
        # reply, not a hang and not a dropped connection
        errs = []
        done = []

        def one(i):
            try:
                with PlanClient("127.0.0.1", router.port,
                                conf=tconf) as c:
                    done.append(c.collect(df))
            except PlanServerError as e:
                errs.append(e)

        ths = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert len(done) >= 1
        assert errs and all(e.unavailable and e.retryable and
                            e.retry_after_ms for e in errs)
        # WITH the client retry budget the same burst fully completes
        done2, errs2 = [], []

        def two(i):
            try:
                with PlanClient("127.0.0.1", router.port, conf=tconf,
                                unavailable_retries=6) as c:
                    done2.append(c.collect(df))
            except Exception as e:
                errs2.append(e)

        ths = [threading.Thread(target=two, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert errs2 == [] and len(done2) == 3
        ten = router.serving_stats()["tenants"]["acme"]
        assert ten["rejectedQuota"] >= 1
        assert ten["inFlight"] == 0
    finally:
        router.stop(grace_s=5)


def test_weighted_fair_queueing_unit():
    """Deterministic WFQ: a 3:1 weight split grants contended slots in
    ~3:1 proportion (stride scheduling over virtual time)."""
    from spark_rapids_tpu.server.router import TenantAdmission
    adm = TenantAdmission({"heavy": 3.0, "light": 1.0}, quota=0,
                          timeout_ms=10000)
    adm.gate("w0", 1)
    adm.acquire("heavy", "w0")          # saturate the single slot
    grants = []
    lock = threading.Lock()

    def waiter(tenant):
        adm.acquire(tenant, "w0")
        with lock:
            grants.append(tenant)

    threads = [threading.Thread(target=waiter,
                                args=("heavy" if i % 2 == 0 else
                                      "light",), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    time.sleep(0.2)                     # all 8 queued behind the slot
    for _ in range(9):
        adm.release("w0")
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=5)
    assert len(grants) == 8
    # of the first 4 grants, heavy (weight 3) got at least 3
    assert grants[:4].count("heavy") >= 3, grants
    snap = adm.snapshot()
    assert snap["heavy"]["admitted"] == 5   # 1 initial + 4 waiters
    assert snap["light"]["admitted"] == 4


@pytest.mark.slow
def test_admission_timeout_is_structured_unavailable(tabs):
    router = Router(
        workers=1,
        conf={"spark.rapids.tpu.server.fleet.admissionTimeoutMs": "200",
              "spark.rapids.tpu.server.fleet.maxInflightPerWorker": "1"},
        worker_conf={
            "spark.rapids.tpu.server.test.collectDelayMs": "1500",
            "spark.rapids.tpu.server.resultCache.enabled": "false",
        }).start()
    df = _facts_query(tabs)
    try:
        errs, done = [], []

        def one(i):
            try:
                with PlanClient("127.0.0.1", router.port) as c:
                    done.append(c.collect(df))
            except PlanServerError as e:
                errs.append(e)

        ths = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert len(done) >= 1
        assert errs and all(e.unavailable and e.retry_after_ms
                            for e in errs)
        ten = router.serving_stats()["tenants"]["default"]
        assert ten["rejectedTimeout"] >= 1
    finally:
        router.stop(grace_s=5)


# ---------------------------------------------------------------------------
# smoke-tier mini fleet job (~20s): loadbench --fleet with tiny params
# ---------------------------------------------------------------------------


@pytest.mark.smoke
def test_mini_fleet_loadbench_smoke():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import server_loadbench
    finally:
        sys.path.pop(0)
    rep = server_loadbench.run_fleet_load(
        clients=4, rounds=3, rows=1000, fleet=2,
        tenants=2, unique_fraction=0.25)
    assert rep["queries"] == 4 * 3 * 4
    assert rep["errors"] == 0
    assert rep["leaked_sessions"] == 0
    # shape affinity: plans landed deterministically; counters add up
    assert sum(rep["per_worker_qps"]["plans"].values()) \
        == rep["queries"]
    assert rep["router_overhead_ms"]["n"] > 0
    assert set(rep["tenants"]) == {"t0", "t1"}


# ---------------------------------------------------------------------------
# Catalyst bridge through the fleet (ISSUE 14 satellite): a fixture
# translated client-side routes through the router on the plandoc shape
# fingerprint like any native plan, bit-for-bit vs the native twin
# ---------------------------------------------------------------------------


@pytest.mark.smoke
def test_catalyst_fixture_vs_native_through_router(tabs):
    from harness import bridge_corpus as BC
    router = Router(workers=2).start()
    try:
        with PlanClient("127.0.0.1", router.port) as c:
            text = BC.load_fixture("bench_hash_agg", "/nonexistent")
            translated = c.collect_catalyst(
                text, tables={"sales": tabs["sales"]})
            worker_a = c.last_worker
            native = BC.NATIVE_BUILDERS["bench_hash_agg"](tabs, "")
            expected = c.collect(native)
            assert translated.equals(expected)
            assert worker_a, "router must report the serving worker"
            # repeat translation routes to the SAME worker: the router
            # fingerprints the translated plandoc exactly like a native
            # plan, so the bridge inherits shape-affinity caching
            c.collect_catalyst(text, tables={"sales": tabs["sales"]})
            assert c.last_worker == worker_a
    finally:
        router.stop(grace_s=5)
        _assert_no_worker_leak(router)
