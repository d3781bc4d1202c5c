"""Adaptive query execution suite (ISSUE 17 acceptance).

The runtime re-planner (plan/adaptive.py + the session/overrides/
exchange/join seams) makes two families of decisions, both of which
must be bit-for-bit invisible in results and never silent in
observability:

  1. cost-fed placement — Session.prepare consults the observed-cost
     store under the planning-cache fingerprint and replays the
     measured CPU-vs-device winner, bypassing the planning cache in
     both directions, with a conf'd exploration floor;
  2. runtime re-planning at exchange boundaries — coalesce tiny
     partitions, split skewed ones into piece ranges, switch a
     shuffled join to broadcast when the build side measures small.

Plus the feeding discipline (a result-cache hit executed nothing and
must not touch the EWMAs), the lint that pins the never-silent
contract, and the fleet legs (cost sync between workers; adaptive on
vs off bit-for-bit through a 2-worker router) in TestAdaptiveFleet.

Tier placement: the differential tests collect real queries (several
multi-second plans each), so they ride the full tier via `slow`;
tier-1 keeps the sub-second gates (the adaptive lint and the presplit
unit) — same split the chaos/serving suites use.
"""

import importlib
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace
from spark_rapids_tpu.exec.join import JoinType
from spark_rapids_tpu.exec.sort import asc
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Count, Sum
from spark_rapids_tpu.plan import adaptive, plancache, table
from spark_rapids_tpu.plan.session import Session


def _load_tool(name):
    tools = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


K = "spark.rapids.tpu."
COST_FED = {
    K + "sql.adaptive.costFeedback.enabled": "true",
    K + "trace.costStore.enabled": "true",
    K + "server.planCache.enabled": "true",
}


@pytest.fixture(autouse=True)
def _fresh_adaptive_state():
    """Cost-fed planning reads three process singletons — the observed
    costs, the planning cache, and the per-fingerprint run counter —
    so every test starts them empty (other suites' fingerprints would
    otherwise advise into these queries)."""
    trace.observed_costs().clear()
    plancache.planning_cache().clear()
    adaptive.clear_runs()
    adaptive.clear_reasons()
    yield
    trace.observed_costs().clear()
    plancache.planning_cache().clear()
    adaptive.clear_runs()
    adaptive.clear_reasons()


def _facts(n=600, seed=5):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": rng.integers(0, 32, n).astype(np.int64),
        "g": rng.integers(0, 8, n).astype(np.int32),
        "v": rng.integers(-100, 100, n).astype(np.int64),
    })
    dim = pa.table({
        "dk": np.arange(32, dtype=np.int64),
        "w": (np.arange(32) % 7).astype(np.int64),
    })
    return fact, dim


def _agg_query(fact, v=0):
    # order_by pins row order: a placement flip (device hash-agg vs the
    # host interpreter) may emit unordered groups in a different order,
    # and the bit-for-bit comparison needs a canonical one
    return (table(fact).where(col("v") > lit(int(v)))
            .group_by("k").agg(Sum(col("v")).alias("s"),
                               Count().alias("c"))
            .order_by("k"))


# ---------------------------------------------------------------------------
# 1. the lint is tier-1: adaptive decisions cannot be silent
# ---------------------------------------------------------------------------


def test_lint_adaptive_clean():
    lint = _load_tool("lint_adaptive")
    assert lint.lint_all() == []


# ---------------------------------------------------------------------------
# 2. cost-fed placement
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cost_fed_replay_of_measured_device_path():
    """Run 1 measures the device path; run 2 of the same shape must
    take the cost-fed path — planning cache BYPASSED (both directions),
    a costFed reason recorded, results bit-for-bit equal."""
    fact, _ = _facts()
    ses = Session(dict(COST_FED))
    t1 = ses.collect(_agg_query(fact))
    fp = ses.last_fingerprint
    assert fp is not None
    assert ses.last_cache["plan"] == "miss"
    ops = trace.observed_costs().get(fp)
    assert adaptive.QUERY_DEVICE_OP in ops       # run 1 fed the store

    snap0 = adaptive.metrics().snapshot()
    hits0 = plancache.metrics().snapshot()["planCacheHitCount"]
    t2 = ses.collect(_agg_query(fact))
    assert t2.equals(t1)
    assert ses.last_cache["plan"] == "bypass: adaptive cost-fed (device)"
    # never replayed FROM the planning cache (the cached entry from run
    # 1 exists but must not serve a cost-fed plan)
    assert plancache.metrics().snapshot()["planCacheHitCount"] == hits0
    snap1 = adaptive.metrics().snapshot()
    assert snap1["costFedPlanCount"] == snap0["costFedPlanCount"] + 1
    assert any(r.startswith("costFed:") for r in ses.adaptive_decisions())


@pytest.mark.slow
def test_cost_fed_flips_to_measured_cpu_winner_bit_for_bit():
    """When the store says the CPU path measured faster, the re-planner
    must force the whole plan to the host — and the host interpreter
    must produce the identical table."""
    fact, _ = _facts()
    ses = Session(dict(COST_FED))
    t1 = ses.collect(_agg_query(fact))
    fp = ses.last_fingerprint
    # seed an (absurdly) fast CPU measurement for this fingerprint: the
    # EWMA comparison in advise() now prefers cpu
    trace.observed_costs().observe(fp, adaptive.QUERY_CPU_OP, wall_ns=1)

    t2 = ses.collect(_agg_query(fact))
    assert t2.equals(t1)
    assert ses.last_cache["plan"] == "bypass: adaptive cost-fed (cpu)"
    reasons = ses.adaptive_decisions()
    assert any("-> cpu" in r for r in reasons), reasons
    # the forced-cpu run executed on the host and fed query:cpu — the
    # EWMA is real now, not just the seeded fiction
    assert trace.observed_costs().get(fp)[adaptive.QUERY_CPU_OP][
        "count"] >= 2


@pytest.mark.slow
def test_exploration_re_measures_the_unmeasured_path():
    """Every exploreEvery-th cost-fed plan of a fingerprint runs the
    OTHER path so its EWMA exists: with only the device path measured
    and exploreEvery=2, the second cost-fed plan must explore cpu —
    after which both paths are measured."""
    fact, _ = _facts()
    conf = dict(COST_FED)
    conf[K + "sql.adaptive.costFeedback.exploreEvery"] = "2"
    ses = Session(conf)
    t1 = ses.collect(_agg_query(fact))          # measures device
    fp = ses.last_fingerprint

    t2 = ses.collect(_agg_query(fact))          # cost-fed run 1: device
    assert t2.equals(t1)
    assert any(r.startswith("costFed:")
               for r in ses.adaptive_decisions())

    snap0 = adaptive.metrics().snapshot()
    t3 = ses.collect(_agg_query(fact))          # cost-fed run 2: explore
    assert t3.equals(t1)
    reasons = ses.adaptive_decisions()
    assert any(r.startswith("explore:") for r in reasons), reasons
    snap1 = adaptive.metrics().snapshot()
    assert snap1["explorationRunCount"] == \
        snap0["explorationRunCount"] + 1
    ops = trace.observed_costs().get(fp)
    assert adaptive.QUERY_CPU_OP in ops          # exploration paid off


@pytest.mark.slow
def test_cost_feedback_off_never_advises():
    fact, _ = _facts()
    conf = dict(COST_FED)
    conf[K + "sql.adaptive.costFeedback.enabled"] = "false"
    ses = Session(conf)
    snap0 = adaptive.metrics().snapshot()
    t1 = ses.collect(_agg_query(fact))
    t2 = ses.collect(_agg_query(fact))
    assert t2.equals(t1)
    assert ses.last_cache["plan"] == "hit"       # normal planning cache
    assert adaptive.metrics().snapshot()["costFedPlanCount"] == \
        snap0["costFedPlanCount"]


# ---------------------------------------------------------------------------
# 3. feeding discipline: cached serves measured nothing
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_result_cache_hit_does_not_feed_cost_store():
    """Satellite regression: a result-cache hit replays stored bytes —
    nothing executed — so neither the per-operator EWMAs nor the
    whole-query query:device wall may move (a stream of cached serves
    would otherwise drag the EWMAs toward zero and flip placement)."""
    fact, _ = _facts()
    conf = dict(COST_FED)
    conf[K + "server.resultCache.enabled"] = "true"
    ses = Session(conf)
    df = _agg_query(fact)
    assert ses.try_cached_result(df) is None     # miss: key armed
    t1 = ses.collect(df)                         # executes + stores
    fp = ses.last_fingerprint
    before = trace.observed_costs().get(fp)
    assert before[adaptive.QUERY_DEVICE_OP]["count"] == 1

    t2 = ses.try_cached_result(df)               # hit: nothing ran
    assert t2 is not None and t2.equals(t1)
    assert ses.last_cache["result"] == "hit"
    after = trace.observed_costs().get(fp)
    assert after == before, \
        "a cached serve fed the observed-cost store"


# ---------------------------------------------------------------------------
# 4. runtime re-planning at exchange boundaries
# ---------------------------------------------------------------------------


def _skew_tables(n=4096, keys=48, seed=17):
    """Key 0 owns ~half the fact rows — after hash partitioning one
    shuffle partition is hot and the rest are thin."""
    rng = np.random.default_rng(seed)
    ks = np.concatenate([
        np.zeros(n // 2, dtype=np.int64),
        rng.integers(1, keys, n - n // 2).astype(np.int64)])
    rng.shuffle(ks)
    fact = pa.table({
        "k": ks,
        "g": rng.integers(0, 8, n).astype(np.int32),
        "v": rng.integers(-100, 100, n).astype(np.int64),
    })
    dim = pa.table({
        "dk": np.arange(keys, dtype=np.int64),
        "w": rng.integers(0, 10, keys).astype(np.int64),
    })
    return fact, dim


def _skew_join(fact, dim, slices=8):
    # batch_rows bounds each slice's batch: piece boundaries are the
    # granularity a skewed partition can split at
    return (table(fact, num_slices=slices,
                  batch_rows=max(1, fact.num_rows // slices))
            .join(table(dim), ["k"], ["dk"], JoinType.INNER)
            .group_by("g")
            .agg(Sum(col("v")).alias("sv"), Sum(col("w")).alias("sw"),
                 Count().alias("c"))
            .order_by("g"))


_SHUFFLED = {
    # pin the planner to the shuffled join: these tests exercise
    # RUNTIME re-planning, not the byte-estimate broadcast
    K + "sql.autoBroadcastJoinThreshold": "0",
    K + "shuffle.partitions": "8",
}


@pytest.mark.slow
def test_skew_split_and_coalesce_bit_for_bit():
    """The hot partition splits into piece-range reader partitions
    (build replicated) while the thin partitions coalesce — and the
    re-planned layout returns exactly the static plan's table."""
    fact, dim = _skew_tables()
    static = Session({**_SHUFFLED,
                      K + "sql.adaptive.enabled": "false"})
    expected = static.collect(_skew_join(fact, dim))

    conf = {**_SHUFFLED,
            K + "sql.adaptive.enabled": "true",
            K + "sql.adaptive.skewJoin.splitRows": "512",
            K + "sql.adaptive.broadcastJoin.enabled": "false"}
    ses = Session(conf)
    snap0 = adaptive.metrics().snapshot()
    got = ses.collect(_skew_join(fact, dim))
    assert got.equals(expected)
    reasons = ses.adaptive_decisions()
    assert any(r.startswith("skewSplit:") for r in reasons), reasons
    assert any(r.startswith("coalesce:") for r in reasons), reasons
    snap1 = adaptive.metrics().snapshot()
    assert snap1["skewSplitCount"] > snap0["skewSplitCount"]
    assert snap1["coalescedPartitionCount"] > \
        snap0["coalescedPartitionCount"]
    assert snap1["replanCount"] > snap0["replanCount"]


@pytest.mark.slow
def test_runtime_broadcast_switch_bit_for_bit():
    """A build side that MEASURES under maxBuildRows switches the
    shuffled join to broadcast at runtime — identical table, decision
    recorded."""
    fact, dim = _facts(n=800)
    q = (lambda: table(fact, num_slices=4,
                       batch_rows=fact.num_rows // 4)
         .join(table(dim), ["k"], ["dk"], JoinType.INNER)
         .group_by("g").agg(Sum(col("v")).alias("sv"),
                            Count().alias("c"))
         .order_by("g"))
    static = Session({**_SHUFFLED,
                      K + "sql.adaptive.enabled": "false"})
    expected = static.collect(q())

    conf = {**_SHUFFLED,
            K + "sql.adaptive.enabled": "true",
            K + "sql.adaptive.broadcastJoin.enabled": "true",
            K + "sql.adaptive.broadcastJoin.maxBuildRows": "100000"}
    ses = Session(conf)
    snap0 = adaptive.metrics().snapshot()
    got = ses.collect(q())
    assert got.equals(expected)
    assert any(r.startswith("broadcastSwitch:")
               for r in ses.adaptive_decisions())
    assert adaptive.metrics().snapshot()["broadcastSwitchCount"] == \
        snap0["broadcastSwitchCount"] + 1


@pytest.mark.slow
def test_broadcast_switch_never_fires_for_right_outer():
    """RIGHT/FULL outer build tails fold to one partition under a
    replicated build — the runtime switch excludes them."""
    fact, dim = _facts(n=500)
    q = (lambda: table(fact, num_slices=4,
                       batch_rows=fact.num_rows // 4)
         .join(table(dim), ["k"], ["dk"], JoinType.RIGHT_OUTER)
         .group_by("w").agg(Count().alias("c"))
         .order_by("w"))
    static = Session({**_SHUFFLED,
                      K + "sql.adaptive.enabled": "false"})
    expected = static.collect(q())
    conf = {**_SHUFFLED,
            K + "sql.adaptive.enabled": "true",
            K + "sql.adaptive.broadcastJoin.enabled": "true",
            K + "sql.adaptive.broadcastJoin.maxBuildRows": "100000"}
    ses = Session(conf)
    got = ses.collect(q())
    assert got.equals(expected)
    assert not any(r.startswith("broadcastSwitch:")
                   for r in ses.adaptive_decisions())


def test_presplit_cuts_oversized_input_before_first_attempt():
    """The skew re-plan's retry seam: an input already measured far
    over the row target splits BEFORE the first device attempt (no
    burned OOM attempts), in order, metric bumped."""
    from spark_rapids_tpu.memory.retry import presplit_inputs
    from spark_rapids_tpu.memory.retry import metrics as retry_metrics

    class FakeInput:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi
            self.rows = hi - lo
            self.name = "fake"

        def split(self, floor_rows):
            if self.rows <= max(floor_rows, 1) or self.rows < 2:
                return None
            mid = self.lo + self.rows // 2
            return [FakeInput(self.lo, mid), FakeInput(mid, self.hi)]

    pre0 = retry_metrics().snapshot()["preSplitCount"]
    out = presplit_inputs(FakeInput(0, 4000), 1000)
    assert len(out) >= 4
    assert all(c.rows <= 1000 for c in out)
    # in-order, gapless: concatenating the chunks re-forms the input
    spans = [(c.lo, c.hi) for c in out]
    assert spans[0][0] == 0 and spans[-1][1] == 4000
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    assert retry_metrics().snapshot()["preSplitCount"] == \
        pre0 + len(out) - 1

    # an input at/under target passes through untouched
    small = FakeInput(0, 1000)
    assert presplit_inputs(small, 1000) == [small]


# ---------------------------------------------------------------------------
# 5. adaptive on vs off: bit-for-bit over the five bench shapes
# ---------------------------------------------------------------------------


def _five_shapes(tmp_path):
    """The five serving-bench shapes (the fleet suite's _shapes), built
    over fresh local tables."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
    n = 2000
    rng = np.random.default_rng(11)
    lineitem = pa.table({
        "k": rng.integers(0, 3, n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        "l_extendedprice": rng.uniform(1.0, 1e5, n),
    })
    sales = pa.table({
        "k": rng.integers(0, 256, n).astype(np.int64),
        "ss_quantity": rng.integers(1, 100, n).astype(np.int64),
    })
    facts = pa.table({
        "k": rng.integers(0, 64, n).astype(np.int64),
        "v": rng.integers(-1000, 1000, n).astype(np.int64),
    })
    dims = pa.table({
        "k": np.arange(64, dtype=np.int64),
        "w": (np.arange(64) % 10).astype(np.int64),
    })
    ppath = str(tmp_path / "part-0.parquet")
    pq.write_table(pa.table({
        "k": rng.integers(0, 100, n).astype(np.int64),
        "v": rng.uniform(-10.0, 10.0, n),
    }), ppath)

    # every builder ends in a TOTAL order (the group key is unique
    # after the agg): adaptive re-plans change partition layout, and an
    # unordered group-by's row order is plan-dependent — the bit-for-bit
    # comparison needs the canonical order, same as the bench legs
    def q1(v):
        return (table(lineitem)
                .where(col("l_quantity") > lit(int(v)))
                .group_by("k")
                .agg(Sum(col("l_extendedprice")).alias("rev"),
                     Count().alias("n"))
                .order_by("k"))

    def hash_agg(v):
        return (table(sales)
                .where(col("ss_quantity") > lit(int(v)))
                .group_by("k").agg(Sum(col("ss_quantity")).alias("q"))
                .order_by("k"))

    def join_sort(v):
        return (table(facts)
                .where(col("v") > lit(int(v)))
                .join(table(dims), ["k"], ["k"])
                .group_by("w").agg(Sum(col("v")).alias("s"))
                .order_by(asc(col("w"))))

    def parquet_scan(v):
        src = ParquetSource([ppath])
        df = DataFrame(LogicalScan((), source=src,
                                   _schema=src.schema()))
        return (df.where(col("k") > lit(int(v)))
                .group_by("k").agg(Count().alias("n"))
                .order_by("k"))

    def exchange(v):
        return (table(facts, num_slices=4)
                .where(col("v") > lit(int(v)))
                .group_by("k").agg(Sum(col("v")).alias("s"))
                .order_by("k"))

    return [("q1_stage", q1), ("hash_agg", hash_agg),
            ("join_sort", join_sort), ("parquet_scan", parquet_scan),
            ("exchange", exchange)]


ADAPTIVE_ON = {
    **COST_FED,
    K + "sql.adaptive.enabled": "true",
    K + "sql.adaptive.broadcastJoin.enabled": "true",
}
ADAPTIVE_OFF = {
    K + "sql.adaptive.enabled": "false",
    K + "sql.adaptive.costFeedback.enabled": "false",
    K + "server.planCache.enabled": "false",
}


@pytest.mark.slow
def test_adaptive_on_off_bit_for_bit_five_shapes(tmp_path):
    """The whole-subsystem contract over the serving-bench shapes:
    with cost feedback AND every runtime re-plan armed, repeated
    collects (the second one cost-fed) equal the all-off plan."""
    shapes = _five_shapes(tmp_path)
    on, off = Session(dict(ADAPTIVE_ON)), Session(dict(ADAPTIVE_OFF))
    fed0 = adaptive.metrics().snapshot()["costFedPlanCount"]
    for name, build in shapes:
        expected = off.collect(build(10))
        for rnd in range(2):
            got = on.collect(build(10))
            assert got.equals(expected), \
                f"shape {name} round {rnd} diverged under adaptive"
    # at least one shape's second collect took the cost-fed path
    assert adaptive.metrics().snapshot()["costFedPlanCount"] > fed0


# ---------------------------------------------------------------------------
# 6. the fleet: costs measured on worker A plan queries on worker B
# ---------------------------------------------------------------------------


FLEET_CONF = {
    **ADAPTIVE_ON,
    # repeat collects must EXECUTE (a cached serve never reaches
    # prepare, so it can neither feed nor consume costs)
    K + "server.resultCache.enabled": "false",
}


@pytest.mark.serving
class TestAdaptiveFleet:

    @pytest.mark.slow
    def test_cost_sync_feeds_worker_b(self, tmp_path):
        """Worker A measures a shape; Router.sync_costs() merges and
        pushes the store fleet-wide; worker B's FIRST collect of that
        shape takes the cost-fed path — observability end to end
        (reply reasons, worker stats, router stats)."""
        from spark_rapids_tpu.server import PlanClient
        from spark_rapids_tpu.server.router import Router
        shapes = _five_shapes(tmp_path)
        build = dict(shapes)["hash_agg"]
        router = Router(workers=2, worker_conf=dict(FLEET_CONF)).start()
        try:
            with PlanClient("127.0.0.1", router.port) as c:
                t1 = c.collect(build(10))
                home = c.last_worker
                assert home
            # push A's measurements everywhere (on-demand sync: the
            # conf'd auto-sync cadence is covered by costSyncEveryPlans)
            synced = router.sync_costs()
            assert synced["workers"] == 2
            assert synced["fingerprints"] >= 1
            assert synced["adopted"] >= 1

            other = next(w for w in router.workers.values()
                         if w.wid != home)
            with PlanClient("127.0.0.1", other.port) as direct:
                t2 = direct.collect(build(10))
                assert t2.equals(t1)
                # B never planned this shape, yet its first plan was
                # cost-fed from A's measurement
                assert direct.last_cache["plan"].startswith(
                    "bypass: adaptive cost-fed"), direct.last_cache
                assert any(r.startswith("costFed:")
                           for r in direct.last_adaptive), \
                    direct.last_adaptive
                st = direct.stats()
                assert st["schemaVersion"] == 5
                assert st["adaptive"]["costFedPlanCount"] >= 1

            rst = router.serving_stats()
            assert rst["schemaVersion"] == 4
            assert rst["adaptive"]["costSyncCount"] == 1
            assert rst["adaptive"]["costEntriesAdopted"] >= 1
        finally:
            router.stop(grace_s=5)
        for w in router.workers.values():
            assert not w.alive()

    @pytest.mark.slow
    def test_fleet_adaptive_on_off_bit_for_bit(self, tmp_path):
        """Adaptive on (cost feedback + runtime re-plans + periodic
        cost sync) vs all-off, five shapes, two rounds each, through a
        2-worker fleet — every table bit-for-bit."""
        from spark_rapids_tpu.server import PlanClient
        from spark_rapids_tpu.server.router import Router
        shapes = _five_shapes(tmp_path)
        oracle = Session(dict(ADAPTIVE_OFF))
        expected = {name: oracle.collect(build(10))
                    for name, build in shapes}
        router = Router(
            workers=2,
            conf={K + "server.fleet.costSync.everyPlans": "3"},
            worker_conf=dict(FLEET_CONF)).start()
        try:
            with PlanClient("127.0.0.1", router.port) as c:
                for rnd in range(2):
                    for name, build in shapes:
                        got = c.collect(build(10))
                        assert got.equals(expected[name]), \
                            f"shape {name} round {rnd} diverged " \
                            f"through the adaptive fleet"
            rst = router.serving_stats()
            # 20 plans at everyPlans=3 -> the auto-sync cadence fired
            assert rst["adaptive"]["costSyncCount"] >= 1
            assert rst["adaptive"]["costSyncEveryPlans"] == 3
        finally:
            router.stop(grace_s=5)
        for w in router.workers.values():
            assert not w.alive()
