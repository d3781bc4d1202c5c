"""Planner→mesh integration: PLANNED queries lowered onto the SPMD mesh
data plane (reference shape: GpuShuffleExchangeExecBase.scala:262 — the
planner's exchanges define the distributed dataflow).

The Session with shuffle.mode=ICI must (a) produce results equal to the
CPU interpreter, and (b) actually execute through MeshStageExec —
mesh_exchange/mesh_broadcast collectives — not the host-mediated loop.
"""

import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.join import JoinType
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Average, Count, Max, \
    Min, Sum
from spark_rapids_tpu.plan import Session, table

from harness.asserts import assert_tables_equal, rows_of
from harness.data_gen import IntegerGen, LongGen, StringGen, gen_table

ICI = {"spark.rapids.tpu.shuffle.mode": "ICI"}

FACT = gen_table([("k", IntegerGen(min_val=0, max_val=40)),
                  ("g", IntegerGen(min_val=0, max_val=6)),
                  ("v", LongGen(min_val=-1000, max_val=1000))],
                 n=1200, seed=400)
DIM = gen_table([("dk", IntegerGen(min_val=0, max_val=40, null_prob=0.0)),
                 ("w", LongGen(min_val=0, max_val=9))], n=41, seed=401)


def _ici_vs_cpu(df_fn, require_mesh=True, ignore_order=True):
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session(ICI)
    expected = cpu.collect(df_fn())
    actual = tpu.collect(df_fn())
    if require_mesh:
        names = tpu.executed_exec_names()
        assert any("MeshStage" in n for n in names), names
    assert_tables_equal(actual, expected, ignore_order=ignore_order)
    return tpu


def test_planned_groupby_on_mesh():
    ses = _ici_vs_cpu(lambda: table(FACT).group_by("k").agg(
        Sum(col("v")).alias("s"), Count(col("v")).alias("c"),
        Min(col("v")).alias("mn"), Max(col("v")).alias("mx")))
    assert "MeshStageExec" in ses.executed_exec_names()


def test_planned_filter_project_groupby_on_mesh():
    _ici_vs_cpu(lambda: table(FACT)
                .where(col("v") > lit(0))
                .select(col("k"), (col("v") * lit(2)).alias("v2"))
                .group_by("k").agg(Sum(col("v2")).alias("s")))


def test_planned_global_agg_on_mesh():
    _ici_vs_cpu(lambda: table(FACT).group_by().agg(
        Sum(col("v")).alias("s"), Count().alias("c")))


def test_planned_join_groupby_on_mesh():
    """The VERDICT r1 done-criterion: a planned join+groupby query runs
    through mesh_broadcast + mesh_exchange on the 8-device mesh and matches
    the interpreter."""
    def q():
        return (table(FACT)
                .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
                .group_by("g")
                .agg(Sum(col("w")).alias("sw"), Count().alias("c")))
    ses = _ici_vs_cpu(q)
    lowered = next(e for e in [ses.last_plan] if e is not None)
    assert "mesh_broadcast(all_gather)" in lowered.lowered, lowered.lowered
    assert "mesh_exchange(all_to_all)" in lowered.lowered, lowered.lowered


def test_planned_left_outer_join_on_mesh():
    small_dim = gen_table([("dk", IntegerGen(min_val=0, max_val=20)),
                           ("w", LongGen())], n=15, seed=402)
    _ici_vs_cpu(lambda: table(FACT).join(
        table(small_dim), ["k"], ["dk"], JoinType.LEFT_OUTER))


def test_unsupported_plan_falls_back_to_host_path():
    """Sorts have no mesh lowering (v1): the query still answers correctly
    through the host exchanges, with no MeshStageExec in the plan."""
    ses = _ici_vs_cpu(lambda: table(FACT).order_by("v").limit(17),
                      require_mesh=False, ignore_order=False)
    assert not any("MeshStage" in n for n in ses.executed_exec_names())


def test_mesh_join_overflow_retries():
    """A high-fanout join must survive the static-capacity overflow by
    re-lowering with a doubled expansion factor."""
    left = pa.table({"k": pa.array([1] * 300, pa.int32()),
                     "x": pa.array(range(300), pa.int64())})
    right = pa.table({"k2": pa.array([1] * 40, pa.int32()),
                      "y": pa.array(range(40), pa.int64())})
    def q():
        return table(left).join(table(right), ["k"], ["k2"], JoinType.INNER)
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session(ICI)
    expected = cpu.collect(q())
    actual = tpu.collect(q())   # 300×40 pairs ≫ 2× stream capacity
    assert_tables_equal(actual, expected, ignore_order=True)


# ---------------------------------------------------------------------------
# Round-3 widened lowerings: shuffled co-partitioned joins, chained
# exchanges, global sort (splitter range exchange), TopN
# ---------------------------------------------------------------------------

NO_BROADCAST = dict(ICI)
NO_BROADCAST["spark.rapids.tpu.sql.autoBroadcastJoinThreshold"] = 0
# (the adaptive run-time switch to a broadcast join stays ON, its default:
# planning runs nothing, so the lowering sees the join as planned)


def _shuffled_vs_cpu(df_fn, ignore_order=True, require_exchanges=0):
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session(NO_BROADCAST)
    expected = cpu.collect(df_fn())
    actual = tpu.collect(df_fn())
    names = tpu.executed_exec_names()
    assert any("MeshStage" in n for n in names), names
    if require_exchanges:
        stage = tpu.last_plan
        n_ex = stage.lowered.count("mesh_exchange(all_to_all)")
        assert n_ex >= require_exchanges, (n_ex, stage.lowered)
    assert_tables_equal(actual, expected, ignore_order=ignore_order)
    return tpu


def test_planned_shuffled_join_on_mesh():
    """Both sides hash-exchanged on the join keys, local probe per device
    (reference: GpuShuffledHashJoinExec:85)."""
    _shuffled_vs_cpu(lambda: table(FACT).join(table(DIM), ["k"], ["dk"],
                                              JoinType.INNER),
                     require_exchanges=2)


def test_planned_shuffled_right_outer_join_on_mesh():
    """RIGHT OUTER is legal on the shuffled path: co-partitioning makes
    per-device unmatched-build tails exact."""
    _shuffled_vs_cpu(lambda: table(FACT).join(table(DIM), ["k"], ["dk"],
                                              JoinType.RIGHT_OUTER),
                     require_exchanges=2)


def test_planned_join_agg_sort_chain_on_mesh():
    """The q72 shape: shuffled join + group-by + global sort — >=3 chained
    exchanges in ONE SPMD program."""
    from spark_rapids_tpu.exec.sort import desc

    def q():
        return (table(FACT)
                .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
                .group_by("g")
                .agg(Sum(col("v")).alias("sv"), Count().alias("c"))
                .order_by(desc(col("sv"))))
    _shuffled_vs_cpu(q, ignore_order=False, require_exchanges=3)


def test_planned_global_sort_on_mesh():
    """Splitter-routed range exchange + local sort: output order must
    equal the CPU interpreter's EXACTLY (cross-device total order)."""
    from spark_rapids_tpu.exec.sort import asc, desc

    def q():
        return table(FACT).order_by(desc(col("v")), asc(col("k")))
    ses = _shuffled_vs_cpu(q, ignore_order=False, require_exchanges=1)
    assert "MeshStageExec" in ses.executed_exec_names()


def _join_under_group_by():
    return (table(FACT)
            .join(table(DIM), ["k"], ["dk"], JoinType.INNER)
            .group_by("g")
            .agg(Sum(col("v")).alias("sv"), Count().alias("c")))


def test_mesh_giveway_reason_is_visible():
    """ICI shuffle mode asked for the mesh data plane; when lowering gives
    way to the host-mediated exchange the session SAYS so, with the reason,
    in executed_exec_names() and explain() — never a silent switch. Here a
    PLANNED broadcast build (the 41-row side) under FULL OUTER, which no
    device of the mesh can finish alone."""
    def q():
        return table(FACT).join(table(DIM), ["k"], ["dk"],
                                JoinType.FULL_OUTER)
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session(ICI)
    # explain() plans nothing: the give-way is a fact of a collect
    assert "gave way" not in tpu.explain(q())
    assert tpu.last_mesh_giveway is None
    actual = tpu.collect(q())
    names = tpu.executed_exec_names()
    assert not any("MeshStage" in n for n in names), names
    assert tpu.last_mesh_giveway == \
        "JoinType.FULL_OUTER needs global matched-build state under a " \
        "replicated build"
    assert f"MeshGiveWay[{tpu.last_mesh_giveway}]" in names
    assert "last collect: mesh lowering gave way to the host-mediated " \
        "exchange: JoinType.FULL_OUTER needs" in tpu.explain(q())
    assert_tables_equal(actual, cpu.collect(q()), ignore_order=True)
    # a plan that does lower reports no give-way
    lowers = Session(NO_BROADCAST)
    lowers.collect(_join_under_group_by())
    assert lowers.last_mesh_giveway is None
    assert "MeshStageExec" in lowers.executed_exec_names()


def test_the_run_time_broadcast_switch_left_on_does_not_stop_the_lowering():
    """Until ISSUE 33, planning the group-by above a shuffled join RAN the
    join's 41-row build side to count partitions; the adaptive switch then
    turned the join into a broadcast one before the lowering saw it, and
    the session gave way (``MeshGiveWay[broadcast join without broadcast
    exchange child]``) unless ``adaptive.broadcastJoin.enabled`` was
    pinned off. Planning runs nothing now: with the switch at its default
    (on) the plan lowers to one mesh stage."""
    conf = dict(NO_BROADCAST)
    assert "spark.rapids.tpu.sql.adaptive.broadcastJoin.enabled" not in conf
    conf["spark.rapids.tpu.sql.adaptive.broadcastJoin.enabled"] = True
    cpu = Session({"spark.rapids.tpu.sql.enabled": False})
    tpu = Session(conf)
    actual = tpu.collect(_join_under_group_by())
    assert tpu.last_mesh_giveway is None
    assert tpu.executed_exec_names() == ["MeshStageExec"]
    assert tpu.last_plan.lowered.count("mesh_exchange(all_to_all)") >= 3
    assert_tables_equal(actual, cpu.collect(_join_under_group_by()),
                        ignore_order=True)
