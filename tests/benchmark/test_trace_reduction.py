"""The reduction from a profiler trace to ``busy_s``, ``device_idle_pct``,
``programs_per_query`` and the breakdown: on hand-made intervals whose
answers are known, and on a small trace recorded on a TPU v5e."""

import json
import os
import types

import pytest

from benchlib import BENCH
from rtbench import loader, xplane

MS = 1e6        # nanoseconds


def _trace():
    # device 0: [0,10) [5,20) overlap -> 20 ms; [50,60) -> 10 ms; total 30
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 0 * MS, 10 * MS),
           ("%sort.2 = f32[8]{0} sort(%p)", 5 * MS, 15 * MS),
           ("%fusion.1 = f32[8]{0} fusion(%p)", 50 * MS, 10 * MS)]
    modules = [("jit_a(1)", 0 * MS, 20 * MS), ("jit_b(2)", 50 * MS, 10 * MS)]
    host = {"worker": [("ExecuteOnStream", 0 * MS, 22 * MS),
                       ("PjRtCompile", 21 * MS, 28 * MS),
                       ("inner.load", 25 * MS, 20 * MS)],
            "other": [("short", 30 * MS, 1 * MS)]}
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host,
            "extent_ns": 100 * MS}


def test_union_merges_overlaps():
    assert xplane.union([(5, 20), (0, 10), (50, 60), (60, 61)]) == [
        [0, 20], [50, 61]]


def test_known_intervals_give_known_numbers():
    r = xplane.reduce(_trace())
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["program_executions"] == 2
    # an operation is named by the program execution that holds it
    assert r["device_ops"] == [["jit_a/sort.2", pytest.approx(0.015)],
                               ["jit_a/fusion.1", pytest.approx(0.010)],
                               ["jit_b/fusion.1", pytest.approx(0.010)]]
    # one gap, 20..50 ms: PjRtCompile covers 28 ms of it and inner.load,
    # inside it, 20 ms; of those over half the gap, the innermost names it
    assert r["idle_gaps"] == [["inner.load", pytest.approx(0.030)]]


def test_metric_readers_on_the_known_trace():
    r = xplane.reduce(_trace())
    rec = types.SimpleNamespace(error=None, query=0)
    run = {"trace": r, "peaks": loader.peaks("TPU v5 lite"),
           "slice": {"records": [rec, rec]},
           "touched_bytes": [819e9 * 0.003]}
    assert loader.metric("device_idle_pct").read(run) == pytest.approx(70.0)
    assert loader.metric("programs_per_query").read(run) == 1.0
    # two queries of 3 ms least each over 30 ms busy
    assert loader.metric("hbm_roofline_pct").read(run) == pytest.approx(20.0)


def test_a_gap_nothing_covers_is_unattributed():
    t = _trace()
    t["host"] = {"worker": [("brief", 21 * MS, 2 * MS)]}
    assert xplane.reduce(t)["idle_gaps"][0][0] == "unattributed"


def test_a_gap_that_begins_before_its_leaf_is_still_the_leafs():
    """The pull that holds the leaf covers all of the gap and the leaf
    nine tenths: the leaf names it (PERF.md section 7, PR 28's reading of
    ``tpcds_sf1.q3``: gaps inside ``scan.h2d`` read ``HashJoinExec``)."""
    t = _trace()
    t["host"] = {"rtpu-q-0": [("query", 0 * MS, 90 * MS),
                              ("HashJoinExec", 10 * MS, 70 * MS),
                              ("scan.h2d", 23 * MS, 30 * MS),
                              ("brief", 30 * MS, 10 * MS)]}
    assert xplane.reduce(t)["idle_gaps"] == [["scan.h2d",
                                              pytest.approx(0.030)]]


def test_two_host_lines_of_one_name_are_both_read():
    """Two threads called ``python`` (and two with no name): the event that
    names the gap sits on the first, which the second used to overwrite."""
    t = _trace()
    t["host"] = xplane.host_lines([
        ("python", [("from_arrow", 19 * MS, 32 * MS)]),
        ("", [("noise", 0 * MS, 1 * MS)]),
        ("python", [("tick", 90 * MS, 1 * MS)]),
        ("", [("noise", 95 * MS, 1 * MS)]),
        ("python", [])])
    assert list(t["host"]) == ["python", "", "python#2", "#2", "python#3"]
    assert t["host"]["python"] == [("from_arrow", 19 * MS, 32 * MS)]
    assert xplane.reduce(t)["idle_gaps"][0][0] == "from_arrow"


def test_a_trace_that_does_not_say_its_length_is_refused():
    t = _trace()
    t["extent_ns"] = None
    with pytest.raises(ValueError):
        xplane.reduce(t)


def test_nothing_on_the_device_reads_as_nothing():
    assert xplane.reduce({"devices": [], "host": {},
                          "extent_ns": 1e9}) is None
    empty = {"devices": [{"name": "d", "ops": [], "modules": []}],
             "host": {}, "extent_ns": 1e9}
    assert xplane.reduce(empty) is None
    run = {"trace": None, "slice": None, "peaks": None}
    for name in ("device_idle_pct", "programs_per_query",
                 "hbm_roofline_pct"):
        assert loader.metric(name).read(run) is None


RECORDED = os.path.join(BENCH, "recorded", "tpu_v5e_small.xplane.pb")


def test_recorded_tpu_trace_reads_as_it_did_when_recorded():
    with open(os.path.join(BENCH, "recorded", "tpu_v5e_small.json")) as f:
        want = json.load(f)
    trace = xplane.load(RECORDED)
    assert list(trace["host"]) == want["host_lines"]
    assert [d["name"] for d in trace["devices"]] == want["devices"]
    # the slice's length is the trace's own, profiler start to stop
    assert trace["extent_ns"] == want["extent_ns"]
    r = xplane.reduce(trace)
    assert r["program_executions"] == want["program_executions"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert [n for n, _ in r["device_ops"]] == want["top_ops"]
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        want["device_idle_pct"], rel=1e-9)
    # an independent count of the same busy time: a sweep over a fine grid
    ops = trace["devices"][0]["ops"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    step = (hi - lo) / 200000
    import numpy as np
    covered = np.zeros(200000, dtype=bool)
    for _, s, d in ops:
        covered[int((s - lo) / step):int(np.ceil((s + d - lo) / step))] = True
    assert covered.sum() * step / 1e9 == pytest.approx(r["busy_s"], rel=0.02)
