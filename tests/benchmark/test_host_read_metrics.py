"""Every idle stretch of a device trace put down to what the host was doing
(``rtbench/idle.attribute``), and the three readers of the engine's host
reads: ``host_reads_per_query`` and ``host_read_wait_ms`` over the spans'
``hostReads`` / ``hostReadUs``, ``idle_after_read_pct`` over the reduced
trace's ``idle_by_host``. Each on hand-made input whose answer is known, on
a trace recorded on a TPU v5e, and on the record of a program that lacks
what it reads, where it answers nothing and not 0. ``loader.metric`` finds
a reader by the name ``BENCHMARK.json`` lists it under."""

import gzip
import json
import os
import random
import types

import pytest

from benchlib import REPO
from rtbench import idle, loader, xplane

MS = 1e6        # nanoseconds
US = 1000       # microseconds a millisecond
RECORDED = os.path.join(REPO, "benchmarks", "recorded")


def _trace():
    # device 0 busy [10,20) [30,40) [60,70) [80,85) of 100 ms: idle
    # [0,10) [20,30) [40,60) [70,80) [85,100), 65 ms
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", s * MS, d * MS)
           for s, d in ((10, 10), (30, 10), (60, 10), (80, 5))]
    modules = [("jit_a(1)", s * MS, d * MS)
               for s, d in ((10, 10), (30, 10), (60, 10), (80, 5))]
    host = {"rtpu-q-0": [("query", 0 * MS, 88 * MS),
                         ("HashJoinExec", 15 * MS, 63 * MS),
                         # ends inside [20,30): wins over the join's pull,
                         # which covers all of it and is longer
                         ("sync.joinTotal", 18 * MS, 4 * MS),
                         # ends while the device is busy: charges nothing
                         ("sync.aggGroups", 31 * MS, 4 * MS),
                         # two end inside [40,60): the first takes it whole
                         ("sync.buildRows", 41 * MS, 2 * MS),
                         ("sync.splitCounts", 44 * MS, 10 * MS)],
            "rtpu-read-0": [("scan.decode", 50 * MS, 2 * MS)]}
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host,
            "extent_ns": 100 * MS}


WANT = {"query": 0.010,             # the lead: only the query covers it
        "sync.joinTotal": 0.010,
        "sync.buildRows": 0.020,
        "HashJoinExec": 0.010,      # [70,80): the join's pull covers 8 ms
        "unattributed": 0.015}      # the tail: the query covers 3 ms


def test_every_stretch_is_charged_whole_to_one_name():
    got = idle.attribute(_trace())
    assert got.keys() == WANT.keys()
    for name, seconds in WANT.items():
        assert got[name] == pytest.approx(seconds)


def test_the_stretches_sum_to_the_idle_time_of_device_0():
    t = _trace()
    r = xplane.reduce(t)
    assert sum(idle.attribute(t).values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert idle.stretches(t)[0] == (0.0, 10 * MS)
    assert idle.stretches(t)[-1] == (85 * MS, 100 * MS)


def test_without_a_read_a_stretch_is_what_covers_it():
    t = _trace()
    t["host"] = {"rtpu-q-0": [e for e in t["host"]["rtpu-q-0"]
                              if not e[0].startswith(idle.SYNC)]}
    got = idle.attribute(t)
    assert got == pytest.approx({"query": 0.010, "HashJoinExec": 0.040,
                                 "unattributed": 0.015})
    # and each is what xplane names a gap by
    for a, b in idle.stretches(t):
        assert idle._covered(t["host"], [(a, b)])[(a, b)] \
            == xplane._covering(t["host"], a, b)


def test_a_trace_with_no_device_operation_reads_as_nothing():
    t = _trace()
    t["devices"][0]["ops"] = []
    assert idle.attribute(t) is None
    t["devices"] = []
    assert idle.attribute(t) is None


def test_the_midpoint_index_names_every_gap_as_covering_does():
    rng = random.Random(39)
    host = {}
    for line in ("rtpu-q-0", "rtpu-read-0", "python", "python#2"):
        events = []
        for _ in range(120):
            s = rng.uniform(0, 1000)
            events.append((rng.choice("abcdef"), s, rng.uniform(0.1, 80)))
        host[line] = events
    gaps = []
    for _ in range(300):
        a = rng.uniform(0, 1000)
        gaps.append((a, a + rng.uniform(0.01, 60)))
    got = idle._covered(host, gaps)
    assert got == {g: xplane._covering(host, *g) for g in gaps}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("spans") / "tpu_v5e_spans.xplane.pb"
    with gzip.open(os.path.join(RECORDED,
                                "tpu_v5e_spans.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(RECORDED, "tpu_v5e_spans.json")) as f:
        want = json.load(f)
    return xplane.load(str(path)), want


def test_on_a_chip_trace_the_stretches_sum_to_its_idle_time(recorded):
    trace, want = recorded
    r = xplane.reduce(trace)
    # the reduction reads what it did before, and the charge of every idle
    # stretch beside it
    assert set(r) == {"busy_s", "window_s", "device_count",
                      "program_executions", "device_ops", "idle_gaps",
                      "idle_by_host"}
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["program_executions"] == want["program_executions"]
    assert r["idle_gaps"] == [[n, pytest.approx(s)]
                              for n, s in want["idle_gaps"]]
    by_host = idle.attribute(trace)
    assert r["idle_by_host"] == by_host
    assert sum(by_host.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # recorded before the door: no stretch follows a read, and each of the
    # ten longest gaps is charged to the name the reduction gives it
    assert not any(n.startswith(idle.SYNC) for n in by_host)
    for name, seconds in r["idle_gaps"]:
        assert by_host[name] >= seconds * (1 - 1e-9)


def test_on_the_small_chip_trace_too():
    trace = xplane.load(os.path.join(RECORDED, "tpu_v5e_small.xplane.pb"))
    with open(os.path.join(RECORDED, "tpu_v5e_small.json")) as f:
        want = json.load(f)
    r = xplane.reduce(trace)
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert sum(idle.attribute(trace).values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def _span(i, parent, name, dur_ms, kind="operator", **attrs):
    return {"id": i, "parent": parent, "name": name, "kind": kind,
            "tsUs": 0, "durUs": dur_ms * US, "tid": 1, "attrs": attrs}


def _profile(reads=True):
    spans = [_span(1, None, "query", 1000, "query"),
             _span(2, 1, "execute", 900, "execute")]
    if reads:
        spans += [
            _span(3, 2, "HashAggregateExec", 890, pullUs=880 * US,
                  hostReads=6, hostReadUs=40 * US),
            _span(4, 3, "HashJoinExec", 800, pullUs=700 * US,
                  joinProbeBatches=62, hostReads=64, hostReadUs=250 * US),
            # the exchange's read lands on its write span
            _span(5, 4, "ShuffleExchangeExec.write", 30, "shuffle",
                  hostReads=1, hostReadUs=10 * US)]
    else:
        spans += [_span(3, 2, "HashAggregateExec", 890, pullUs=880 * US)]
    return {"queryId": "q", "component": "server", "tsUs": 0,
            "durUs": 1000 * US, "droppedSpans": 0, "spans": spans,
            "tracer": 2, "overflow": {}}


def _run(*profiles, by_host=None):
    return {"done": [types.SimpleNamespace(
        error=None, query=0,
        trace={"queryId": "q", "profiles": [
            {"component": "client", "spans": []}, p]})
        for p in profiles],
        "trace": None if by_host is None else {"idle_by_host": by_host}}


SPAN_READERS = {"host_reads_per_query": 71.0, "host_read_wait_ms": 300.0}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_the_span_readers_on_the_canned_tree(name):
    assert loader.metric(name).read(_run(_profile())) \
        == pytest.approx(SPAN_READERS[name])
    # the mean is over the queries that have it
    both = _run(_profile(), _profile(reads=False), _profile())
    assert loader.metric(name).read(both) \
        == pytest.approx(SPAN_READERS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_a_program_without_the_counters_reads_as_nothing(name):
    assert loader.metric(name).read(_run(_profile(reads=False))) is None
    untraced = {"done": [types.SimpleNamespace(error=None, query=0,
                                               trace=None)], "trace": None}
    assert loader.metric(name).read(untraced) is None


def test_idle_after_read_is_the_share_of_the_stretches_after_a_read():
    read = loader.metric("idle_after_read_pct").read
    assert read(_run(by_host=idle.attribute(_trace()))) \
        == pytest.approx(100.0 * 0.030 / 0.065)
    assert read(_run(by_host={"sync.joinTotal": 3.0, "scan.decode": 1.0})) \
        == pytest.approx(75.0)
    # as run.py hands it the reduced trace
    assert read({"trace": xplane.reduce(_trace())}) \
        == pytest.approx(100.0 * 0.030 / 0.065)


def test_idle_after_read_reads_nothing_without_reads_or_the_reduction():
    read = loader.metric("idle_after_read_pct").read
    # a rehearsal (no device plane) and a reduction without the key
    assert read(_run()) is None
    assert read({"done": [], "trace": {"busy_s": 1.0}}) is None
    # a program without the door: no stretch follows a read
    assert read(_run(by_host={"scan.decode": 1.0, "unattributed": 2.0})) \
        is None
