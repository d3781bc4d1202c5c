"""The two readers of the hash join's counters: ``join_candidates`` over
the operator spans' ``joinCandidates`` and ``join_slots_out`` over their
``joinSlotsOut``, each on a canned record whose answer is known and on the
record of a program that lacks what it reads, where it answers nothing and
not 0; and the least bytes of an expansion that a roofline share of the
expand program will divide. ``loader.metric`` finds a reader by the name
``BENCHMARK.json`` lists it under."""

import types

import pytest

import benchlib  # noqa: F401  (puts benchmarks/ on the path)
from rtbench import loader

MS = 1000       # microseconds
NAMES = ["join_candidates", "join_slots_out"]


def _span(i, parent, name, dur_ms, kind="operator", **attrs):
    return {"id": i, "parent": parent, "name": name, "kind": kind,
            "tsUs": 0, "durUs": dur_ms * MS, "tid": 1, "attrs": attrs}


def _profile(joins=True):
    spans = [
        _span(1, None, "query", 1000, "query"),
        _span(2, 1, "execute", 900, "execute"),
        _span(3, 2, "HashAggregateExec", 890, pullUs=880 * MS, pulls=2),
    ]
    if joins:
        spans += [
            # a self-join of two probe batches, and a semi join above it
            # that counts its matches and makes no pair batch
            _span(4, 3, "HashJoinExec", 800, pullUs=700 * MS, pulls=3,
                  joinProbeBatches=2, joinCandidates=5_000_000,
                  joinSlotsOut=(1 << 23) + (1 << 22)),
            _span(5, 4, "HashJoinExec", 300, pullUs=250 * MS, pulls=9,
                  joinProbeBatches=8, joinCandidates=80_000_000,
                  joinSlotsOut=0, graceBuckets=0),
        ]
    else:
        spans += [_span(6, 3, "ProjectExec", 200, pullUs=190 * MS, pulls=4)]
    return {"queryId": "q", "component": "server", "tsUs": 0,
            "durUs": 1000 * MS, "droppedSpans": 0, "spans": spans,
            "tracer": 2, "overflow": {}}


def _run(*profiles):
    return {"done": [types.SimpleNamespace(
        error=None, query=0,
        trace={"queryId": "q", "profiles": [
            {"component": "client", "spans": []}, p]})
        for p in profiles]}


WANT = {"join_candidates": 85_000_000.0,
        "join_slots_out": float((1 << 23) + (1 << 22))}


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_canned_tree(name):
    assert loader.metric(name).read(_run(_profile())) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_reads_as_nothing(name):
    assert loader.metric(name).read(_run(_profile(joins=False))) is None
    untraced = {"done": [types.SimpleNamespace(error=None, query=0,
                                               trace=None)]}
    assert loader.metric(name).read(untraced) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_over_the_queries_that_have_it(name):
    both = _run(_profile(), _profile(joins=False), _profile())
    assert loader.metric(name).read(both) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_an_older_tracers_profile_reads_as_nothing(name):
    old = dict(_profile(), tracer=1)
    assert loader.metric(name).read(_run(old)) is None


def test_the_least_bytes_of_an_expansion():
    """Q95's self-join at SF1: 9.0M candidate pairs of two (int64 order,
    int32 warehouse) rows read, 6.1M kept pairs of one int64 written."""
    from rtbench.touched import column_bytes
    pair = 2 * (column_bytes({"type": "int64"})
                + column_bytes({"type": "int32"}))
    out = column_bytes({"type": "int64"})
    least = loader.metric("join_candidates").expansion_bytes
    assert least(9_023_806, 6_126_200, pair, out) \
        == 9_023_806 * 24 + 6_126_200 * 8
    assert least(0, 0, pair, out) == 0
