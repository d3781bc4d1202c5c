"""The two readers of the rollup's and the window's spans and counters (PR
35): ``window_ms`` over the own time of the ``KeyBatchingExec`` and
``WindowExec`` operator spans and ``expand_slots_out`` over the operator
spans' ``expandSlotsOut``, each on a canned record whose answer is known and
on the record of a program that lacks what it reads, where it answers
nothing and not 0. ``loader.metric`` finds a reader by the name
``BENCHMARK.json`` lists it under."""

import types

import pytest

import benchlib  # noqa: F401  (puts benchmarks/ on the path)
from rtbench import loader

MS = 1000       # microseconds
NAMES = ["window_ms", "expand_slots_out"]


def _span(i, parent, name, dur_ms, kind="operator", **attrs):
    return {"id": i, "parent": parent, "name": name, "kind": kind,
            "tsUs": 0, "durUs": dur_ms * MS, "tid": 1, "attrs": attrs}


def _profile(rollup=True):
    spans = [
        _span(1, None, "query", 1000, "query"),
        _span(2, 1, "execute", 900, "execute"),
        _span(3, 2, "SortExec", 890, pullUs=880 * MS, pulls=2),
    ]
    if rollup:
        spans += [
            # the window's own time: its pulls less its child's
            _span(4, 3, "WindowExec", 800, pullUs=700 * MS, pulls=2,
                  windowBatches=1, windowSlots=1 << 20, windowExprs=1),
            _span(5, 4, "KeyBatchingExec", 650, pullUs=600 * MS, pulls=2,
                  keyBatchRowsIn=750000),
            _span(6, 5, "HashAggregateExec", 560, pullUs=550 * MS, pulls=2),
            _span(7, 6, "ExpandExec", 300, pullUs=290 * MS, pulls=4,
                  expandProjections=1, expandBatchesOut=3,
                  expandSlotsOut=3 << 18),
            _span(8, 7, "ProjectExec", 200, pullUs=190 * MS, pulls=4),
        ]
    else:
        spans += [_span(6, 3, "HashAggregateExec", 560, pullUs=550 * MS,
                        pulls=2),
                  _span(8, 6, "ProjectExec", 200, pullUs=190 * MS, pulls=4)]
    return {"queryId": "q", "component": "server", "tsUs": 0,
            "durUs": 1000 * MS, "droppedSpans": 0, "spans": spans,
            "tracer": 2, "overflow": {}}


def _run(*profiles):
    return {"done": [types.SimpleNamespace(
        error=None, query=0,
        trace={"queryId": "q", "profiles": [
            {"component": "client", "spans": []}, p]})
        for p in profiles]}


# window: (700 - 600) of WindowExec + (600 - 550) of KeyBatchingExec
WANT = {"window_ms": 150.0, "expand_slots_out": float(3 << 18)}


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_canned_tree(name):
    assert loader.metric(name).read(_run(_profile())) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_span_or_counter_reads_as_nothing(name):
    assert loader.metric(name).read(_run(_profile(rollup=False))) is None
    untraced = {"done": [types.SimpleNamespace(error=None, query=0,
                                               trace=None)]}
    assert loader.metric(name).read(untraced) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_over_the_queries_that_have_it(name):
    both = _run(_profile(), _profile(rollup=False))
    got = loader.metric(name).read(both)
    # the spans' mean is over every traced query of the window, the
    # counter's over the queries whose operators carry it
    want = WANT[name] / 2 if name == "window_ms" else WANT[name]
    assert got == pytest.approx(want)


def test_an_older_tracers_profile_reads_as_nothing():
    old = dict(_profile(), tracer=1)
    for name in NAMES:
        assert loader.metric(name).read(_run(old)) is None
