"""The two readers of the decimal path's span and counter (PR 31):
``scan_decimal_ms`` over ``scan.h2d.decimal`` and ``dec128_device_bytes``
over the operator spans' ``dec128Bytes``, each on a canned record whose
answer is known and on the record of a program that lacks what it reads,
where it answers nothing and not 0. ``BENCHMARK.json`` does not list them
yet (PERF.md, Open questions): ``loader.metric`` finds a reader by name."""

import types

import pytest

import benchlib  # noqa: F401  (puts benchmarks/ on the path)
from rtbench import loader

MS = 1000       # microseconds
NAMES = ["scan_decimal_ms", "dec128_device_bytes"]


def _span(i, parent, name, dur_ms, kind="span", **attrs):
    return {"id": i, "parent": parent, "name": name, "kind": kind,
            "tsUs": 0, "durUs": dur_ms * MS, "tid": 1, "attrs": attrs}


def _profile(decimals=True):
    spans = [
        _span(1, None, "query", 1000, "query"),
        _span(2, 1, "execute", 900, "execute"),
        _span(3, 2, "HashAggregateExec", 890, "operator", pullUs=800 * MS,
              pulls=2, **({"dec128Columns": 4, "dec128Bytes": 4096}
                          if decimals else {})),
        _span(4, 3, "ProjectExec", 700, "operator", pullUs=600 * MS,
              pulls=3, **({"dec128Columns": 2, "dec128Bytes": 1 << 20}
                          if decimals else {})),
        _span(5, 4, "ScanExec", 500, "operator", pullUs=400 * MS, pulls=3),
        _span(6, 5, "scan.h2d", 120, "transfer"),
        _span(8, 5, "scan.h2d", 100, "transfer"),
    ]
    if decimals:
        spans += [_span(7, 6, "scan.h2d.decimal", 70, "transfer",
                        values=8, columns=4, bytes=128),
                  _span(9, 8, "scan.h2d.decimal", 50, "transfer",
                        values=8, columns=4, bytes=128)]
    return {"queryId": "q", "component": "server", "tsUs": 0,
            "durUs": 1000 * MS, "droppedSpans": 0, "spans": spans,
            "tracer": 2, "overflow": {}}


def _run(*profiles):
    return {"done": [types.SimpleNamespace(
        error=None, query=0,
        trace={"queryId": "q", "profiles": [
            {"component": "client", "spans": []}, p]})
        for p in profiles]}


WANT = {"scan_decimal_ms": 120.0, "dec128_device_bytes": 4096 + (1 << 20)}


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_canned_tree(name):
    assert loader.metric(name).read(_run(_profile())) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_span_or_counter_reads_as_nothing(name):
    assert loader.metric(name).read(_run(_profile(decimals=False))) is None
    untraced = {"done": [types.SimpleNamespace(error=None, query=0,
                                               trace=None)]}
    assert loader.metric(name).read(untraced) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_over_the_queries_that_have_it(name):
    both = _run(_profile(), _profile(decimals=False))
    got = loader.metric(name).read(both)
    # the span's mean is over every traced query of the window, the
    # counter's over the queries whose operators carry it
    want = WANT[name] / 2 if name == "scan_decimal_ms" else WANT[name]
    assert got == pytest.approx(want)
