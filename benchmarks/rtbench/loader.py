"""Finds what belongs to one configuration, traffic mix, query, generator or
per-layer metric by the name ``BENCHMARK.json`` (or another data file) gives
it. A later PR adds files and entries; nothing here holds a name."""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchmarkError(Exception):
    """A fault of the benchmark's own data or of the run; no result line."""


def _checked(name):
    if not isinstance(name, str) or not _NAME.match(name):
        raise BenchmarkError(f"not a name: {name!r}")
    return name


def _json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"no such file: {path}") from None


def _module(path, name):
    if not os.path.exists(path):
        raise BenchmarkError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return _json(os.path.join(REPO, "BENCHMARK.json"))


def cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in bench['workloads']]}")


def config(bench, name):
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(REPO, c["file"]))
    raise BenchmarkError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name):
    return _json(os.path.join(ROOT, "traffic", _checked(name) + ".json"))


def generator(name):
    return _module(os.path.join(ROOT, "datagen", _checked(name) + ".py"),
                   f"rtbench_datagen_{name}")


def query(family, name):
    return _module(os.path.join(ROOT, "queries", _checked(family),
                                _checked(name) + ".py"),
                   f"rtbench_query_{family}_{name}")


def query_params(query, entry, rehearsal=False):
    """The query's validation parameters, with what the traffic entry sets
    and, in a rehearsal, what it sets for the tiny scale."""
    over = dict(entry.get("params") or {})
    if rehearsal:
        over.update(entry.get("rehearsal_params") or {})
    return dict(query.PARAMS, **over)


def metric(name):
    return _module(os.path.join(ROOT, "metrics", _checked(name) + ".py"),
                   f"rtbench_metric_{name.replace('.', '_')}")


def peaks(device_kind):
    table = _json(os.path.join(ROOT, "peaks.json"))
    for entry in table["devices"]:
        if device_kind in entry["device_kinds"]:
            return entry
    raise BenchmarkError(
        f"device kind {device_kind!r} is not in peaks.json: add its "
        f"published peaks with their source before measuring on it")
