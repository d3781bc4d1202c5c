"""The generators against their configuration files and the specs' value
rules, one case per table. What a table has to hold belongs to the generator
that makes it: ``domains/<generator>.py`` beside this file, found by the
configuration's ``generator`` as ``rtbench/loader.py`` finds the generator
itself. This file holds no table's name, so a configuration with tables of
its own brings its checks as a new file."""

import json
import os

import pyarrow as pa
import pytest

from benchlib import BENCH, domains
from rtbench import loader

SCALE = 0.02
SEED = 2 ** 31 + 12345          # seeds are larger than 32 signed bits hold


def _configs():
    out = []
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            cfg = json.load(f)
        out += [(cfg, t) for t in cfg["tables"]]
    return out


CASES = _configs()
IDS = [f"{c['name']}.{t}" for c, t in CASES]


@pytest.fixture(scope="module")
def made():
    cache = {}

    def get(cfg):
        if cfg["name"] not in cache:
            gen = loader.generator(cfg["generator"])
            cache[cfg["name"]] = gen.generate(cfg, SCALE, SEED,
                                              sorted(cfg["tables"]))
        return cache[cfg["name"]]
    return get


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_schema_is_the_configurations(cfg, table, made):
    t = made(cfg)[table]
    want = [(c["name"], c["type"]) for c in cfg["tables"][table]["columns"]]
    assert [(f.name, str(f.type)) for f in t.schema] == want


def _check(cfg, table, kind):
    module = domains(cfg["generator"])
    found = getattr(module, kind, {})
    if table not in found:
        pytest.fail(f"table {table!r} of configuration {cfg['name']!r} has "
                    f"no entry in {kind} of {module.__file__}: add one")
    return found[table]


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_row_count(cfg, table, made):
    _check(cfg, table, "ROWS")(cfg, SCALE, made(cfg))


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_same_seed_same_bytes(cfg, table):
    gen = loader.generator(cfg["generator"])

    def ipc(seed):
        t = gen.generate(cfg, SCALE / 4, seed, [table])[table]
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        return sink.getvalue().to_pybytes()
    assert ipc(SEED) == ipc(SEED)
    # a table no seed changes (a calendar) is named beside its checks
    if table not in getattr(domains(cfg["generator"]), "SEEDLESS", ()):
        assert ipc(SEED) != ipc(SEED + 1)


@pytest.mark.parametrize("cfg,table", CASES, ids=IDS)
def test_value_domains(cfg, table, made):
    tables = made(cfg)
    _check(cfg, table, "DOMAINS")(tables[table], tables, cfg)
