"""The per-layer readers of the engine's span tree (``rtbench/spantree.py``
and the eight metrics that use it): each on a canned record whose answer is
known, on a record of the older tracer, and all eight in one traced
rehearsal."""

import json
import os
import textwrap
import types

import pytest

from benchlib import BENCH, REPO, run_cli
from rtbench import loader, spantree

MS = 1000       # microseconds
NEW = ["execute_ms", "plan_materialize_ms", "lowerings_per_query",
       "relower_ms", "scan_decode_ms", "scan_h2d_ms", "gc_pause_ms",
       "span_unaccounted_pct"]


def _span(i, parent, name, dur_ms, kind="span", tid=1, **attrs):
    return {"id": i, "parent": parent, "name": name, "kind": kind,
            "tsUs": 0, "durUs": dur_ms * MS, "tid": tid, "attrs": attrs}


def _profile(tracer=2, overflow=None):
    spans = [
        _span(1, None, "query", 1000, "query"),
        _span(2, 1, "plan.prepare", 300, "plan"),
        _span(3, 2, "plan.overrides", 290, "plan"),
        _span(4, 3, "plan.materialize", 200, "plan", exec="X", rows=5),
        _span(5, 3, "plan.materialize", 50, "plan", exec="Y", rows=5),
        _span(6, 1, "execute", 600, "execute"),
        # an operator fills its parent by its pulls, not by its extent
        _span(7, 6, "SortExec", 599, "operator", pullUs=500 * MS, pulls=2),
        _span(8, 7, "ScanExec", 590, "operator", pullUs=300 * MS, pulls=3),
        # decode on two pool threads: thread-seconds, overlapping the scan
        _span(9, 8, "scan.decode", 250, "scan", tid=2),
        _span(10, 8, "scan.decode", 150, "scan", tid=3),
        _span(11, 8, "scan.h2d", 120, "transfer"),
        _span(12, 7, "jit.trace", 10, "jit", fun="f"),
        _span(13, 7, "jit.lower", 30, "jit", fun="jit(f)"),
        _span(14, 7, "jit.compile", 60, "jit", fun="jit(f)", cacheHit=True),
        # a collection inside the compile: the compile's time already
        _span(15, 14, "gc", 40, "gc", collected=9),
        _span(16, 8, "jit.lower", 20, "jit", fun="jit(g)"),
        _span(17, 6, "result.d2h", 50, "transfer", bytes=8),
    ]
    p = {"queryId": "q", "component": "server", "tsUs": 0,
         "durUs": 1000 * MS, "droppedSpans": 0, "spans": spans}
    if tracer >= 2:
        p["tracer"] = tracer
        p["overflow"] = overflow or {}
    return p


def _run(*profiles):
    done = [types.SimpleNamespace(
        error=None, query=0,
        trace={"queryId": "q", "profiles": [
            {"component": "client", "spans": []}, p]})
        for p in profiles]
    return {"done": done}


WANT = {"execute_ms": 600.0, "plan_materialize_ms": 250.0,
        "lowerings_per_query": 2.0, "relower_ms": 120.0,
        "scan_decode_ms": 400.0, "scan_h2d_ms": 120.0,
        "gc_pause_ms": 40.0,
        # query 1000 - 300 - 600 = 100; plan.prepare 300 - 290 = 10;
        # execute 600 - 500 (the root operator's pulls) - 50 = 50
        "span_unaccounted_pct": 16.0}


@pytest.mark.parametrize("name", NEW)
def test_reads_the_canned_tree(name):
    assert loader.metric(name).read(_run(_profile())) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_an_older_tracers_tree_reads_as_nothing_but_the_execute_span(name):
    got = loader.metric(name).read(_run(_profile(tracer=1)))
    assert got == (600.0 if name == "execute_ms" else None)
    untraced = {"done": [types.SimpleNamespace(error=None, query=0,
                                               trace=None)]}
    assert loader.metric(name).read(untraced) is None


def test_none_of_a_kind_reads_zero_and_the_mean_is_over_queries():
    bare = _profile()
    bare["spans"] = [s for s in bare["spans"]
                     if s["name"] not in ("gc", "plan.materialize")
                     and not s["name"].startswith("jit.")]
    run = _run(bare)
    assert loader.metric("gc_pause_ms").read(run) == 0.0
    assert loader.metric("plan_materialize_ms").read(run) == 0.0
    assert loader.metric("lowerings_per_query").read(run) == 0.0
    both = _run(bare, _profile())
    assert loader.metric("relower_ms").read(both) == pytest.approx(60.0)
    assert loader.metric("lowerings_per_query").read(both) == 1.0


def test_what_the_span_cap_dropped_still_counts():
    p = _profile(overflow={"jit.lower": [3, 45 * MS],
                           "jit.compile": [3, 15 * MS]})
    run = _run(p)
    assert loader.metric("lowerings_per_query").read(run) == 5.0
    assert loader.metric("relower_ms").read(run) == pytest.approx(180.0)


def test_self_time_leaves_out_children_on_other_threads():
    p = _profile()
    # the scan: 300 ms of pulls, less the H2D and the lowering on its own
    # thread; the decodes ran elsewhere
    assert spantree.self_us(p, ("ScanExec",)) == (300 - 120 - 20) * MS
    assert spantree.self_us(p, ("SortExec",)) \
        == (500 - 300 - 10 - 30 - 60) * MS


FIRST_EXECUTION = textwrap.dedent('''
    """run.py as it is; the server's profile of the harness's FIRST
    submission, a shape's first execution on a fresh server, is read with
    the benchmark's own readers and printed: no switch of the harness."""
    import sys
    import types
    sys.path.insert(0, {bench!r})
    import run
    from spark_rapids_tpu.server.client import PlanClient

    served, seen = PlanClient.collect, []

    def collect(self, df, *a, **k):
        table = served(self, df, *a, **k)
        if not seen:
            seen.append(types.SimpleNamespace(error=None, query=0,
                                              trace=self.last_trace()))
            for name in ("lowerings_per_query", "relower_ms"):
                value = run.loader.metric(name).read({{"done": seen}})
                print(f"first execution {{name}}: {{value!r}}",
                      file=sys.stderr)
        return table
    PlanClient.collect = collect
    sys.exit(run.main({args!r}))
''')


def test_a_traced_rehearsal_prints_all_eight(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"]][-8:] == NEW
    driver = tmp_path / "driver.py"
    driver.write_text(FIRST_EXECUTION.format(bench=BENCH, args=[
        "--workload", "tpcds_sf1.q3", "--seed", str(2 ** 31 + 28),
        "--seconds", "1", "--trace", "1", "--rehearsal", "--work-dir",
        str(tmp_path / "work")]))
    rc, last, out, err = run_cli(str(driver), [])
    assert rc == 0, err[-3000:]
    assert last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(NEW) <= set(m)
    # a warm query lowers nothing since the program table (PR 29): both are
    # reported, and 0 is a reading
    assert m["lowerings_per_query"] >= 0 and m["relower_ms"] >= 0
    # the cost is still there in a shape's first execution on a fresh
    # server (this rehearsal's cache directory is its own, and empty)
    first = {ln.split(": ")[0].split()[-1]: float(ln.split(": ")[1])
             for ln in err.splitlines()
             if ln.startswith("first execution ")}
    assert first["lowerings_per_query"] >= 1 and first["relower_ms"] > 0
    assert first["lowerings_per_query"] > m["lowerings_per_query"]
    assert first["relower_ms"] > m["relower_ms"]
    assert m["scan_decode_ms"] > 0 and m["scan_h2d_ms"] > 0
    assert m["execute_ms"] > m["relower_ms"]
    assert m["gc_pause_ms"] >= 0 and m["plan_materialize_ms"] >= 0
    assert 0 <= m["span_unaccounted_pct"] < 5
    units = {k: v["unit"] for k, v in last["metrics"].items()}
    assert units["lowerings_per_query"] == "lowerings"
    assert units["span_unaccounted_pct"] == "%"


ENGINE_SPANS = {"plan.prepare", "execute", "result.d2h", "scan.h2d",
                "scan.decode", "HashAggregateExec", "FilterExec",
                "FileSourceScanExec[parquet]"}


def test_recorded_chip_trace_names_idle_gaps_by_engine_spans(tmp_path):
    """One traced query through the server on a TPU v5e, the profiler
    started by ``PlanClient.profile``: the engine's spans are host events
    of the trace, on lines of their own, and the benchmark's reduction
    names gaps by them."""
    import gzip
    from rtbench import xplane
    recorded = os.path.join(REPO, "benchmarks", "recorded")
    with open(os.path.join(recorded, "tpu_v5e_spans.json")) as f:
        want = json.load(f)
    path = tmp_path / "tpu_v5e_spans.xplane.pb"
    with gzip.open(os.path.join(recorded,
                                "tpu_v5e_spans.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = xplane.load(str(path))
    assert [d["name"] for d in trace["devices"]] == want["devices"]
    assert trace["extent_ns"] == want["extent_ns"]
    # every line of the host's plane, the one with no name too
    host = trace["host"]
    assert sorted(host) == sorted(want["host_lines"]) and "" in host
    assert "python" not in host
    held = {name for name, _, _ in host["rtpu-q-0"]}
    assert ENGINE_SPANS - {"scan.decode"} <= held
    assert {name for line in ("rtpu-read-0", "rtpu-read-1")
            for name, _, _ in host[line]} == {"scan.decode"}
    r = xplane.reduce(trace)
    assert r["program_executions"] == want["program_executions"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert [n for n, _ in r["device_ops"]] == want["top_ops"]
    # four gaps of a millisecond read np.asarray(jax.Array) where they read
    # result.d2h when recorded: the innermost event over half the gap names
    # it now (tpu_v5e_spans.json, idle_gaps_renamed)
    gaps = [n for n, _ in r["idle_gaps"]]
    assert gaps == [n for n, _ in want["idle_gaps"]]
    assert [g for _, g in r["idle_gaps"]] == pytest.approx(
        [g for _, g in want["idle_gaps"]], rel=1e-9)
    assert set(gaps) & ENGINE_SPANS
    assert "unattributed" not in gaps
    assert not any("_lambda_" in n or "jit_kernel" in n
                   for n in gaps + want["top_ops"])
