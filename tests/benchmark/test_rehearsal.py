"""Every cell of BENCHMARK.json end to end on a CPU server at a tiny scale
(``run.py --rehearsal``): the last line's keys, no JAX backend in the client,
every plan on the device path, replies equal to the reference. Several
workers run these at once: each run has its own work directory and port."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from benchlib import REPO, rehearsal_args, run_cli

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
RUN = os.path.join(REPO, "benchmarks", "run.py")


def _metrics_of(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell, tmp_path):
    # the twin of test_fault_*.py's broken runs: the same seed and arguments
    rc, last, out, err = run_cli(RUN, rehearsal_args(cell, tmp_path))
    assert rc == 0, err[-3000:]
    # rc 0 also says the client initialised no JAX backend: run.py checks
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert set(last["metrics"]) == _metrics_of("end_to_end", cell)
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for plan in last["plans"]:
        ops = [ln.strip() for ln in plan.splitlines()]
        assert ops and all(op.startswith("*") for op in ops), plan
    assert last["compared"]["exact_mismatches"] == {"value": 0, "limit": 0}
    assert last["compared"]["replies_compared"]["value"] \
        == last["attempted"]
    # each number compared is printed beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(last["compared"]):]
    assert all(ln.startswith("compared ") and " limit " in ln for ln in tail)
    assert not os.listdir(tmp_path)     # data, control and trace are gone


@pytest.mark.parametrize("cell,seconds", [(CELLS[0], 1), (CELLS[1], 5)])
def test_traced_rehearsal_reports_the_per_layer_metrics(cell, seconds,
                                                        tmp_path):
    rc, last, out, err = run_cli(RUN, [
        "--workload", cell, "--seed", 2 ** 31 + 9, "--seconds", seconds,
        "--trace", 1, "--rehearsal", "--work-dir", tmp_path])
    assert rc == 0, err[-3000:]
    assert last["correct"] is True
    # a CPU has no peak in peaks.json and its trace no device plane: the
    # readers of the device's trace and of shares of the chip return
    # nothing, never 0
    off_chip = {"hbm_roofline_pct", "device_idle_pct", "peak_hbm_bytes",
                "programs_per_query", "idle_after_read_pct"}
    assert set(last["metrics"]) == _metrics_of("per_layer", cell) - off_chip
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["metrics"]["cpu_fallback_ops"]["value"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["plan_prepare_ms"]["value"] > 0
    assert last["metrics"]["scan_host_ms"]["value"] > 0
    # where the window goes on after its first query, that query's trace is
    # thrown away and the slice begins at the second
    first = 1 if last["attempted"] == 1 else 2
    assert f"queries from number {first} on" in err
    assert not os.listdir(tmp_path)


def test_no_workload_of_that_name_is_an_error_with_no_result(tmp_path):
    rc, last, out, err = run_cli(RUN, [
        "--workload", "no_such.cell", "--seed", 1, "--seconds", 1,
        "--trace", 0, "--rehearsal", "--work-dir", tmp_path])
    assert rc != 0 and out.strip() == ""


def test_without_the_system_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths: non-zero exit, nothing printed."""
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns(
                            "__pycache__", ".work"))
    rc, last, out, err = run_cli(
        str(tmp_path / "benchmarks" / "run.py"),
        ["--workload", CELLS[0], "--seed", 1, "--seconds", 1, "--trace", 0],
        cwd=str(tmp_path))
    assert rc != 0 and out.strip() == ""


def test_no_accelerator_is_an_error_with_no_result(tmp_path):
    """Without ``--rehearsal`` a server that finds only a CPU ends the run:
    a measurement path never falls back."""
    rc, last, out, err = run_cli(RUN, [
        "--workload", CELLS[0], "--seed", 2 ** 31 + 1, "--seconds", 1,
        "--trace", 0, "--work-dir", tmp_path])
    assert rc != 0 and out.strip() == ""
    assert "no accelerator" in err
    assert not os.listdir(tmp_path)


def _processes_naming(text):
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode(errors="replace")
        except OSError:
            continue
        if text in cmd:
            found.append((int(pid), cmd.replace("\0", " ")))
    return found


def test_terminated_mid_run_stops_its_server_and_prints_no_result(tmp_path):
    """The traced server's command line names its control directory, which
    is this test's own: once it is up, SIGTERM to the benchmark has to take
    the server with it and leave nothing behind."""
    run = subprocess.Popen(
        [sys.executable, RUN, "--workload", CELLS[0], "--seed", "11",
         "--seconds", "30", "--trace", "1", "--rehearsal", "--work-dir",
         str(tmp_path)], cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    try:
        limit = time.time() + 120
        while not [c for _, c in _processes_naming(str(tmp_path))
                   if "serve_traced.py" in c]:
            assert run.poll() is None and time.time() < limit
            time.sleep(0.1)
        run.send_signal(signal.SIGTERM)
        out, _ = run.communicate(timeout=60)
    finally:
        if run.poll() is None:
            run.kill()
            run.wait()
    assert run.returncode == 128 + signal.SIGTERM
    assert out.strip() == ""
    assert _processes_naming(str(tmp_path)) == []
    assert not os.listdir(tmp_path)
