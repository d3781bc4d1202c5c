"""A chip belongs to one process. Importing the plan-builder surface — what a
client, a router or a load generator does — must therefore initialise no JAX
backend, and ``chip_smoke.py`` (a client) must fail loudly off the chip.
Every case runs in a subprocess: this test process already holds a backend.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENTRY_MODULES = [
    "spark_rapids_tpu.server.client",
    "spark_rapids_tpu.server.router",
    "spark_rapids_tpu.server.plandoc",
    "spark_rapids_tpu.plan.session",
    "bench",
    "chip_smoke",
]


def _run(argv, env_extra=None, timeout=600):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + argv, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


@pytest.mark.parametrize("module", ENTRY_MODULES)
def test_import_initialises_no_backend(module):
    code = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    r = _run(["-c", code])
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_dir_comes_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set no code names another directory;
    unset, it is <checkout>/.jax_compilation_cache."""
    code = ("import os, jax\n"
            "from spark_rapids_tpu import compile_cache\n"
            "print(compile_cache.enable())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = _run(["-c", code], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path)] * 2
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [
        os.path.join(ROOT, ".jax_compilation_cache")] * 2


def test_chip_smoke_rehearsal_on_cpu_never_says_ok():
    """The whole served path at a toy size on a CPU server: every phase
    runs and is checked against pyarrow, the repeat submissions compile
    nothing new — and the last line still refuses to call it a chip run."""
    r = _run(["chip_smoke.py", "--rows", "4096", "--allow-cpu"])
    lines = _json_lines(r.stdout)
    assert r.returncode != 0, r.stdout[-2000:]
    last = lines[-1]
    assert last["ok"] is False and last["device"]["platform"] == "cpu", last
    assert '"ok": true' not in r.stdout
    checked = [ln["query"] for ln in lines if ln.get("phase") == "checked"]
    assert checked == ["q1_stage", "hash_agg", "join_sort"], r.stdout[-3000:]
    queries = [ln for ln in lines if ln.get("phase") == "query"]
    assert len(queries) == 6 and not any(q["fell_back"] for q in queries)
    assert all(q["cache_entries_added"] == 0
               for q in queries if q["attempt"] == "repeat"), queries
    assert any(ln.get("exit_code") == 0 for ln in lines), "server exit code"


def test_chip_smoke_without_a_chip_fails_before_the_data():
    r = _run(["chip_smoke.py", "--rows", "4096"])
    lines = _json_lines(r.stdout)
    assert r.returncode != 0
    assert lines[-1]["ok"] is False and "not on a TPU" in lines[-1]["error"]
    assert not any(ln.get("phase") == "data" for ln in lines)


def test_chip_smoke_four_chip_phase_on_virtual_devices():
    """--chips 4 runs only the mesh phase; its MeshStage / all-to-all /
    four-shard checks fire, and it agrees with the host-mediated exchange."""
    r = _run(["chip_smoke.py", "--chips", "4", "--rows", "8192"],
             {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    lines = _json_lines(r.stdout)
    assert r.returncode != 0 and lines[-1]["ok"] is False
    assert lines[-1]["device"]["count"] == 4
    assert not any(ln.get("phase") in ("server", "query") for ln in lines)
    mesh = [ln for ln in lines if ln.get("phase") == "mesh"]
    assert any("MeshStageExec" in ln.get("execs", []) for ln in mesh), mesh
    assert any(ln.get("all_to_all_in_program", 0) > 0 for ln in mesh), mesh
    inputs = next(ln["inputs"] for ln in mesh if "inputs" in ln)
    assert all(len(set(i["devices"])) == 4 and min(i["rows_per_device"]) > 0
               for i in inputs), inputs
    assert any(ln.get("equal_to_host_exchange") for ln in lines)


def test_chip_smoke_four_chip_phase_needs_four_devices():
    r = _run(["chip_smoke.py", "--chips", "4", "--rows", "8192"],
             {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    lines = _json_lines(r.stdout)
    assert r.returncode != 0 and "four devices" in lines[-1]["error"]
