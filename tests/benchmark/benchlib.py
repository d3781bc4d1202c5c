"""Shared by the tests of the benchmark (``benchmarks/``): they import its
library the way ``run.py`` does, from the benchmark's own directory."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import json          # noqa: E402
import subprocess    # noqa: E402

import pytest        # noqa: E402

DOMAINS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "domains")


def domains(generator):
    """The module of checks of one generator, ``domains/<generator>.py``,
    found by the generator's name as ``rtbench/loader.py`` finds the
    generator itself. One that is missing fails the test that asks, with
    the file to add: nothing is skipped."""
    from rtbench import loader
    path = os.path.join(DOMAINS_DIR, generator + ".py")
    if not os.path.exists(path):
        pytest.fail(f"generator {generator!r} has no checks: add {path} "
                    f"exporting DOMAINS = {{table: check(t, tables, cfg)}} "
                    f"and ROWS = {{table: rule(cfg, scale, tables)}}")
    return loader._module(path, f"domains_{generator}")


def run_cli(script, args, cwd=REPO, timeout=600):
    """Run one of the benchmark's commands as the driver would; returns
    ``(exit code, last stdout line parsed or None, stdout, stderr)``."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR",
                        "BENCH_RUN")}
    p = subprocess.run([sys.executable, script] + [str(a) for a in args],
                       cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return p.returncode, last, p.stdout, p.stderr


REHEARSAL_SEED = 2 ** 31 + 33


def rehearsal_args(cell, work_dir):
    """A cell's untraced rehearsal on a CPU server, as ``run.py`` takes it.
    The clean run (``test_rehearsal.py``) and each run with its timed path
    broken underneath (``test_fault_*.py``) take these same arguments, so
    the clean run is the broken ones' twin."""
    return [str(a) for a in (
        "--workload", cell, "--seed", REHEARSAL_SEED, "--seconds", 2,
        "--trace", 0, "--rehearsal", "--work-dir", work_dir)]
