"""``correct`` has to come out false when it should: the controls of
``benchmarks/control.py`` at a size a test can hold. The whole rehearsal runs
with the timed path broken underneath are ``test_fault_answer_altered.py``
(an answer altered where the client receives it) and
``test_fault_batch_lost.py`` (half of the scan's batches lost under the
server's plan)."""

import json
import os

import pytest

from benchlib import REPO
from rtbench import loader

import control      # benchmarks/control.py

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _cell(name):
    bench = loader.benchmark()
    cell = loader.cell(bench, name)
    return (loader.config(bench, cell["config"]),
            loader.traffic(cell["traffic"]))


@pytest.mark.parametrize("cell", CELLS)
def test_a_control_comes_out_not_correct(cell, tmp_path):
    config, traffic = _cell(cell)
    got = control.readings(config, traffic, 0.1, 2 ** 31 + 21,
                           str(tmp_path), rehearsal=True)
    by_name = {r["control"]: r for r in got}
    assert by_name["lost_batch"]["correct"] is False
    assert by_name["lost_batch"]["exact_mismatches"] > 0
    guarantees = config["guarantees"]
    if guarantees["control_precision"] is None:
        # no precision control, and the configuration says why
        assert "lower_precision" not in by_name
        assert guarantees["control_precision_why"]
    else:
        low = by_name["lower_precision"]
        assert low["correct"] is False
        if guarantees["double_rel_err"] is not None:
            # doubles: the lower precision reads over three times the limit
            assert low["double_rel_err"] > 3 * guarantees["double_rel_err"]
        else:
            # no double: lower precision has to get an exact value wrong
            assert low["exact_mismatches"] > 0
    assert not os.listdir(tmp_path)


def test_a_control_precision_that_is_no_float_type_is_refused():
    config = dict(_cell(CELLS[0])[0])
    config["guarantees"] = dict(config["guarantees"],
                                control_precision="none that a query sees")
    with pytest.raises(loader.BenchmarkError, match="control_precision"):
        control.control_precision(config)
