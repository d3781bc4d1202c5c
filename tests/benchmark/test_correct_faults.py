"""``correct`` has to come out false when it should: the controls of
``benchmarks/control.py`` at a size a test can hold, and whole rehearsal runs
with the timed path broken underneath (an answer altered where the client
receives it; half of the scan's batches lost under the server's plan)."""

import json
import os
import textwrap

import pytest

from benchlib import BENCH, REPO, run_cli
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


DRIVER = textwrap.dedent('''
    import sys
    sys.path.insert(0, {bench!r})
    import run
    from rtbench import plans

    FAULT = {fault!r}

    def alter(table):
        """One cell of the reply changed, the least a comparison can see:
        a double by one part in 10**6, anything else by one unit."""
        import decimal
        import pyarrow as pa
        i = table.num_columns - 1
        col = table.column(i)
        values = col.to_pylist()
        v = values[0]
        if isinstance(v, float):
            values[0] = v * (1 + 1e-6)
        elif isinstance(v, decimal.Decimal):
            values[0] = v + decimal.Decimal("0.01")
        else:
            values[0] = v + 1
        return table.set_column(i, table.schema.field(i),
                                pa.array(values, col.type))

    if FAULT == "answer_altered":
        from spark_rapids_tpu.server.client import PlanClient
        served = PlanClient.collect
        count = [0]
        traffic = run.loader.traffic(run.loader.cell(
            run.loader.benchmark(), {cell!r})["traffic"])
        # one pass of the mix by the harness, then each client's own
        warm = (1 + run.CONNECTION_WARM_PASSES * traffic["clients"]) \
            * len(traffic["queries"])

        def collect(self, df, *a, **k):
            t = served(self, df, *a, **k)
            count[0] += 1
            return alter(t) if count[0] == warm + 1 else t    # first timed
        PlanClient.collect = collect
    elif FAULT == "batch_lost":
        whole = plans.scanner

        def scanner(written, query):
            big = max(query.TABLES, key=lambda t: written[t]["rows"])
            paths = written[big]["paths"]
            assert len(paths) > 1
            half = dict(written[big], paths=paths[:len(paths) // 2])
            short = dict(written, **{{big: half}})
            return whole(short, query)
        plans.scanner = scanner
        run.data.ROWS_PER_FILE = 1 << 12      # several files at this size
    sys.exit(run.main({args!r}))
''')


@pytest.mark.parametrize("fault", ["answer_altered", "batch_lost"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_reads_not_correct(cell, fault, tmp_path):
    args = ["--workload", cell, "--seed", str(2 ** 31 + 33), "--seconds",
            "2", "--trace", "0", "--rehearsal", "--work-dir",
            str(tmp_path / "work")]
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER.format(bench=BENCH, fault=fault, cell=cell, args=args))
    rc, last, out, err = run_cli(str(driver), [])
    assert rc == 0, err[-3000:]
    assert last["correct"] is False
    assert last["failed"] == 0
    broken = [n for n, c in last["compared"].items()
              if c["limit"] is not None and c["value"] > c["limit"]]
    assert broken, last["compared"]
    # and the same run with nothing broken is correct
    if fault == "answer_altered":
        driver.write_text(DRIVER.format(bench=BENCH, fault="none", cell=cell,
                                        args=args))
        rc, last, out, err = run_cli(str(driver), [])
        assert rc == 0 and last["correct"] is True
