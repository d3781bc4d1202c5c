"""Whole rehearsal runs with the timed path broken underneath, shared by
``test_fault_answer_altered.py`` and ``test_fault_batch_lost.py``: one file
a fault, so that workers that take whole files run them side by side. Each
run takes the clean rehearsal's own arguments (``benchlib.rehearsal_args``):
``test_rehearsal.py::test_cell_rehearses_end_to_end`` is its twin, the same
run with nothing broken, and reads ``correct`` true."""

import json
import os
import textwrap

from benchlib import BENCH, REPO, rehearsal_args, run_cli

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]

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


def check_not_correct(cell, fault, tmp_path):
    """``cell``'s rehearsal with ``fault`` planted under ``run.main`` ends
    normally, fails no query, and reads ``correct`` false with some number
    compared over its limit."""
    args = rehearsal_args(cell, tmp_path / "work")
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER.format(bench=BENCH, fault=fault, cell=cell,
                                    args=args))
    rc, last, out, err = run_cli(str(driver), [])
    assert rc == 0, err[-3000:]
    assert last["correct"] is False, last["compared"]
    assert last["failed"] == 0, last["failed"]
    broken = [n for n, c in last["compared"].items()
              if c["limit"] is not None and c["value"] > c["limit"]]
    assert broken, last["compared"]
