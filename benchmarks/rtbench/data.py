"""A cell's tables: made from the seed by the configuration's generator,
written as Parquet inside the checkout, read back by the plain reference."""

import os
from concurrent.futures import ThreadPoolExecutor

import pyarrow as pa
import pyarrow.parquet as pq

from . import loader

ROWS_PER_FILE = 1 << 20     # one served scan batch (io/source.py batch_rows)


def write_tables(config, scale, seed, tables, out_dir):
    """Generate ``tables`` and write each as files of at most
    ``ROWS_PER_FILE`` rows under ``out_dir``. Returns
    ``{table: {"paths": [...], "rows": n}}``."""
    os.makedirs(out_dir, exist_ok=True)
    gen = loader.generator(config["generator"])
    out, jobs = {}, []
    for name, table in gen.generate(config, scale, seed,
                                    sorted(tables)).items():
        _check_schema(config, name, table)
        n_files = max(-(-table.num_rows // ROWS_PER_FILE), 1)
        per = -(-table.num_rows // n_files)
        paths = [os.path.join(out_dir, f"{name}-{i:03d}.parquet")
                 for i in range(n_files)]
        jobs += [(table.slice(i * per, per), p) for i, p in enumerate(paths)]
        out[name] = {"paths": paths, "rows": table.num_rows}
    with ThreadPoolExecutor(max_workers=8) as pool:
        for f in [pool.submit(pq.write_table, t, p) for t, p in jobs]:
            f.result()
    return out


def _check_schema(config, name, table):
    want = [(c["name"], c["type"]) for c in config["tables"][name]["columns"]]
    got = [(f.name, str(f.type)) for f in table.schema]
    if want != got:
        raise loader.BenchmarkError(
            f"generator {config['generator']!r} made {name} as {got}, the "
            f"configuration states {want}")


def reader(written):
    """``read(table, columns)`` for the plain reference."""
    def read(table, columns):
        return pa.concat_tables(
            pq.read_table(p, columns=list(columns))
            for p in written[table]["paths"])
    return read
