"""The controls of "how ``correct`` is decided": the plain reference put in
the program's place with one thing about it lowered or broken, read through
the same comparison and against the same limits as a run. A control has to
come out as not correct.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--scale 1.0]

``lower_precision``  the reference's arithmetic in the precision the
                     configuration states as its control
                     (``guarantees.control_precision``, a numpy float type's
                     name: float32 below float64; below exact decimals,
                     float32 sums rounded to cents). Not run where the
                     configuration states none (null, with
                     ``control_precision_why``)
``lost_batch``       the largest table's last file is never read: the
                     guarantee that every row of every scanned file counts
Host code only (numpy, pyarrow); prints one JSON line a seed and control.
"""

import argparse
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from rtbench import compare, data, loader      # noqa: E402


def control_precision(config):
    """The numpy float type the configuration names, or None."""
    name = config["guarantees"]["control_precision"]
    if name is None:
        return None
    kind = getattr(np, name, None)
    if not (isinstance(kind, type) and issubclass(kind, np.floating)):
        raise loader.BenchmarkError(
            f"guarantees.control_precision {name!r} of configuration "
            f"{config['name']!r} is no numpy float type's name (or null)")
    return kind


def lower_precision(query, read, params, precision):
    return query.reference(read, params, money=precision)


def lost_batch(query, read, params, written):
    """The largest table without its last file; where it is one file,
    without that file's second half."""
    big = max(query.TABLES, key=lambda t: written[t]["rows"])
    paths = written[big]["paths"]
    if len(paths) > 1:
        short = dict(written, **{big: dict(written[big], paths=paths[:-1])})
        return query.reference(data.reader(short), params)

    def lossy(table, columns):
        t = read(table, columns)
        return t.slice(0, t.num_rows // 2) if table == big else t
    return query.reference(lossy, params)


def readings(config, traffic, scale, seed, work, rehearsal=False):
    family = config["family"]
    limit = config["guarantees"]["double_rel_err"]
    precision = control_precision(config)
    out_dir = os.path.join(work, f"control-{config['name']}-{seed}")
    out = []
    try:
        for entry in traffic["queries"]:
            query = loader.query(family, entry["query"])
            params = loader.query_params(query, entry, rehearsal)
            written = data.write_tables(config, scale, seed,
                                        sorted(query.TABLES), out_dir)
            read = data.reader(written)
            want = query.reference(read, params)
            answers = {"lost_batch": lost_batch(query, read, params, written)}
            if precision is not None:
                answers["lower_precision"] = lower_precision(
                    query, read, params, precision)
            for name, answer in answers.items():
                r = compare.compare(answer, want, query.ORDERED)
                _, correct = compare.verdict([r], 0, limit)
                out.append({"control": name, "query": entry["query"],
                            "seed": seed, "rows": want.num_rows,
                            "correct": correct, **r})
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--scale", type=float, default=1.0)
    args = p.parse_args(argv)
    bench = loader.benchmark()
    cell = loader.cell(bench, args.workload)
    config = loader.config(bench, cell["config"])
    traffic = loader.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for r in readings(config, traffic, args.scale, seed,
                          os.path.join(HERE, ".work")):
            print(json.dumps(dict(r, workload=cell["name"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
