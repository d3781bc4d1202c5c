"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the CLIENT: it makes the cell's tables from ``--seed``,
starts one server child as a deployment does, warms each query of the
traffic mix, drives the measured window through ``PlanClient.collect``
and, once the window has closed and the server has stopped, compares every
timed reply with the plain reference. It never initialises a JAX backend, so
it never holds the chip its server needs. Everything that belongs to one
cell, configuration, traffic mix, query or per-layer metric is a data file
or a small module found by name (``rtbench/loader.py``); this file holds no
such name.

The last line of standard output is the result object. ``--rehearsal`` runs
the same path on a CPU server at the traffic file's rehearsal scale: the
device it names is ``cpu`` and it is never a chip result.
"""

import time

T_START = time.perf_counter()

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import signal               # noqa: E402
import sys                  # noqa: E402
import threading            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from rtbench import (compare, data, launcher, loader, plans,   # noqa: E402
                     touched, window, xplane)

SERVER_READY_TIMEOUT_S = 300
WARMUP_TIMEOUT_S = 1100     # a cell's first run in a checkout compiles
CONTROL_TIMEOUT_S = 120
SLICE_SECONDS = 10.0        # the traced slice: whole queries for about this
# a shape's first executions run slower, on a server and on a connection:
# each client submits the mix this often on its own connection before the
# window, and again until the connection is this old (PERF.md section 5)
CONNECTION_WARM_PASSES = 2
CONNECTION_WARM_S = 20.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU server, rehearsal scale; never a chip result")
    p.add_argument("--work-dir", default=None,
                   help="where data, trace and control files go (default: "
                        "benchmarks/.work inside the checkout)")
    return p.parse_args(argv)


class Tracer:
    """Asks ``serve_traced.py`` to start the profiler before client 0's
    first submission of the window and to stop it after the first reply that
    comes ``SLICE_SECONDS`` later, so the slice holds whole queries: as many
    as fit, and one where a query is longer than that. Where the window goes
    on after its first query, that query's trace is thrown away and the
    profiler started anew: the slice begins at the window's second query,
    and at its first only where the window holds a single one."""

    def __init__(self, control):
        self.control = control
        self.trace_dir = os.path.join(control, "trace")
        self.begin = self.end = None        # perf_counter around the slice
        self.first = None                   # client 0's queries before it

    def _ask(self, verb):
        done = os.path.join(self.control, verb + ".done")
        if os.path.exists(done):
            os.remove(done)
        with open(os.path.join(self.control, verb), "w"):
            pass
        limit = time.perf_counter() + CONTROL_TIMEOUT_S
        while not os.path.exists(done):
            if time.perf_counter() > limit:
                raise loader.BenchmarkError(
                    f"the traced server did not answer {verb!r}")
            time.sleep(0.005)

    def between(self, mine):
        """``mine``: client 0's records so far, before its next submission;
        None once the window is over."""
        if mine is None:
            if self.begin is not None and self.end is None:
                self._stop()
        elif self.end is not None:
            pass
        elif self.begin is None:
            self._start(0)
        elif self.first == 0 and len(mine) == 1:
            self._ask("stop")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self._start(1)
        elif time.perf_counter() - self.begin >= SLICE_SECONDS:
            self._stop()

    def _start(self, first):
        self._ask("start")
        self.begin, self.first = time.perf_counter(), first

    def _stop(self):
        self.end = time.perf_counter()
        self._ask("stop")

    def holds(self, record):
        return self.begin <= record.submit and record.reply <= self.end


def _terminated(signum, frame):
    # unwind through the ``finally`` that stops the server child
    raise SystemExit(128 + signum)


def main(argv=None):
    args = parse(argv)
    signal.signal(signal.SIGTERM, _terminated)
    bench = loader.benchmark()
    cell = loader.cell(bench, args.workload)
    config = loader.config(bench, cell["config"])
    traffic = loader.traffic(cell["traffic"])
    if not os.path.isdir(os.path.join(loader.REPO, "spark_rapids_tpu")):
        raise loader.BenchmarkError(
            "the system under test is not in this directory")
    family = config["family"]
    entries = traffic["queries"]
    queries = [loader.query(family, e["query"]) for e in entries]
    params = [loader.query_params(q, e, args.rehearsal)
              for q, e in zip(queries, entries)]
    scale = float(traffic.get("rehearsal_scale", 0.01)) \
        if args.rehearsal else 1.0

    work = args.work_dir or os.path.join(HERE, ".work")
    run_dir = os.path.join(work, f"run-{cell['name']}-{args.seed}"
                           + ("-t" if args.trace else ""))
    data_dir = os.path.join(work, f"data-{cell['config']}-{args.seed}"
                            + ("-r" if args.rehearsal else ""))
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    control = os.path.join(run_dir, "control") if args.trace else None

    if args.rehearsal:
        # the server child inherits these. Tests run several rehearsals at
        # once: each its own cache, so that compiles_in_window counts this
        # run's programs alone
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            run_dir, "jaxcache")

    # build the engine's native library here, before the server needs it
    # (chip_smoke.py's rule): a failed build leaves the engine on its Python
    # paths, and the numbers would be another system's
    from spark_rapids_tpu.utils import native
    if native.load_error() is not None:
        raise loader.BenchmarkError(
            f"native library: {native.load_error()}")

    server, making = None, None
    try:
        # the server first: it takes the chip, or shows there is none,
        # while a thread makes the tables
        server = launcher.Server(traced_control_dir=control)
        tables = sorted({t for q in queries for t in q.TABLES})
        made = {}

        def make():
            t0 = time.perf_counter()
            made["written"] = data.write_tables(config, scale, args.seed,
                                                tables, data_dir)
            made["seconds"] = time.perf_counter() - t0
        making = threading.Thread(target=make, name="datagen")
        making.start()
        port = server.wait_ready(SERVER_READY_TIMEOUT_S)
        result = measure(args, bench, cell, config, traffic, queries, params,
                         made, making, server, port, control)
    finally:
        if server is not None:
            server.kill()
        if making is not None:
            making.join()
        shutil.rmtree(data_dir, ignore_errors=True)
        shutil.rmtree(run_dir, ignore_errors=True)

    from jax._src import xla_bridge
    if xla_bridge._backends:
        raise loader.BenchmarkError(
            f"the client process initialised a JAX backend: "
            f"{list(xla_bridge._backends)}")
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


def measure(args, bench, cell, config, traffic, queries, params, made,
            making, server, port, control):
    from spark_rapids_tpu import compile_cache
    from spark_rapids_tpu.server.client import PlanClient

    conf = dict(config.get("conf") or {}, **(traffic.get("conf") or {}))
    if args.trace:
        conf["spark.rapids.tpu.trace.enabled"] = True
    timeout_s = float(traffic["timeout_s"])

    def connect(timeout=timeout_s):
        return PlanClient("127.0.0.1", port, conf=conf, timeout=timeout)

    admin = connect(WARMUP_TIMEOUT_S)
    device = admin.stats()["server"]["device"]
    log(f"device: {device}")
    if args.rehearsal:
        peaks = None
    else:
        if device["platform"] != "tpu":
            raise loader.BenchmarkError(
                f"the server runs on {device['platform']!r}: no accelerator")
        if device["count"] != cell["chips"]:
            raise loader.BenchmarkError(
                f"the cell asks for {cell['chips']} chip(s), the server "
                f"found {device['count']}")
        peaks = loader.peaks(device["kind"])

    making.join()
    if "written" not in made:
        raise loader.BenchmarkError("the tables could not be made")
    written = made["written"]
    log(f"data: {({t: written[t]['rows'] for t in written})} in "
        f"{made['seconds']:.1f} s")
    dfs = [q.plan(plans.scanner(written, q), p)
           for q, p in zip(queries, params)]
    explained = [admin.explain(df) for df in dfs]
    # each shape once on this connection: in a checkout's first run it
    # compiles, in every run it loads the programs. The window's clients
    # then warm their own connections (window.run), each query
    # CONNECTION_WARM_PASSES times and until the connection is
    # CONNECTION_WARM_S old
    cold = []
    for df in dfs:
        t0 = time.perf_counter()
        admin.collect(df)
        cold.append(round(time.perf_counter() - t0, 3))
    log(f"first submissions, seconds: {cold}")

    tracer = Tracer(control) if args.trace else None
    entries = {}

    def opened():
        entries["before"] = compile_cache.entry_count()
    records, first, last, warm = window.run(
        connect, dfs, [e.get("weight", 1) for e in traffic["queries"]],
        int(traffic["clients"]), args.seconds, args.seed,
        warm_passes=CONNECTION_WARM_PASSES,
        warm_seconds=0.0 if args.rehearsal else CONNECTION_WARM_S,
        opened=opened, want_trace=bool(args.trace),
        between=tracer.between if tracer else None)
    setup_s = first - T_START
    log(f"warm-up seconds: {warm}")
    entries_after = compile_cache.entry_count()
    device_after = admin.stats()["server"]["device"]
    rc = server.shutdown(admin)
    log(f"server exit code: {rc}")

    # only now the plain reference: the window has closed, the peak has been
    # read, the server and its device state are gone
    done = [r for r in records if r.error is None]
    failed = [r for r in records if r.error is not None]
    for r in failed:
        log(f"failed query: {r.error}")
    read = data.reader(written)
    answers = [q.reference(read, p) for q, p in zip(queries, params)]
    readings = [compare.compare(r.table, answers[r.query],
                                queries[r.query].ORDERED) for r in done]
    compared, correct = compare.verdict(
        readings, len(failed), config["guarantees"]["double_rel_err"])

    window_s = last - first
    seconds = [r.seconds if r.error is None else timeout_s for r in records]
    run = {
        "args": args, "cell": cell, "config": config, "traffic": traffic,
        "queries": queries, "records": records, "done": done,
        "window_s": window_s, "setup_s": setup_s, "seconds": seconds,
        "clients": int(traffic["clients"]), "timeout_s": timeout_s,
        "scanned_rows": [plans.scanned_rows(written, q) for q in queries],
        "touched_bytes": [touched.touched_bytes(config, q) for q in queries],
        "entries_before": entries["before"], "entries_after": entries_after,
        "device_after": device_after, "peaks": peaks, "explained": explained,
        "slice": None, "trace": None,
    }
    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": device_after.get("peakBytesInUse")}
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed)}
    kinds = "per_layer" if args.trace else "end_to_end"
    if args.trace:
        reduce_trace(run, tracer, out_device, result)
    metrics = {}
    for m in bench[kinds]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = loader.metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = out_device
    if args.rehearsal:
        result["rehearsal"] = True
    result["query_seconds"] = [round(x, 3) for x in seconds]
    result["plans"] = explained
    result["compared"] = compared
    return result


def reduce_trace(run, tracer, out_device, result):
    """The traced slice: the queries that lie in it, and what the
    profiler's trace says of the device while they ran. The slice's length
    is the trace's own, profiler start to stop."""
    if tracer.begin is None or tracer.end is None:
        raise loader.BenchmarkError("the window held no query: nothing "
                                    "was traced")
    run["slice"] = {"records": [r for r in run["records"]
                                if tracer.holds(r)]}
    path = xplane.find_trace(tracer.trace_dir)
    if path is None:
        raise loader.BenchmarkError("the traced server wrote no trace")
    log(f"trace: {path} ({os.path.getsize(path)} bytes), client 0's "
        f"queries from number {tracer.first + 1} on, "
        f"{len(run['slice']['records'])} in the slice")
    reduced = xplane.reduce(xplane.load(path))
    if reduced is None:
        if run["args"].rehearsal:
            return      # a CPU trace has no device plane: no device numbers
        raise loader.BenchmarkError(
            "no operation ran on the device in the traced slice")
    run["trace"] = reduced
    out_device["busy_s"] = reduced["busy_s"]
    out_device["window_s"] = reduced["window_s"]
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}


if __name__ == "__main__":
    try:
        sys.exit(main())
    except loader.BenchmarkError as e:
        log(f"benchmark error: {e}")
        sys.exit(1)
