"""Chip smoke: three real-size queries served from ONE chip by
``python -m spark_rapids_tpu.server``, each answered twice and checked
against pyarrow — the quickest proof that the system still starts on the
device.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the mesh data plane, on four chips

This process is the CLIENT. It imports pyarrow and the plan-builder surface,
never initialises a JAX backend (checked before exit), and so never holds
the chip its server child needs. It

1. writes TPC-H-shaped Parquet made from ``--seed`` under ``.chip_smoke/``
   (2**24 lineitem rows in 8 files, 2**24 store_sales rows over 2**20 keys,
   a 2**22 x 2**19 join) with bench.py's generators;
2. starts one server as a deployment does and waits for its readiness line;
3. submits, through ``PlanClient``, the Q1 stage, the high-cardinality
   group-by and join -> sort -> limit, each scanning those files by path,
   each twice (cold, then repeat);
4. compares every reply with pyarrow compute on the same files (ints and
   counts exact, doubles to the differential tests' tolerance);
5. asks the server which device it ran on, stops it through the
   ``shutdown`` op and checks its exit code.

Every phase prints one JSON object per line; the LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only when every phase passed on a TPU. Under ``JAX_PLATFORMS=cpu`` (a
rehearsal: ``--rows 4096 --allow-cpu``) the last line never says ok and the
exit code is non-zero.

``--chips 4`` runs ONLY the four-chip phase, in this process: one Session
in ICI shuffle mode over a 4-device mesh runs the shuffled-join -> group-by
-> sort query of ``__graft_entry__.multichip_query`` at 2**22 fact rows, and
the same query through the host-mediated exchange, and compares the two.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

REL_TOL = 1e-6      # tests/harness/asserts.py: assert_rows_equal's default
ABS_TOL = 1e-9
QUERY_TIMEOUT_S = 1000.0    # per reply; the whole smoke has 1200 s
# f64 sums and averages run on the device only with this on (the planner
# otherwise keeps them on the CPU: the chip carries f64 as an f32 pair,
# docs/tpu_compat.md). The tolerance above is what the replies are held to.
QUERY_CONF = {"spark.rapids.tpu.sql.incompatibleOps.enabled": True}
READY_PREFIX = "spark-rapids-tpu plan server listening on "


def emit(**obj):
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def write_tables(out_dir, rows, seed):
    """The three query inputs as Parquet files; returns {name: [paths]} and
    emits rows and bytes per table."""
    import bench
    n_keys = max(rows >> 4, 16)
    stream, build = bench.join_tables(max(rows >> 2, 64), max(rows >> 5, 8),
                                      seed=seed + 2)
    tables = {
        "lineitem": (bench.lineitem_table(rows, seed=seed), 8),
        "store_sales": (bench.store_sales_table(rows, n_keys, seed=seed + 1),
                        8),
        "join_stream": (stream, 4),
        "join_build": (build, 1),
    }
    paths = {}
    for name, (table, n_files) in tables.items():
        per = -(-table.num_rows // n_files)
        paths[name] = []
        for i in range(n_files):
            p = os.path.join(out_dir, f"{name}-{i}.parquet")
            pq.write_table(table.slice(i * per, per), p)
            paths[name].append(p)
        emit(phase="data", table=name, rows=table.num_rows,
             arrow_bytes=table.nbytes, files=n_files,
             parquet_bytes=sum(os.path.getsize(p) for p in paths[name]))
    return paths


# ---------------------------------------------------------------------------
# the three plans, and pyarrow's answer to each
# ---------------------------------------------------------------------------

def scan(paths):
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.plan.logical import DataFrame, LogicalScan
    src = ParquetSource(paths)
    return DataFrame(LogicalScan((), source=src, _schema=src.schema()))


def q1_stage(paths):
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
    return (scan(paths["lineitem"])
            .where(col("l_shipdate") <= lit(10471))
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"),
                    (col("l_extendedprice") * (lit(1.0) - col("l_discount")))
                    .alias("disc_price"))
            .group_by("l_returnflag", "l_linestatus")
            .agg(Sum(col("l_quantity")).alias("sum_qty"),
                 Sum(col("l_extendedprice")).alias("sum_base_price"),
                 Sum(col("disc_price")).alias("sum_disc_price"),
                 Average(col("l_quantity")).alias("avg_qty"),
                 Average(col("l_discount")).alias("avg_disc"),
                 Count().alias("count_order")))


def q1_oracle(paths):
    t = pa.concat_tables(pq.read_table(p) for p in paths["lineitem"])
    f = t.filter(pc.less_equal(t.column("l_shipdate"), 10471))
    f = f.append_column("disc_price", pc.multiply(
        f.column("l_extendedprice"),
        pc.subtract(1.0, f.column("l_discount"))))
    return f.group_by(["l_returnflag", "l_linestatus"]).aggregate(
        [("l_quantity", "sum"), ("l_extendedprice", "sum"),
         ("disc_price", "sum"), ("l_quantity", "mean"),
         ("l_discount", "mean"), ("l_quantity", "count")])


def hash_agg(paths):
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
    return (scan(paths["store_sales"]).group_by("ss_item_sk")
            .agg(Sum(col("ss_quantity")).alias("sq"),
                 Sum(col("ss_net_profit")).alias("sp"),
                 Average(col("ss_sales_price")).alias("ap"),
                 Count().alias("c")))


def hash_agg_oracle(paths):
    t = pa.concat_tables(pq.read_table(p) for p in paths["store_sales"])
    return t.group_by(["ss_item_sk"]).aggregate(
        [("ss_quantity", "sum"), ("ss_net_profit", "sum"),
         ("ss_sales_price", "mean"), ("ss_item_sk", "count")])


JOIN_LIMIT = 100


def join_sort(paths):
    from spark_rapids_tpu.exec.join import JoinType
    from spark_rapids_tpu.exec.sort import desc
    from spark_rapids_tpu.expressions import col
    return (scan(paths["join_stream"])
            .join(scan(paths["join_build"]), ["l_orderkey"], ["o_orderkey"],
                  JoinType.INNER)
            .order_by(desc(col("l_revenue"))).limit(JOIN_LIMIT))


def join_sort_oracle(paths):
    stream = pa.concat_tables(pq.read_table(p) for p in paths["join_stream"])
    build = pq.read_table(paths["join_build"][0])
    top = stream.take(pc.select_k_unstable(
        stream, k=JOIN_LIMIT, sort_keys=[("l_revenue", "descending")]))
    j = top.join(build, keys="l_orderkey", right_keys="o_orderkey",
                 join_type="inner")
    # the engine's join output keeps both key columns
    j = j.append_column("o_orderkey", j.column("l_orderkey"))
    return j.select(["l_orderkey", "l_revenue", "o_orderkey", "o_custkey"]) \
        .sort_by([("l_revenue", "descending")])


def compare(name, got, expected, n_keys=0):
    """Row-for-row: ints and counts exact, doubles within REL_TOL/ABS_TOL.
    A group-by's rows come in any order: they are matched on their
    ``n_keys`` leading key columns. 0 keys: the order is part of the answer."""
    if got.num_rows != expected.num_rows \
            or got.num_columns != expected.num_columns:
        raise SmokeFailure(
            f"{name}: shape {got.num_rows}x{got.num_columns} != "
            f"{expected.num_rows}x{expected.num_columns}")
    if n_keys:
        got = got.sort_by([(got.column_names[i], "ascending")
                           for i in range(n_keys)])
        expected = expected.sort_by([(expected.column_names[i], "ascending")
                                     for i in range(n_keys)])
    for i in range(got.num_columns):
        a = got.column(i).to_numpy(zero_copy_only=False)
        e = expected.column(i).to_numpy(zero_copy_only=False)
        if np.issubdtype(e.dtype, np.floating):
            ok = np.isclose(a.astype(np.float64), e, rtol=REL_TOL,
                            atol=ABS_TOL, equal_nan=True)
        else:
            ok = a == e
        if not ok.all():
            bad = int(np.argmin(ok))
            raise SmokeFailure(
                f"{name}: column {got.column_names[i]} row {bad}: "
                f"{a[bad]!r} != {e[bad]!r} "
                f"({int((~ok).sum())} of {len(ok)} rows differ)")


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------

class Server:
    """``python -m spark_rapids_tpu.server --port 0`` as a child process."""

    def __init__(self, ready_timeout_s):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "spark_rapids_tpu.server", "--port", "0"],
            cwd=HERE, stdout=subprocess.PIPE, text=True)
        self.port = None
        ready = threading.Event()

        def pump():
            # the readiness line, then whatever else the server prints
            for line in self.proc.stdout:
                if self.port is None and line.startswith(READY_PREFIX):
                    self.port = int(line.rsplit(":", 1)[1])
                    ready.set()
                else:
                    print(f"[server] {line.rstrip()}", file=sys.stderr)
            ready.set()

        threading.Thread(target=pump, daemon=True).start()
        ready.wait(ready_timeout_s)
        if self.port is None:
            self.kill()
            raise SmokeFailure(
                f"server gave no readiness line within {ready_timeout_s}s "
                f"(exit code {self.proc.poll()})")

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def served_phase(args):
    from spark_rapids_tpu import compile_cache
    from spark_rapids_tpu.server.client import PlanClient
    from spark_rapids_tpu.utils import native

    # build the native library here, before the server needs it: a failed
    # build leaves the engine on its Python paths, and nobody would know
    native_error = native.load_error()
    emit(phase="native", loaded=native_error is None, error=native_error)
    if native_error is not None:
        raise SmokeFailure(f"native library: {native_error}")

    # one directory per run: two smokes in one checkout (the tests run
    # several) must not delete each other's files
    data_dir = os.path.join(HERE, ".chip_smoke", f"run-{os.getpid()}")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    emit(phase="compile_cache", dir=compile_cache.cache_dir(),
         entries_at_start=compile_cache.entry_count())
    server = None
    try:
        # the server first: it takes the chip, or shows that there is none
        # before a second is spent on data
        t0 = time.perf_counter()
        server = Server(ready_timeout_s=300)
        emit(phase="server", port=server.port,
             ready_seconds=round(time.perf_counter() - t0, 3))
        client = PlanClient("127.0.0.1", server.port, conf=QUERY_CONF,
                            timeout=QUERY_TIMEOUT_S)
        device = client.stats()["server"]["device"]
        emit(phase="server", device=device)
        if device["platform"] != "tpu" and not args.allow_cpu:
            raise SmokeFailure(
                f"the server runs on {device['platform']!r}, not on a TPU")

        t0 = time.perf_counter()
        paths = write_tables(data_dir, args.rows, args.seed)
        emit(phase="data", seconds=round(time.perf_counter() - t0, 3))

        queries = [("q1_stage", q1_stage, q1_oracle, 2),
                   ("hash_agg", hash_agg, hash_agg_oracle, 1),
                   ("join_sort", join_sort, join_sort_oracle, 0)]
        expected = {}
        for name, _, oracle, _ in queries:
            t0 = time.perf_counter()
            expected[name] = oracle(paths)
            emit(phase="oracle", query=name, rows=expected[name].num_rows,
                 seconds=round(time.perf_counter() - t0, 3))

        for name, build, _, n_keys in queries:
            df = build(paths)
            plan = client.explain(df)
            on_cpu = [ln.strip() for ln in plan.splitlines()
                      if ln.lstrip().startswith("!")]
            emit(phase="explain", query=name, plan=plan.splitlines(),
                 on_cpu=on_cpu)
            if on_cpu:
                raise SmokeFailure(f"{name}: operators fell back: {on_cpu}")
            for attempt in ("cold", "repeat"):
                before = compile_cache.entry_count()
                t0 = time.perf_counter()
                got = client.collect(df)
                seconds = time.perf_counter() - t0
                added = compile_cache.entry_count() - before
                emit(phase="query", query=name, attempt=attempt,
                     seconds=round(seconds, 3), rows=got.num_rows,
                     execs=client.last_execs,
                     fell_back=client.last_fell_back,
                     cache_entries_added=added)
                if client.last_fell_back:
                    raise SmokeFailure(
                        f"{name}: ran on the CPU: {client.last_fell_back}")
                compare(name, got, expected[name], n_keys)
                if attempt == "repeat" and added:
                    raise SmokeFailure(
                        f"{name}: the repeat submission compiled {added} "
                        f"new programs")
            emit(phase="checked", query=name, equal_to_pyarrow=True)

        device = client.stats()["server"]["device"]
        emit(phase="server", device=device,
             cache_entries=compile_cache.entry_count())
        client._request({"msg": "shutdown"})
        client.close()
        rc = server.proc.wait(timeout=60)
        emit(phase="server", exit_code=rc)
        if rc != 0:
            raise SmokeFailure(f"server exited with code {rc}")
        return device
    finally:
        if server is not None:
            server.kill()
        shutil.rmtree(data_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# four chips: the mesh data plane against the host-mediated exchange
# ---------------------------------------------------------------------------

def mesh_phase(args):
    from spark_rapids_tpu import compile_cache
    emit(phase="compile_cache", dir=compile_cache.enable())
    import jax

    import __graft_entry__ as g
    from spark_rapids_tpu.plan import Session

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit(phase="mesh", device=device)
    if len(devs) != 4:
        raise SmokeFailure(f"--chips 4 needs four devices, found {len(devs)}")

    q = g.multichip_query(args.rows, seed=args.seed)
    ici = Session(dict(g.MULTICHIP_CONF,
                       **{"spark.rapids.tpu.mesh.devices": 4}))
    t0 = time.perf_counter()
    got = ici.collect(q())
    names = ici.executed_exec_names()
    emit(phase="mesh", seconds=round(time.perf_counter() - t0, 3),
         rows=got.num_rows, execs=names)
    if not any(n.startswith("MeshStage") for n in names):
        raise SmokeFailure(f"no MeshStage executed: {names}")

    # the executable MeshStageExec._run executed and the inputs it staged
    stage = ici.last_plan
    shards = stage.staged
    emit(phase="mesh", inputs=shards, lowered=stage.lowered)
    if not shards:
        raise SmokeFailure("the mesh stage staged no input")
    for s in shards:
        if len(set(s["devices"])) != 4 or min(s["rows_per_device"]) <= 0:
            raise SmokeFailure(f"input not spread over four devices: {s}")
    hlo = stage.executed.as_text()
    n_a2a = hlo.count("all-to-all")
    emit(phase="mesh", all_to_all_in_program=n_a2a)
    if n_a2a == 0:
        raise SmokeFailure("the mesh program holds no all-to-all")

    host = Session({k: v for k, v in g.MULTICHIP_CONF.items()
                    if k != "spark.rapids.tpu.shuffle.mode"})
    t0 = time.perf_counter()
    exp = host.collect(q())
    host_names = host.executed_exec_names()
    emit(phase="host_exchange", seconds=round(time.perf_counter() - t0, 3),
         rows=exp.num_rows, execs=host_names)
    if any(n.startswith("MeshStage") for n in host_names) \
            or not any("ShuffleExchange" in n for n in host_names):
        raise SmokeFailure(
            f"the comparison run did not use the host exchange: {host_names}")
    compare("mesh_vs_host_exchange", got, exp)
    emit(phase="checked", query="multichip", equal_to_host_exchange=True)
    return device


# ---------------------------------------------------------------------------

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=20260930)
    p.add_argument("--rows", type=int, default=None,
                   help="lineitem / store_sales / fact rows; for rehearsal "
                        "only (default: 2**24, or 2**22 with --chips 4)")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--allow-cpu", action="store_true",
                   help="rehearsal: run to the end on a CPU server, never "
                        "print ok")
    args = p.parse_args(argv)
    if args.rows is None:
        args.rows = 1 << 22 if args.chips == 4 else 1 << 24

    try:
        device = mesh_phase(args) if args.chips == 4 else served_phase(args)
    except Exception as e:     # whatever went wrong, the last line says so
        import traceback
        traceback.print_exc()
        emit(ok=False, error=f"{type(e).__name__}: {e}")
        return 1
    if args.chips == 1:
        from jax._src import xla_bridge
        if xla_bridge._backends:
            emit(ok=False, error="the client process initialised a JAX "
                 f"backend: {list(xla_bridge._backends)}")
            return 1
    head = {"platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}
    if device["platform"] != "tpu":
        emit(ok=False, device=head,
             error="the queries ran, but not on a TPU"
             + (" (--allow-cpu rehearsal)" if args.allow_cpu else ""))
        return 1
    if device["count"] != args.chips:
        emit(ok=False, device=head,
             error=f"expected {args.chips} device(s)")
        return 1
    emit(ok=True, device=head)
    return 0


if __name__ == "__main__":
    sys.exit(main())
