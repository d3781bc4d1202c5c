"""Exchange microbench: make ici_exchange explain itself.

The last driver-verified BENCH number put ici_exchange at 0.384x vs a
single-core pyarrow oracle, and the r5 verdict asked (Next #3) for a
device-only microbench that times the MESH ALL_TO_ALL and the
HOST-MEDIATED exchange separately, so the fused-collective path and the
host-boundary path stop being one opaque number.

Four timed sections over the same hash-partitioned table:

  mesh_all_to_all   shard_map + jax.lax.all_to_all row routing
                    (parallel/mesh.py mesh_exchange) on the visible
                    device mesh — the ICI data plane, no host boundary.
  host_exchange     ShuffleExchangeExec write+read: device partition-id
                    eval + per-partition slicing, catalog-registered
                    pieces, coalesced reads. Host-mediated control, data
                    stays on device.
  wire_serialize    the host BOUNDARY itself: framing every partition for
                    the wire, old per-array path vs the serialize-once
                    packed path (pack -> frame straight from the packed
                    buffer), synchronous vs pipelined (D2H of partition
                    P+1 overlapped with framing/compression of P).
  dict_partition    compressed execution (dictenc.py) on a STRING-HEAVY
                    table: hash partitioning + exchange + wire framing
                    with dictionary-encoded string columns (dict + codes)
                    vs the padded byte-matrix form, over the host exchange
                    path and the mesh all_to_all path (the mesh stack
                    decodes at the boundary — measured as such).

Run on any backend (`JAX_PLATFORMS=cpu python tools/exchange_microbench.py`
uses the virtual multi-device CPU mesh); on the real chip the mesh section
is the ICI number. Prints one JSON line per section plus a summary table.
"""

from __future__ import annotations

import json
import os
import sys
import time

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # virtual multi-device mesh for CPU runs (same trick as tests/conftest)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ROWS = int(os.environ.get("XBENCH_ROWS", 1 << 19))
N_PARTS = int(os.environ.get("XBENCH_PARTS", 8))
REPS = int(os.environ.get("XBENCH_REPS", 5))


def _table(n):
    rng = np.random.default_rng(17)
    import pyarrow as pa
    return pa.table({
        "k": rng.integers(0, 1 << 20, n).astype(np.int64),
        "v": rng.uniform(-1e3, 1e3, n),
        "g": rng.integers(0, 64, n).astype(np.int32),
    })


def _time(fn, reps=REPS):
    """Min over reps (this class of host is noisy; the round-5 notes used
    the same discipline)."""
    return _time_group([fn], reps)[0]


def _time_group(fns, reps=REPS):
    """Time alternatives INTERLEAVED (A/B/A/B...), min per alternative —
    so drift on a loaded host hits every alternative equally."""
    for fn in fns:
        fn()                                 # warmup / compile
    best = [float("inf")] * len(fns)
    out = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            out[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return list(zip(best, out))


def _emit(section, dt, note="", **extra):
    row = {"section": section, "ms": round(dt * 1e3, 2), **extra}
    if note:
        row["note"] = note
    print(json.dumps(row), flush=True)
    return row


def bench_mesh_all_to_all(batch, schema):
    """shard_map + all_to_all row routing — the ICI data plane."""
    import jax
    if not hasattr(jax, "shard_map") and not hasattr(
            getattr(jax, "experimental", None), "shard_map"):
        return None, "jax.shard_map unavailable in this environment"
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from spark_rapids_tpu.parallel.mesh import (mesh_exchange,
                                                stack_batches)
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map  # noqa: F401
    devs = jax.devices()
    n_dev = min(len(devs), N_PARTS)
    mesh = Mesh(np.array(devs[:n_dev]), ("data",))
    from spark_rapids_tpu.exec.common import slice_batch
    per = batch.capacity // n_dev
    shards = [jax.jit(slice_batch, static_argnums=3)(
        batch, i * per, per, per) for i in range(n_dev)]
    stacked = stack_batches(shards, schema)

    from functools import partial
    from jax.sharding import PartitionSpec as P

    def local(b):
        pids = (b.columns[0].data % n_dev).astype(jnp.int32)
        return mesh_exchange(b, pids, n_dev)

    sm = shard_map(local, mesh=mesh, in_specs=P("data"),
                   out_specs=P("data"))
    fn = jax.jit(lambda s: sm(s))

    def run():
        out = fn(stacked)
        jax.block_until_ready(out.columns[0].data)
        return out
    dt, _ = _time(run)
    return dt, f"{n_dev} devices"


def bench_host_exchange(table):
    """ShuffleExchangeExec full write+read (device-resident pieces).
    ONE exec is reused across reps (do_close resets the materialized
    state) so the timing is the steady-state data path, not per-instance
    XLA retracing."""
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], N_PARTS),
                             InMemoryScanExec(table))

    def run():
        rows = 0
        for p in range(ex.num_partitions):
            for b in ex.do_execute_partition(p):
                rows += int(b.num_rows)
        ex.do_close()        # reset: the next rep rematerializes
        return rows
    return _time(run)


def bench_wire_serialize(table):
    """The host boundary: frame every partition for the wire."""
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    from spark_rapids_tpu.shuffle.serializer import (batch_to_arrays,
                                                     serialize_host)
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], N_PARTS),
                             InMemoryScanExec(table))
    ex.partition_row_counts()        # materialize once, outside the timers
    parts = ex._materialize()

    def run_legacy():
        # r5 path: per-partition D2H flatten to an array dict, then frame
        # each array through its own tobytes round-trip — all sequential
        total = 0
        for pieces in parts:
            for sb, _rows in pieces:
                b = sb.get()
                try:
                    arrays = batch_to_arrays(b)
                finally:
                    sb.done_with()
                total += len(serialize_host(arrays, int(b.num_rows),
                                            "lz4"))
        return total

    def run_packed(depth):
        total = 0
        for _p, frames in ex.serialized_partitions(codec="lz4",
                                                   depth=depth):
            total += sum(len(f) for f in frames)
        return total

    legacy, packed_sync, packed_pipe = _time_group(
        [run_legacy, lambda: run_packed(0), lambda: run_packed(2)])
    ex.close()
    return legacy, packed_sync, packed_pipe


def _string_table(n):
    """String-heavy shape: one wide low-cardinality string (city names,
    24 bytes) + one tiny flag string — the padded byte matrix dominates
    the wire bytes, the dictionaries stay small."""
    rng = np.random.default_rng(23)
    import pyarrow as pa
    cities = np.array([f"city_{i:04d}_{'x' * 14}" for i in range(512)])
    status = np.array(["ACTIVE", "INACTIVE", "PENDING", "CLOSED"])
    return pa.table({
        "k": rng.integers(0, 1 << 20, n).astype(np.int64),
        "city": pa.array(cities[rng.integers(0, 512, n)]),
        "status": pa.array(status[rng.integers(0, 4, n)]),
        "v": rng.uniform(-1e3, 1e3, n),
    })


def _dict_encode_table(table):
    from spark_rapids_tpu.dictenc import dictionary_encode_arrow
    return dictionary_encode_arrow(table)


def bench_dict_partition():
    """dict+codes vs padded bytes through the STRING-keyed exchange."""
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    n = min(N_ROWS, 1 << 18)          # strings are ~5x the bytes of ints
    plain = _string_table(n)
    enc = _dict_encode_table(plain)

    def exchange(t):
        ex = ShuffleExchangeExec(HashPartitioning([col("city")], N_PARTS),
                                 InMemoryScanExec(t))

        def run():
            rows = 0
            for p in range(ex.num_partitions):
                for b in ex.do_execute_partition(p):
                    rows += int(b.num_rows)
            ex.do_close()
            return rows
        return run

    def wire(t):
        ex = ShuffleExchangeExec(HashPartitioning([col("city")], N_PARTS),
                                 InMemoryScanExec(t))
        ex.partition_row_counts()

        def run():
            total = 0
            for _p, frames in ex.serialized_partitions(codec="none"):
                total += sum(len(f) for f in frames)
            return total
        return run

    (xp, _), (xe, _) = _time_group([exchange(plain), exchange(enc)])
    (wp, nbp), (we, nbe) = _time_group([wire(plain), wire(enc)])
    return n, (xp, xe), (wp, nbp, we, nbe)


def bench_dict_mesh():
    """Mesh all_to_all over the string-heavy shape, padded vs encoded
    input. stack_batches decodes dict strings at the mesh boundary (the
    device-axis stack has no per-shard dictionary slot), so the encoded
    number measures decode-at-boundary + the same collective — the
    honest cost of entering the ICI path from compressed form."""
    from spark_rapids_tpu.batch import from_arrow
    n = min(N_ROWS, 1 << 17)
    plain = _string_table(n)
    enc = _dict_encode_table(plain)
    pb, schema = from_arrow(plain)
    eb, _ = from_arrow(enc, schema=schema)
    dtp, note = bench_mesh_all_to_all(pb, schema)
    if dtp is None:
        return None, note, None
    dte, _ = bench_mesh_all_to_all(eb, schema)
    return dtp, note, dte


def bench_scan_prefetch(table):
    """Scan-side prefetch overlap (pipeline.py), measured honestly:

    - MULTITHREADED is reported as ONE number: its bounded_map window
      already keeps decode futures in flight between pulls — it IS a
      prefetch pipeline, and adding a second handoff stage measurably
      regressed on small hosts, so read_split skips the stage there.
    - PERFILE decodes on the consumer thread, so it isolates the
      primitive's decode(N+1)/consume(N) overlap. The consumer waits
      off-CPU per batch: on JAX_PLATFORMS=cpu a real device program
      would fight the decoder for the same host cores, while on the
      real chip device time IS off-CPU — which is exactly what the wait
      models (labeled as simulation)."""
    import tempfile
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io.parquet import ParquetSource
    from spark_rapids_tpu.io.source import ReaderType
    tmp = tempfile.mkdtemp(prefix="xbench_scan_")
    n_files = 16
    per = table.num_rows // n_files
    paths = []
    for i in range(n_files):
        p = os.path.join(tmp, f"part-{i}.parquet")
        pq.write_table(table.slice(i * per, per), p)
        paths.append(p)

    def run(reader, depth, device_ms=4.0):
        src = ParquetSource(paths, reader_type=reader, batch_rows=per)
        src._prefetch_depth = depth
        rows = 0
        for t in src.read_split(src.files):
            rows += t.num_rows
            time.sleep(device_ms / 1e3)       # simulated off-CPU device
        return rows

    (mt, _), = _time_group([lambda: run(ReaderType.MULTITHREADED, 2)])
    pf = _time_group([lambda: run(ReaderType.PERFILE, 0),
                      lambda: run(ReaderType.PERFILE, 2)])
    return mt, pf[0][0], pf[1][0]


def main():
    import pyarrow as pa  # noqa: F401
    from spark_rapids_tpu.batch import from_arrow
    table = _table(N_ROWS)
    batch, schema = from_arrow(table)
    rows = []
    print(f"# exchange microbench: {N_ROWS} rows, {N_PARTS} partitions, "
          f"{REPS} reps, platform="
          f"{__import__('jax').devices()[0].platform}", flush=True)

    try:
        dt, note = bench_mesh_all_to_all(batch, schema)
        if dt is None:
            _emit("mesh_all_to_all", 0.0, note=f"SKIPPED: {note}")
        else:
            rows.append(_emit("mesh_all_to_all", dt, note=note,
                              Mrows_per_s=round(N_ROWS / dt / 1e6, 1)))
    except Exception as e:
        _emit("mesh_all_to_all", 0.0,
              note=f"SKIPPED: {type(e).__name__}: {e}")

    dt, _ = bench_host_exchange(table)
    rows.append(_emit("host_exchange", dt,
                      Mrows_per_s=round(N_ROWS / dt / 1e6, 1)))

    (dtl, nb), (dts, _), (dtp, _) = bench_wire_serialize(table)
    rows.append(_emit("wire_serialize_legacy", dtl,
                      MB=round(nb / 1e6, 1),
                      Mrows_per_s=round(N_ROWS / dtl / 1e6, 1)))
    rows.append(_emit("wire_serialize_packed", dts,
                      Mrows_per_s=round(N_ROWS / dts / 1e6, 1)))
    rows.append(_emit("wire_serialize_packed_pipelined", dtp,
                      Mrows_per_s=round(N_ROWS / dtp / 1e6, 1),
                      note="D2H of P+1 overlaps framing of P"))

    nd, (xp, xe), (wp, nbp, we, nbe) = bench_dict_partition()
    rows.append(_emit("dict_exchange_padded", xp,
                      Mrows_per_s=round(nd / xp / 1e6, 1),
                      note=f"string-keyed exchange, {nd} rows"))
    rows.append(_emit("dict_exchange_encoded", xe,
                      Mrows_per_s=round(nd / xe / 1e6, 1),
                      note="dict+codes: murmur3 per DISTINCT entry + "
                           "gather; codes through the slice kernels"))
    rows.append(_emit("dict_wire_padded", wp, MB=round(nbp / 1e6, 1),
                      Mrows_per_s=round(nd / wp / 1e6, 1)))
    rows.append(_emit("dict_wire_encoded", we, MB=round(nbe / 1e6, 1),
                      Mrows_per_s=round(nd / we / 1e6, 1),
                      note=f"dict+codes frames: {nbe / nbp:.2f}x the "
                           f"padded bytes"))

    try:
        dtp, mnote, dte = bench_dict_mesh()
        if dtp is None:
            _emit("dict_mesh", 0.0, note=f"SKIPPED: {mnote}")
        else:
            rows.append(_emit("dict_mesh_padded", dtp, note=mnote))
            rows.append(_emit("dict_mesh_encoded", dte, note=mnote +
                              "; decode-at-boundary included"))
    except Exception as e:
        _emit("dict_mesh", 0.0, note=f"SKIPPED: {type(e).__name__}: {e}")

    mt, pf0, pf2 = bench_scan_prefetch(table)
    rows.append(_emit("scan_multithreaded", mt,
                      Mrows_per_s=round(N_ROWS / mt / 1e6, 1),
                      note="4ms simulated off-CPU device wait per batch; "
                           "the reader pool window is its own prefetch"))
    rows.append(_emit("scan_perfile_sync", pf0,
                      Mrows_per_s=round(N_ROWS / pf0 / 1e6, 1),
                      note="prefetch.depth=0, same off-CPU wait"))
    rows.append(_emit("scan_perfile_prefetch", pf2,
                      Mrows_per_s=round(N_ROWS / pf2 / 1e6, 1),
                      note="prefetch.depth=2: decode N+1 hides behind "
                           "the off-CPU wait of N (the real-chip shape)"))

    print("\n| section | ms | Mrows/s |")
    print("|---|---|---|")
    for r in rows:
        print(f"| {r['section']} | {r['ms']} | "
              f"{r.get('Mrows_per_s', '-')} |")


if __name__ == "__main__":
    main()
