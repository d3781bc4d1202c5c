#!/usr/bin/env python
"""Chaos soak: peer kills + layered network/OOM fault schedules over
long mixed workloads, asserting zero wrong results and zero leaks.

The standing proof behind the query-recovery plane (ISSUE 11
acceptance): run the five bench shapes through real TcpTransport
exchanges for ``--duration`` seconds while a seeded schedule

- KILLS the primary block server before or mid-way through the reduce
  phase (lineage recompute at ``replicas=0``, replica failover at
  ``replicas=1``),
- layers deterministic NETWORK faults (drop/delay/truncate/corrupt/mix
  — shuffle/netfault.py) over the surviving fetch traffic, and
- layers deterministic OOM injection (memory/retry.py) over the scan
  H2D + recompute paths so recovery itself recovers,

and after EVERY query checks the three invariants the plane promises:

1. results bit-for-bit identical to the clean baseline run,
2. zero leaked catalog pins and zero cached client connections,
3. handler/server threads drained back to the baseline.

Run:  python tools/chaos_soak.py --duration 300 --seed 7
Exit: 0 = soak clean; 1 = any wrong result, leak, or unexpected error.
The summary JSON on stdout carries the recovery counters
(recomputeCount / recomputedPartitions / replicaBytes) so a soak that
never actually exercised recovery is visible, not silently green.

The short pytest wrappers live in tests/test_query_recovery.py: a
couple of rounds run in tier-1; the ≥5-minute soak is behind the
``chaos`` marker (nightly)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

# On the CPU by design: the soak exercises the recovery plane over eight
# virtual devices, never the chip (set before jax imports, as conftest does)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np           # noqa: E402
import pyarrow as pa         # noqa: E402

from spark_rapids_tpu.batch import to_arrow                    # noqa: E402
from spark_rapids_tpu.exec import InMemoryScanExec             # noqa: E402
from spark_rapids_tpu.expressions import col                   # noqa: E402
from spark_rapids_tpu.memory.catalog import device_budget      # noqa: E402
from spark_rapids_tpu.memory.retry import oom_injection        # noqa: E402
from spark_rapids_tpu.shuffle import HashPartitioning          # noqa: E402
from spark_rapids_tpu.shuffle.lineage import (                 # noqa: E402
    LineageRegistry, metrics as lineage_metrics)
from spark_rapids_tpu.shuffle.multithreaded import (           # noqa: E402
    MultithreadedShuffleExchangeExec)
from spark_rapids_tpu.shuffle.netfault import (                # noqa: E402
    net_injection, net_injector)
from spark_rapids_tpu.shuffle.transport import TcpTransport    # noqa: E402
from spark_rapids_tpu import trace as qtrace                   # noqa: E402

N_PARTS = 4
BATCH_ROWS = 700
WINDOW = 64 << 10

#: fault legs the scheduler draws from (weights favor the interesting
#: combinations; "none" keeps a clean control leg in every soak)
KILL_POINTS = ("none", "before_read", "mid_read")
NET_MODES = ("", "every-3", "every-4")
NET_KINDS = ("mix", "drop", "corrupt", "truncate")
OOM_MODES = ("", "every-7")


def make_tables(rows: int):
    """The five bench shapes' keyed tables (bench.py: q1_stage,
    hash_agg, join_sort, parquet_scan, exchange)."""
    def rng(s):
        return np.random.default_rng(s)

    tables = {
        "q1_stage": pa.table({
            "k": rng(3).integers(0, 3, rows).astype(np.int32),
            "l_quantity": rng(3).integers(1, 51, rows).astype(np.int64),
            "l_extendedprice": rng(3).uniform(1.0, 1e5, rows),
        }),
        "hash_agg": pa.table({
            "k": rng(5).integers(0, 256, rows).astype(np.int64),
            "ss_quantity": rng(5).integers(1, 100, rows).astype(np.int64),
        }),
        "join_sort": pa.table({
            "k": rng(9).integers(0, 64, rows).astype(np.int64),
            "v": rng(9).integers(-1000, 1000, rows).astype(np.int64),
            "cls": rng(9).integers(0, 7, rows).astype(np.int64),
        }),
        "parquet_scan": pa.table({
            "k": rng(13).integers(0, 1000, rows).astype(np.int64),
            "v": rng(13).uniform(-10.0, 10.0, rows),
        }),
        "exchange": pa.table({
            "k": rng(11).integers(0, 64, rows).astype(np.int32),
            "v": rng(11).integers(-1000, 1000, rows).astype(np.int64),
        }),
    }
    return tables


def run_query(table: pa.Table, *, replicas: int = 0, kill: str = "none"):
    """One wire-exchange query over a 2-peer topology. The map side
    publishes into the PRIMARY block server (and replicates to the
    second peer when ``replicas``); the reduce side pulls every block
    over the wire; ``kill`` closes the primary before/mid reduce.
    Returns the per-partition arrow tables; raises on leaks."""
    primary = TcpTransport(window_bytes=WINDOW)
    replica = TcpTransport(window_bytes=WINDOW)
    primary.peers = {2: replica.address}       # replication target
    client = TcpTransport(peers={1: primary.address, 2: replica.address},
                          retries=2, connect_timeout_s=2.0,
                          io_timeout_s=2.0, backoff_base_ms=1.0,
                          window_bytes=WINDOW)
    registry = LineageRegistry()
    ex = MultithreadedShuffleExchangeExec(
        HashPartitioning([col("k")], N_PARTS),
        InMemoryScanExec(table, batch_rows=BATCH_ROWS),
        transport=primary, read_transport=client,
        replicas=replicas, lineage_registry=registry)
    try:
        parts = []
        for p in range(N_PARTS):
            if (kill == "before_read" and p == 0) or \
                    (kill == "mid_read" and p == 1):
                primary.close()
            parts.append([to_arrow(b, ex.output_schema)
                          for b in ex.execute_partition(p)])
        return parts
    finally:
        ex.cleanup()
        client.close()
        replica.close()
        primary.close()
        assert not client._conns, "leaked client connections"


def same(parts_a, parts_b) -> bool:
    if len(parts_a) != len(parts_b):
        return False
    for pa_, pb_ in zip(parts_a, parts_b):
        if len(pa_) != len(pb_):
            return False
        for ta, tb in zip(pa_, pb_):
            if not ta.equals(tb):       # bit-for-bit
                return False
    return True


def threads_drained(baseline: int, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if threading.active_count() <= baseline:
            return True
        time.sleep(0.05)
    return False


def soak(duration_s: float, seed: int = 0, rows: int = 3000,
         verbose: bool = True) -> dict:
    """The soak loop; returns the summary dict (see module docstring)."""
    rng = np.random.default_rng(seed)
    tables = make_tables(rows)
    cat = device_budget()
    # clean per-shape baselines, once (these also warm the shared
    # reader/writer pools so the thread baseline is honest)
    baselines = {name: run_query(t) for name, t in tables.items()}
    threads_drained(threading.active_count() + 2)
    baseline_threads = threading.active_count()
    lm0 = lineage_metrics().snapshot()

    t0 = time.monotonic()
    stats = {"rounds": 0, "kills": 0, "net_rounds": 0, "oom_rounds": 0,
             "wrong_results": 0, "leaked_pins": 0, "leaked_threads": 0,
             "errors": 0}
    failures = []
    # every round runs under a traced query_id into this recorder; when
    # a round fails, its flight-recorder dump rides the JSON summary —
    # a red soak names the query AND shows where its time went
    recorder = qtrace.FlightRecorder(capacity=64, slow_query_ms=0)
    flight = {}
    names = sorted(tables)
    while time.monotonic() - t0 < duration_s:
        name = names[int(rng.integers(len(names)))]
        kill = KILL_POINTS[int(rng.integers(len(KILL_POINTS)))]
        replicas = int(rng.integers(2))
        net_mode = NET_MODES[int(rng.integers(len(NET_MODES)))]
        net_kind = NET_KINDS[int(rng.integers(len(NET_KINDS)))]
        oom_mode = OOM_MODES[int(rng.integers(len(OOM_MODES)))]
        qid = qtrace.mint_query_id()
        leg = (f"query={qid} {name} kill={kill} replicas={replicas} "
               f"net={net_mode or 'off'}/{net_kind} "
               f"oom={oom_mode or 'off'}")
        stats["rounds"] += 1
        stats["kills"] += kill != "none"
        stats["net_rounds"] += bool(net_mode)
        stats["oom_rounds"] += bool(oom_mode)

        def _flight_dump():
            flight[qid] = {"leg": leg,
                           "profiles": recorder.profiles(qid)}

        try:
            with net_injection(net_mode, seed=int(rng.integers(1 << 30)),
                               fault_kind=net_kind, delay_ms=5), \
                    oom_injection(oom_mode,
                                  seed=int(rng.integers(1 << 30))), \
                    qtrace.query_trace(qid, component="soak",
                                       recorder=recorder):
                parts = run_query(tables[name], replicas=replicas,
                                  kill=kill)
        except Exception as e:           # soak accounting: count + go on
            stats["errors"] += 1
            failures.append(f"{leg}: {type(e).__name__}: {e}")
            _flight_dump()
            net_injector().configure("")
            continue
        if not same(parts, baselines[name]):
            stats["wrong_results"] += 1
            failures.append(f"{leg}: WRONG RESULT")
            _flight_dump()
        if cat.total_pinned() != 0:
            stats["leaked_pins"] += 1
            failures.append(f"{leg}: {cat.total_pinned()} leaked pins")
            _flight_dump()
        if not threads_drained(baseline_threads):
            stats["leaked_threads"] += 1
            failures.append(
                f"{leg}: threads not drained "
                f"({threading.active_count()} > {baseline_threads}: "
                f"{sorted(t.name for t in threading.enumerate())})")
            _flight_dump()
            baseline_threads = threading.active_count()   # don't cascade
        if verbose and stats["rounds"] % 20 == 0:
            print(f"[{time.monotonic() - t0:7.1f}s] "
                  f"{stats['rounds']} rounds, "
                  f"{stats['kills']} kills, failures="
                  f"{len(failures)}", file=sys.stderr, flush=True)

    lm1 = lineage_metrics().snapshot()
    stats["duration_s"] = round(time.monotonic() - t0, 1)
    stats["recomputeCount"] = lm1["recomputeCount"] - lm0["recomputeCount"]
    stats["recomputedPartitions"] = (lm1["recomputedPartitions"]
                                     - lm0["recomputedPartitions"])
    stats["replicaBytes"] = lm1["replicaBytes"] - lm0["replicaBytes"]
    stats["lineageMissCount"] = (lm1["lineageMissCount"]
                                 - lm0["lineageMissCount"])
    stats["failures"] = failures
    #: flight-recorder dump per failed round (query_id -> {leg,
    #: profiles}): the span timeline of exactly the rounds that went red
    stats["flight"] = flight
    stats["ok"] = not (failures or stats["wrong_results"]
                       or stats["leaked_pins"] or stats["errors"])
    return stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="chaos soak over the query-recovery plane")
    p.add_argument("--duration", type=float, default=300.0,
                   help="soak wall-clock seconds (default 300)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rows", type=int, default=3000,
                   help="rows per shape table")
    p.add_argument("--json-out", default="",
                   help="also write the summary JSON to this path")
    args = p.parse_args(argv)
    stats = soak(args.duration, seed=args.seed, rows=args.rows)
    blob = json.dumps(stats, indent=2)
    print(blob)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(blob + "\n")
    return 0 if stats["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
