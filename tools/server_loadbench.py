"""Serving-tier load bench: many concurrent framed-TCP clients against
one embedded PlanServer — or, with ``--fleet N``, against a Router in
front of N plan-server worker subprocesses.

The acceptance instrument for ISSUE 10 (single server) and ISSUE 12
(fleet): it reports QPS + p50/p99 latency split by repeated vs unique
shapes, the plan/result cache hit counters, and admission stats; fleet
mode adds the per-tenant breakdown, router overhead p50/p99, and
per-worker QPS. ``--compare`` re-runs the identical workload with the
caches disabled (single) or with ONE worker (fleet) so the scaling is
measured on the same machine.

    python tools/server_loadbench.py --clients 100 --rounds 5 --compare \
        --json-out BENCH_loadbench.json
    python tools/server_loadbench.py --fleet 4 --clients 500 --rounds 3 \
        --tenants 4 --compare --json-out BENCH_fleet.json

Fleet legs: the *repeat-shape* leg re-submits the SAME four shapes with
fresh literals — every query plans against a warm planning cache (and a
warm XLA compile cache on its home worker) but still executes, so QPS
scales with workers; the *unique-shape* leg pays cold planning. The
result cache is left OFF in fleet scaling runs for exactly that reason:
a byte-serving router measures the router's GIL, not the fleet.

Results land in docs/profiling.md; the <2-min smoke-tier mini runs are
``pytest -m "serving and smoke"`` (tests/test_serving_concurrent.py and
tests/test_serving_fleet.py), which drive this module with small
parameters.

The device is whatever JAX gives the serving processes — nothing here pins
a platform. Every report names it: ``device`` (single server: this process
IS the server) or ``worker_devices`` (fleet: this process only routes, stays
off JAX and says so under ``router_process_backends``; one worker per chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tables(rows: int):
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(17)
    lineitem = pa.table({
        "k": rng.integers(0, 3, rows).astype(np.int32),
        "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
        "l_extendedprice": rng.uniform(1.0, 1e5, rows),
    })
    sales = pa.table({
        "k": rng.integers(0, 256, rows).astype(np.int64),
        "ss_quantity": rng.integers(1, 100, rows).astype(np.int64),
    })
    facts = pa.table({
        "k": rng.integers(0, 64, rows).astype(np.int64),
        "v": rng.integers(-1000, 1000, rows).astype(np.int64),
    })
    dims = pa.table({
        "k": np.arange(64, dtype=np.int64),
        "w": rng.integers(0, 10, 64).astype(np.int64),
    })
    return {"lineitem": lineitem, "sales": sales, "facts": facts,
            "dims": dims}


def _shapes(tabs):
    """The bench shapes as (name, df_builder(literal)) pairs — each
    builder varies ONE comparison literal, so every variant shares a
    plan-shape fingerprint (repeat = same literal, unique = fresh)."""
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Count, Sum
    from spark_rapids_tpu.plan import table

    def q1(v):
        return (table(tabs["lineitem"])
                .where(col("l_quantity") > lit(int(v)))
                .group_by("k")
                .agg(Sum(col("l_extendedprice")).alias("rev"),
                     Count().alias("n")))

    def hash_agg(v):
        return (table(tabs["sales"])
                .where(col("ss_quantity") > lit(int(v)))
                .group_by("k").agg(Sum(col("ss_quantity")).alias("q")))

    def join_sort(v):
        from spark_rapids_tpu.exec.sort import asc
        return (table(tabs["facts"])
                .where(col("v") > lit(int(v)))
                .join(table(tabs["dims"]), ["k"], ["k"])
                .group_by("w").agg(Sum(col("v")).alias("s"))
                .order_by(asc(col("w"))))

    def exchange(v):
        return (table(tabs["facts"], num_slices=4)
                .where(col("v") > lit(int(v)))
                .group_by("k").agg(Sum(col("v")).alias("s")))

    return [("q1_stage", q1), ("hash_agg", hash_agg),
            ("join_sort", join_sort), ("exchange", exchange)]


def _pct(xs, p):
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))
    return xs[i]


def run_load(clients: int, rounds: int, rows: int,
             plan_cache: bool, result_cache: bool,
             concurrent_collects: int = 4,
             unique_fraction: float = 0.25,
             host: str = "127.0.0.1",
             client_timeout: float = 900.0,
             trace: bool = False) -> dict:
    """Drive ``clients`` threads x ``rounds`` x shapes; round 0 plants
    each shape, later rounds repeat it (same literal) except a
    ``unique_fraction`` of queries that draw a fresh literal.
    ``trace`` turns query tracing on server-side — the --trace legs
    measure its overhead against the identical untraced workload."""
    from spark_rapids_tpu.server import PlanClient, PlanServer
    conf = {
        "spark.rapids.tpu.server.planCache.enabled": str(plan_cache),
        "spark.rapids.tpu.server.resultCache.enabled": str(result_cache),
        "spark.rapids.tpu.server.concurrentCollects":
            str(concurrent_collects),
        "spark.rapids.tpu.server.maxSessions": str(max(64, clients + 8)),
        "spark.rapids.tpu.trace.enabled": str(trace),
    }
    tabs = _tables(rows)
    shapes = _shapes(tabs)
    from spark_rapids_tpu.plan import plancache
    counters0 = plancache.metrics().snapshot()
    server = PlanServer(host=host, conf=conf).start()
    samples = []          # (shape, kind, ms, cached, plan_info)
    lock = threading.Lock()
    errors = []

    def worker(ci: int):
        try:
            with PlanClient(host, server.port,
                            timeout=client_timeout) as c:
                for r in range(rounds):
                    for si, (name, build) in enumerate(shapes):
                        unique = r > 0 and \
                            ((ci * 31 + r * 7 + si) % 100) < \
                            unique_fraction * 100
                        lit_v = 25 if not unique else \
                            1 + (ci * 131 + r * 17 + si * 7) % 900
                        kind = "unique" if unique else \
                            ("first" if r == 0 else "repeat")
                        t0 = time.perf_counter()
                        c.collect(build(lit_v))
                        ms = (time.perf_counter() - t0) * 1e3
                        with lock:
                            samples.append(
                                (name, kind, ms, c.last_cached,
                                 c.last_cache.get("plan", "")))
        except Exception as e:    # pragma: no cover - surfaced below
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")

    t_start = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    # closed clients unwind their handler threads on the next recv;
    # give the server a moment to drain before counting leaks
    deadline = time.monotonic() + 5.0
    while server.active_sessions and time.monotonic() < deadline:
        time.sleep(0.02)
    stats = server.serving_stats()
    # the process-wide counters outlive a run (the --compare leg shares
    # the process): report THIS run's deltas
    stats["counters"] = {k: v - counters0.get(k, 0)
                         for k, v in stats["counters"].items()}
    leaked_sessions = server.active_sessions
    server.stop()
    if errors:
        raise RuntimeError("loadbench clients failed:\n" +
                           "\n".join(errors[:5]))

    def agg(pred):
        xs = [ms for (_, kind, ms, _, _) in samples if pred(kind)]
        return {"n": len(xs), "p50_ms": round(_pct(xs, 50), 3),
                "p99_ms": round(_pct(xs, 99), 3)}

    total = len(samples)
    out = {
        "clients": clients, "rounds": rounds, "rows": rows,
        "plan_cache": plan_cache, "result_cache": result_cache,
        "concurrent_collects": concurrent_collects,
        "wall_s": round(wall, 3),
        "qps": round(total / wall, 1) if wall else 0.0,
        "queries": total,
        "all": agg(lambda k: True),
        "repeat": agg(lambda k: k == "repeat"),
        "unique": agg(lambda k: k == "unique"),
        "first": agg(lambda k: k == "first"),
        "result_cache_served": sum(1 for s in samples if s[3]),
        "plan_cache_hits_client": sum(1 for s in samples
                                      if s[4] == "hit"),
        "server": stats,
        "device": stats["server"]["device"],
        "leaked_sessions": leaked_sessions,
    }
    return out


def _initialised_backends() -> list:
    """JAX backends this (routing) process has initialised: [] on a chip
    host, where holding the chip here would starve the workers."""
    from jax._src import xla_bridge
    return sorted(xla_bridge._backends)


def run_fleet_load(clients: int, rounds: int, rows: int, fleet: int,
                   tenants: int = 1,
                   unique_fraction: float = 0.25,
                   concurrent_collects: int = 4,
                   result_cache: bool = False,
                   repeat_literals: bool = False,
                   rolling_restart: bool = False,
                   retries: int = 8,
                   shape_variants: int = 0,
                   shapes_per_client: int = 0,
                   cpus_per_worker: int = 0,
                   duplicate_fraction: float = 0.0,
                   sharing: bool = False,
                   digest_book: dict = None,
                   host: str = "127.0.0.1",
                   client_timeout: float = 900.0) -> dict:
    """Drive ``clients`` threads through a Router over ``fleet`` worker
    subprocesses. The *repeat* leg re-submits the same shapes with
    fresh literals (warm planning cache, real execution — the scaling
    leg) unless ``repeat_literals`` (same literals: the result-cache /
    rehydration leg); the *unique* leg varies the plan STRUCTURE (a
    distinct limit node) so planning is cold. ``rolling_restart``
    triggers a full fleet restart once round 0 completes — the
    zero-downtime acceptance: the report carries every client error and
    the persistent-tier rehydration hit count.

    ``shape_variants`` > 0 expands the 4 base shapes into that many
    structurally-distinct variants (an extra limit node each) so the
    consistent-hash ring load-balances — with only 4 shapes on 4
    workers the hash can pin 2 shapes to one worker and idle another,
    which measures ring imbalance, not fleet throughput.
    ``shapes_per_client`` > 0 gives each client a deterministic subset
    (variants stay shared ACROSS clients, so repeats still hit warm
    caches) to bound total query count at high client counts.

    ``duplicate_fraction`` > 0 turns that fraction of clients into
    *duplicators*: each round they all submit the SAME query (same
    shape, same literal, synchronized at a round barrier), the
    duplicate-heavy leg of the cross-query work-sharing acceptance
    (ISSUE 18). ``sharing`` enables
    ``spark.rapids.tpu.server.sharing.*`` router- and worker-side; the
    report then carries per-leg dedup / subplan / scan-share counters.
    ``digest_book`` (a shared dict) bit-for-bit-gates results: every
    (shape, literal) result's content digest must match across clients,
    rounds, and LEGS (pass the same dict to the sharing-off leg)."""
    from spark_rapids_tpu.server import PlanClient
    from spark_rapids_tpu.server.router import Router

    cpusets = None
    if cpus_per_worker > 0:
        # equal core slices per worker: the 1-vs-N comparison measures
        # fleet structure, not one worker's XLA thread pool grabbing
        # the whole machine in the 1-worker leg
        ncpu = os.cpu_count() or 1
        cpusets = []
        for i in range(fleet):
            lo = (i * cpus_per_worker) % ncpu
            hi = min(lo + cpus_per_worker - 1, ncpu - 1)
            cpusets.append(f"{lo}-{hi}")
    tabs = _tables(rows)
    base = _shapes(tabs)
    if shape_variants and shape_variants > len(base):
        shapes = []
        for j in range(shape_variants):
            name, build = base[j % len(base)]
            shapes.append((
                f"{name}~v{j}",
                # bind j now; the limit bound makes variant j a distinct
                # plan SHAPE with identical rows/semantics
                lambda v, _b=build, _j=j: _b(v).limit(10**9 - _j)))
    else:
        shapes = base
    base_conf = {"spark.rapids.tpu.server.fleet.tenant.weights":
                 ",".join(f"t{i}={1 + i % 2}" for i in range(tenants))}
    if sharing:
        # conf feeds router AND workers: the router dedups in-flight
        # duplicates before they reach a worker; a worker dedups the
        # ones that slip through (and runs subplan/scan sharing)
        base_conf["spark.rapids.tpu.server.sharing.enabled"] = "true"
    router = Router(
        workers=fleet,
        worker_cpusets=cpusets,
        conf=base_conf,
        worker_conf={
            "spark.rapids.tpu.server.resultCache.enabled":
                str(result_cache),
            "spark.rapids.tpu.server.concurrentCollects":
                str(concurrent_collects),
            "spark.rapids.tpu.server.maxSessions":
                str(max(64, clients + 8)),
        }).start()
    samples = []    # (shape, kind, ms, tenant, worker, cached, sharing)
    lock = threading.Lock()
    errors = []
    finished_clients = [0]
    restart_report = {}
    restart_done = threading.Event()
    # duplicate-heavy legs synchronize each round so the duplicators'
    # queries actually overlap in flight (what in-flight dedup dedups);
    # a broken barrier (an errored client) degrades to free-running
    barrier = threading.Barrier(clients) \
        if duplicate_fraction > 0 and clients > 1 \
        and not rolling_restart else None

    def _round_sync():
        if barrier is None:
            return
        try:
            barrier.wait(timeout=client_timeout)
        except threading.BrokenBarrierError:
            pass

    def worker(ci: int):
        tenant = f"t{ci % tenants}"
        duplicator = duplicate_fraction > 0 and \
            ci < int(clients * duplicate_fraction + 0.5)
        my_shapes = list(enumerate(shapes))
        if shapes_per_client and shapes_per_client < len(shapes):
            my_shapes = [my_shapes[(ci * 7 + m * 13) % len(shapes)]
                         for m in range(shapes_per_client)]
        if duplicator:
            # every duplicator drives the SAME deterministic shape list
            my_shapes = list(enumerate(shapes))
            if shapes_per_client and shapes_per_client < len(shapes):
                my_shapes = my_shapes[:shapes_per_client]
        try:
            with PlanClient(
                    host, router.port, timeout=client_timeout,
                    unavailable_retries=retries,
                    retry_budget_ms=int(client_timeout * 1000),
                    conf={"spark.rapids.tpu.server.fleet.tenantId":
                          tenant}) as c:
                # a rolling-restart leg keeps the load on until the
                # roll completes, then runs ONE more full round against
                # the replacements (that round is what proves
                # rehydration); bounded in case the roll wedges
                r, extra = 0, 0
                while True:
                    _round_sync()
                    for si, (name, build) in my_shapes:
                        if duplicator:
                            # IDENTICAL to every other duplicator this
                            # round: same shape, same literal — the
                            # in-flight dedup leg
                            lit_v = 25 if r == 0 else \
                                1 + (r * 17 + si * 7) % 900
                            df = build(lit_v)
                            kind = "dup"
                            qkey = (name, lit_v, 0)
                        else:
                            unique = r > 0 and \
                                ((ci * 31 + r * 7 + si) % 100) < \
                                unique_fraction * 100
                            lit_v = 25 if (repeat_literals or r == 0) \
                                else 1 + (ci * 131 + r * 17 + si * 7) \
                                % 900
                            df = build(lit_v)
                            qkey = (name, lit_v, 0)
                            if unique:
                                # a distinct limit bound = a distinct
                                # plan SHAPE (plan fields stay in the
                                # fingerprint): cold planning, same rows
                                bound = 10**9 - (ci * 997 + r * 131 + si)
                                df = df.limit(bound)
                                kind = "unique"
                                qkey = (name, lit_v, bound)
                            else:
                                kind = "first" if r == 0 else "repeat"
                        t0 = time.perf_counter()
                        out = c.collect(df)
                        ms = (time.perf_counter() - t0) * 1e3
                        if digest_book is not None:
                            # bit-for-bit gate, within AND across legs
                            from spark_rapids_tpu.plan.plancache import \
                                content_digest
                            d = content_digest(out)
                            with lock:
                                seen = digest_book.setdefault(qkey, d)
                            if seen != d:
                                raise AssertionError(
                                    f"result diverged for {qkey}: "
                                    f"{d} != {seen}")
                        with lock:
                            samples.append(
                                (name, kind, ms, tenant,
                                 c.last_worker, c.last_cached,
                                 c.last_sharing))
                    r += 1
                    if r < rounds:
                        continue
                    if not rolling_restart or r >= rounds * 50:
                        break
                    if restart_done.is_set():
                        if extra >= 1:
                            break
                        extra += 1      # the proving post-restart round
        except Exception as e:    # surfaced in the report
            if barrier is not None:
                barrier.abort()   # never strand the healthy clients
            with lock:
                errors.append(f"client {ci}: {type(e).__name__}: {e}")
        finally:
            with lock:
                finished_clients[0] += 1

    def restarter():
        # wait for round 0 (every shape planted fleet-wide), then roll
        per_client = shapes_per_client \
            if shapes_per_client and shapes_per_client < len(shapes) \
            else len(shapes)
        target = clients * per_client
        while True:
            with lock:
                n = len(samples)
                # the target can become unreachable (erroring clients
                # produce no samples): never outlive the client fleet
                done = finished_clients[0] >= clients
            if n >= target or done:
                break
            time.sleep(0.05)
        restart_report.update(router.rolling_restart(grace_s=30))
        restart_done.set()

    t_start = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    rt = None
    if rolling_restart:
        rt = threading.Thread(target=restarter, daemon=True)
        rt.start()
    for t in threads:
        t.join()
    if rt is not None:
        rt.join()
    wall = time.perf_counter() - t_start
    deadline = time.monotonic() + 5.0
    while router.active_sessions and time.monotonic() < deadline:
        time.sleep(0.02)
    stats = router.serving_stats()
    leaked_sessions = router.active_sessions
    router.stop(grace_s=10)

    def agg(pred):
        xs = [s[2] for s in samples if pred(s)]
        return {"n": len(xs), "p50_ms": round(_pct(xs, 50), 3),
                "p99_ms": round(_pct(xs, 99), 3),
                "qps": round(len(xs) / wall, 1) if wall else 0.0}

    per_worker_plans = stats["routing"]["perWorkerPlans"]
    tenant_stats = {}
    for i in range(tenants):
        tn = f"t{i}"
        t_agg = agg(lambda s, tn=tn: s[3] == tn)
        t_agg.update(stats["tenants"].get(tn, {}))
        tenant_stats[tn] = t_agg
    rehydration = sum(
        (ws or {}).get("counters", {}).get("resultStoreHitCount", 0)
        for ws in stats["workers"].values())
    # per-leg work-sharing counters: the router's own dedup block plus
    # every worker's sharing block summed (a worker that died mid-run
    # reports null and is skipped)
    worker_sharing = {}
    for ws in stats["workers"].values():
        for k, v in ((ws or {}).get("sharing") or {}).items():
            if isinstance(v, int):
                worker_sharing[k] = worker_sharing.get(k, 0) + v
    return {
        "fleet": fleet, "clients": clients, "rounds": rounds,
        "rows": rows, "tenants_n": tenants,
        "result_cache": result_cache,
        "repeat_literals": repeat_literals,
        "concurrent_collects": concurrent_collects,
        "sharing": sharing,
        "duplicate_fraction": duplicate_fraction,
        "wall_s": round(wall, 3),
        "qps": round(len(samples) / wall, 1) if wall else 0.0,
        "queries": len(samples),
        "errors": len(errors),
        "error_samples": errors[:5],
        "all": agg(lambda s: True),
        "repeat": agg(lambda s: s[1] == "repeat"),
        "unique": agg(lambda s: s[1] == "unique"),
        "first": agg(lambda s: s[1] == "first"),
        "dup": agg(lambda s: s[1] == "dup"),
        "dedup_served": sum(1 for s in samples if s[6] == "inflight"),
        "sharing_counters": {
            "router": stats.get("sharing"),
            "workers": worker_sharing or None,
        },
        "result_cache_served": sum(1 for s in samples if s[5]),
        "per_worker_qps": {
            "plans": per_worker_plans,
            "qps": {w: round(n / wall, 1) if wall else 0.0
                    for w, n in per_worker_plans.items()},
        },
        "router_overhead_ms": stats["routing"]["overheadMs"],
        "failovers": stats["routing"]["failovers"],
        "fingerprint_fallbacks": stats["routing"]["fingerprintFallbacks"],
        "tenants": tenant_stats,
        "rolling_restart": restart_report or None,
        "rehydration_hits": rehydration,
        "leaked_sessions": leaked_sessions,
        "worker_devices": {
            wid: ((ws or {}).get("server") or {}).get("device")
            for wid, ws in stats["workers"].items()},
        "router_process_backends": _initialised_backends(),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--clients", type=int, default=100)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--rows", type=int, default=20000)
    p.add_argument("--unique-fraction", type=float, default=0.25)
    p.add_argument("--concurrent-collects", type=int, default=4)
    p.add_argument("--no-plan-cache", action="store_true")
    p.add_argument("--no-result-cache", action="store_true")
    p.add_argument("--compare", action="store_true",
                   help="re-run the same workload with both caches off "
                        "and report the repeated-shape p50 ratio")
    p.add_argument("--json-out", default=None,
                   help="append the report into a BENCH-style sidecar")
    p.add_argument("--client-timeout", type=float, default=900.0,
                   help="per-client socket timeout, seconds; uncached "
                        "high-fan-in runs queue long on a CPU host")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="drive a Router over N worker subprocesses "
                        "instead of one embedded server; --compare "
                        "re-runs with ONE worker for the scaling ratio")
    p.add_argument("--tenants", type=int, default=1,
                   help="fleet mode: spread clients over this many "
                        "tenant ids (per-tenant breakdown in the report)")
    p.add_argument("--shape-variants", type=int, default=0,
                   help="fleet mode: expand the 4 base shapes into this "
                        "many structurally-distinct variants so the "
                        "hash ring load-balances")
    p.add_argument("--shapes-per-client", type=int, default=0,
                   help="fleet mode: each client drives only this many "
                        "(shared) shapes, bounding total queries at "
                        "high client counts")
    p.add_argument("--compare-clients", type=int, default=0,
                   help="client count for the --compare 1-worker leg "
                        "(default: same as --clients; a saturated "
                        "1-worker leg needs far fewer clients for the "
                        "same QPS measurement)")
    p.add_argument("--cpus-per-worker", type=int, default=0,
                   help="taskset-pin each worker to this many cores so "
                        "a single-host 1-vs-N comparison holds "
                        "per-worker compute constant across legs")
    p.add_argument("--duplicate-fraction", type=float, default=0.0,
                   help="fleet mode: this fraction of clients submit "
                        "the SAME query each round (synchronized) — "
                        "the in-flight-dedup duplicate-heavy leg")
    p.add_argument("--sharing-compare", action="store_true",
                   help="fleet mode: run the identical duplicate-heavy "
                        "workload twice — sharing.* ON then OFF — "
                        "bit-for-bit gated through a shared digest "
                        "book, and report the QPS ratio (the ISSUE 18 "
                        "acceptance leg)")
    p.add_argument("--restart-under-load", action="store_true",
                   help="fleet mode: add a leg that rolls the whole "
                        "fleet mid-run (result cache ON, repeated "
                        "literals) — zero errors + nonzero rehydration "
                        "hits is the acceptance")
    p.add_argument("--trace", action="store_true",
                   help="single-server mode: add traced legs (query "
                        "tracing ON, identical workload) and report the "
                        "cached repeat-path and uncached p50 overhead "
                        "of tracing vs the untraced legs")
    args = p.parse_args(argv)

    if args.fleet > 0 and args.sharing_compare:
        # the ISSUE 18 acceptance instrument: identical duplicate-heavy
        # workload, sharing ON vs OFF, one shared digest book so every
        # result is bit-for-bit gated across clients, rounds, AND legs
        book: dict = {}
        on = run_fleet_load(
            args.clients, args.rounds, args.rows, fleet=args.fleet,
            tenants=args.tenants,
            unique_fraction=args.unique_fraction,
            concurrent_collects=args.concurrent_collects,
            duplicate_fraction=args.duplicate_fraction,
            sharing=True, digest_book=book,
            client_timeout=args.client_timeout)
        off = run_fleet_load(
            args.clients, args.rounds, args.rows, fleet=args.fleet,
            tenants=args.tenants,
            unique_fraction=args.unique_fraction,
            concurrent_collects=args.concurrent_collects,
            duplicate_fraction=args.duplicate_fraction,
            sharing=False, digest_book=book,
            client_timeout=args.client_timeout)
        report = {
            "sharing_on": on, "sharing_off": off,
            "bit_for_bit_queries": len(book),
            "qps_speedup": round(on["qps"] / off["qps"], 3)
            if off["qps"] else None,
            "dup_qps_speedup": round(
                on["dup"]["qps"] / off["dup"]["qps"], 3)
            if off["dup"]["qps"] else None,
        }
    elif args.fleet > 0:
        report = {"fleet_loadbench": run_fleet_load(
            args.clients, args.rounds, args.rows, fleet=args.fleet,
            tenants=args.tenants,
            unique_fraction=args.unique_fraction,
            concurrent_collects=args.concurrent_collects,
            shape_variants=args.shape_variants,
            shapes_per_client=args.shapes_per_client,
            cpus_per_worker=args.cpus_per_worker,
            duplicate_fraction=args.duplicate_fraction,
            client_timeout=args.client_timeout)}
        if args.compare:
            cc = args.compare_clients or args.clients
            report["fleet_loadbench_1worker"] = run_fleet_load(
                cc, args.rounds, args.rows, fleet=1,
                tenants=args.tenants,
                unique_fraction=args.unique_fraction,
                concurrent_collects=args.concurrent_collects,
                shape_variants=args.shape_variants,
                shapes_per_client=args.shapes_per_client,
                cpus_per_worker=args.cpus_per_worker,
                client_timeout=args.client_timeout)
            for leg in ("repeat", "unique"):
                a = report["fleet_loadbench"][leg]["qps"]
                b = report["fleet_loadbench_1worker"][leg]["qps"]
                report[f"{leg}_qps_scaling"] = \
                    round(a / b, 3) if b else None
        if args.restart_under_load:
            report["fleet_rolling_restart"] = run_fleet_load(
                min(args.clients, 48), 4, args.rows, fleet=args.fleet,
                tenants=args.tenants, unique_fraction=0.0,
                concurrent_collects=args.concurrent_collects,
                shape_variants=args.shape_variants,
                shapes_per_client=args.shapes_per_client,
                cpus_per_worker=args.cpus_per_worker,
                result_cache=True, repeat_literals=True,
                rolling_restart=True,
                client_timeout=args.client_timeout)
    else:
        report = {"loadbench": run_load(
            args.clients, args.rounds, args.rows,
            plan_cache=not args.no_plan_cache,
            result_cache=not args.no_result_cache,
            concurrent_collects=args.concurrent_collects,
            unique_fraction=args.unique_fraction,
            client_timeout=args.client_timeout)}
        if args.compare:
            report["loadbench_uncached"] = run_load(
                args.clients, args.rounds, args.rows,
                plan_cache=False, result_cache=False,
                concurrent_collects=args.concurrent_collects,
                unique_fraction=args.unique_fraction,
                client_timeout=args.client_timeout)
            a = report["loadbench"]["repeat"]["p50_ms"]
            b = report["loadbench_uncached"]["repeat"]["p50_ms"]
            report["repeat_p50_speedup"] = round(b / a, 3) if a else None
        if args.trace:
            # tracing-overhead legs: IDENTICAL workload with
            # trace.enabled on the server. The cached repeat path (a
            # result-cache serve wrapped in a span tree) is the
            # acceptance number — observability must cost ≲3% there;
            # the uncached leg bounds the worst case (every operator /
            # serializer / admission span live)
            traced_cached = run_load(
                args.clients, args.rounds, args.rows,
                plan_cache=not args.no_plan_cache,
                result_cache=not args.no_result_cache,
                concurrent_collects=args.concurrent_collects,
                unique_fraction=args.unique_fraction,
                client_timeout=args.client_timeout, trace=True)
            base_rep = report["loadbench"]["repeat"]["p50_ms"]
            tr_rep = traced_cached["repeat"]["p50_ms"]
            trace_report = {
                "repeat_p50_ms_untraced": base_rep,
                "repeat_p50_ms_traced": tr_rep,
                "repeat_p50_overhead_pct": round(
                    (tr_rep - base_rep) / base_rep * 100, 2)
                if base_rep else None,
                "traced": traced_cached,
            }
            if "loadbench_uncached" in report:
                traced_uncached = run_load(
                    args.clients, args.rounds, args.rows,
                    plan_cache=False, result_cache=False,
                    concurrent_collects=args.concurrent_collects,
                    unique_fraction=args.unique_fraction,
                    client_timeout=args.client_timeout, trace=True)
                bu = report["loadbench_uncached"]["repeat"]["p50_ms"]
                tu = traced_uncached["repeat"]["p50_ms"]
                trace_report["uncached_repeat_p50_ms_untraced"] = bu
                trace_report["uncached_repeat_p50_ms_traced"] = tu
                trace_report["uncached_repeat_p50_overhead_pct"] = \
                    round((tu - bu) / bu * 100, 2) if bu else None
                trace_report["traced_uncached"] = traced_uncached
            report["loadbench_trace"] = trace_report
    print(json.dumps(report, indent=2))
    if args.json_out:
        existing = {}
        if os.path.exists(args.json_out):
            try:
                with open(args.json_out) as f:
                    existing = json.load(f)
            except (OSError, ValueError):
                existing = {}
        existing.update(report)
        with open(args.json_out, "w") as f:
            json.dump(existing, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
