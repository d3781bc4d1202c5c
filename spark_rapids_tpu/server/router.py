"""Serving fleet router: consistent-hash plan routing over a pool of
plan-server worker subprocesses.

PR 10's loadbench analysis (docs/profiling.md) showed one Python
process saturating around ~100 GIL-bound clients — the process, not the
engine, became the ceiling. The answer is the "Accelerating Presto with
GPUs" coordinator/worker shape (PAPERS.md): accelerated workers are a
*pool*, and worker health + cache locality are the coordinator's
problem. This router:

- speaks the existing framed-TCP protocol (``protocol.py``) on both
  sides, so every client and every worker is unchanged wire-wise;
- routes each ``plan`` by **consistent hash of its plan-shape
  fingerprint** (``plancache.shape_fingerprint_doc`` — the exact
  fingerprint that keys the worker's planning cache, computed
  router-side over the plandoc dialect), so repeat shapes land on the
  worker whose planning cache and XLA compile cache are already warm
  (the Theseus argument: re-paying compilation on a cold worker is
  data movement you chose to do);
- fans ``table``/``drop_table`` out to every live worker and aggregates
  the acks (``invalidated`` sums per-worker counts; the shared
  persistent result tier is invalidated idempotently by the first
  worker reached);
- layers **per-tenant admission** above each worker's
  ``concurrentCollects``: hard concurrency quotas answer a structured
  ``unavailable`` + ``retry_after_ms`` (the PlanClient retry budget
  resubmits), and contended worker slots are granted by weighted fair
  queueing (stride scheduling over ``fleet.tenant.weights``) so a heavy
  tenant cannot starve a light one;
- **fails over**: a worker that dies mid-query is marked suspect on the
  first broken transaction and dead once its process is observed gone
  (the PR-11 discipline — a success rehabilitates a suspect, only a
  replacement resurrects a corpse); the in-flight plan is resubmitted
  to the next worker on the ring after replaying the session's tables;
- performs **zero-downtime rolling restarts**: drain one worker at a
  time (its ring slots fail over to live workers, its in-flight plans
  finish), stop it via the PR-9 ``stop()`` contract (the ``shutdown``
  wire op), spawn a replacement at the SAME ring position, and let the
  shared persistent result tier rehydrate its cache on read-through.

Run standalone:  python -m spark_rapids_tpu.server.router --port 9098
"""

from __future__ import annotations

import bisect
import hashlib
import json
import os
import re
import shutil
import socket
import socketserver
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from ..config import (FLEET_ADMISSION_TIMEOUT_MS, FLEET_DRAIN_TIMEOUT_MS,
                      FLEET_MAX_INFLIGHT_PER_WORKER,
                      FLEET_SPILLOVER_QUEUE_DEPTH, FLEET_TENANT_ID,
                      FLEET_TENANT_MAX_CONCURRENT, FLEET_TENANT_WEIGHTS,
                      FLEET_VNODES, FLEET_WORKER_RETRIES, FLEET_WORKERS,
                      FLEET_RESULT_STORE_PATH, FLEET_COST_SYNC_PLANS,
                      RapidsTpuConf,
                      SERVER_CONCURRENT_COLLECTS, SERVER_RESULT_CACHE_ENABLED,
                      SERVER_RETRY_AFTER_MS, SERVER_TRACE_RECORDER_ENTRIES,
                      SERVER_TRACE_SLOW_QUERY_MS, TRACE_ENABLED,
                      TRACE_MAX_SPANS, TRACE_SINK_PATH)
from .. import trace as qtrace
from . import protocol

_READY_RE = re.compile(r"listening on ([\d.]+):(\d+)")

# worker states — the PR-11 liveness vocabulary applied to subprocesses
LIVE = "live"
DRAINING = "draining"      # rolling restart: no new plans, finish in-flight
SUSPECT = "suspect"        # one broken transaction; tried last, a success
#                            rehabilitates
DEAD = "dead"              # process observed gone; only replace_worker
#                            resurrects the slot


def _hpoint(s: str) -> int:
    return int.from_bytes(
        hashlib.blake2b(s.encode("utf-8"), digest_size=8).digest(), "big")


def _admin_request(host: str, port: int, header: dict,
                   timeout: float = 5.0) -> dict:
    """One-shot control-plane request (stats/shutdown): fresh
    connection, preamble + hello handshake, one op, reply returned.
    The single implementation behind every router->worker admin touch."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        protocol.send_preamble(s)
        protocol.recv_preamble(s)
        protocol.send_msg(s, {"msg": "hello", "conf": {}})
        protocol.recv_msg(s)
        protocol.send_msg(s, header)
        reply, _ = protocol.recv_msg(s)
        return reply


class WorkerHandle:
    """One plan-server worker subprocess + its routing identity. The
    ring hashes ``wid`` alone (not the generation), so a replacement
    spawned by the rolling restart inherits the dead worker's hash
    slots — the shapes that were pinned to it come straight back to the
    warmed-from-disk replacement."""

    def __init__(self, wid: str, conf: Dict[str, str], host: str,
                 spawn_timeout_s: float = 60.0,
                 cpuset: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None):
        self.wid = wid
        self.conf = dict(conf)
        self.host = host
        self.generation = 0
        self.state = LIVE
        self.port: int = 0
        self.proc: Optional[subprocess.Popen] = None
        self.restarts = 0
        self.plans = 0                 # plans completed on this worker
        self.failures = 0              # broken transactions observed
        self._spawn_timeout_s = spawn_timeout_s
        #: optional taskset CPU list — a single-host fleet bench pins
        #: each worker to an equal core slice so 1-vs-N scaling
        #: measures fleet structure, not XLA's whole-machine intra-op
        #: thread pool leaking between legs
        self.cpuset = cpuset
        self.extra_env = dict(env or {})

    # ---- lifecycle ----
    def spawn(self) -> "WorkerHandle":
        cmd = [sys.executable, "-m", "spark_rapids_tpu.server",
               "--host", self.host, "--port", "0"]
        for k, v in self.conf.items():
            cmd += ["--conf", f"{k}={v}"]
        if self.cpuset:
            cmd = ["taskset", "-c", self.cpuset] + cmd
        env = dict(os.environ)
        env.update(self.extra_env)
        # make the engine package importable regardless of the router's
        # cwd (the worker is `python -m`, not a script next to it)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True)
        line = self._await_readiness()
        m = _READY_RE.search(line)
        if not m:
            raise RuntimeError(
                f"worker {self.wid} produced no readiness line: {line!r}")
        self.port = int(m.group(2))
        self.generation += 1
        self.state = LIVE
        return self

    def _await_readiness(self) -> str:
        """The PR-9 readiness contract: the worker prints its bound
        address on stdout. Lines before it (import-time warnings —
        stderr is merged in) are scanned past, and the SAME daemon
        thread keeps draining the pipe for the worker's whole life: an
        undrained pipe fills its ~64KB kernel buffer and wedges a
        chatty worker mid-write, which would read as a mysterious
        suspect/dead promotion. Reading on a thread also means a worker
        that wedges during import cannot hang the router."""
        box: dict = {}
        head: List[str] = []
        ready = threading.Event()

        def read_and_drain():
            try:
                for line in self.proc.stdout:
                    if "line" not in box:
                        if len(head) < 20:
                            head.append(line)
                        if _READY_RE.search(line):
                            box["line"] = line
                            ready.set()
                    # keep consuming past readiness: the drain IS the
                    # point — never let the pipe fill
            except Exception as e:      # robust-ok: surfaced below
                box["err"] = e
            finally:
                ready.set()             # EOF before readiness unblocks

        threading.Thread(target=read_and_drain, daemon=True,
                         name=f"worker-{self.wid}-stdout").start()
        ready.wait(self._spawn_timeout_s)
        if "line" not in box:
            self.kill()
            raise RuntimeError(
                f"worker {self.wid} not ready within "
                f"{self._spawn_timeout_s}s; err={box.get('err')!r} "
                f"output head: {''.join(head)[:2000]!r}")
        return box["line"]

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self) -> None:
        if self.proc is None:
            return
        try:
            self.proc.kill()
            self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass   # net-ok: teardown of a possibly-dead subprocess

    def graceful_stop(self, grace_s: float = 10.0) -> bool:
        """Stop via the ``shutdown`` wire op (the worker runs its own
        PlanServer.stop()); True when the process exited in time."""
        if not self.alive():
            return True
        try:
            _admin_request(self.host, self.port,
                           {"msg": "shutdown", "grace_s": grace_s})
        except (OSError, protocol.ProtocolError):
            pass   # net-ok: a worker mid-death still gets terminated below
        try:
            self.proc.wait(timeout=grace_s + 5.0)
            return True
        except subprocess.TimeoutExpired:
            self.kill()
            return False

    def snapshot(self) -> dict:
        return {"id": self.wid, "state": self.state, "port": self.port,
                "pid": self.proc.pid if self.proc else None,
                "generation": self.generation, "plans": self.plans,
                "failures": self.failures, "restarts": self.restarts,
                "alive": self.alive()}


class HashRing:
    """Consistent-hash ring over worker ids with virtual nodes. Lookup
    returns EVERY distinct worker in ring order from the fingerprint's
    point — the head is the home worker, the tail is the failover
    order, so a drained/dead worker's slots fall to its ring successor
    deterministically."""

    def __init__(self, vnodes: int = 64):
        self.vnodes = vnodes
        self._points: List[Tuple[int, str]] = []

    def rebuild(self, wids: List[str]) -> None:
        pts = []
        for wid in wids:
            for i in range(self.vnodes):
                pts.append((_hpoint(f"{wid}#{i}"), wid))
        pts.sort()
        self._points = pts

    def ordered(self, fingerprint: str) -> List[str]:
        pts = self._points
        if not pts:
            return []
        p = _hpoint(fingerprint)
        i = bisect.bisect_left(pts, (p, ""))
        seen, out = set(), []
        for j in range(len(pts)):
            wid = pts[(i + j) % len(pts)][1]
            if wid not in seen:
                seen.add(wid)
                out.append(wid)
        return out


# ---------------------------------------------------------------------------
# tenant admission: quotas + weighted fair queueing
# ---------------------------------------------------------------------------


class QuotaExceeded(Exception):
    pass


class AdmissionTimeout(Exception):
    pass


class _Reroute(Exception):
    """The target worker started draining while this plan queued; pick
    a new worker from the ring."""


class _Waiter:
    __slots__ = ("event", "granted", "rerouted", "tenant")

    def __init__(self, tenant: str):
        self.event = threading.Event()
        self.granted = False
        self.rerouted = False
        self.tenant = tenant


class _Tenant:
    __slots__ = ("name", "weight", "vtime", "inflight", "admitted",
                 "rejected_quota", "rejected_timeout", "wait_ns")

    def __init__(self, name: str, weight: float):
        self.name = name
        self.weight = max(0.001, weight)
        self.vtime = 0.0           # stride-scheduling pass value
        self.inflight = 0          # plans open fleet-wide (queued + running)
        self.admitted = 0
        self.rejected_quota = 0
        self.rejected_timeout = 0
        self.wait_ns = 0


class _WorkerGate:
    __slots__ = ("capacity", "inflight", "waiters")

    def __init__(self, capacity: int):
        self.capacity = max(1, capacity)
        self.inflight = 0
        self.waiters: Dict[str, deque] = {}     # tenant -> deque[_Waiter]


class TenantAdmission:
    """Router-side admission, layered ABOVE each worker's
    ``concurrentCollects`` semaphore: per-tenant hard quotas
    (``fleet.tenant.maxConcurrent``) reject with retry-after; contended
    per-worker dispatch slots (``fleet.maxInflightPerWorker``) are
    granted in weighted-fair order — each grant advances the tenant's
    virtual time by 1/weight, and the waiter with the LOWEST virtual
    time is served next (stride scheduling), so throughput converges to
    the weight ratios under saturation."""

    def __init__(self, weights: Dict[str, float], quota: int,
                 timeout_ms: int):
        self._lock = threading.Lock()
        self._weights = dict(weights)
        self.quota = int(quota)
        self.timeout_s = timeout_ms / 1000.0
        self._tenants: Dict[str, _Tenant] = {}
        self._gates: Dict[str, _WorkerGate] = {}

    def _tenant(self, name: str) -> _Tenant:
        t = self._tenants.get(name)
        if t is None:
            t = _Tenant(name, self._weights.get(name, 1.0))
            # a newly active tenant starts at the live minimum vtime —
            # it must not replay "missed" history and starve incumbents
            live = [x.vtime for x in self._tenants.values()
                    if x.inflight > 0]
            t.vtime = min(live) if live else 0.0
            self._tenants[name] = t
        return t

    def gate(self, wid: str, capacity: int) -> None:
        with self._lock:
            g = self._gates.get(wid)
            if g is None:
                self._gates[wid] = _WorkerGate(capacity)
            else:
                g.capacity = max(1, capacity)

    # ---- per-plan tenant quota ----
    def open_plan(self, tenant: str) -> None:
        with self._lock:
            t = self._tenant(tenant)
            if self.quota > 0 and t.inflight >= self.quota:
                t.rejected_quota += 1
                raise QuotaExceeded(
                    f"tenant {tenant!r} at maxConcurrent={self.quota}")
            t.inflight += 1

    def close_plan(self, tenant: str) -> None:
        with self._lock:
            self._tenants[tenant].inflight -= 1

    # ---- per-attempt worker slot ----
    def acquire(self, tenant: str, wid: str) -> None:
        t0 = time.perf_counter_ns()
        with self._lock:
            t = self._tenant(tenant)
            g = self._gates[wid]
            if g.inflight < g.capacity and not g.waiters:
                g.inflight += 1
                t.vtime += 1.0 / t.weight
                t.admitted += 1
                return
            w = _Waiter(tenant)
            g.waiters.setdefault(tenant, deque()).append(w)
            # a free slot may exist while the queue is nonempty only
            # transiently; granting here closes the window
            self._grant_locked(g)
        w.event.wait(self.timeout_s)
        with self._lock:
            t.wait_ns += time.perf_counter_ns() - t0
            # the grant races the timeout, but both resolve under this
            # lock: granted wins (the slot is already charged to us and
            # the caller releases it in its finally)
            if w.granted:
                return
            q = g.waiters.get(tenant)
            if q is not None:
                try:
                    q.remove(w)
                except ValueError:
                    pass
                if not q:
                    g.waiters.pop(tenant, None)
            if w.rerouted:
                raise _Reroute()
            t.rejected_timeout += 1
        raise AdmissionTimeout(
            f"tenant {tenant!r} waited past admissionTimeoutMs "
            f"for worker {wid}")

    def release(self, wid: str) -> None:
        with self._lock:
            g = self._gates.get(wid)
            if g is None:
                return
            g.inflight -= 1
            self._grant_locked(g)

    def _grant_locked(self, g: _WorkerGate) -> None:
        while g.inflight < g.capacity and g.waiters:
            # weighted fair pick: the waiting tenant with the lowest
            # virtual time is next; ties break deterministically by name
            name = min(g.waiters,
                       key=lambda n: (self._tenant(n).vtime, n))
            q = g.waiters[name]
            w = q.popleft()
            if not q:
                del g.waiters[name]
            t = self._tenant(name)
            g.inflight += 1
            t.vtime += 1.0 / t.weight
            t.admitted += 1
            w.granted = True
            w.event.set()

    def drain_gate(self, wid: str) -> None:
        """Reroute every queued waiter of a draining worker; their plans
        re-pick a worker from the ring."""
        with self._lock:
            g = self._gates.get(wid)
            if g is None:
                return
            for q in g.waiters.values():
                for w in q:
                    w.rerouted = True
                    w.event.set()
            g.waiters.clear()

    def gate_inflight(self, wid: str) -> int:
        with self._lock:
            g = self._gates.get(wid)
            return g.inflight if g else 0

    def load(self, wid: str) -> int:
        """In-flight + queued plans on a worker's gate — the bounded-
        load signal the spillover policy reads."""
        with self._lock:
            g = self._gates.get(wid)
            if g is None:
                return 0
            return g.inflight + sum(len(q) for q in g.waiters.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {name: {
                "weight": t.weight, "inFlight": t.inflight,
                "admitted": t.admitted,
                "rejectedQuota": t.rejected_quota,
                "rejectedTimeout": t.rejected_timeout,
                "waitTimeNs": t.wait_ns,
            } for name, t in self._tenants.items()}


def parse_weights(spec: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition("=")
        try:
            out[name.strip()] = float(w)
        except ValueError:
            raise ValueError(f"malformed tenant weight {part!r}")
    return out


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


class WorkerUnavailable(Exception):
    """The worker refused the handshake with a STRUCTURED unavailable
    reply (maxSessions backpressure) — healthy protocol, busy worker.
    Distinct from a transport fault so callers forward the reply's
    retry_after_ms instead of marking a live worker suspect."""

    def __init__(self, reply: dict):
        super().__init__(reply.get("error", "worker unavailable"))
        self.reply = dict(reply)
        self.reply.pop("fatal", None)   # the backend conn died, not
        #                                 the client's router session


class _Backend:
    """One upstream connection: (client session) x (worker generation).
    Holds the worker generation it handshook with, so a restarted
    worker is detected by comparison, reconnected, and replayed."""

    __slots__ = ("sock", "generation")

    def __init__(self, sock: socket.socket, generation: int):
        self.sock = sock
        self.generation = generation

    def request(self, header: dict, body: bytes = b""):
        protocol.send_msg(self.sock, header, body)
        return protocol.recv_msg(self.sock)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:  # net-ok: teardown
            pass


class _RouterHandler(socketserver.BaseRequestHandler):
    def handle(self):
        sock: socket.socket = self.request
        router: "Router" = self.server.router      # type: ignore
        sock.settimeout(router.idle_timeout)
        try:
            version = protocol.recv_preamble(sock)
            protocol.send_preamble(sock)
        except (protocol.ProtocolError, OSError, socket.timeout):
            # net-ok: malformed preamble — nothing registered yet
            return
        if version != protocol.PROTOCOL_VERSION:
            self._try_send(sock, {
                "msg": "error", "fatal": True,
                "error": f"protocol version mismatch: client {version}, "
                         f"router {protocol.PROTOCOL_VERSION}"})
            return
        session = _RouterSession(router, sock)
        with router.track_lock:
            router.active_conns.add(sock)
            router.session_count += 1
        try:
            session.loop()
        finally:
            session.close_backends()
            with router.track_lock:
                router.active_conns.discard(sock)
                router.session_count -= 1

    @staticmethod
    def _try_send(sock, reply: dict, body: bytes = b"") -> bool:
        try:
            protocol.send_msg(sock, reply, body)
            return True
        except OSError:  # net-ok: client gone; reply is best-effort
            return False


class _RouterSession:
    """Per-client-connection routing state: the session conf + tenant,
    the uploaded tables (kept as decoded pa.Table + IPC bytes + digest
    so they can be replayed to failover/replacement workers), and one
    backend connection per worker generation."""

    def __init__(self, router: "Router", sock: socket.socket):
        self.router = router
        self.sock = sock
        self.conf: Dict[str, str] = dict(router.client_base_conf)
        self.tenant = "default"
        self.tables: Dict[str, dict] = {}   # name -> {ipc, digest, table}
        self.backends: Dict[str, _Backend] = {}

    # ---- lifecycle ----
    def loop(self) -> None:
        router = self.router
        while not router.shutting_down.is_set():
            try:
                header, body = protocol.recv_msg(self.sock)
            except (protocol.ProtocolError, OSError, socket.timeout):
                # net-ok: truncated frame / idle timeout — per-connection
                # isolation, the router stays up
                return
            try:
                reply, reply_body = self.serve_one(header, body)
            except Exception as e:   # per-request isolation
                reply = {"msg": "error",
                         "error": f"{type(e).__name__}: {e}",
                         "traceback": traceback.format_exc()}
                # a fleet error names the query it belongs to
                if header.get("query_id"):
                    reply["query_id"] = str(header["query_id"])
                reply_body = b""
            if not _RouterHandler._try_send(self.sock, reply, reply_body):
                return
            if reply.get("fatal"):
                return

    def close_backends(self) -> None:
        for b in self.backends.values():
            b.close()
        self.backends.clear()

    # ---- backends ----
    def backend(self, w: WorkerHandle) -> _Backend:
        b = self.backends.get(w.wid)
        if b is not None and b.generation == w.generation:
            return b
        if b is not None:
            b.close()
        s = socket.create_connection((w.host, w.port),
                                     timeout=self.router.backend_timeout)
        try:
            protocol.send_preamble(s)
            protocol.recv_preamble(s)
            b = _Backend(s, w.generation)
            reply, _ = b.request({"msg": "hello", "conf": self.conf})
            if reply.get("msg") == "error":
                if reply.get("unavailable"):
                    raise WorkerUnavailable(reply)
                raise protocol.ProtocolError(
                    f"worker {w.wid} refused hello: {reply.get('error')}")
            # replay the session's tables: a failover or replacement
            # worker starts with an empty per-connection registry
            for name, rec in self.tables.items():
                reply, _ = b.request({"msg": "table", "name": name},
                                     rec["ipc"])
                if reply.get("msg") == "error":
                    raise protocol.ProtocolError(
                        f"worker {w.wid} refused table replay "
                        f"{name!r}: {reply.get('error')}")
        except BaseException:
            try:
                s.close()
            except OSError:  # net-ok: cleanup; the cause re-raises below
                pass
            raise
        self.backends[w.wid] = b
        return b

    def invalidate_backend(self, wid: str) -> None:
        b = self.backends.pop(wid, None)
        if b is not None:
            b.close()

    # ---- dispatch ----
    def serve_one(self, header: dict, body: bytes):
        msg = header.get("msg")
        if msg == "hello":
            self.conf.update(header.get("conf") or {})
            self.tenant = str(
                self.conf.get(FLEET_TENANT_ID.key) or "default")
            return {"msg": "hello_ack", "server": "spark-rapids-tpu",
                    "router": True, "tenant": self.tenant,
                    "version": protocol.PROTOCOL_VERSION}, b""
        if msg == "table":
            return self.serve_table(header, body)
        if msg == "drop_table":
            return self.serve_drop(header)
        if msg == "stats":
            return {"msg": "stats",
                    "stats": self.router.serving_stats()}, b""
        if msg == "trace":
            return self.serve_trace(header)
        if msg == "plan":
            return self.serve_plan(header)
        raise ValueError(f"unknown message {msg!r}")

    def serve_trace(self, header: dict):
        """The fleet's stitched-timeline read: the router's own
        flight-recorder leg for the query, PLUS the leg of the worker
        that served it (looked up in the query->worker LRU and fetched
        over an admin connection). ``what=costs`` merges the per-worker
        observed-cost stores instead (highest observation count wins
        per operator)."""
        router = self.router
        if header.get("what") == "costs":
            merged = router.merged_costs(header.get("fingerprint"))
            return {"msg": "trace_ack", "costs": merged}, b""
        qid = header.get("query_id") or None
        profiles = [qtrace.with_self_times(p)     # as the worker's do
                    for p in router.recorder.profiles(
                        qid, last=int(header.get("last", 0) or 0))]
        wid = router.worker_for_query(qid) if qid else None
        if wid is not None:
            with router._lock:
                w = router.workers.get(wid)
            if w is not None and w.alive():
                try:
                    reply = _admin_request(w.host, w.port,
                                           {"msg": "trace",
                                            "query_id": qid})
                    profiles = profiles + list(
                        reply.get("profiles") or [])
                except (OSError, protocol.ProtocolError):
                    pass    # net-ok: the router leg still answers
        return {"msg": "trace_ack", "profiles": profiles,
                "recorder": router.recorder.stats()}, b""

    def serve_table(self, header: dict, body: bytes):
        from ..plan import plancache
        name = header["name"]
        table = protocol.ipc_to_table(body)
        digest = plancache.digest_ipc(body)
        old = self.tables.get(name)
        if old is not None and old["digest"] != digest:
            # re-upload with new content: router-tier flights parked on
            # results over the old bytes must re-execute, not be served
            # the pre-replace result
            self.router.single_flight.invalidate_digest(old["digest"])
        # fan out FIRST, record after: a backend freshly created during
        # the fan-out replays the registry in its handshake, and with
        # the new table already recorded it would receive the same IPC
        # bytes twice (and its replace-invalidation ack — performed by
        # the replay, not the explicit send — would be dropped from the
        # aggregated count)
        invalidated, acked = self._fan_out(
            {"msg": "table", "name": name}, body)
        self.tables[name] = {"ipc": body, "digest": digest,
                             "table": table}
        return {"msg": "table_ack", "name": name,
                "rows": table.num_rows, "digest": digest,
                "invalidated": invalidated, "workers": acked}, b""

    def serve_drop(self, header: dict):
        name = header["name"]
        rec = self.tables.pop(name, None)
        if rec is not None:
            # a duplicate parked on a flight over the dropped table
            # re-executes against post-drop state
            self.router.single_flight.invalidate_digest(rec["digest"])
        invalidated, acked = self._fan_out(
            {"msg": "drop_table", "name": name})
        return {"msg": "table_ack", "name": name,
                "invalidated": invalidated, "workers": acked}, b""

    def _fan_out(self, header: dict, body: bytes = b"") -> Tuple[int, int]:
        """Send a table-registry op to every routable worker; the
        summed ``invalidated`` stays additive across the fleet because
        persistent-tier deletion is idempotent (the first worker
        reached empties the store; later workers count only their own
        memory tiers). A worker that breaks mid-fan-out is marked per
        the suspect/dead discipline and skipped — its replacement
        replays the CURRENT table set on reconnect, so the registry
        converges."""
        invalidated = 0
        acked = 0
        for w in self.router.routable_workers():
            try:
                reply, _ = self.backend(w).request(header, body)
            except WorkerUnavailable:
                # busy, not broken: no suspect marking; its replacement
                # backend replays the current table set on next use
                continue
            except (OSError, protocol.ProtocolError) as e:
                # net-ok: the fault IS handled — the worker is marked
                # suspect/dead and its backend dropped; fan-out acks
                # only what succeeded (the replay converges the rest)
                self.invalidate_backend(w.wid)
                self.router.note_failure(w, e)
                continue
            if reply.get("msg") == "error":
                continue    # per-worker isolation; ack what succeeded
            self.router.note_ok(w)
            invalidated += int(reply.get("invalidated", 0))
            acked += 1
        return invalidated, acked

    def serve_plan(self, header: dict):
        router = self.router
        t_open = time.perf_counter_ns()
        # --- fingerprint (router-side, over the plandoc dialect) ---
        # merged exactly as the worker's Session merges it (worker base
        # conf <- hello conf <- plan conf), so the fingerprint the ring
        # hashes IS the fingerprint keying the worker's planning cache
        try:
            conf = RapidsTpuConf(dict(router.worker_conf, **self.conf,
                                      **(header.get("conf") or {})))
        except KeyError as e:
            reply = {"msg": "error", "error": f"unknown config: {e}"}
            if header.get("query_id"):
                reply["query_id"] = str(header["query_id"])
            return reply, b""
        # adopt the client-minted query identity (mint for bare
        # clients) and stamp it into the forwarded header, so the
        # worker's spans/errors and the router's own leg all share it
        query_id = str(header.get("query_id") or qtrace.mint_query_id())
        header["query_id"] = query_id
        import contextlib
        with contextlib.ExitStack() as _stack:
            if conf.get(TRACE_ENABLED.key):
                _stack.enter_context(qtrace.query_trace(
                    query_id, component="router",
                    max_spans=int(conf.get(TRACE_MAX_SPANS.key)),
                    recorder=router.recorder,
                    sink_path=str(conf.get(TRACE_SINK_PATH.key))))
            with qtrace.span("router.fingerprint", kind="router"):
                fp = router.fingerprint(
                    header.get("plan"),
                    {n: r["table"] for n, r in self.tables.items()},
                    conf)
            if header.get("mode") == "explain":
                # no device work: route by fingerprint, skip admission
                return self._attempt_on_ring(header, fp, admission=False,
                                             t_open=t_open,
                                             spent_ns_box=[0])
            # --- tenant quota ---
            try:
                router.admission.open_plan(self.tenant)
            except QuotaExceeded as e:
                return {"msg": "error", "unavailable": True,
                        "retryable": True,
                        "retry_after_ms": router.retry_after_ms,
                        "quota": True, "query_id": query_id,
                        "error": f"tenant quota: {e}"}, b""
            try:
                # worker round-trips AND admission-queue waits
                # accumulate here; overhead = router CPU only
                # (fingerprint, routing, framing), the number a "thin
                # coordinator" must keep flat
                spent_ns_box = [0]
                reply, body = self._dispatch_deduped(
                    header, fp, conf, query_id, t_open, spent_ns_box)
                if reply.get("msg") == "result":
                    overhead = (time.perf_counter_ns() - t_open
                                - spent_ns_box[0])
                    router.note_plan_served(reply.get("worker", ""),
                                            overhead)
                    router.note_query_worker(query_id,
                                             reply.get("worker", ""))
                    reply["router_overhead_ms"] = round(overhead / 1e6,
                                                        3)
                    reply["tenant"] = self.tenant
                elif reply.get("msg") == "error" and \
                        not reply.get("query_id"):
                    reply["query_id"] = query_id
                return reply, body
            finally:
                router.admission.close_plan(self.tenant)

    def _dispatch_deduped(self, header: dict, fp: str, conf,
                          query_id: str, t_open: int,
                          spent_ns_box: List[int]):
        """Router-tier in-flight dedup: a plan whose RESULT key matches
        one already dispatched parks on that flight and is served the
        leader's reply bytes verbatim — duplicates coalesce at the
        router regardless of which ring candidate each copy would have
        landed on, and a parked duplicate holds NO worker slot (only
        its tenant-quota ticket). Uncacheable or sharing-off plans
        dispatch directly."""
        from ..plan import plancache, sharing as _sharing
        router = self.router
        rkd = None
        if _sharing.inflight_on(conf):
            try:
                rkd = plancache.result_key_doc(
                    header.get("plan"),
                    {n: r["table"] for n, r in self.tables.items()},
                    conf)
            except Exception:   # Uncacheable / malformed doc: the
                rkd = None      # worker surfaces the real error
        if rkd is None:
            return self._attempt_on_ring(header, fp, admission=True,
                                         t_open=t_open,
                                         spent_ns_box=spent_ns_box)
        sf = router.single_flight
        timeout_s = _sharing.wait_timeout_s(conf)
        while True:
            role, flight = sf.begin(rkd[0], rkd[1])
            if role == "leader":
                router.sharing.note("inflight_leaders")
                return self._lead_flight(flight, header, fp, t_open,
                                         spent_ns_box)
            router.sharing.note("inflight_waits")
            t_wait = time.perf_counter_ns()
            with qtrace.span("sharing.inflightWait",
                             kind="cache") as sp:
                out = sf.wait(flight, timeout_s)
                if sp is not None:
                    sp.attrs["outcome"] = out.state
            # time parked on a sibling's flight is worker-side wait,
            # not router CPU — keep it out of the overhead metric
            spent_ns_box[0] += time.perf_counter_ns() - t_wait
            if out.state == "result":
                router.sharing.note("inflight_served")
                reply = dict(out.payload)
                reply["query_id"] = query_id
                reply["sharing"] = "inflight"
                return reply, out.ipc
            if out.state == "promoted":
                router.sharing.note("inflight_promoted")
                return self._lead_flight(flight, header, fp, t_open,
                                         spent_ns_box)
            if out.state in ("invalidated", "failed"):
                # a table drop/replace outdated the flight (or the
                # leader retired with nothing): re-begin against
                # post-drop state — never serve the stale result or
                # the leader's error verbatim
                router.sharing.note("inflight_invalidated")
                continue
            # timeout: go solo (no publish — the flight is not ours)
            router.sharing.note("inflight_timeouts")
            return self._attempt_on_ring(header, fp, admission=True,
                                         t_open=t_open,
                                         spent_ns_box=spent_ns_box)

    def _lead_flight(self, flight, header: dict, fp: str, t_open: int,
                     spent_ns_box: List[int]):
        """Dispatch as the flight's leader and settle it: a result
        reply publishes its payload + body to every parked duplicate;
        anything else (error reply, transport failure) fails the
        flight, promoting exactly one waiter to re-execute."""
        router = self.router
        try:
            reply, body = self._attempt_on_ring(
                header, fp, admission=True, t_open=t_open,
                spent_ns_box=spent_ns_box)
        except BaseException as e:
            router.single_flight.fail(flight, e)
            raise
        if reply.get("msg") == "result":
            router.single_flight.complete(flight, body, reply)
        else:
            router.single_flight.fail(flight)
        return reply, body

    def _attempt_on_ring(self, header: dict, fp: str, admission: bool,
                         t_open: int, spent_ns_box: List[int]):
        """Try the plan on the ring's ordered candidates: home worker
        first, then failover successors. Suspects are tried LAST; a
        draining/dead worker is never a candidate. Each failover
        attempt re-replays the session's tables (the backend handshake
        does it) and counts against ``fleet.workerRetries``."""
        router = self.router
        attempts_left = router.worker_retries + 1
        last_unavailable = None
        resnapshot = True
        while resnapshot and attempts_left > 0:
            resnapshot = False
            ordered = router.candidates(fp)
            if admission:
                ordered = router.spill_order(ordered)
            if not ordered:
                return ({"msg": "error", "unavailable": True,
                         "retryable": True,
                         "retry_after_ms": router.retry_after_ms,
                         "error": "no live workers in the fleet"}, b"")
            for w in ordered:
                if attempts_left <= 0:
                    break
                attempts_left -= 1
                acquired = False
                if admission:
                    t_adm = time.perf_counter_ns()
                    adm_span = qtrace.span("router.admission",
                                           kind="admission",
                                           worker=w.wid,
                                           tenant=self.tenant)
                    adm_span.__enter__()
                    try:
                        router.admission.acquire(self.tenant, w.wid)
                        acquired = True
                    except _Reroute:
                        # the worker started draining while we queued:
                        # re-snapshot the ring and pick its successor
                        resnapshot = True
                        attempts_left += 1   # a reroute is not a failure
                        break
                    except AdmissionTimeout as e:
                        return ({"msg": "error", "unavailable": True,
                                 "retryable": True,
                                 "retry_after_ms": router.retry_after_ms,
                                 "error": str(e)}, b"")
                    finally:
                        adm_span.__exit__(None, None, None)
                        spent_ns_box[0] += \
                            time.perf_counter_ns() - t_adm
                t_w = time.perf_counter_ns()
                disp_span = qtrace.span("router.dispatch", kind="router",
                                        worker=w.wid)
                disp_span.__enter__()
                try:
                    reply, body = self.backend(w).request(header)
                except WorkerUnavailable as e:
                    # maxSessions refusal at the backend handshake: the
                    # worker is healthy — forward the structured reply
                    # if every candidate is busy, never mark suspect
                    last_unavailable = (e.reply, b"")
                    continue
                except (OSError, protocol.ProtocolError) as e:
                    # net-ok: the failover path — suspect/dead marking +
                    # resubmission to the next ring candidate. The time
                    # burned on the broken socket is worker-side wait,
                    # not router CPU (the finally keeps it out of the
                    # overhead metric)
                    self.invalidate_backend(w.wid)
                    router.note_failure(w, e)
                    router.note_failover()
                    last_unavailable = (
                        {"msg": "error", "unavailable": True,
                         "retryable": True,
                         "retry_after_ms": router.retry_after_ms,
                         "error": f"worker {w.wid} failed mid-query: "
                                  f"{type(e).__name__}: {e}"}, b"")
                    continue
                finally:
                    disp_span.__exit__(None, None, None)
                    spent_ns_box[0] += time.perf_counter_ns() - t_w
                    if acquired:
                        router.admission.release(w.wid)
                router.note_ok(w)
                if reply.get("msg") == "error" and \
                        reply.get("unavailable"):
                    # breaker open / worker admission full: healthy
                    # protocol, unhealthy worker — fail the shape over,
                    # remember the reply in case EVERY candidate is
                    # unavailable
                    if reply.get("fatal"):
                        self.invalidate_backend(w.wid)
                        reply.pop("fatal", None)
                    last_unavailable = (reply, b"")
                    continue
                if reply.get("msg") == "error" and reply.get("fatal"):
                    # e.g. watchdog timeout: the worker closed our
                    # backend session. The ROUTER owns this client's
                    # session state (conf + tables), so the client
                    # connection survives — drop the backend (the next
                    # plan reconnects + replays) and forward non-fatal
                    self.invalidate_backend(w.wid)
                    reply.pop("fatal", None)
                if reply.get("msg") == "result":
                    reply["worker"] = w.wid
                    w.plans += 1
                return reply, body
        return last_unavailable if last_unavailable is not None else (
            {"msg": "error", "unavailable": True, "retryable": True,
             "retry_after_ms": router.retry_after_ms,
             "error": "every candidate worker failed"}, b"")


class _ThreadingRouterServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


class Router:
    """Embeddable router handle (tests embed it; production runs
    ``python -m spark_rapids_tpu.server.router``)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 workers: Optional[int] = None,
                 conf: Optional[dict] = None,
                 worker_conf: Optional[dict] = None,
                 idle_timeout: float = 600.0,
                 backend_timeout: float = 600.0,
                 spawn_timeout_s: float = 60.0,
                 worker_cpusets: Optional[List[str]] = None,
                 worker_env: Optional[Dict[str, str]] = None):
        tconf = RapidsTpuConf(dict(conf or {}))
        self.conf = tconf
        n = int(workers if workers is not None
                else tconf.get(FLEET_WORKERS.key))
        self.idle_timeout = idle_timeout
        self.backend_timeout = backend_timeout
        self.retry_after_ms = int(tconf.get(SERVER_RETRY_AFTER_MS.key))
        self.worker_retries = int(tconf.get(FLEET_WORKER_RETRIES.key))
        self.spillover_depth = int(
            tconf.get(FLEET_SPILLOVER_QUEUE_DEPTH.key))
        self.drain_timeout_s = int(
            tconf.get(FLEET_DRAIN_TIMEOUT_MS.key)) / 1000.0
        #: conf seeded into every client session (tenantId etc. ride the
        #: client hello on top)
        self.client_base_conf: Dict[str, str] = {}

        # --- worker conf: the fleet serves results by default, through
        # a SHARED persistent tier so restarts rehydrate ---
        wconf = dict(conf or {})
        wconf.update(worker_conf or {})
        wconf.setdefault(SERVER_RESULT_CACHE_ENABLED.key, "true")
        self._own_store_dir = None
        if not str(wconf.get(FLEET_RESULT_STORE_PATH.key, "")).strip():
            self._own_store_dir = tempfile.mkdtemp(
                prefix="rtpu_resultstore_")
            wconf[FLEET_RESULT_STORE_PATH.key] = self._own_store_dir
        self.worker_conf = wconf
        self.store_path = wconf[FLEET_RESULT_STORE_PATH.key]

        # --- admission ---
        self.admission = TenantAdmission(
            parse_weights(str(tconf.get(FLEET_TENANT_WEIGHTS.key))),
            int(tconf.get(FLEET_TENANT_MAX_CONCURRENT.key)),
            int(tconf.get(FLEET_ADMISSION_TIMEOUT_MS.key)))
        per_worker = int(tconf.get(FLEET_MAX_INFLIGHT_PER_WORKER.key))
        self._gate_capacity = per_worker if per_worker > 0 else int(
            RapidsTpuConf(wconf).get(SERVER_CONCURRENT_COLLECTS.key))

        # --- fleet (spawned in parallel: N cold engine imports) ---
        self._lock = threading.Lock()
        self.workers: Dict[str, WorkerHandle] = {}
        self.ring = HashRing(int(tconf.get(FLEET_VNODES.key)))
        self._spawn_timeout_s = spawn_timeout_s
        handles = [WorkerHandle(
            f"w{i}", self.worker_conf, host,
            spawn_timeout_s=spawn_timeout_s,
            cpuset=(worker_cpusets[i % len(worker_cpusets)]
                    if worker_cpusets else None),
            env=worker_env) for i in range(n)]
        errs: List[BaseException] = []

        def _spawn(w: WorkerHandle):
            try:
                w.spawn()
            except BaseException as e:
                errs.append(e)

        ts = [threading.Thread(target=_spawn, args=(w,), daemon=True)
              for w in handles]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            for w in handles:
                w.kill()
            if self._own_store_dir is not None:
                # __init__ never returns, so stop() can't clean it up
                shutil.rmtree(self._own_store_dir, ignore_errors=True)
            raise RuntimeError(f"fleet spawn failed: {errs[0]}") from \
                errs[0]
        for w in handles:
            self.admission.gate(w.wid, self._gate_capacity)
            self.workers[w.wid] = w
        with self._lock:
            self._rebuild_ring_locked()

        # --- metrics ---
        self.plans_routed = 0
        self.failovers = 0
        self.fp_fallbacks = 0
        self.spillovers = 0
        self._overhead_ns = deque(maxlen=8192)
        # --- cross-query in-flight dedup (router tier) ---
        # per-Router instance (embedded multi-router tests must not
        # cross-talk), keyed on the same digest-embedded result key the
        # workers dedup on — duplicates are coalesced HERE regardless of
        # which ring candidate each copy would have hashed to
        from ..plan import sharing as _sharing
        self.single_flight = _sharing.SingleFlight()
        self.sharing = _sharing.SharingMetrics()
        # --- adaptive cost sharing (0 = on-demand only) ---
        self.cost_sync_plans = int(tconf.get(FLEET_COST_SYNC_PLANS.key))
        self.cost_syncs = 0
        self.cost_entries_adopted = 0

        # --- observability: the router's own flight recorder (its leg
        # of each traced query's timeline) + which worker served which
        # query_id, so the 'trace' op can fetch the worker's leg and
        # answer ONE stitched timeline ---
        self.recorder = qtrace.FlightRecorder(
            capacity=int(tconf.get(SERVER_TRACE_RECORDER_ENTRIES.key)),
            slow_query_ms=int(tconf.get(SERVER_TRACE_SLOW_QUERY_MS.key)))
        self._served: "OrderedDict[str, str]" = OrderedDict()

        # --- frontend ---
        srv = _ThreadingRouterServer((host, port), _RouterHandler)
        srv.router = self                      # type: ignore
        self._server = srv
        self.shutting_down = threading.Event()
        self.track_lock = threading.Lock()
        self.active_conns: set = set()
        self.session_count = 0
        self._thread: Optional[threading.Thread] = None

    # ---- fleet management ----
    def _rebuild_ring_locked(self) -> None:
        self.ring.rebuild([w.wid for w in self.workers.values()
                           if w.state in (LIVE, SUSPECT)])

    def candidates(self, fingerprint: str) -> List[WorkerHandle]:
        """Ring-ordered candidates: LIVE workers in ring order first,
        then SUSPECT ones (tried last, per the PR-11 discipline)."""
        with self._lock:
            order = self.ring.ordered(fingerprint)
            ws = [self.workers[wid] for wid in order
                  if wid in self.workers]
            live = [w for w in ws if w.state == LIVE]
            suspect = [w for w in ws if w.state == SUSPECT]
            return live + suspect

    def routable_workers(self) -> List[WorkerHandle]:
        """Fan-out targets: every worker whose process can still answer
        (draining workers included — their in-flight queries must see
        table drops)."""
        with self._lock:
            return [w for w in self.workers.values()
                    if w.state in (LIVE, SUSPECT, DRAINING)
                    and w.alive()]

    def note_failure(self, w: WorkerHandle,
                     error: Optional[BaseException] = None) -> None:
        """One broken transaction marks a worker SUSPECT; a process
        observed dead is promoted DEAD immediately (no rehabilitation
        without replacement — the PR-11 rule that a corpse cannot beat
        itself back into the ring). A connection the peer closed or
        reset (``error`` a ConnectionError) is how a killed process
        looks a moment BEFORE the kernel lets it be reaped, so only then
        is it given half a second to become reapable, outside the lock;
        a timeout or any other fault reads the process as it is."""
        if isinstance(error, ConnectionError) and w.proc is not None:
            try:
                w.proc.wait(timeout=0.5)
            except subprocess.TimeoutExpired:
                pass
        with self._lock:
            w.failures += 1
            if not w.alive():
                w.state = DEAD
            elif w.state == LIVE:
                w.state = SUSPECT
            self._rebuild_ring_locked()

    def note_ok(self, w: WorkerHandle) -> None:
        if w.state == SUSPECT:
            with self._lock:
                if w.state == SUSPECT:
                    w.state = LIVE
                    self._rebuild_ring_locked()

    def note_failover(self) -> None:
        with self._lock:
            self.failovers += 1

    def spill_order(self, ordered: List[WorkerHandle]
                    ) -> List[WorkerHandle]:
        """Bounded-load consistent hashing (fleet.spilloverQueueDepth):
        when the home worker's gate already holds that many in-flight +
        queued plans, dispatch to the least-loaded candidate instead
        (ring order breaks ties). Affinity yields to utilization only
        under skew — the spilled worker plans the shape once and is
        warm for it thereafter."""
        if self.spillover_depth <= 0 or len(ordered) < 2:
            return ordered
        if self.admission.load(ordered[0].wid) < self.spillover_depth:
            return ordered
        loads = [self.admission.load(w.wid) for w in ordered]
        best = min(range(len(ordered)), key=lambda i: (loads[i], i))
        if best == 0:
            return ordered
        with self._lock:
            self.spillovers += 1
        return [ordered[best]] + [w for i, w in enumerate(ordered)
                                  if i != best]

    def note_plan_served(self, wid: str, overhead_ns: int) -> None:
        with self._lock:
            self.plans_routed += 1
            self._overhead_ns.append(overhead_ns)
            due = (self.cost_sync_plans > 0
                   and self.plans_routed % self.cost_sync_plans == 0)
        if due:
            # outside the lock: sync_costs fans out over the network
            self.sync_costs()

    # ---- adaptive cost sharing ----
    def merged_costs(self, fp: Optional[str] = None
                     ) -> Dict[str, Dict[str, dict]]:
        """Pull every routable worker's observed-cost store over the
        ``trace what=costs`` admin op and merge per operator — the
        highest observation count wins, so the worker that has seen a
        shape most often speaks for the fleet."""
        merged: Dict[str, Dict[str, dict]] = {}
        for w in self.routable_workers():
            try:
                reply = _admin_request(
                    w.host, w.port,
                    {"msg": "trace", "what": "costs",
                     **({"fingerprint": fp} if fp else {})})
            except (OSError, protocol.ProtocolError):
                continue    # net-ok: costs are best-effort reads
            for fprint, ops in (reply.get("costs") or {}).items():
                if not ops:
                    continue
                dst = merged.setdefault(fprint, {})
                for op, e in ops.items():
                    if op not in dst or \
                            e.get("count", 0) > \
                            dst[op].get("count", 0):
                        dst[op] = e
        return merged

    def sync_costs(self) -> dict:
        """Fleet cost sync: merge the per-worker observed-cost stores
        (merged_costs) and push the result back to every routable
        worker over the ``costs_load`` op. Afterwards worker B plans
        from costs worker A measured — the adaptive cost-fed path
        works fleet-wide, not just per worker. Best-effort per worker;
        returns {'workers': pushed, 'fingerprints': merged,
        'adopted': total entries adopted across the fleet}."""
        merged = self.merged_costs()
        pushed = 0
        adopted = 0
        if merged:
            for w in self.routable_workers():
                try:
                    reply = _admin_request(
                        w.host, w.port,
                        {"msg": "costs_load", "costs": merged})
                except (OSError, protocol.ProtocolError):
                    continue    # net-ok: the next sync catches it up
                pushed += 1
                adopted += int(reply.get("adopted", 0) or 0)
        with self._lock:
            self.cost_syncs += 1
            self.cost_entries_adopted += adopted
        return {"workers": pushed, "fingerprints": len(merged),
                "adopted": adopted}

    def note_query_worker(self, query_id: str, wid: str) -> None:
        """Remember which worker served a query_id (bounded LRU) so the
        ``trace`` op can fetch that worker's flight-recorder leg."""
        if not query_id:
            return
        with self._lock:
            self._served[query_id] = wid
            self._served.move_to_end(query_id)
            while len(self._served) > 4096:
                self._served.popitem(last=False)

    def worker_for_query(self, query_id: str) -> Optional[str]:
        with self._lock:
            return self._served.get(query_id)

    def fingerprint(self, doc, tables, conf: RapidsTpuConf) -> str:
        """The plan-shape fingerprint, computed router-side. A plan the
        fingerprint path cannot handle still routes — consistently — on
        a hash of its raw document (counted, never silent)."""
        from ..plan import plancache
        try:
            return plancache.shape_fingerprint_doc(doc, tables, conf)
        except Exception:
            with self._lock:
                self.fp_fallbacks += 1
            return hashlib.blake2b(
                json.dumps(doc, sort_keys=True, default=str)
                .encode("utf-8"), digest_size=16).hexdigest()

    # ---- rolling restart ----
    def drain_worker(self, wid: str) -> bool:
        """Stop routing to ``wid``, reroute its queued plans, and wait
        for its in-flight plans to finish (bounded by drainTimeoutMs).
        Returns True when the drain completed; False when the worker
        died mid-drain (promoted DEAD — the PR-11 discipline: never
        wait out a corpse's timeout)."""
        with self._lock:
            w = self.workers[wid]
            w.state = DRAINING
            self._rebuild_ring_locked()
        self.admission.drain_gate(wid)
        deadline = time.monotonic() + self.drain_timeout_s
        while time.monotonic() < deadline:
            if not w.alive():
                with self._lock:
                    w.state = DEAD
                return False
            if self.admission.gate_inflight(wid) == 0:
                return True
            time.sleep(0.02)
        return self.admission.gate_inflight(wid) == 0

    def replace_worker(self, wid: str, grace_s: float = 10.0
                       ) -> WorkerHandle:
        """Stop (gracefully when it drained; kill when it is a corpse)
        and respawn the worker at the SAME ring position. The
        replacement's generation bump makes every session's backend
        reconnect + replay; its result cache rehydrates from the
        persistent tier on read-through."""
        with self._lock:
            w = self.workers[wid]
        if w.alive():
            w.graceful_stop(grace_s)
        else:
            w.kill()
        w.restarts += 1
        w.spawn()           # bumps generation, state back to LIVE
        self.admission.gate(wid, self._gate_capacity)
        with self._lock:
            self._rebuild_ring_locked()
        return w

    def rolling_restart(self, grace_s: float = 10.0) -> dict:
        """Zero-downtime rolling restart: one worker at a time —
        drain, stop via the shutdown/stop() contract, respawn, wait
        ready — while the rest of the fleet keeps serving the drained
        worker's hash slots."""
        report = {"workers": [], "drained": 0, "died_mid_drain": 0,
                  "drain_timeout": 0}
        for wid in list(self.workers):
            drained = self.drain_worker(wid)
            if drained:
                report["drained"] += 1
            elif self.workers[wid].state == DEAD:
                report["died_mid_drain"] += 1
            else:
                # alive past drainTimeoutMs: a slow drain, not a death —
                # the replacement below still stops it (stop() cancels
                # the wedged in-flight work within its own grace)
                report["drain_timeout"] += 1
            self.replace_worker(wid, grace_s=grace_s)
            report["workers"].append(
                {"id": wid, "drained": drained,
                 "generation": self.workers[wid].generation})
        return report

    # ---- stats ----
    def _pct(self, xs: List[int], p: float) -> float:
        if not xs:
            return 0.0
        xs = sorted(xs)
        i = min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))
        return xs[i] / 1e6

    def serving_stats(self) -> dict:
        """Fleet-wide stable-schema stats: the router's own routing /
        admission counters plus each worker's serving_stats() fetched
        over the ``stats`` wire op on short-lived ADMIN connections —
        never a session's backends, whose handshake would replay the
        session's whole table set to workers it never queried just to
        read counters (best-effort — a dead worker reports null)."""
        with self._lock:
            overhead = list(self._overhead_ns)
            worker_snaps = [w.snapshot() for w in self.workers.values()]
            plans = self.plans_routed
            failovers = self.failovers
            fallbacks = self.fp_fallbacks
            cost_syncs = self.cost_syncs
            cost_adopted = self.cost_entries_adopted
        per_worker = {}
        for w in self.routable_workers():
            try:
                reply = _admin_request(w.host, w.port, {"msg": "stats"})
                per_worker[w.wid] = reply.get("stats") \
                    if isinstance(reply, dict) else None
            except (OSError, protocol.ProtocolError):
                per_worker[w.wid] = None   # net-ok: stats are
                #                            best-effort; null marks it
        return {
            # v2: adds the `trace` block (the router's flight-recorder
            # occupancy/slow/dropped counters; each worker's own trace
            # block rides its per-worker stats below)
            # v3: adds the `adaptive` block (fleet cost syncs; each
            # worker's own adaptive decision counters ride its
            # per-worker stats below)
            # v4: adds the `sharing` block (router-tier in-flight
            # dedup; each worker's full sharing block — subplan cache,
            # scan-share registry — rides its per-worker stats below)
            "schemaVersion": 4,
            "adaptive": {
                "costSyncCount": cost_syncs,
                "costEntriesAdopted": cost_adopted,
                "costSyncEveryPlans": self.cost_sync_plans,
            },
            "sharing": dict(self.sharing.snapshot(),
                            inflight=self.single_flight.stats()),
            "router": True,
            "trace": {
                "recorder": self.recorder.stats(),
            },
            "server": {
                "host": str(self.address[0]), "port": int(self.port),
                "activeSessions": self.active_sessions,
            },
            "fleet": {
                "workers": worker_snaps,
                "storePath": self.store_path,
            },
            "routing": {
                "plans": plans,
                "failovers": failovers,
                "fingerprintFallbacks": fallbacks,
                "spillovers": self.spillovers,
                "overheadMs": {
                    "p50": round(self._pct(overhead, 50), 3),
                    "p99": round(self._pct(overhead, 99), 3),
                    "n": len(overhead),
                },
                "perWorkerPlans": {s["id"]: s["plans"]
                                   for s in worker_snaps},
            },
            "tenants": self.admission.snapshot(),
            "workers": per_worker,
        }

    # ---- frontend lifecycle ----
    @property
    def address(self):
        return self._server.server_address

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def active_sessions(self) -> int:
        with self.track_lock:
            return self.session_count

    def start(self) -> "Router":
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="plan-router",
            daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def stop(self, grace_s: float = 10.0) -> None:
        if self.shutting_down.is_set():
            return
        self.shutting_down.set()
        with self.track_lock:
            conns = list(self.active_conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:  # net-ok: peer already hung up
                pass
            try:
                sock.close()
            except OSError:  # net-ok: teardown
                pass
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
        for w in self.workers.values():
            if w.alive():
                w.graceful_stop(grace_s)
            else:
                w.kill()
        if self._own_store_dir is not None:
            shutil.rmtree(self._own_store_dir, ignore_errors=True)


def readiness_line(router: Router) -> str:
    return (f"spark-rapids-tpu plan router listening on "
            f"{router.address[0]}:{router.port} "
            f"({len(router.workers)} workers)")


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        description="spark-rapids-tpu serving-fleet router")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9098)
    p.add_argument("--workers", type=int, default=None,
                   help="worker subprocess count (default: "
                        "spark.rapids.tpu.server.fleet.workers)")
    p.add_argument("--conf", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="router + worker base conf (repeatable)")
    p.add_argument("--worker-conf", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="extra conf for the workers only (repeatable)")
    args = p.parse_args(argv)

    def kv(pairs):
        out = {}
        for item in pairs:
            k, _, v = item.partition("=")
            out[k] = v
        return out

    router = Router(args.host, args.port, workers=args.workers,
                    conf=kv(args.conf), worker_conf=kv(args.worker_conf))
    print(readiness_line(router), flush=True)
    try:
        router.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        router.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
