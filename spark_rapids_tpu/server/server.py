"""Plan server: the engine side of the external-driver seam.

Each connection is an isolated driver session: its own conf (sent with
``hello``), its own table registry, one query at a time. Planning
(tagging/fallback/CBO/mesh lowering) and execution both happen here, via
the same ``Session`` every in-process caller uses — so a plan submitted
over the wire takes exactly the code path of ``Session.collect``, and the
response carries the executed exec names + fallback list the way the
reference's plan-capture listener exposes them to its test harness
(ExecutionPlanCaptureCallback.scala:31).

Serving-tier fault policy (reference: the executor fatal-error exit
policy, Plugin.scala:215-393, applied at a query frontend the way
"Accelerating Presto with GPUs" degrades gracefully when the
accelerator is unhealthy):

- **admission** — at most ``spark.rapids.tpu.server.maxSessions``
  concurrent connections; over the bound, a structured ``unavailable``
  reply with ``retry_after_ms`` instead of an unbounded thread pile-up;
- **circuit breaker** — every ``plan`` consults the executor's health
  (``ExecutorRuntime.ensure_healthy``); once a fatal device error
  poisons the runtime, plans get ``unavailable`` + retry-after, never a
  dead connection;
- **watchdog** — a per-query deadline (``plan`` header ``timeout_ms``,
  default ``spark.rapids.tpu.server.queryTimeoutMs``) returns a
  structured RETRYABLE error when the collect overruns instead of tying
  the handler thread forever; ``stop()`` cancels in-flight queries and
  unblocks their handlers.

Run standalone:  python -m spark_rapids_tpu.server --port 9099
"""

from __future__ import annotations

import socket
import socketserver
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional

import pyarrow as pa

from ..plan.logical import DataFrame
from ..plan.session import Session
from . import plandoc, protocol


class QueryCancelledError(RuntimeError):
    """The server cancelled this query (deadline overrun or stop())."""


def _runtime_health() -> None:
    """Default breaker probe: the process ExecutorRuntime, when one
    exists (a device-less test server has nothing to poison)."""
    from ..plugin import ExecutorRuntime
    runtime = ExecutorRuntime._instance
    if runtime is not None:
        runtime.ensure_healthy()


class CircuitBreaker:
    """CLOSED while the executor is healthy, OPEN once a fatal device
    error poisons it: plans are answered ``unavailable`` (with a
    retry-after hint for the client's scheduler) instead of queueing
    onto a dead device. The breaker re-probes health on every admit, so
    it closes again the moment the runtime is replaced/healthy (the
    half-open probe is free here — ``ensure_healthy`` is a field
    check)."""

    def __init__(self, health_check: Optional[Callable[[], None]] = None,
                 retry_after_ms: int = 1000):
        self.health_check = health_check or _runtime_health
        self.retry_after_ms = retry_after_ms
        self.rejected_count = 0

    def admit(self) -> Optional[str]:
        """None = admit; otherwise the reason the executor is
        unavailable."""
        try:
            self.health_check()
            return None
        except Exception as e:
            self.rejected_count += 1
            return f"{type(e).__name__}: {e}"

    def record_failure(self, exc: BaseException) -> None:
        """Classify a query failure against the runtime's fatal-marker
        policy; a fatal one poisons the runtime, opening the breaker for
        every subsequent plan (reference: onTaskFailed →
        executor-unusable). ONLY execution-phase failures (tagged where
        the collect actually ran) are classified: the fatal markers are
        message substrings, and letting request-validation errors — whose
        text echoes client-controlled input — reach them would let one
        crafted message poison the executor for every session."""
        if not getattr(exc, "_rtpu_exec_phase", False):
            return
        from ..plugin import ExecutorRuntime
        runtime = ExecutorRuntime._instance
        if runtime is not None and runtime.classify_failure(exc):
            runtime.on_task_failed(exc)


class _TableRegistry(dict):
    """Per-connection table registry (name -> pa.Table) plus the content
    digest of each upload — the dependency key the result cache is
    invalidated on when a client drops or replaces a table."""

    def __init__(self):
        super().__init__()
        self.digests: Dict[str, str] = {}


class _ActiveQuery:
    def __init__(self, thread: threading.Thread, cancel: threading.Event):
        self.thread = thread
        self.cancel = cancel
        #: set under track_lock when the handler abandons this query on
        #: deadline overrun: the WORKER now owns the maxSessions slot
        #: and releases it when the collect actually ends, so abandoned
        #: workers still count against the admission bound
        self.owns_admission = False


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        from ..trace import name_thread
        name_thread("rtpu-q")       # its own line in a device trace
        sock: socket.socket = self.request
        srv = self.server
        sock.settimeout(srv.idle_timeout)   # type: ignore[attr-defined]
        try:
            version = protocol.recv_preamble(sock)
            protocol.send_preamble(sock)
        except (protocol.ProtocolError, OSError, socket.timeout):
            # net-ok: malformed/temporized preamble — drop the
            # connection; nothing is registered yet
            return
        # the admission slot is taken only AFTER the preamble completes:
        # a connection that never speaks (slowloris) must not hold a
        # maxSessions slot for the whole idle timeout
        admitted = srv.admission.acquire(blocking=False)
        try:
            if not admitted:
                self._try_send(sock, {
                    "msg": "error", "fatal": True, "unavailable": True,
                    "retryable": True,
                    "retry_after_ms": srv.retry_after_ms,
                    "error": f"server at maxSessions="
                             f"{srv.max_sessions}; retry later"})
                return
            if version != protocol.PROTOCOL_VERSION:
                self._try_send(sock, {
                    "msg": "error", "fatal": True,
                    "error": f"protocol version mismatch: client {version}, "
                             f"server {protocol.PROTOCOL_VERSION}"})
                return
            with srv.track_lock:
                srv.active_conns.add(sock)
                srv.session_count += 1
            try:
                self._session_loop(sock)
            finally:
                with srv.track_lock:
                    srv.active_conns.discard(sock)
                    srv.session_count -= 1
        finally:
            if admitted and not getattr(self, "_admission_transferred",
                                        False):
                srv.admission.release()

    @staticmethod
    def _try_send(sock, reply: dict, body: bytes = b"") -> bool:
        try:
            protocol.send_msg(sock, reply, body)
            return True
        except OSError:  # net-ok: client gone; reply is best-effort
            return False

    def _session_loop(self, sock) -> None:
        srv = self.server
        tables = _TableRegistry()
        conf = dict(srv.base_conf)          # type: ignore[attr-defined]
        while not srv.shutting_down.is_set():
            try:
                header, body = protocol.recv_msg(sock)
            except (protocol.ProtocolError, OSError, socket.timeout):
                # net-ok: oversized/truncated frame or idle timeout —
                # per-connection isolation; the server stays up
                return
            reply, reply_body = self._serve_one(header, body, tables, conf)
            if not self._try_send(sock, reply, reply_body):
                return
            if reply.get("fatal"):
                return

    def _serve_one(self, header, body, tables, conf):
        srv = self.server
        if header.get("msg") == "plan":
            reason = srv.breaker.admit()
            if reason is not None:
                return {"msg": "error", "unavailable": True,
                        "retryable": True,
                        "retry_after_ms": srv.retry_after_ms,
                        "error": f"executor unavailable: {reason}"}, b""
            try:
                # an EXPLICIT timeout_ms wins, including 0 (= unbounded,
                # matching the queryTimeoutMs conf's documented meaning)
                timeout_ms = int(header.get("timeout_ms",
                                            srv.default_timeout_ms) or 0)
            except (TypeError, ValueError):
                return {"msg": "error",
                        "error": f"invalid timeout_ms "
                                 f"{header.get('timeout_ms')!r}"}, b""
            if timeout_ms > 0:
                return self._serve_with_watchdog(header, body, tables,
                                                 conf, timeout_ms)
        try:
            return self._dispatch(header, body, tables, conf,
                                  srv.shutting_down.is_set)
        except Exception as e:   # per-request isolation: report, keep conn
            srv.breaker.record_failure(e)
            reply = {"msg": "error", "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()}
            # every error reply names the query it belongs to — a fleet
            # failure must be attributable to a client request
            if header.get("query_id"):
                reply["query_id"] = str(header["query_id"])
            return reply, b""

    def _serve_with_watchdog(self, header, body, tables, conf,
                             timeout_ms: int):
        """Run the plan on a watchdog-supervised worker. On deadline
        overrun the handler replies a structured RETRYABLE error and
        closes the session (fatal=True): the worker may still be inside
        an uninterruptible collect, so the connection must not accept
        further queries that would interleave with it. The worker checks
        its cancel flag at the cancellation points (pre-execution and
        the test delay loop) and is joined — bounded — by stop()."""
        srv = self.server
        cancel = threading.Event()
        done = threading.Event()
        box: dict = {}

        def cancelled() -> bool:
            return cancel.is_set() or srv.shutting_down.is_set()

        query = _ActiveQuery(None, cancel)

        def work():
            from ..trace import name_thread
            name_thread("rtpu-q")
            try:
                box["reply"] = self._dispatch(header, body, tables, conf,
                                              cancelled)
            except Exception as e:
                # classify HERE, not on receipt: a query that overran its
                # deadline still fails later on this thread, and a fatal
                # device error must open the breaker even though the
                # handler already replied timeout and moved on
                srv.breaker.record_failure(e)
                box["exc"] = e
            finally:
                done.set()
                with srv.track_lock:
                    srv.active_queries[:] = [
                        q for q in srv.active_queries if q is not query]
                    owned = query.owns_admission
                if owned:
                    srv.admission.release()

        worker = threading.Thread(target=work, daemon=True,
                                  name="plan-query")
        query.thread = worker
        with srv.track_lock:
            srv.active_queries.append(query)
        worker.start()
        if not done.wait(timeout_ms / 1000.0):
            cancel.set()
            with srv.track_lock:
                if any(q is query for q in srv.active_queries):
                    # the worker is still collecting: hand it the
                    # admission slot so abandoned queries keep counting
                    # against maxSessions until they actually end (the
                    # handler's finally skips the release)
                    query.owns_admission = True
                    self._admission_transferred = True
            reply = {"msg": "error", "fatal": True, "retryable": True,
                     "timeout": True,
                     "error": f"query exceeded its {timeout_ms}ms "
                              f"deadline; cancelled — resubmit (possibly "
                              f"with a larger timeout_ms)"}
            if header.get("query_id"):
                # name the abandoned query: its trace (when enabled) is
                # in the flight recorder under this id once the worker
                # actually ends
                reply["query_id"] = str(header["query_id"])
            return reply, b""
        if "exc" in box:
            e = box["exc"]      # already breaker-classified by the worker
            # the exception was caught on the WORKER thread — format its
            # own traceback, not this handler thread's (empty) one
            reply = {"msg": "error", "error": f"{type(e).__name__}: {e}",
                     "retryable": isinstance(e, QueryCancelledError),
                     "traceback": "".join(traceback.format_exception(
                         type(e), e, e.__traceback__))}
            if header.get("query_id"):
                reply["query_id"] = str(header["query_id"])
            return reply, b""
        return box["reply"]

    def _dispatch(self, header, body, tables, conf,
                  cancelled: Callable[[], bool]):
        srv = self.server
        msg = header.get("msg")
        if msg == "hello":
            conf.update(header.get("conf") or {})
            return {"msg": "hello_ack",
                    "server": "spark-rapids-tpu",
                    "version": protocol.PROTOCOL_VERSION}, b""
        if msg == "stats":
            # fleet-ops surface: the router aggregates these per worker
            return {"msg": "stats",
                    "stats": srv.plan_server.serving_stats()}, b""
        if msg == "shutdown":
            # graceful drain hook for subprocess workers (the rolling
            # restart's stop() seam, reachable over the wire): ack, then
            # stop off-thread so the reply reaches the caller before the
            # listener closes its connections
            grace = float(header.get("grace_s", 10.0))

            def _stop():
                time.sleep(0.05)      # let the ack flush
                srv.plan_server.stop(grace_s=grace)

            threading.Thread(target=_stop, daemon=True,
                             name="server-shutdown").start()
            return {"msg": "shutdown_ack", "fatal": True}, b""
        if msg == "table":
            from ..plan import plancache, sharing
            name = header["name"]
            digest = plancache.digest_ipc(body)
            invalidated = 0
            old = tables.digests.get(name)
            if old is not None and old != digest:
                # re-upload with NEW content: results derived from the
                # replaced table must never be served again — neither
                # from the result cache nor from a flight/subplan/scan
                # entry still in motion over the old bytes
                invalidated = plancache.result_cache() \
                    .invalidate_digest(old)
                invalidated += sharing.invalidate_digest(old)
            tables[name] = protocol.ipc_to_table(body)
            # prime the digest memo from the wire bytes we already hold,
            # so result keys never re-hash the table
            plancache.register_digest(tables[name], digest)
            tables.digests[name] = digest
            return {"msg": "table_ack", "name": name,
                    "rows": tables[name].num_rows,
                    "digest": digest, "invalidated": invalidated}, b""
        if msg == "drop_table":
            from ..plan import plancache, sharing
            name = header["name"]
            tables.pop(name, None)
            digest = tables.digests.pop(name, None)
            invalidated = plancache.result_cache() \
                .invalidate_digest(digest) if digest else 0
            if digest:
                # a parked duplicate waiting on a flight over the
                # dropped table must re-execute against post-drop
                # state, never be served the pre-drop result
                invalidated += sharing.invalidate_digest(digest)
            return {"msg": "table_ack", "name": name,
                    "invalidated": invalidated}, b""
        if msg == "trace":
            # the flight-recorder surface: profiles of recent queries
            # (or one query_id), or the observed-cost store — the ops
            # seam PlanClient.last_trace()/observed_costs() read
            from .. import trace as qtrace
            if header.get("what") == "costs":
                store = qtrace.observed_costs()
                fp = header.get("fingerprint")
                costs = {fp: store.get(fp)} if fp else store.snapshot()
                return {"msg": "trace_ack", "costs": costs}, b""
            rec = srv.trace_recorder
            profiles = rec.profiles(header.get("query_id") or None,
                                    last=int(header.get("last", 0) or 0))
            # each span with its own microseconds (selfUs) beside its
            # duration: where the query's time went, exec by exec
            return {"msg": "trace_ack",
                    "profiles": [qtrace.with_self_times(p)
                                 for p in profiles],
                    "recorder": rec.stats()}, b""
        if msg == "profile":
            # a device trace of THIS process, the only one that can take
            # it (it holds the chip): jax.profiler started and stopped on
            # the operator's request. The engine's spans of the queries
            # in between are in it under their own names (trace.py)
            return {"msg": "profile_ack",
                    **srv.plan_server.profile(
                        str(header.get("action")),
                        header.get("dir"))}, b""
        if msg == "costs_load":
            # fleet cost-sharing ingress: adopt a merged observed-cost
            # snapshot the router fanned out (Router.sync_costs), so
            # THIS worker's next prepare of a shape a sibling measured
            # takes the cost-fed planning path. Per-entry highest
            # observation count wins — same rule as the read-side merge.
            from .. import trace as qtrace
            adopted = qtrace.observed_costs().merge_snapshot(
                header.get("costs") or {})
            return {"msg": "costs_ack", "adopted": adopted}, b""
        if msg == "plan":
            from .. import trace as qtrace
            plan = plandoc.doc_to_plan(header["plan"], tables)
            df = DataFrame(plan)
            ses = Session(dict(conf, **(header.get("conf") or {})))
            mode = header.get("mode", "collect")
            if mode == "explain":
                return {"msg": "explained"}, ses.explain(df).encode("utf-8")
            if mode != "collect":
                raise ValueError(f"unknown plan mode {mode!r}")
            if cancelled():
                raise QueryCancelledError("query cancelled by the server")
            # adopt the client-minted query identity (mint one for bare
            # clients) and, when this session traces, open the span tree
            # here so admission/cache/operator/transport spans all share
            # it; the profile lands in this server's flight recorder
            query_id = str(header.get("query_id") or
                           qtrace.mint_query_id())
            import contextlib
            from ..config import (TRACE_ENABLED, TRACE_MAX_SPANS,
                                  TRACE_SINK_PATH)
            with contextlib.ExitStack() as _stack:
                if ses.conf.get(TRACE_ENABLED.key):
                    _stack.enter_context(qtrace.query_trace(
                        query_id, component="server",
                        max_spans=int(ses.conf.get(TRACE_MAX_SPANS.key)),
                        recorder=srv.trace_recorder,
                        sink_path=str(ses.conf.get(TRACE_SINK_PATH.key))))
                return self._collect_plan(header, srv, ses, df,
                                          cancelled, query_id)
        raise ValueError(f"unknown message {msg!r}")

    def _collect_plan(self, header, srv, ses, df,
                      cancelled: Callable[[], bool], query_id: str):
        # result-set cache first, then the in-flight single-flight
        # table: a hit/dedup-serve forwards IPC bytes verbatim — no
        # planning, no admission, no device work (a parked duplicate
        # holds NO collect slot while it waits)
        result = ses.try_cached_result(df, cancelled=cancelled)
        cached = result is not None
        if not cached:
            try:
                result = self._execute_plan(srv, ses, df, cancelled)
            except BaseException as e:
                # leader unwind for failures anywhere before the
                # session settles the flight itself (prepare errors,
                # admission cancellation): promote a parked duplicate
                ses.abort_inflight(e)
                raise
        # cached serves AND cacheable misses publish their IPC bytes
        # on the session (one serialization per result, verbatim)
        from ..trace import span as _trace_span
        with _trace_span("serializer.reply", kind="serializer") as sp:
            body_out = ses.last_result_ipc or \
                protocol.table_to_ipc(result)
            if sp is not None:
                sp.attrs["bytes"] = len(body_out)
        reply = {"msg": "result",
                 "rows": result.num_rows,
                 "execs": ses.executed_exec_names(),
                 "fell_back": ses.fell_back(),
                 "cached": cached,
                 # the query identity every span/error of this request
                 # shares (client-minted when the client sent one)
                 "query_id": query_id,
                 # how each cache layer treated this query, plus the
                 # admission the execution paid — the loadbench and
                 # the acceptance counters read these
                 "cache": dict(ses.last_cache),
                 # operator metrics ride back to the driver the way
                 # the reference posts SQLMetrics to the Spark UI
                 "metrics": {k: int(v)
                             for k, v in ses.metrics().items()}}
        if ses.last_fingerprint:
            # lets a client ask the observed-cost store about exactly
            # this query's shape (trace op, what="costs")
            reply["fingerprint"] = ses.last_fingerprint
        decisions = ses.adaptive_decisions()
        if decisions:
            # never-silent surface of the adaptive re-planner: the
            # reason tag of every cost-fed / exploration / runtime
            # re-plan decision this query took rides the reply
            reply["adaptive"] = decisions
        return reply, body_out

    def _execute_plan(self, srv, ses, df, cancelled):
        # plan/bind, untagged: binding errors echo client-chosen
        # names (a column literally called "...halted...") and
        # must never reach the breaker's substring classifier
        prepared = ses.prepare(df)
        from ..memory.semaphore import AdmissionCancelledError
        # interpret/fallback queries never touch the device:
        # admit them through the slot (they still consume CPU)
        # but reserve no HBM — a CPU-query stream must not spill
        # device-resident state of concurrent device tenants
        reserve = srv.query_reserve_for(df) \
            if prepared[0] == "exec" else 0
        # scan-digest affinity: the admission queue seats waiters
        # next to in-flight queries over the same tables so their
        # uploads overlap in the scan-share registry
        from ..plan import sharing as _sharing
        affinity = _sharing.scan_affinity(df.plan, ses.conf) \
            if prepared[0] == "exec" else frozenset()
        from ..shuffle import lineage
        try:
            with srv.query_admission.admit(
                    reserve, cancelled=cancelled,
                    affinity=affinity), \
                    lineage.cancel_scope(
                        cancelled, exc=QueryCancelledError):
                # the test-only collect delay runs INSIDE the
                # admitted region so collectDelayMs holds a real
                # collect slot — deterministic admission
                # contention for the watchdog/serialization
                # tests (cancellation semantics are unchanged:
                # the delay loop polls the same cancel flag).
                # The lineage cancel scope makes stop()/watchdog
                # cancellation observable INSIDE a collect whose
                # exchange read is recomputing lost partitions:
                # the recompute loop polls the flag between
                # recoveries (and between retry attempts),
                # raises QueryCancelledError, and this admit
                # context releases the slot on unwind.
                self._check_cancel(cancelled, ses)
                try:
                    return ses.collect(df, _prepared=prepared)
                except Exception as e:
                    if prepared[0] == "exec":
                        # planning succeeded and the plan ran on
                        # DEVICE — only these failures may reach
                        # the breaker's fatal-marker
                        # classification (interpreter/fallback
                        # paths never touch the device)
                        e._rtpu_exec_phase = True
                    raise
        except AdmissionCancelledError:
            raise QueryCancelledError(
                "query cancelled while waiting for admission")

    @staticmethod
    def _check_cancel(cancelled: Callable[[], bool], ses: Session) -> None:
        """Pre-execution cancellation point. The test-only collect delay
        (server.test.collectDelayMs) sleeps here in cancellable slices so
        watchdog/stop() paths are deterministic to test; the collect
        itself is not interruptible mid-flight — cancellation closes the
        session and discards the result instead."""
        from ..config import SERVER_TEST_COLLECT_DELAY_MS
        delay_s = int(ses.conf.get(SERVER_TEST_COLLECT_DELAY_MS.key)) \
            / 1000.0
        deadline = time.monotonic() + delay_s
        while True:
            if cancelled():
                raise QueryCancelledError("query cancelled by the server")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            time.sleep(min(remaining, 0.01))


class _ThreadingServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True

    def query_reserve_for(self, df) -> int:
        """Per-query device reservation taken at admission: an explicit
        ``server.queryReserveBytes`` wins; auto (0) reserves the plan's
        logical size estimate (unknown → 64 MiB), capped at
        1/concurrentCollects of the device budget so a full house of
        admitted queries can never over-commit HBM at admission time."""
        if self.query_reserve_bytes > 0:
            return self.query_reserve_bytes
        from ..memory.catalog import device_budget
        from ..plan.overrides import estimate_bytes
        cap = device_budget().device_limit \
            // max(1, self.concurrent_collects)
        est = estimate_bytes(df.plan)
        if est is None:
            est = 64 << 20
        return max(0, min(int(est), cap))


class PlanServer:
    """Embeddable server handle (tests embed it; production runs the
    module entry point as its own process)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 conf: Optional[dict] = None, idle_timeout: float = 600.0,
                 health_check: Optional[Callable[[], None]] = None):
        from ..config import (RapidsTpuConf, SERVER_CONCURRENT_COLLECTS,
                              SERVER_MAX_SESSIONS,
                              SERVER_QUERY_RESERVE_BYTES,
                              SERVER_QUERY_TIMEOUT_MS,
                              SERVER_RETRY_AFTER_MS,
                              SERVER_TRACE_RECORDER_ENTRIES,
                              SERVER_TRACE_SLOW_QUERY_MS)
        tconf = RapidsTpuConf(dict(conf or {}))
        srv = _ThreadingServer((host, port), _Handler)
        srv.base_conf = dict(conf or {})              # type: ignore
        srv.idle_timeout = idle_timeout               # type: ignore
        srv.max_sessions = int(tconf.get(SERVER_MAX_SESSIONS.key))
        srv.retry_after_ms = int(tconf.get(SERVER_RETRY_AFTER_MS.key))
        srv.default_timeout_ms = int(tconf.get(SERVER_QUERY_TIMEOUT_MS.key))
        srv.admission = threading.Semaphore(srv.max_sessions)
        # per-QUERY admission: maxSessions bounds connections, this
        # bounds in-flight collects over the one device (+ a per-query
        # memory reservation against the buffer catalog) so independent
        # tenants overlap H2D/compute/D2H instead of queueing
        srv.concurrent_collects = int(
            tconf.get(SERVER_CONCURRENT_COLLECTS.key))
        srv.query_reserve_bytes = int(
            tconf.get(SERVER_QUERY_RESERVE_BYTES.key))
        from ..memory.semaphore import QueryAdmission
        srv.query_admission = QueryAdmission(srv.concurrent_collects)
        # this server's flight recorder: the bounded ring of recent
        # query profiles + slow-query log the 'trace' wire op serves
        # (per-server, not the process singleton — embedded test
        # servers must not read each other's queries)
        from ..trace import FlightRecorder
        srv.trace_recorder = FlightRecorder(
            capacity=int(tconf.get(SERVER_TRACE_RECORDER_ENTRIES.key)),
            slow_query_ms=int(tconf.get(SERVER_TRACE_SLOW_QUERY_MS.key)))
        srv.breaker = CircuitBreaker(health_check, srv.retry_after_ms)
        srv.shutting_down = threading.Event()
        srv.track_lock = threading.Lock()
        srv.active_conns = set()
        srv.active_queries: List[_ActiveQuery] = []
        srv.session_count = 0
        srv.plan_server = self          # the stats/shutdown op target
        self._server = srv
        self._thread: Optional[threading.Thread] = None
        self._profile_lock = threading.Lock()
        self._profile_dir: Optional[str] = None
        # attach the fleet's shared persistent result tier when the conf
        # names one, BEFORE serving: a replacement worker must rehydrate
        # from its very first read-through. _server=True LOCKS the
        # store for this process — session confs (which merge remote
        # clients' hello/plan conf) can no longer attach or repoint it
        from ..plan import plancache
        plancache.configure_result_store(tconf, _server=True)

    @property
    def address(self):
        return self._server.server_address

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def active_sessions(self) -> int:
        """Admitted, preamble-complete sessions currently connected."""
        with self._server.track_lock:
            return self._server.session_count

    @property
    def active_query_count(self) -> int:
        with self._server.track_lock:
            return len(self._server.active_queries)

    def serving_stats(self) -> dict:
        """Cache + admission + recovery snapshot — the loadbench/ops
        surface AND the ``stats`` wire op's reply body. The schema is
        stable (``schemaVersion`` guards it): the router aggregates
        these fleet-wide and ``readiness_line`` formats from the
        ``server`` block, so every field here is load-bearing."""
        from .. import compile_cache
        from ..plan import adaptive, plancache, sharing
        from ..shuffle.lineage import metrics as lineage_metrics
        from ..trace import observed_costs
        adm = self._server.query_admission
        return {
            # v2: adds the `trace` block (flight-recorder occupancy,
            # slow-query count, dropped spans, cost-store size)
            # v3: adds the `adaptive` block (cost-fed plans,
            # exploration runs, runtime re-plans: coalesces / skew
            # splits / broadcast switches)
            # v4: adds the `sharing` block (in-flight dedup, subplan
            # cache, scan-share registry, admission affinity batching)
            # v5: adds the `programs` block (the process's program
            # table: entries, hits, misses, unkeyed, evictions)
            "schemaVersion": 5,
            "programs": compile_cache.program_table().stats(),
            "adaptive": adaptive.metrics().snapshot(),
            "sharing": dict(
                sharing.metrics().snapshot(),
                inflight=sharing.single_flight().stats(),
                subplanCache=sharing.subplan_cache().stats(),
                scanShare=sharing.scan_share().stats(),
                affinityBatched=adm.affinity_batched,
            ),
            "trace": {
                "recorder": self._server.trace_recorder.stats(),
                "costFingerprints": len(observed_costs()),
            },
            "server": {
                "host": str(self.address[0]),
                "port": int(self.port),
                "activeSessions": self.active_sessions,
                "activeQueries": self.active_query_count,
                "maxSessions": self._server.max_sessions,
                "concurrentCollects": self._server.concurrent_collects,
                "shuttingDown": self._server.shutting_down.is_set(),
                "device": _device_info(),
            },
            "planCacheEntries": len(plancache.planning_cache()),
            "resultCache": plancache.result_cache().stats(),
            "counters": plancache.metrics().snapshot(),
            "admission": {
                "concurrentCollects": adm.max_concurrent,
                "admitted": adm.admitted_count,
                "inFlight": adm.in_flight,
                "waitTimeNs": adm.wait_time_ns,
            },
            # the query-recovery plane: how often serving survived a
            # lost executor by recompute vs replica
            "lineage": lineage_metrics().snapshot(),
        }

    def start(self) -> "PlanServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="plan-server",
            daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def profile(self, action: str, log_dir: Optional[str] = None) -> dict:
        """``start`` a ``jax.profiler`` trace of this process into
        ``log_dir``, or ``stop`` the one running. The Python tracer stays
        off (it would record every call of the engine's host code and slow
        what is measured); host events are taken at level 2, which holds
        the engine's spans and XLA's own. Returns ``{"profiling", "dir"}``;
        starting twice or stopping nothing changes nothing."""
        import jax
        with self._profile_lock:
            if action == "start" and self._profile_dir is None:
                if not log_dir:
                    raise ValueError("profile start needs a directory")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(log_dir),
                                         profiler_options=opts)
                self._profile_dir = str(log_dir)
            elif action == "stop" and self._profile_dir is not None:
                log_dir, self._profile_dir = self._profile_dir, None
                jax.profiler.stop_trace()
                return {"profiling": False, "dir": log_dir}
            elif action not in ("start", "stop"):
                raise ValueError(f"unknown profile action {action!r}")
            return {"profiling": self._profile_dir is not None,
                    "dir": self._profile_dir}

    def stop(self, grace_s: float = 10.0) -> None:
        """Stop accepting, CANCEL in-flight queries (cooperative cancel
        flag + closing their connections, so no handler blocks in recv
        past shutdown), and join the workers up to ``grace_s``."""
        srv = self._server
        srv.shutting_down.set()
        with srv.track_lock:
            queries = list(srv.active_queries)
            conns = list(srv.active_conns)
        for q in queries:
            q.cancel.set()
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:  # net-ok: peer already hung up
                pass
            try:
                sock.close()
            except OSError:  # net-ok: teardown
                pass
        srv.shutdown()
        srv.server_close()
        deadline = time.monotonic() + grace_s
        for q in queries:
            q.thread.join(timeout=max(deadline - time.monotonic(), 0.1))
        if self._thread is not None:
            self._thread.join(timeout=10)


def _device_info() -> dict:
    """The device this process computes on, as JAX reports it — what a
    client needs to tell a chip run from a CPU one. Asking initialises the
    backend, so a server that cannot get its chip fails at start-up (the
    readiness line is formatted from these stats), not at its first query."""
    import jax
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "peakBytesInUse": stats.get("peak_bytes_in_use")}


def readiness_line(server: PlanServer) -> str:
    """The stdout readiness signal wrapping process managers (and the
    router's worker spawner) parse: ``listening on <host>:<port>`` with
    the BOUND port, so ``--port 0`` deployments learn the real one.
    Formatted from ``serving_stats()['server']`` — the stable stats
    schema is the single source for every ops surface, not ad-hoc
    string assembly from server internals."""
    info = server.serving_stats()["server"]
    return (f"spark-rapids-tpu plan server listening on "
            f"{info['host']}:{info['port']}")


def main(argv=None) -> int:
    import argparse
    from .. import compile_cache
    compile_cache.enable()
    p = argparse.ArgumentParser(
        description="spark-rapids-tpu plan server")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9099)
    p.add_argument("--conf", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="base session conf (repeatable)")
    args = p.parse_args(argv)
    conf = {}
    for kv in args.conf:
        k, _, v = kv.partition("=")
        conf[k] = v
    server = PlanServer(args.host, args.port, conf)
    # the port line is the readiness signal for wrapping process managers
    print(readiness_line(server), flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
