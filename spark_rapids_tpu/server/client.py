"""Driver-side client: build DataFrames locally, execute them remotely.

The client process needs only the plan-builder surface (logical plan +
expressions + pyarrow) — no JAX, no device. ``collect`` walks the plan,
ships every in-memory scan table as an Arrow IPC stream (deduplicated per
connection), submits the serialized plan, and decodes the Arrow result.

Backpressure contract: a server (or router) under admission pressure —
maxSessions, an open circuit breaker, a tenant quota, a saturated
weighted-fair queue — answers a structured ``unavailable`` reply carrying
``retry_after_ms``. The client honors it: ``collect`` resubmits up to
``unavailable_retries`` times within a bounded total budget, sleeping a
jittered ``retry_after_ms`` between attempts (jitter breaks the thundering
herd of N clients all told "retry in 1000ms"). A *fatal* unavailable reply
(the server closed the connection, e.g. maxSessions at handshake)
transparently reconnects and re-ships the session's tables first.
"""

from __future__ import annotations

import random
import socket
import time
from typing import Dict, List, Optional

import pyarrow as pa

from ..plan.logical import DataFrame
from . import plandoc, protocol


class PlanServerError(RuntimeError):
    """Structured server-side failure. ``retryable`` marks transient
    conditions (deadline overrun, admission pressure) a client scheduler
    should resubmit; ``unavailable`` + ``retry_after_ms`` carry the
    circuit-breaker / maxSessions / tenant-quota backpressure signal;
    ``fatal`` means the server closed the connection with the reply."""

    def __init__(self, message: str, remote_traceback: str = "",
                 retryable: bool = False, unavailable: bool = False,
                 timeout: bool = False,
                 retry_after_ms: Optional[int] = None,
                 fatal: bool = False,
                 query_id: Optional[str] = None):
        super().__init__(message)
        self.remote_traceback = remote_traceback
        self.retryable = retryable
        self.unavailable = unavailable
        self.timeout = timeout
        self.retry_after_ms = retry_after_ms
        self.fatal = fatal
        #: the query this failure belongs to (the client-minted id the
        #: server echoes) — a fleet error is attributable to a request
        self.query_id = query_id


class PlanClient:
    def __init__(self, host: str, port: int,
                 conf: Optional[dict] = None, timeout: float = 600.0,
                 unavailable_retries: int = 0,
                 retry_budget_ms: int = 30000,
                 _sleep=time.sleep):
        """``unavailable_retries`` > 0 turns on the bounded retry loop
        for ``unavailable`` replies: each attempt sleeps a jittered
        ``retry_after_ms`` (server-chosen; default 1000ms) and the whole
        loop never exceeds ``retry_budget_ms`` wall time. ``_sleep`` is
        injectable for deterministic tests."""
        self._host, self._port = host, port
        self._conf = dict(conf or {})
        self._timeout = timeout
        self.unavailable_retries = int(unavailable_retries)
        self.retry_budget_ms = int(retry_budget_ms)
        self._sleep = _sleep
        self._rng = random.Random()
        self._sock: Optional[socket.socket] = None
        self._known: Dict[str, pa.Table] = {}    # tables the server holds
        #: how many unavailable replies the retry loop absorbed (test +
        #: loadbench surface)
        self.retried_unavailable = 0
        #: plan-capture info from the last collect (test harness surface)
        self.last_execs: List[str] = []
        self.last_fell_back: List[str] = []
        #: operator metrics of the last collect (server-side
        #: Session.metrics(), the reference's SQLMetrics roll-up)
        self.last_metrics: dict = {}
        #: serving-cache treatment of the last collect ({"plan": ...,
        #: "result": ...}) and whether it was served from the result cache
        self.last_cache: dict = {}
        self.last_cached: bool = False
        #: worker id that served the last collect (through a router)
        self.last_worker: str = ""
        #: query identity of the last collect (minted HERE: the client
        #: is where a query is born, so the id it carries across the
        #: fleet is the client's) + the client-side leg of its timeline
        self.last_query_id: str = ""
        self.last_fingerprint: str = ""
        #: adaptive-decision reason tags of the last collect (cost-fed
        #: placement / exploration / runtime re-plans, never silent)
        self.last_adaptive: List[str] = []
        #: "inflight" when the last collect was served by router-tier
        #: in-flight dedup (another client's identical query executed;
        #: this one rode its result) — empty otherwise
        self.last_sharing: str = ""
        self._last_client_profile: Optional[dict] = None
        try:
            self._connect()
        except BaseException:
            # a rejected handshake (version mismatch, maxSessions
            # unavailable reply) must not leak the connection — callers
            # retrying on retry_after_ms would accumulate open fds
            self.close()
            raise

    # ---- lifecycle ----
    def _connect(self) -> None:
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._timeout)
        protocol.send_preamble(self._sock)
        version = protocol.recv_preamble(self._sock)
        if version != protocol.PROTOCOL_VERSION:
            raise PlanServerError(
                f"protocol version mismatch: server {version}, "
                f"client {protocol.PROTOCOL_VERSION}")
        self._request({"msg": "hello", "conf": self._conf})

    def _reconnect(self) -> None:
        """Fresh connection + handshake, then re-ship every table this
        session had registered — the new server-side session starts
        empty (a fatal unavailable reply or a restarted worker dropped
        the old one)."""
        self.close()
        self._connect()
        self._ship_tables(dict(self._known))

    def close(self) -> None:
        if self._sock is None:
            return
        try:
            self._sock.close()
        except OSError:  # net-ok: teardown, socket may already be dead
            pass
        self._sock = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---- core ----
    def _request(self, header: dict, body: bytes = b""):
        try:
            protocol.send_msg(self._sock, header, body)
            reply, reply_body = protocol.recv_msg(self._sock)
        except (OSError, protocol.ProtocolError):
            # an abrupt drop (worker/router restart) kills the socket
            # WITHOUT a fatal reply: close it so the next public call
            # reconnects + re-ships tables instead of failing forever
            # on the same dead fd
            self.close()
            raise
        if reply.get("msg") == "error":
            if reply.get("fatal"):
                # the server closes its side with a fatal reply; drop
                # ours so a later retry knows to reconnect
                self.close()
            raise PlanServerError(
                reply.get("error", "server error"),
                reply.get("traceback", ""),
                retryable=bool(reply.get("retryable")),
                unavailable=bool(reply.get("unavailable")),
                timeout=bool(reply.get("timeout")),
                retry_after_ms=reply.get("retry_after_ms"),
                fatal=bool(reply.get("fatal")),
                query_id=reply.get("query_id"))
        return reply, reply_body

    def _retrying_request(self, header: dict, body: bytes = b"",
                          retries: Optional[int] = None):
        """``_request`` under the bounded unavailable-retry budget."""
        retries = self.unavailable_retries if retries is None else retries
        deadline = time.monotonic() + self.retry_budget_ms / 1000.0
        attempt = 0
        while True:
            try:
                if self._sock is None:
                    self._reconnect()
                return self._request(header, body)
            except PlanServerError as e:
                if not e.unavailable or attempt >= retries:
                    raise
                # jittered retry-after: nominal..2x nominal, so N
                # clients given the same hint don't stampede together
                delay = ((e.retry_after_ms or 1000) / 1000.0) \
                    * (1.0 + self._rng.random())
                if time.monotonic() + delay > deadline:
                    raise   # honoring the hint would blow the budget
                attempt += 1
                self.retried_unavailable += 1
                self._sleep(delay)

    def _ship_tables(self, tables: Dict[str, pa.Table]) -> None:
        for name, t in tables.items():
            self._request({"msg": "table", "name": name},
                          protocol.table_to_ipc(t))

    def _serialize(self, df: DataFrame) -> dict:
        # seed the registry with every table the server already holds so
        # plan_to_doc's identity dedupe reuses their names; ship only the
        # newly-registered ones
        registry: Dict[str, pa.Table] = dict(self._known)
        doc, registry = plandoc.plan_to_doc(df.plan, registry)
        fresh = {n: t for n, t in registry.items() if n not in self._known}
        self._ship_tables(fresh)
        self._known.update(fresh)
        return doc

    # ---- public surface ----
    def collect(self, df: DataFrame, conf: Optional[dict] = None,
                timeout_ms: Optional[int] = None,
                retries: Optional[int] = None) -> pa.Table:
        """``timeout_ms`` sets the server-side per-query deadline (the
        watchdog cancels and answers a retryable error past it); 0 means
        explicitly unbounded; None defers to
        spark.rapids.tpu.server.queryTimeoutMs. ``retries`` overrides
        the client's ``unavailable_retries`` for this one query."""
        from .. import trace as qtrace
        if self._sock is None:
            self._reconnect()
        # mint the query identity HERE: every span, error reply, and
        # flight-recorder profile of this query — client, router,
        # worker, shuffle peers — shares it
        qid = qtrace.mint_query_id()
        self.last_query_id = qid
        tr = qtrace.QueryTrace(qid, component="client", max_spans=64)
        try:
            with qtrace.attached((tr, None)):
                with qtrace.span("client.collect", kind="client"):
                    with qtrace.span("client.serialize", kind="client"):
                        doc = self._serialize(df)
                    header = {"msg": "plan", "mode": "collect",
                              "plan": doc, "conf": conf or {},
                              "query_id": qid}
                    if timeout_ms is not None:
                        header["timeout_ms"] = int(timeout_ms)
                    with qtrace.span("client.request", kind="client"):
                        reply, body = self._retrying_request(
                            header, retries=retries)
        finally:
            # a failed collect still leaves its client-side leg behind
            # (the error names qid too, via PlanServerError.query_id)
            self._last_client_profile = tr.finish()
        self.last_execs = reply.get("execs", [])
        self.last_fell_back = reply.get("fell_back", [])
        self.last_metrics = reply.get("metrics", {})
        self.last_cache = reply.get("cache", {})
        self.last_cached = bool(reply.get("cached"))
        self.last_worker = str(reply.get("worker", ""))
        self.last_fingerprint = str(reply.get("fingerprint", ""))
        self.last_adaptive = reply.get("adaptive", [])
        self.last_sharing = str(reply.get("sharing", ""))
        return protocol.ipc_to_table(body)

    def collect_catalyst(self, plan_json, tables: Optional[Dict[
            str, pa.Table]] = None, conf: Optional[dict] = None,
            timeout_ms: Optional[int] = None,
            retries: Optional[int] = None) -> pa.Table:
        """Translate a Catalyst ``queryExecution`` JSON document
        CLIENT-side (``spark_client.translate``) and collect the result
        through this connection — a plan server or a router fleet, which
        routes it on the plandoc shape fingerprint like any native plan.

        In-memory scans resolve their ``rtpuTable`` names against
        ``tables`` plus tables this session already registered; newly
        referenced tables are registered under those names first, so
        repeat queries reuse the server-side copies (and result-cache
        invalidation on re-upload keeps working). ``conf`` merges over
        the session conf for ``spark.rapids.tpu.bridge.*`` translation
        settings and rides the query as usual otherwise."""
        from . import spark_client
        merged = dict(self._conf)
        merged.update(conf or {})
        pool: Dict[str, pa.Table] = dict(self._known)
        pool.update(tables or {})
        tr = spark_client.translate(plan_json, tables=pool, conf=merged)
        for name in tr.table_names:
            if self._known.get(name) is not pool[name]:
                self.register_table(name, pool[name])
        return self.collect(tr.dataframe, conf=conf,
                            timeout_ms=timeout_ms, retries=retries)

    def register_table(self, name: str, table: pa.Table) -> dict:
        """Upload (or REPLACE) a named server-side table. The ack
        reports the content digest and how many cached results the
        replacement invalidated (memory + persistent tiers)."""
        if self._sock is None:
            self._reconnect()
        reply, _ = self._request({"msg": "table", "name": name},
                                 protocol.table_to_ipc(table))
        self._known[name] = table
        return reply

    def drop_table(self, name: str) -> dict:
        """Drop a server-side table; the ack's ``invalidated`` counts
        the cached results that depended on it across every tier (and,
        through a router, every worker)."""
        if self._sock is None:
            self._reconnect()
        reply, _ = self._request({"msg": "drop_table", "name": name})
        self._known.pop(name, None)
        return reply

    def stats(self) -> dict:
        """The server's serving_stats() (stable schema; through a
        router: the fleet-wide aggregate + per-worker breakdown)."""
        if self._sock is None:
            self._reconnect()
        reply, _ = self._request({"msg": "stats"})
        return reply["stats"]

    def profile(self, action: str, log_dir: Optional[str] = None) -> dict:
        """Start (``action="start"``, with the server-side directory
        ``log_dir``) or stop a ``jax.profiler`` device trace of the
        server process; the engine's spans of the queries in between are
        in it under their own names. Returns ``{"profiling", "dir"}``."""
        if self._sock is None:
            self._reconnect()
        header = {"msg": "profile", "action": action}
        if log_dir is not None:
            header["dir"] = str(log_dir)
        reply, _ = self._request(header)
        return {"profiling": bool(reply.get("profiling")),
                "dir": reply.get("dir")}

    def last_trace(self) -> Optional[dict]:
        """The last collect's stitched timeline: this client's own leg
        plus every profile the server (or router + the worker that
        served it) flight-recorded under the same query_id. Returns
        ``{"queryId", "profiles": [...]}`` — feed it to
        tools/trace_viewer.py for Chrome/Perfetto trace-event JSON —
        or None before any collect. Remote profiles exist only when
        the session ran with spark.rapids.tpu.trace.enabled; their
        spans carry ``selfUs``, each span's own microseconds beside its
        duration (``trace.self_times``)."""
        if not self.last_query_id:
            return None
        if self._sock is None:
            self._reconnect()
        reply, _ = self._request({"msg": "trace",
                                  "query_id": self.last_query_id})
        profiles = list(reply.get("profiles") or [])
        if self._last_client_profile is not None:
            profiles.insert(0, self._last_client_profile)
        return {"queryId": self.last_query_id, "profiles": profiles}

    def trace_profiles(self, query_id: Optional[str] = None,
                       last: int = 0) -> dict:
        """Raw flight-recorder read: profiles (all, the most recent
        ``last``, or one query_id) + recorder occupancy stats."""
        if self._sock is None:
            self._reconnect()
        reply, _ = self._request({"msg": "trace",
                                  "query_id": query_id or "",
                                  "last": int(last)})
        return {"profiles": reply.get("profiles", []),
                "recorder": reply.get("recorder", {})}

    def observed_costs(self, fingerprint: Optional[str] = None) -> dict:
        """The server-side observed-cost store: per-(shape-fingerprint,
        operator) wall/rows/bytes EWMAs (``fingerprint`` narrows to one
        shape — e.g. ``last_fingerprint`` after a collect). Through a
        router the per-worker stores are merged (highest observation
        count wins per operator)."""
        if self._sock is None:
            self._reconnect()
        header = {"msg": "trace", "what": "costs"}
        if fingerprint:
            header["fingerprint"] = fingerprint
        reply, _ = self._request(header)
        return reply.get("costs", {})

    def explain(self, df: DataFrame, conf: Optional[dict] = None) -> str:
        if self._sock is None:
            self._reconnect()
        doc = self._serialize(df)
        _, body = self._retrying_request(
            {"msg": "plan", "mode": "explain", "plan": doc,
             "conf": conf or {}})
        return body.decode("utf-8")
