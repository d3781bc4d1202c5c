"""Query tracing: end-to-end span timelines, a flight recorder, and
observed per-operator costs.

The engine counts everything (per-collect ``retry.*``/``net.*``/
``lineage.*``/``cache.*`` deltas, ``serving_stats()`` at the fleet tier)
but until this layer it could not answer "where did *this* query's time
go": there was no query identity stitched across client → router →
worker → shuffle peers, and no per-operator timeline. Theseus
(PAPERS.md) argues a distributed query platform lives or dies on knowing
where data movement and compute overlap — you cannot tune overlap you
cannot see — and the GPU-offloading cost models in PAPERS.md need
*measured*, not modeled, per-operator costs. Three surfaces:

1. **Span tree per collect** — a ``query_id`` minted at the client (or
   at query open) and propagated through the plan/router wire headers,
   recompute closures, and replicated-fetch peers. Spans wrap admission
   wait, cache lookups, per-operator execution, serializer pack/unpack,
   per-peer transport fetches (with failover/backoff sub-spans), and
   lineage recomputes. ``span()`` is a no-op costing one thread-local
   read when no trace is active, so the off path stays untouched;
   tracing NEVER changes results (the differential suite proves
   bit-for-bit equality with it on).

2. **Flight recorder** — a bounded ring of the last N query profiles
   plus a slow-query log (``server.trace.slowQueryMs``), held by the
   plan server / router and exposed over the ``trace`` wire op; plus a
   conf-gated JSONL sink (``trace.sink.path``) that
   ``tools/trace_viewer.py`` renders as Chrome/Perfetto trace-event
   JSON — a fleet query becomes one stitched timeline.

3. **Observed-cost store** — per-(shape-fingerprint, operator)
   wall/rows/bytes EWMAs recorded at collect close from the existing
   exec metric hooks, living next to the PR-10 planning cache. This is
   the empirical feed the AQE/CBO re-planning loop (ROADMAP item 3)
   consumes: speedup scores become measured, not modeled.

Clock model: every span carries a wall-clock ``tsUs`` (time.time_ns at
open) and a monotonic ``durUs`` (perf_counter delta). Stitching across
processes relies on a shared host clock; cross-host skew shifts whole
process tracks, never distorts durations (docs/observability.md).

This is the engine's ONE tracer. While a query trace is active every
span, and every pull of an operator, is also an event of the same name
in ``jax.profiler``'s trace (a TraceMe: nanoseconds while no profiler
session runs), on the line of the thread it ran on, so a device trace
names its idle gaps by what the engine was doing. ``jax.monitoring``
reports every re-trace, lowering and compile or cache load, and the
collector every full collection; each becomes a span of the operator or
section that caused it.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import uuid
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# metrics (process-wide; Session.metrics() reports `trace.*` deltas the
# way the retry/net/lineage/cache groups do)
# ---------------------------------------------------------------------------


class TraceMetrics:
    """Process-wide tracing counters; sessions report deltas."""

    def __init__(self):
        self._lock = threading.Lock()
        self.span_count = 0
        self.dropped_span_count = 0
        self.profile_count = 0
        self.slow_query_count = 0
        self.cost_observation_count = 0

    def note(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "spanCount": self.span_count,
                "droppedSpanCount": self.dropped_span_count,
                "profileCount": self.profile_count,
                "slowQueryCount": self.slow_query_count,
                "costObservationCount": self.cost_observation_count,
            }


_METRICS = TraceMetrics()


def metrics() -> TraceMetrics:
    return _METRICS


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class Span:
    """One timed section of a query. Durations are monotonic
    (perf_counter); ``ts_us`` is the wall-clock open instant used to
    stitch process tracks together and the instant the profiler's own
    events count from. ``tid`` is the OS thread that opened it: a child
    on another thread overlaps its parent instead of filling it."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "ts_us",
                 "t0_ns", "dur_us", "attrs", "tid", "lazy_rows")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 kind: str, attrs: Dict[str, Any], ago_ns: int = 0):
        # ago_ns: it began that long before now (a span written down
        # once it is over)
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.ts_us = (time.time_ns() - ago_ns) // 1000
        self.t0_ns = time.perf_counter_ns() - ago_ns
        self.dur_us: Optional[int] = None    # None while open
        self.attrs = attrs
        self.tid = threading.get_native_id()
        self.lazy_rows: Optional[list] = None

    def to_dict(self) -> dict:
        d = {"id": self.span_id, "parent": self.parent_id,
             "name": self.name, "kind": self.kind, "tsUs": self.ts_us,
             "durUs": self.dur_us if self.dur_us is not None else 0,
             "tid": self.tid}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class QueryTrace:
    """Thread-safe span tree of one query. Span ids are allocated under
    a lock so producer threads (writer pools, fetch pools, recompute)
    append concurrently; the per-thread parent chain lives in the
    activation thread-local, not here. Span count is bounded
    (``trace.maxSpansPerQuery``): past the cap spans are counted as
    dropped instead of growing without bound, and the ``jit.*`` events
    among them still add to ``overflow``."""

    def __init__(self, query_id: str, component: str = "engine",
                 max_spans: int = 2048):
        self.query_id = query_id
        self.component = component
        self.max_spans = max(1, int(max_spans))
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._open: Dict[int, Span] = {}
        self._next_id = 1
        self.dropped = 0
        #: name -> [events, microseconds] of done spans past the cap
        self.overflow: Dict[str, List[int]] = {}
        self.ts_us = time.time_ns() // 1000
        self._t0_ns = time.perf_counter_ns()
        self.dur_us = 0

    def open_span(self, name: str, kind: str, parent_id: Optional[int],
                  attrs: Dict[str, Any],
                  done: Optional[Tuple[int, int]] = None
                  ) -> Optional[Span]:
        """A new span under ``parent_id``; with ``done`` one that is
        over already, ``(began that many ns ago, lasted that many)``:
        what a listener learns only afterwards, a lowering, a
        collection."""
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                if done is not None:
                    o = self.overflow.setdefault(name, [0, 0])
                    o[0] += 1
                    o[1] += done[1] // 1000
                _METRICS.note("dropped_span_count")
                return None
            s = Span(self._next_id, parent_id, name, kind, attrs,
                     ago_ns=done[0] if done is not None else 0)
            self._next_id += 1
            self._spans.append(s)
            if done is None:
                self._open[s.span_id] = s
            else:
                s.dur_us = done[1] // 1000
        _METRICS.note("span_count")
        return s

    def close_span(self, s: Span) -> None:
        dur = (time.perf_counter_ns() - s.t0_ns) // 1000
        with self._lock:
            if self._open.pop(s.span_id, None) is not None:
                s.dur_us = dur

    def finish(self) -> dict:
        """Close every still-open span (an abandoned iterator never
        exhausts its operator span), resolve the operators' row counts
        (device scalars until now: reading one earlier would wait for
        the device inside the pipeline being timed) and return the
        profile dict."""
        end = time.perf_counter_ns()
        with self._lock:
            for s in self._open.values():
                s.dur_us = (end - s.t0_ns) // 1000
            self._open.clear()
            self.dur_us = (end - self._t0_ns) // 1000
            spans = list(self._spans)
        for s in spans:
            lazy, s.lazy_rows = s.lazy_rows, None
            if lazy is not None:
                try:
                    s.attrs["rows"] = sum(int(x) for x in lazy)
                except Exception:   # a failed query's batches: no count
                    pass
        return self.profile()

    def profile(self) -> dict:
        with self._lock:
            return self.profile_locked()

    def profile_locked(self) -> dict:
        return {
            "queryId": self.query_id,
            "component": self.component,
            #: 2 = pull-scoped operator spans, spans of other threads
            #: and the jit/gc listeners; a reader tells by it whether
            #: "no such span" means none happened or none was recorded
            "tracer": 2,
            "tsUs": self.ts_us,
            "durUs": self.dur_us or
            (time.perf_counter_ns() - self._t0_ns) // 1000,
            "droppedSpans": self.dropped,
            "overflow": {k: list(v) for k, v in self.overflow.items()},
            "spans": [s.to_dict() for s in self._spans],
        }

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


# ---------------------------------------------------------------------------
# thread-local activation + cross-thread propagation
# ---------------------------------------------------------------------------


class _Active:
    """What one thread holds while it works for a traced query: the
    trace, the stack of spans it is inside right now (a proper span from
    enter to exit, an operator span only while one of its pulls runs:
    its *pull frame*), and what its listeners saw and have not yet
    written down."""

    __slots__ = ("trace", "stack", "pending", "cache_hit", "gc")

    def __init__(self, trace: QueryTrace, parent: Optional[Span]):
        self.trace = trace
        self.stack: List[Span] = [parent] if parent is not None else []
        #: spans a listener saw end, (name, end_ns, dur_ns, attrs),
        #: not yet written: the collector's callback may run in the
        #: middle of a write, so it only notes, and an inner trace waits
        #: for the outer one that folds it; the next span boundary of
        #: this thread (or the next lowering) writes
        self.pending: List[tuple] = []
        self.cache_hit = False
        self.gc: Optional[tuple] = None

    def flush(self) -> None:
        """Write the noted events as children of the innermost span."""
        pending, self.pending = self.pending, []
        top = self.stack[-1] if self.stack else None
        now = time.perf_counter_ns()
        written: List[tuple] = []       # (began, ended, span)
        # the last to arrive first: a collection inside a lowering is
        # noted before the lowering that holds it
        for name, end_ns, dur_ns, attrs in reversed(pending):
            parent = top
            for began, ended, holder in written:
                if began <= end_ns - dur_ns and end_ns <= ended:
                    parent = holder
            s = self.trace.open_span(
                name, name.split(".")[0],       # kind "jit" or "gc"
                parent.span_id if parent is not None else None, attrs,
                done=(now - end_ns + dur_ns, dur_ns))
            if s is not None:
                written.append((end_ns - dur_ns, end_ns, s))
            counter = _JIT_COUNTERS.get(name)
            if counter is not None and top is not None:
                top.attrs[counter] = top.attrs.get(counter, 0) + 1


_TLS = threading.local()
_JIT_COUNTERS = {"jit.trace": "traces", "jit.lower": "lowerings",
                 "jit.compile": "compiles"}


def _annotate(name: str):
    """The profiler's own event of that name, entered: a TraceMe, which
    costs nanoseconds while no profiler session runs and lands on this
    thread's line of the device trace while one does."""
    ann = _ANNOTATION(name)
    ann.__enter__()
    return ann


_ANNOTATION = None      # jax.profiler.TraceAnnotation, from _join_jax()


def mint_query_id() -> str:
    """A fresh query identity — minted at the client and propagated in
    the wire headers, so every process a query touches logs the same
    id."""
    return uuid.uuid4().hex[:16]


def active() -> bool:
    return getattr(_TLS, "st", None) is not None


def current_trace() -> Optional[QueryTrace]:
    st = getattr(_TLS, "st", None)
    return st.trace if st is not None else None


def current_query_id() -> Optional[str]:
    st = getattr(_TLS, "st", None)
    return st.trace.query_id if st is not None else None


class _Noop:
    """Shared reusable no-op context manager: the whole cost of a span
    site with tracing off is one thread-local read + this return."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def _leave(st: _Active, s: Span) -> None:
    if st.pending:
        st.flush()
    stack = st.stack
    if stack and stack[-1] is s:
        stack.pop()
    elif s in stack:                # out-of-order close (rare:
        stack.remove(s)             # interleaved generators)


class _SpanCm:
    __slots__ = ("_st", "_span", "_name", "_kind", "_attrs", "_ann")

    def __init__(self, st: _Active, name: str, kind: str,
                 attrs: Dict[str, Any]):
        self._st = st
        self._name = name
        self._kind = kind
        self._attrs = attrs
        self._span = None

    def __enter__(self):
        st = self._st
        if st.pending:
            st.flush()
        parent = st.stack[-1].span_id if st.stack else None
        s = self._span = st.trace.open_span(self._name, self._kind,
                                            parent, self._attrs)
        if s is not None:
            st.stack.append(s)
            self._ann = _annotate(self._name)
        return s

    def __exit__(self, *exc):
        s = self._span
        if s is not None:
            self._ann.__exit__(None, None, None)
            _leave(self._st, s)
            self._st.trace.close_span(s)
        return False


def span(name: str, kind: str = "span", **attrs):
    """Open a child span of the innermost span or pull frame of the
    calling thread, mirrored into the profiler's trace under the same
    name. With no active trace this is a shared no-op — safe on every
    hot path."""
    st = getattr(_TLS, "st", None)
    if st is None:
        return _NOOP
    return _SpanCm(st, name, kind, attrs)


def count(**counters: int) -> None:
    """Add to counters on the innermost span or pull frame of the calling
    thread: inside an exec's ``do_execute_partition``, between its
    children's pulls, that is the exec's own operator span. One
    thread-local read with no active trace."""
    st = getattr(_TLS, "st", None)
    if st is None or not st.stack:
        return
    a = st.stack[-1].attrs
    for name, n in counters.items():
        a[name] = a.get(name, 0) + n


class OperatorSpan:
    """One partition's iteration of one operator. The span runs from the
    first pull to exhaustion, so it also covers what the consumer does
    between pulls; the time INSIDE the pulls (children included) is
    ``pullUs``. Only while a pull runs is the operator on its thread's
    stack (its pull frame), so whatever that thread records then — a
    child operator, an H2D, a lowering — is this operator's, and what it
    records between pulls is the consumer's. Each pull is one event of
    the operator's name in the profiler's trace."""

    __slots__ = ("_trace", "_span", "_st", "_ann", "_pull_ns", "_pulls",
                 "_batches")

    def __init__(self, trace: QueryTrace, s: Span):
        self._trace = trace
        self._span = s
        self._st = None
        self._pull_ns = self._pulls = self._batches = 0
        s.lazy_rows = []

    def enter(self) -> None:
        st = getattr(_TLS, "st", None)
        if st is None or st.trace is not self._trace:
            self._st = None     # pulled where this query is not traced
            return
        self._st = st
        if st.pending:
            st.flush()
        st.stack.append(self._span)
        self._ann = _annotate(self._span.name)

    def exit(self, dur_ns: int, batch=None) -> None:
        if self._st is not None:
            self._ann.__exit__(None, None, None)
            _leave(self._st, self._span)
        self._pull_ns += dur_ns
        self._pulls += 1
        a = self._span.attrs
        a["pullUs"] = self._pull_ns // 1000
        a["pulls"] = self._pulls
        if batch is not None:
            self._batches += 1
            a["batches"] = self._batches
            self._span.lazy_rows.append(batch.num_rows)
            # decimal128 limb matrices this operator emitted and their
            # device bytes (32 a value): shapes, known here without a sync
            for c in batch.columns:
                if getattr(c.data, "ndim", 0) == 2 \
                        and c.dtype.kind.value == "decimal":
                    a["dec128Columns"] = a.get("dec128Columns", 0) + 1
                    a["dec128Bytes"] = a.get("dec128Bytes", 0) \
                        + c.data.size * c.data.dtype.itemsize

    def note_programs(self, hits: int, misses: int) -> None:
        """Of the keyed programs this operator's exec stated when it was
        built, how many the program table had (``programHits``: their
        calls re-trace and re-lower nothing) and how many it had to make
        (``programMisses``: each is traced and lowered once per input
        shape, under ``traces``/``lowerings``/``compiles``)."""
        self._span.attrs["programHits"] = hits
        self._span.attrs["programMisses"] = misses

    def close(self) -> None:
        self._trace.close_span(self._span)


def open_operator(name: str, partition: int) -> Optional[OperatorSpan]:
    """The operator span of ``Exec.execute_partition``; None (after one
    thread-local read) with no active trace, or past the span cap."""
    st = getattr(_TLS, "st", None)
    if st is None:
        return None
    if st.pending:
        st.flush()
    parent = st.stack[-1].span_id if st.stack else None
    s = st.trace.open_span(name, "operator", parent,
                           {"partition": partition, "pullUs": 0,
                            "pulls": 0, "batches": 0})
    return OperatorSpan(st.trace, s) if s is not None else None


def capture() -> Optional[Tuple[QueryTrace, Optional[Span]]]:
    """Snapshot (trace, innermost span) for handoff to a pool thread;
    None with no active trace."""
    st = getattr(_TLS, "st", None)
    if st is None:
        return None
    return (st.trace, st.stack[-1] if st.stack else None)


@contextmanager
def attached(token: Optional[Tuple[QueryTrace, Optional[Span]]]):
    """Activate a captured trace context on THIS thread (reader pools,
    the prefetch thread, writer pools, fetch pools, recompute runners)
    so their spans land in the right tree under the right parent. No-op
    for a None token."""
    if token is None:
        yield
        return
    if _ANNOTATION is None:
        _join_jax()
    prev = getattr(_TLS, "st", None)
    st = _TLS.st = _Active(token[0], token[1])
    try:
        yield
    finally:
        if st.pending:
            st.flush()
        _TLS.st = prev


def call_attached(token, fn: Callable, *args, **kwargs):
    """Run ``fn`` under ``attached(token)`` — the pool.submit shim."""
    with attached(token):
        return fn(*args, **kwargs)


@contextmanager
def query_trace(query_id: Optional[str] = None,
                component: str = "engine",
                max_spans: int = 2048,
                recorder: Optional["FlightRecorder"] = None,
                sink_path: str = ""):
    """Open (and activate) a trace for one query on this thread; on
    exit, finish it and hand the profile to ``recorder`` and the JSONL
    ``sink_path`` when given. Yields the QueryTrace."""
    tr = QueryTrace(query_id or mint_query_id(), component=component,
                    max_spans=max_spans)
    _gc_watch(+1)
    try:
        with attached((tr, None)):
            with span("query", kind="query"):
                yield tr
    finally:
        _gc_watch(-1)
        profile = tr.finish()
        _METRICS.note("profile_count")
        if recorder is not None:
            recorder.record(profile)
        if sink_path:
            sink_profile(sink_path, profile)


# ---------------------------------------------------------------------------
# listeners: what JAX and the collector do on a traced thread, charged to
# the span that thread is inside
# ---------------------------------------------------------------------------

_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    # the backend's compile OR the persistent cache's load: cacheHit says
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_NESTED_SLACK_NS = 20_000


def _on_jit_duration(event: str, secs: float, **kw) -> None:
    st = getattr(_TLS, "st", None)
    if st is None:
        return
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    end = time.perf_counter_ns()
    dur = int(secs * 1e9)
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if name == "jit.trace":
        # a jitted function traces the jitted functions it calls (every
        # jnp op is one) and each reports: keep the outermost, which
        # arrives last and covers them
        # (they are the tail of what is pending: notes come in the order
        # they ended, and an operator's program calls hundreds of ops)
        began, pending, nested = end - dur - _NESTED_SLACK_NS, st.pending, 0
        keep = len(pending)
        while keep and pending[keep - 1][1] - pending[keep - 1][2] >= began:
            keep -= 1
        for p in pending[keep:]:
            if p[0] == "jit.trace":
                nested += 1 + p[3].get("nested", 0)
        if nested:
            attrs["nested"] = nested
            # a collection inside the outer trace stays, as its child
            st.pending = pending[:keep] + [p for p in pending[keep:]
                                           if p[0] != "jit.trace"]
    elif name == "jit.compile":
        attrs["cacheHit"], st.cache_hit = st.cache_hit, False
    st.pending.append((name, end, dur, attrs))
    if name != "jit.trace":
        st.flush()


def _on_jit_event(event: str, **kw) -> None:
    st = getattr(_TLS, "st", None)
    if st is None:
        return
    if event == _CACHE_HIT_EVENT:
        st.cache_hit = True


def _join_jax() -> None:
    """Once a process, when its first trace becomes active (importing
    this module imports no JAX): take the profiler's annotation class,
    and listen to ``jax.monitoring``, which reports every trace, lowering
    and backend compile or cache load with its duration and the
    function's name."""
    global _ANNOTATION
    from jax import monitoring
    from jax.profiler import TraceAnnotation
    with _SINGLETON_LOCK:
        if _ANNOTATION is not None:
            return
        _ANNOTATION = TraceAnnotation
    monitoring.register_event_duration_secs_listener(_on_jit_duration)
    monitoring.register_event_listener(_on_jit_event)


_gc_open = 0


def _on_gc(phase: str, info: dict) -> None:
    """A full collection stops every thread of the process; it is
    charged, as span ``gc``, to what the collecting thread was doing."""
    if info.get("generation") != 2:
        return
    st = getattr(_TLS, "st", None)
    if st is None:
        return
    if phase == "start":
        st.gc = (time.perf_counter_ns(), _annotate("gc"))
    elif st.gc is not None:
        (t0, ann), st.gc = st.gc, None
        ann.__exit__(None, None, None)
        end = time.perf_counter_ns()
        st.pending.append(("gc", end, end - t0,
                           {"collected": int(info.get("collected", 0))}))


def _gc_watch(delta: int) -> None:
    """The collector's callback is installed while at least one query
    trace is open in the process and removed with the last."""
    global _gc_open
    import gc
    with _SINGLETON_LOCK:
        before, _gc_open = _gc_open, _gc_open + delta
        if before == 0 and _gc_open == 1:
            gc.callbacks.append(_on_gc)
        elif before == 1 and _gc_open == 0:
            gc.callbacks.remove(_on_gc)


# ---------------------------------------------------------------------------
# OS thread names: one line a thread in the profiler's trace and in top -H
# ---------------------------------------------------------------------------

_thread_numbers: Dict[str, int] = {}


def name_thread(prefix: str) -> str:
    """Give the calling thread the OS name ``<prefix>-<n>`` (at most 15
    characters; ``prctl`` on Linux, nothing elsewhere). The profiler
    names a thread's line by its OS name and Python gives a thread none,
    so unnamed threads all share the line ``python``."""
    with _SINGLETON_LOCK:
        n = _thread_numbers[prefix] = _thread_numbers.get(prefix, -1) + 1
    name = f"{prefix}-{n}"[:15]
    if sys.platform == "linux":
        global _PRCTL
        if _PRCTL is None:
            import ctypes
            _PRCTL = ctypes.CDLL(None, use_errno=True).prctl
            _PRCTL.argtypes = [ctypes.c_int, ctypes.c_char_p,
                               ctypes.c_ulong, ctypes.c_ulong,
                               ctypes.c_ulong]
            _PRCTL.restype = ctypes.c_int
        _PRCTL(15, name.encode(), 0, 0, 0)      # PR_SET_NAME
    return name


_PRCTL = None


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------


def inside_us(s: dict) -> int:
    """Microseconds a span dict holds its thread: an operator's time
    inside its pulls, any other span's duration."""
    if s.get("kind") == "operator":
        return int((s.get("attrs") or {}).get("pullUs", 0))
    return int(s.get("durUs") or 0)


def self_times(spans: List[dict]) -> Dict[int, int]:
    """Span id -> microseconds of the span's own: ``inside_us`` minus
    that of its children on the same thread (a child on another thread
    overlaps its parent; a child on the same one fills part of it).
    Over one thread's spans the self times add up to the root's
    ``inside_us``."""
    own = {s["id"]: inside_us(s) for s in spans}
    tid = {s["id"]: s.get("tid") for s in spans}
    for s in spans:
        p = s.get("parent")
        if p in own and tid[p] == s.get("tid"):
            own[p] -= inside_us(s)
    return {i: max(v, 0) for i, v in own.items()}


def with_self_times(profile: dict) -> dict:
    """A copy of ``profile`` whose spans carry ``selfUs``."""
    own = self_times(profile.get("spans", []))
    return dict(profile, spans=[dict(s, selfUs=own[s["id"]])
                                for s in profile.get("spans", [])])


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded in-memory ring of the last N query profiles plus a
    slow-query log (queries over ``slow_query_ms``). The plan server and
    the router each own one; the process singleton serves in-process
    sessions and tools. ``stats()`` is the ``serving_stats()`` trace
    block."""

    def __init__(self, capacity: int = 128, slow_query_ms: int = 1000):
        self._lock = threading.Lock()
        self.capacity = max(1, int(capacity))
        self.slow_query_ms = int(slow_query_ms)
        self._ring: "deque[dict]" = deque(maxlen=self.capacity)
        self._slow: "deque[dict]" = deque(maxlen=self.capacity)
        self.recorded = 0
        self.slow_queries = 0
        self.dropped_spans = 0

    def record(self, profile: dict) -> None:
        with self._lock:
            self._ring.append(profile)
            self.recorded += 1
            self.dropped_spans += int(profile.get("droppedSpans", 0))
            if self.slow_query_ms > 0 and \
                    profile.get("durUs", 0) >= self.slow_query_ms * 1000:
                self._slow.append(profile)
                self.slow_queries += 1
                _METRICS.note("slow_query_count")

    def profiles(self, query_id: Optional[str] = None,
                 last: int = 0) -> List[dict]:
        """Profiles for one query id, or the most recent ``last`` (0 =
        all) in arrival order."""
        with self._lock:
            if query_id is not None:
                return [p for p in self._ring
                        if p.get("queryId") == query_id]
            out = list(self._ring)
        return out[-last:] if last > 0 else out

    def slow(self) -> List[dict]:
        with self._lock:
            return list(self._slow)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._ring),
                    "capacity": self.capacity,
                    "recorded": self.recorded,
                    "slowQueries": self.slow_queries,
                    "slowQueryMs": self.slow_query_ms,
                    "droppedSpans": self.dropped_spans}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()


_RECORDER: Optional[FlightRecorder] = None
_SINGLETON_LOCK = threading.Lock()


def flight_recorder() -> FlightRecorder:
    """The process-wide recorder (in-process sessions and tools record
    here; a PlanServer/Router owns its own instance)."""
    global _RECORDER
    with _SINGLETON_LOCK:
        if _RECORDER is None:
            _RECORDER = FlightRecorder()
        return _RECORDER


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------

_SINK_LOCK = threading.Lock()


def sink_profile(path: str, profile: dict) -> None:
    """Append one profile as a JSON line (``trace.sink.path``). Sink
    failures never fail the query — tracing is observability, not the
    data path."""
    try:
        line = json.dumps(profile, separators=(",", ":"),
                          default=str) + "\n"
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with _SINK_LOCK:
            with open(path, "a", encoding="utf-8") as f:
                f.write(line)
    except OSError:  # robust-ok: best-effort sink, documented contract
        pass


# ---------------------------------------------------------------------------
# observed-cost store (the AQE feed, next to the PR-10 planning cache)
# ---------------------------------------------------------------------------


class ObservedCostStore:
    """Per-(shape-fingerprint, operator) EWMAs of observed wall time,
    rows, and bytes — recorded at collect close from the exec metric
    roll-up, so the CBO's speedup scores (ROADMAP item 3) can consult
    measured reality instead of its static model. LRU-bounded by
    fingerprint; an entry's ``count`` says how many collects fed it."""

    def __init__(self, max_fingerprints: int = 1024, alpha: float = 0.2):
        self._lock = threading.Lock()
        self.max_fingerprints = max(1, int(max_fingerprints))
        self.alpha = float(alpha)
        #: fp -> {op: {"wallNs","rows","bytes","count"}}
        self._fps: "OrderedDict[str, Dict[str, dict]]" = OrderedDict()

    def observe(self, fingerprint: str, op: str, wall_ns: int,
                rows: int = 0, nbytes: int = 0,
                alpha: Optional[float] = None) -> None:
        a = self.alpha if alpha is None else float(alpha)
        with self._lock:
            ops = self._fps.get(fingerprint)
            if ops is None:
                ops = self._fps[fingerprint] = {}
            self._fps.move_to_end(fingerprint)
            e = ops.get(op)
            if e is None:
                ops[op] = {"wallNs": float(wall_ns), "rows": float(rows),
                           "bytes": float(nbytes), "count": 1}
            else:
                e["wallNs"] += a * (wall_ns - e["wallNs"])
                e["rows"] += a * (rows - e["rows"])
                e["bytes"] += a * (nbytes - e["bytes"])
                e["count"] += 1
            while len(self._fps) > self.max_fingerprints:
                self._fps.popitem(last=False)
        _METRICS.note("cost_observation_count")

    def get(self, fingerprint: str) -> Dict[str, dict]:
        """{op: {"wallNs","rows","bytes","count"}} — empty when this
        fingerprint was never observed."""
        with self._lock:
            ops = self._fps.get(fingerprint)
            return {op: dict(e) for op, e in ops.items()} if ops else {}

    def fingerprints(self) -> List[str]:
        with self._lock:
            return list(self._fps)

    def snapshot(self) -> Dict[str, Dict[str, dict]]:
        with self._lock:
            return {fp: {op: dict(e) for op, e in ops.items()}
                    for fp, ops in self._fps.items()}

    def merge_snapshot(self, snap: Dict[str, Dict[str, dict]]) -> int:
        """Fold another store's snapshot into this one — the fleet
        cost-sharing op (router sync / costs_load wire op). Per (fp,
        op), the entry with the HIGHER observation count wins (same
        rule the router's trace-op merge applies): a better-measured
        EWMA beats a fresher-but-thinner one, and re-merging the same
        snapshot is idempotent. Returns entries adopted."""
        adopted = 0
        with self._lock:
            for fp, ops in snap.items():
                if not isinstance(ops, dict):
                    continue
                mine = self._fps.get(fp)
                if mine is None:
                    mine = self._fps[fp] = {}
                self._fps.move_to_end(fp)
                for op, e in ops.items():
                    try:
                        entry = {"wallNs": float(e["wallNs"]),
                                 "rows": float(e.get("rows", 0)),
                                 "bytes": float(e.get("bytes", 0)),
                                 "count": int(e["count"])}
                    except (KeyError, TypeError, ValueError):
                        continue     # malformed peer entry: skip, not fail
                    cur = mine.get(op)
                    if cur is None or entry["count"] > cur["count"]:
                        mine[op] = entry
                        adopted += 1
            while len(self._fps) > self.max_fingerprints:
                self._fps.popitem(last=False)
        return adopted

    def clear(self) -> None:
        with self._lock:
            self._fps.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._fps)


_COSTS: Optional[ObservedCostStore] = None


def observed_costs() -> ObservedCostStore:
    global _COSTS
    with _SINGLETON_LOCK:
        if _COSTS is None:
            _COSTS = ObservedCostStore()
        return _COSTS


def note_operator_costs(fingerprint: Optional[str], plan,
                        alpha: Optional[float] = None) -> None:
    """Fold one executed plan's per-operator metrics into the store:
    wall from ``opTime`` (the NS_TIMING convention: time inside the
    operator's iterator), rows from ``numOutputRows``, bytes from any
    declared ``*Bytes`` metric the exec emitted. The walk includes
    ``child_execs`` refs (exchange inputs, CPU-fallback islands) that
    ``collect_metrics``'s plain-children walk misses — a CPU-topped
    plan's measured host costs are exactly the comparison point an
    offload-decision CBO needs. No fingerprint (plan cache off /
    uncacheable) → nothing to key on, skip."""
    if fingerprint is None or plan is None:
        return
    agg: Dict[str, Dict[str, int]] = {}
    stack, seen = [plan], set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(getattr(node, "children", ()) or ())
        stack.extend(getattr(node, "child_execs", ()) or ())
        mdict = getattr(node, "metrics", None)
        if not isinstance(mdict, dict):
            continue
        e = agg.setdefault(getattr(node, "name", type(node).__name__),
                           {"wallNs": 0, "rows": 0, "bytes": 0})
        for mname, m in mdict.items():
            total = getattr(m, "total", None)
            if total is None:
                continue
            v = int(total())
            if mname == "opTime":
                e["wallNs"] += v
            elif mname == "numOutputRows":
                e["rows"] += v
            elif mname.endswith("Bytes") or mname.endswith("bytes"):
                e["bytes"] += v
    store = observed_costs()
    for op, e in agg.items():
        if e["wallNs"] or e["rows"] or e["bytes"]:
            store.observe(fingerprint, op, e["wallNs"], e["rows"],
                          e["bytes"], alpha=alpha)
