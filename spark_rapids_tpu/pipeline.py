"""Bounded producer/consumer pipeline (double-buffered prefetch).

Reference: the CUDA plugin hides host latency behind device compute with
pinned-memory prefetch — the multi-file reader decodes the NEXT batch on
its thread pool while the current one is in flight to the device
(GpuMultiFileReader.scala:441 readAsync over MultiFileReaderThreadPool,
staging through PinnedMemoryPool). JAX has no pinned-host allocator to
expose, but the overlap itself is a host-side structure: run the producer
(decode / D2H staging) one stage ahead of the consumer (`device_put` /
compute / framing) through a BOUNDED queue.

This module is that one structure, shared by the scan side
(io/source.py: decode batch N+1 while batch N is in device_put/compute)
and the exchange side (shuffle/exchange.py: D2H-stage partition P+1 while
partition P is framed/compressed). Contract:

- ``depth <= 0`` returns the source iterator unchanged — the synchronous
  path, bit for bit (``spark.rapids.tpu.prefetch.depth=0`` is the
  kill switch).
- Single-core hosts skip the thread handoff entirely (same policy as the
  single-core inline fast path in io/source.py: a thread cannot overlap
  CPU-bound work there, and the queue handoff taxes the hot loop).
- Producer exceptions are re-raised at the consumer, after all items
  produced before the failure have been consumed.
- Closing the iterator (consumer abort: limits, errors downstream)
  cancels the producer promptly and joins it — no leaked threads. The
  poison-pill DONE marker always lands, so the consumer never blocks on
  a dead producer.
- ``overlapTime`` metric: producer work hidden behind the consumer
  (busy time minus the time the consumer spent waiting on the queue) —
  the number that makes the overlap visible in metric roll-ups next to
  the xprof trace.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Iterable, Iterator, Optional

_ITEM, _ERR, _DONE = 0, 1, 2

#: joins/cancellation must complete well inside this (seconds); a producer
#: stuck past it indicates a hung decode, not a pipeline bug
_JOIN_TIMEOUT_S = 30.0


def prefetched(source: Iterable, depth: int, pool=None, metrics=None,
               name: str = "prefetch", force_thread: bool = False,
               stage: str = "prefetch"):
    """Wrap ``source`` so it is produced ``depth`` items ahead of the
    consumer on a background thread. Returns the plain iterator (no
    thread, no queue) when depth<=0 or on single-core hosts —
    ``force_thread`` overrides the single-core policy for I/O-bound
    producers (and tests). ``stage`` names the pipeline in a query
    trace: the producer thread is ``rtpu-<stage>-<n>`` to the OS and a
    consumer that blocks records ``<stage>.prefetchWait``."""
    if depth is None or depth <= 0:
        return iter(source)
    if not force_thread and (os.cpu_count() or 1) <= 1:
        return iter(source)
    return PrefetchIterator(source, depth, pool=pool, metrics=metrics,
                            name=name, stage=stage)


class PrefetchIterator:
    """Iterator over ``source`` produced ahead through a bounded queue.

    ``pool`` runs the producer on an executor instead of a dedicated
    thread. NOTE for pool users: the producer OCCUPIES one worker for the
    iterator's whole lifetime — a pool whose every worker is a producer
    that submits work back into the same pool deadlocks, which is why the
    scan side uses a dedicated thread and lets the decode tasks have the
    shared reader pool to themselves."""

    def __init__(self, source: Iterable, depth: int, pool=None,
                 metrics=None, name: str = "prefetch",
                 stage: str = "prefetch"):
        from . import trace as qtrace
        self._source = source
        self._stage = stage
        # the producer works for the query that consumes: its spans (a
        # scan's decode) go under the span that built this iterator
        self._trace_token = qtrace.capture()
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._cancel = threading.Event()
        self._metrics = metrics if metrics is not None else {}
        self._busy_ns = 0       # producer time spent inside next(source)
        self._wait_ns = 0       # consumer time spent blocked on the queue
        self._finished = False
        self._future = None
        self._thread: Optional[threading.Thread] = None
        if pool is not None:
            self._future = pool.submit(self._run)
        else:
            self._thread = threading.Thread(
                target=self._run, name=f"{name}-producer", daemon=True)
            self._thread.start()

    # ---- producer side ----
    def _put(self, item) -> bool:
        """Blocking put that observes cancellation; False = cancelled."""
        while not self._cancel.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        from . import trace as qtrace
        if self._thread is not None:
            qtrace.name_thread(f"rtpu-{self._stage}")
        with qtrace.attached(self._trace_token):
            self._produce()

    def _produce(self) -> None:
        it = iter(self._source)
        try:
            while not self._cancel.is_set():
                t0 = time.perf_counter_ns()
                try:
                    item = next(it)
                except StopIteration:
                    break
                except BaseException as e:   # re-raised at the consumer
                    self._busy_ns += time.perf_counter_ns() - t0
                    self._put((_ERR, e))
                    return
                self._busy_ns += time.perf_counter_ns() - t0
                if not self._put((_ITEM, item)):
                    break
        finally:
            if self._cancel.is_set():
                # consumer abort: release the source's resources (file
                # handles, nested pipelines) on the thread that drove it
                close = getattr(it, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:
                        pass
            if not self._put((_DONE, None)):
                # cancelled with a full queue: make room so the marker
                # lands (close() is draining concurrently; benign race)
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    pass
                try:
                    self._q.put_nowait((_DONE, None))
                except queue.Full:
                    pass

    def _producer_done(self) -> bool:
        if self._thread is not None:
            return not self._thread.is_alive()
        return self._future.done()

    def _join(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=_JOIN_TIMEOUT_S)
        else:
            try:
                self._future.result(timeout=_JOIN_TIMEOUT_S)
            except Exception:
                pass   # producer errors were already routed via _ERR

    # ---- consumer side ----
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        t0 = time.perf_counter_ns()
        try:
            tag, val = self._q.get_nowait()
        except queue.Empty:
            from . import trace as qtrace
            with qtrace.span(f"{self._stage}.prefetchWait", kind="wait"):
                tag, val = self._q.get()
        self._wait_ns += time.perf_counter_ns() - t0
        if tag == _ITEM:
            return val
        self._finish()
        if tag == _ERR:
            raise val
        raise StopIteration

    def _finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        self._join()
        get = getattr(self._metrics, "get", None)
        if get is not None:
            m = get("overlapTime")
            if m is not None:
                m.add(max(self._busy_ns - self._wait_ns, 0))
            w = get("prefetchWaitTime")
            if w is not None:
                w.add(self._wait_ns)

    def close(self) -> None:
        """Consumer abort: cancel the producer, drain, join. Idempotent."""
        if self._finished:
            return
        self._cancel.set()
        # drain so a producer blocked on a full queue can observe the
        # cancel and exit; bounded in case the producer is hung mid-item
        deadline = time.monotonic() + _JOIN_TIMEOUT_S
        while not self._producer_done() and time.monotonic() < deadline:
            try:
                self._q.get(timeout=0.05)
            except queue.Empty:
                pass
        while True:   # leftover items + the DONE marker
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._finish()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # abandoned mid-stream (consumer generator GC'd): stop the
        # producer rather than letting it fill the queue and park forever
        try:
            self.close()
        except Exception:
            pass


def close_iterator(it) -> None:
    """Close an iterator if it supports it (PrefetchIterator or
    generator) — the consumer-side finally-block helper."""
    close = getattr(it, "close", None)
    if close is not None:
        close()
