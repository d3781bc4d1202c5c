"""Task-scoped OOM retry: split-and-retry execution over spillable inputs.

Reference: RmmRapidsRetryIterator.scala (withRetry / withRetryNoSplit —
an operator that hits device OOM releases what it holds, lets the store
spill, and re-executes, halving its input on repeated OOM instead of
failing the query), GpuOOM/SplitAndRetryOOM classification, and RmmSpark's
deterministic OOM injection (forceRetryOOM/forceSplitAndRetryOOM) that
makes every retry path testable without a real allocator.

The TPU twin:

- ``with_retry(input, body, split=...)`` — run ``body(input)``; on a
  retryable OOM (OutOfBudgetError from the buffer catalog, or an XLA
  ``RESOURCE_EXHAUSTED`` surfaced by the runtime) release the pins the
  attempt took (catalog pin snapshot/restore), force a synchronous spill,
  back off while other semaphore holders drain, and re-run. A second OOM
  on the same input splits it in half (down to
  ``spark.rapids.tpu.retry.splitFloorRows``) and the halves re-enter the
  queue IN ORDER, so concatenated results are bit-for-bit identical to
  the no-OOM path.
- ``with_retry_no_split(body)`` — same recovery loop for bodies whose
  input cannot be halved (final merges, broadcast builds).
- ``SpillableInput`` — the handle an operator parks a batch in across a
  retry boundary: the batch lives in the spill catalog (unpinned between
  attempts → spillable under pressure), not as a raw device array.
- ``OomInjector`` — deterministic fault injection
  (``spark.rapids.tpu.test.injectOOM.{mode,seed,skipCount,oomCount}``):
  synthetic OOM thrown at the instrumented allocation sites so every
  retry path runs on CPU. ``every-N`` fires at every Nth allocation
  check; ``random`` fires with seeded probability. A trigger throws
  ``oomCount`` consecutive OOMs on the triggering thread (RmmSpark's
  numOOMs), and re-attempts inside a retry scope suppress NEW triggers so
  the recovery itself terminates.
- Final OOM (retries exhausted, split floor reached) raises
  ``FinalOOMError`` after writing a state dump to
  ``spark.rapids.tpu.memory.oomDumpDir`` when set: catalog tier
  occupancy, pinned handles, per-operator retry/split counts, semaphore
  holders.
"""

from __future__ import annotations

import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

from .catalog import BufferCatalog, OutOfBudgetError, SpillableBatch

#: substrings that classify a runtime error as a retryable device OOM
#: (the plugin.py failure matcher's RESOURCE_EXHAUSTED family — an XLA
#: HBM OOM is retryable here and only FATAL once retries are exhausted)
RETRYABLE_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "HBM OOM")


class InjectedOOMError(OutOfBudgetError):
    """Synthetic OOM from the fault-injection layer (test-only)."""


class FinalOOMError(MemoryError):
    """OOM that survived the retry state machine: pins were released,
    the store spilled, the input was split down to the floor, and the
    allocation still failed. Carries the oomDumpDir report path when one
    was written."""

    def __init__(self, msg: str, dump_path: Optional[str] = None):
        super().__init__(msg)
        self.dump_path = dump_path


class RetryCancelledError(RuntimeError):
    """The caller's ``cancelled`` hook fired between retry attempts —
    the body is not re-run. Cleanup already happened (the failed
    attempt's pins were restored, queued inputs closed), so the caller
    can unwind immediately; lineage recompute maps this onto the plan
    server's query-cancellation error."""


def is_retryable_oom(exc: BaseException) -> bool:
    """True when the retry state machine should handle ``exc``: a buffer
    catalog OutOfBudgetError (including injected OOM) or an XLA
    RESOURCE_EXHAUSTED surfaced through the runtime. FinalOOMError is
    NEVER retryable — it already consumed its retries."""
    if isinstance(exc, FinalOOMError):
        return False
    if isinstance(exc, OutOfBudgetError):
        return True
    msg = str(exc)
    return any(m in msg for m in RETRYABLE_OOM_MARKERS)


# ---------------------------------------------------------------------------
# retry policy knobs (session conf applied via apply_session_conf)
# ---------------------------------------------------------------------------

class _RetryPolicy:
    def __init__(self):
        self.enabled = True
        self.max_retries = 8
        self.split_floor_rows = 1 << 10
        self.dump_dir = ""


_POLICY = _RetryPolicy()
_POLICY_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# metrics (reference: the retryCount/splitAndRetryCount/retryBlockTime
# task metrics GpuTaskMetrics rolls into the Spark UI)
# ---------------------------------------------------------------------------

class RetryMetrics:
    """Process-wide retry counters; sessions report deltas between
    snapshots the way the python-semaphore wait metric does."""

    def __init__(self):
        self._lock = threading.Lock()
        self.retry_count = 0
        self.split_and_retry_count = 0
        self.retry_block_time_ns = 0
        self.spill_bytes_triggered = 0
        # adaptive skew pre-splits: inputs cut to the skew row target
        # BEFORE the first device attempt (with_retry presplit_rows) —
        # splits the OOM state machine never had to discover
        self.pre_split_count = 0
        #: per-operator {name: [retries, splits]} for the OOM dump
        self.per_op: Dict[str, List[int]] = {}

    def note_retry(self, name: str) -> None:
        with self._lock:
            self.retry_count += 1
            self.per_op.setdefault(name, [0, 0])[0] += 1

    def note_split(self, name: str) -> None:
        with self._lock:
            self.split_and_retry_count += 1
            self.per_op.setdefault(name, [0, 0])[1] += 1

    def note_presplit(self, name: str) -> None:
        with self._lock:
            self.pre_split_count += 1
            self.per_op.setdefault(name, [0, 0])[1] += 1

    def note_block(self, ns: int) -> None:
        with self._lock:
            self.retry_block_time_ns += int(ns)

    def note_spill(self, nbytes: int) -> None:
        with self._lock:
            self.spill_bytes_triggered += int(nbytes)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "retryCount": self.retry_count,
                "splitAndRetryCount": self.split_and_retry_count,
                "retryBlockTime": self.retry_block_time_ns,
                "retrySpillBytes": self.spill_bytes_triggered,
                "preSplitCount": self.pre_split_count,
            }


_METRICS = RetryMetrics()


def metrics() -> RetryMetrics:
    return _METRICS


# ---------------------------------------------------------------------------
# deterministic fault injection (reference: RmmSpark.forceRetryOOM /
# forceSplitAndRetryOOM + the spark.rapids.sql.test.injectRetryOOM conf)
# ---------------------------------------------------------------------------

class OomInjector:
    """Throws InjectedOOMError at instrumented allocation sites.

    Modes: ``""`` (off), ``every-N`` (every Nth eligible check fires),
    ``random`` (seeded probability 0.2 per check; ``random-0.35`` to set
    it). ``skip_count`` exempts the first K checks (aim at a deep site);
    ``oom_count`` throws that many CONSECUTIVE OOMs per trigger on the
    triggering thread — >1 forces the split path, > maxRetries forces a
    final OOM. Checks under an active retry re-attempt (``suppressed()``)
    never start a NEW trigger, so recovery terminates; pending
    consecutive OOMs still fire there (that is the point of oom_count).
    The first check after a trigger sequence is an uncounted free pass,
    so even ``every-1`` converges at sites that re-allocate outside a
    suppressed scope.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._gen = 0
        self.configure("")

    def configure(self, mode: str, seed: int = 0, skip_count: int = 0,
                  oom_count: int = 1) -> None:
        with self._lock:
            mode = (mode or "").strip().lower()
            self._mode = mode
            self._every = 0
            self._p = 0.0
            if mode.startswith("every-"):
                self._every = max(int(mode.split("-", 1)[1]), 1)
            elif mode.startswith("random"):
                self._p = float(mode.split("-", 1)[1]) \
                    if "-" in mode else 0.2
            elif mode not in ("", "off"):
                raise ValueError(f"unknown injectOOM.mode {mode!r}")
            self._rng = random.Random(seed)
            self._skip_left = max(int(skip_count), 0)
            self._oom_count = max(int(oom_count), 1)
            self._checks = 0
            self.injected = 0
            # invalidate every thread's pending/free state WITHOUT
            # replacing self._tls: another thread may be inside
            # suppressed() right now (apply_session_conf runs at every
            # collect, concurrent with other sessions' retry loops), and
            # swapping the local out from under its finally would crash
            # the recovery path with an AttributeError
            self._gen += 1

    @property
    def enabled(self) -> bool:
        return bool(self._every or self._p)

    @contextmanager
    def suppressed(self):
        """Scope for retry re-attempts: no NEW triggers fire inside."""
        self._tls.suppress = getattr(self._tls, "suppress", 0) + 1
        try:
            yield
        finally:
            self._tls.suppress = max(
                getattr(self._tls, "suppress", 1) - 1, 0)

    def check(self, site: str) -> None:
        """Instrumented-allocation-site hook; raises InjectedOOMError when
        the schedule says this allocation fails."""
        if not self.enabled:
            return
        if getattr(self._tls, "gen", -1) != self._gen:
            # a reconfigure happened since this thread last triggered:
            # its pending/free state belongs to the old schedule
            self._tls.gen = self._gen
            self._tls.pending = 0
            self._tls.free = False
        pending = getattr(self._tls, "pending", 0)
        if pending > 0:
            self._tls.pending = pending - 1
            if self._tls.pending == 0:
                self._tls.free = True
            with self._lock:
                self.injected += 1
            raise InjectedOOMError(
                f"injected OOM at {site} (consecutive {self._oom_count - pending + 1}/"
                f"{self._oom_count})")
        if getattr(self._tls, "free", False):
            # post-trigger free pass: the first check after a trigger
            # sequence succeeds and is not counted, so retry recovery
            # makes progress even at an every-1 site that re-allocates
            # outside a suppressed() scope
            self._tls.free = False
            return
        if getattr(self._tls, "suppress", 0) > 0:
            return
        with self._lock:
            if self._skip_left > 0:
                self._skip_left -= 1
                return
            self._checks += 1
            n = self._checks
            fire = (self._every and n % self._every == 0) or \
                (self._p and self._rng.random() < self._p)
            if fire:
                self.injected += 1
        if fire:
            if self._oom_count > 1:
                self._tls.pending = self._oom_count - 1
            else:
                self._tls.free = True
            raise InjectedOOMError(f"injected OOM at {site} (check #{n})")


_INJECTOR = OomInjector()


def injector() -> OomInjector:
    return _INJECTOR


def maybe_inject(site: str) -> None:
    """Allocation-site hook (the RmmSpark injection point twin). Cheap
    no-op while injection is off."""
    _INJECTOR.check(site)


@contextmanager
def oom_injection(mode: str, seed: int = 0, skip_count: int = 0,
                  oom_count: int = 1):
    """Test helper: enable injection inside the block, restore off after."""
    _INJECTOR.configure(mode, seed, skip_count, oom_count)
    try:
        yield _INJECTOR
    finally:
        _INJECTOR.configure("")


# ---------------------------------------------------------------------------
# conf plumbing (Session.collect applies its conf before executing)
# ---------------------------------------------------------------------------

def apply_session_conf(conf) -> None:
    """Install a session's retry/injection settings process-wide (the
    executor-singleton shape of the reference: RmmSpark state is
    per-process; the last session to run configures it)."""
    from ..config import (OOM_DUMP_DIR, RETRY_ENABLED, RETRY_MAX_RETRIES,
                          RETRY_SPLIT_FLOOR_ROWS, INJECT_OOM_MODE,
                          INJECT_OOM_SEED, INJECT_OOM_SKIP_COUNT,
                          INJECT_OOM_OOM_COUNT)
    with _POLICY_LOCK:
        _POLICY.enabled = bool(conf.get(RETRY_ENABLED.key))
        _POLICY.max_retries = int(conf.get(RETRY_MAX_RETRIES.key))
        _POLICY.split_floor_rows = int(conf.get(RETRY_SPLIT_FLOOR_ROWS.key))
        _POLICY.dump_dir = str(conf.get(OOM_DUMP_DIR.key) or "")
    _INJECTOR.configure(str(conf.get(INJECT_OOM_MODE.key)),
                        int(conf.get(INJECT_OOM_SEED.key)),
                        int(conf.get(INJECT_OOM_SKIP_COUNT.key)),
                        int(conf.get(INJECT_OOM_OOM_COUNT.key)))
    # the network injector rides the same entry point (one conf-apply
    # per collect configures BOTH process-wide fault layers)
    from ..shuffle import netfault
    netfault.apply_session_conf(conf)


def set_dump_dir(path: str) -> None:
    with _POLICY_LOCK:
        _POLICY.dump_dir = path or ""


@contextmanager
def retry_policy(**overrides):
    """Test helper: temporarily override retry policy fields
    (enabled/max_retries/split_floor_rows/dump_dir)."""
    old = {k: getattr(_POLICY, k) for k in overrides}
    with _POLICY_LOCK:
        for k, v in overrides.items():
            setattr(_POLICY, k, v)
    try:
        yield
    finally:
        with _POLICY_LOCK:
            for k, v in old.items():
                setattr(_POLICY, k, v)


# ---------------------------------------------------------------------------
# spillable retry input (reference: SpillableColumnarBatch held across
# withRetry boundaries + the splitSpillableInHalfByRows split policy)
# ---------------------------------------------------------------------------

class SpillableInput:
    """A batch parked in the spill catalog while it waits for (re-)use by
    a retry body. Unpinned between attempts — under memory pressure the
    input itself spills to host/disk and unspills on the next acquire."""

    def __init__(self, sb: SpillableBatch, schema, catalog: BufferCatalog,
                 rows: int):
        self.sb = sb
        self.schema = schema
        self.catalog = catalog
        self.rows = int(rows)

    @classmethod
    def from_batch(cls, batch, schema, catalog: Optional[BufferCatalog]
                   = None) -> "SpillableInput":
        from .catalog import device_budget
        cat = catalog or device_budget()
        rows = int(batch.num_rows)
        return cls(SpillableBatch(cat, batch, schema), schema, cat, rows)

    @classmethod
    def admit(cls, batch, schema, catalog: Optional[BufferCatalog] = None,
              name: str = "admit") -> "SpillableInput":
        """from_batch under the retry loop — registration reserves budget
        and is itself an (instrumented) allocation site."""
        from .catalog import device_budget
        cat = catalog or device_budget()
        return with_retry_no_split(
            lambda: cls.from_batch(batch, schema, cat),
            catalog=cat, name=name)

    def acquire(self):
        """Materialize on device and pin; pair with release()."""
        return self.sb.get()

    def release(self) -> None:
        self.sb.done_with()

    def close(self) -> None:
        self.sb.close()

    def split(self, floor_rows: int) -> Optional[List["SpillableInput"]]:
        """Halve by rows (SplitAndRetryOOM's split policy). None when at
        the floor. Closes self on success — the halves own the rows."""
        n = self.rows
        if n <= max(int(floor_rows), 1) or n < 2:
            return None
        import jax.numpy as jnp
        from ..batch import bucket_capacity
        from ..exec.common import jit_named, slice_batch
        mid = n // 2
        b = self.acquire()
        try:
            slicer = jit_named("slice_batch", slice_batch,
                               static_argnums=3)
            left = slicer(b, jnp.int32(0), jnp.int32(mid),
                          bucket_capacity(mid))
            right = slicer(b, jnp.int32(mid), jnp.int32(n - mid),
                           bucket_capacity(n - mid))
        finally:
            self.release()
        # register the halves transactionally: each registration reserves
        # budget and runs at peak pressure — an OOM on the right half
        # must close the already-registered left half, not leak it
        left_si = SpillableInput.from_batch(left, self.schema, self.catalog)
        try:
            right_si = SpillableInput.from_batch(right, self.schema,
                                                 self.catalog)
        except BaseException:
            left_si.close()
            raise
        self.close()
        return [left_si, right_si]


def admit_all(batches, schema, catalog: Optional[BufferCatalog] = None,
              name: str = "admit") -> List[SpillableInput]:
    """``SpillableInput.admit`` over a sequence, transactionally: if a
    later admit raises (final OOM, anything non-retryable), the already-
    admitted handles are closed before the error propagates — no
    ownerless catalog entries."""
    out: List[SpillableInput] = []
    try:
        for b in batches:
            out.append(SpillableInput.admit(b, schema, catalog, name=name))
    except BaseException:
        for si in out:
            si.close()
        raise
    return out


def split_input_halves(item):
    """Default split policy for with_retry: halve a SpillableInput (or
    anything with ``.split(floor_rows)``, e.g. a host-table wrapper) down
    to spark.rapids.tpu.retry.splitFloorRows."""
    return item.split(_POLICY.split_floor_rows)


def presplit_inputs(inp, target_rows: int,
                    split: Callable = split_input_halves) -> List:
    """Adaptive pre-split: cut an input measured over ``target_rows``
    rows into in-order chunks BEFORE the first device attempt, using
    the same split policy with_retry applies on OOM. A skew re-plan
    that already measured one hot batch far over the row target should
    not have to burn OOM attempts to discover what the shuffle
    statistics already said; the split floor still bounds recursion.
    Inputs without a ``rows`` measure pass through untouched."""
    work, out = deque([inp]), []
    while work:
        item = work.popleft()
        rows = getattr(item, "rows", None)
        if rows is not None and rows > target_rows:
            halves = split(item)
            if halves:
                _METRICS.note_presplit(getattr(item, "name", "presplit"))
                for h in reversed(halves):
                    work.appendleft(h)
                continue
        out.append(item)
    return out


def split_host_table(t):
    """Split policy for host-side (pyarrow) tables at the H2D boundary:
    device_put of half the rows needs half the fresh HBM. Zero-copy
    slices; row order is preserved so the device batches concatenate
    bit-for-bit with the unsplit path."""
    n = t.num_rows
    if n <= max(_POLICY.split_floor_rows, 1) or n < 2:
        return None
    mid = n // 2
    return [t.slice(0, mid), t.slice(mid)]


# ---------------------------------------------------------------------------
# the retry state machine
# ---------------------------------------------------------------------------

def _recover(cat: BufferCatalog, pin_snapshot, attempt: int,
             semaphore) -> None:
    """Between attempts: release the pins the failed attempt took, force
    the store to spill, and back off while other semaphore holders drain
    (reference: the block/spill state transitions in RmmSpark's per-task
    state machine)."""
    from ..trace import span as _trace_span
    cat.restore_pins(pin_snapshot)
    spill0 = cat.spilled_to_host + cat.spilled_to_disk
    cat.synchronous_spill(max(cat.device_used, 1))
    spilled = cat.spilled_to_host + cat.spilled_to_disk - spill0
    _METRICS.note_spill(spilled)
    # bounded exponential backoff; release the admission semaphore across
    # the sleep so concurrent tasks can finish and free device memory.
    # The span makes retry stalls attributable on a query's timeline —
    # "14 seconds" spent here is OOM pressure, not operator work.
    delay = min(0.001 * (1 << min(attempt, 6)), 0.05)
    t0 = time.perf_counter_ns()
    with _trace_span("retry.backoff", kind="retry", attempt=attempt,
                     spillBytes=int(spilled)):
        depth = 0
        if semaphore is not None:
            depth = semaphore.held_depth()
            for _ in range(depth):
                semaphore.release_if_held()
        try:
            time.sleep(delay)
        finally:
            if semaphore is not None:
                for _ in range(depth):
                    semaphore.acquire_if_necessary()
    _METRICS.note_block(time.perf_counter_ns() - t0)


def _close_item(item) -> None:
    close = getattr(item, "close", None)
    if close is not None:
        try:
            close()
        except Exception:
            pass


def _final_oom(exc: BaseException, cat: BufferCatalog, name: str,
               semaphore, attempts: int) -> FinalOOMError:
    path = write_oom_dump(cat, semaphore=semaphore, op=name, exc=exc)
    suffix = f"; state dumped to {path}" if path else \
        " (set spark.rapids.tpu.memory.oomDumpDir for a state dump)"
    return FinalOOMError(
        f"{name}: device OOM survived {attempts} attempts (pins released, "
        f"store spilled, input at split floor): {exc}{suffix}", path)


def with_retry(inp, body: Callable, split: Optional[Callable] = None,
               *, catalog: Optional[BufferCatalog] = None, name: str = "op",
               max_retries: Optional[int] = None, semaphore=None,
               close_input: bool = True,
               cancelled: Optional[Callable[[], bool]] = None,
               presplit_rows: Optional[int] = None):
    """Generator: run ``body`` over ``inp`` and whatever ``split`` makes
    of it under OOM, yielding each result in input-row order.

    On a retryable OOM the attempt's catalog pins are released (snapshot/
    restore), the store spills, and the body re-runs; a second OOM on the
    same item invokes ``split(item)`` (halves re-enter the queue in
    order, so concatenated results are bit-for-bit the no-OOM output).
    ``body`` must be re-runnable and must undo its OWN partial side
    effects (e.g. close staged catalog handles) before letting a
    retryable OOM propagate — the framework restores pins, not arbitrary
    state. Items are closed after use when ``close_input`` (and on any
    raise), matching withRetry's ownership of its spillable input.

    ``cancelled`` (optional) is polled before every attempt: a retry
    storm must not ride out its whole backoff budget after the server
    already cancelled the query (stop()/watchdog during a lineage
    recompute) — the loop raises RetryCancelledError instead of
    re-running the body.

    ``presplit_rows`` (optional, the adaptive skew-join seam): an input
    measuring over this many rows is split through the SAME machinery
    BEFORE its first attempt, so a re-planned hot partition whose one
    giant batch the shuffle statistics already measured never has to
    OOM its way down to a workable size."""
    cat = catalog
    if cat is None:
        from .catalog import device_budget
        cat = device_budget()
    if max_retries is None:
        max_retries = _POLICY.max_retries
    if semaphore is None:
        # default to the process admission semaphore: a retrying holder
        # must drain its slot across the backoff so concurrent tasks can
        # finish and free HBM (no-op for threads that hold nothing)
        from .semaphore import global_semaphore
        semaphore = global_semaphore()
    if presplit_rows is not None and presplit_rows > 0 and \
            split is not None and _POLICY.enabled:
        work = deque(presplit_inputs(inp, presplit_rows, split))
    else:
        work = deque([inp])
    try:
        while work:
            item = work.popleft()
            attempt = 0
            while True:
                if cancelled is not None and cancelled():
                    _close_item(item)
                    raise RetryCancelledError(
                        f"{name}: cancelled before attempt "
                        f"{attempt + 1} — the query was stopped while "
                        f"its retry loop was recovering")
                snap = cat.pin_snapshot()
                try:
                    if attempt == 0 or not _POLICY.enabled:
                        result = body(item)
                    else:
                        # re-attempts never start NEW injected triggers —
                        # recovery must converge (pending consecutive
                        # OOMs from oomCount still fire)
                        with _INJECTOR.suppressed():
                            result = body(item)
                except BaseException as e:
                    # every failed attempt gives back the pins it took —
                    # also on the non-retryable path, so a body that dies
                    # mid-pin-loop cannot strand batches unspillable
                    # (restore is a no-op for pins the body released
                    # itself before raising)
                    cat.restore_pins(snap)
                    if not (_POLICY.enabled and is_retryable_oom(e)):
                        _close_item(item)
                        raise
                    attempt += 1
                    _METRICS.note_retry(name)
                    halves = None
                    if attempt >= 2 and split is not None:
                        # split() re-acquires the full batch and registers
                        # the halves — allocations at peak pressure. An
                        # OOM inside it is one more failed attempt (spill,
                        # back off, try again), NOT an escape from the
                        # state machine.
                        try:
                            with _INJECTOR.suppressed():
                                halves = split(item)
                        except BaseException as se:
                            if not is_retryable_oom(se):
                                _close_item(item)
                                raise
                        if halves:
                            _METRICS.note_split(name)
                            for h in reversed(halves):
                                work.appendleft(h)
                            break   # halves are fresh items
                    if attempt > max_retries:
                        _close_item(item)
                        raise _final_oom(e, cat, name, semaphore,
                                         attempt) from e
                    _recover(cat, snap, attempt, semaphore)
                else:
                    if close_input:
                        _close_item(item)
                    yield result
                    break
    except BaseException:
        while work:                      # free queued spillable inputs
            _close_item(work.popleft())
        raise


class _NoInput:
    """Sentinel input for with_retry_no_split (nothing to close/split)."""

    def __repr__(self):
        return "<no-input>"


_NO_INPUT = _NoInput()


def with_retry_no_split(body: Callable, *, catalog: Optional[BufferCatalog]
                        = None, name: str = "op",
                        max_retries: Optional[int] = None, semaphore=None,
                        cancelled: Optional[Callable[[], bool]] = None):
    """Run a no-argument ``body`` under the retry loop (no split policy:
    final merges, broadcast builds, single acquires). Returns the body's
    result (reference: withRetryNoSplit)."""
    return next(with_retry(_NO_INPUT, lambda _i: body(), split=None,
                           catalog=catalog, name=name,
                           max_retries=max_retries, semaphore=semaphore,
                           close_input=False, cancelled=cancelled))


def acquire_with_retry(sb: SpillableBatch, *, catalog: Optional[BufferCatalog]
                       = None, name: str = "acquire"):
    """Pin a spillable handle under the retry loop — the unspill path
    reserves device budget and can itself OOM."""
    return with_retry_no_split(sb.get, catalog=catalog or sb.catalog,
                               name=name)


def register_with_retry(batch, schema, *, catalog: Optional[BufferCatalog]
                        = None, name: str = "register",
                        priority: int = 0) -> SpillableBatch:
    """SpillableBatch registration under the retry loop — register()
    reserves budget for the new handle and can OOM under pressure."""
    cat = catalog
    if cat is None:
        from .catalog import device_budget
        cat = device_budget()
    return with_retry_no_split(
        lambda: SpillableBatch(cat, batch, schema, priority),
        catalog=cat, name=name)


# ---------------------------------------------------------------------------
# final-OOM state dump (spark.rapids.tpu.memory.oomDumpDir; reference:
# spark.rapids.memory.gpu.oomDumpDir heap/state dumps on alloc failure)
# ---------------------------------------------------------------------------

def write_oom_dump(catalog: BufferCatalog, semaphore=None,
                   op: Optional[str] = None, exc: Optional[BaseException]
                   = None, dump_dir: Optional[str] = None) -> Optional[str]:
    """Write the post-retry OOM report. Returns the path, or None when no
    dump dir is configured (or the write itself fails — a dump must never
    mask the original OOM)."""
    d = dump_dir if dump_dir is not None else _POLICY.dump_dir
    if not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"oom-{os.getpid()}-{threading.get_ident()}-"
               f"{int(time.time() * 1000)}.txt")
        lines = ["=== spark-rapids-tpu OOM dump ==="]
        if op:
            lines.append(f"operator: {op}")
        if exc is not None:
            lines.append(f"error: {type(exc).__name__}: {exc}")
        lines.append("")
        lines.append("--- catalog tier occupancy ---")
        lines.append(catalog.tier_summary())
        lines.append("")
        lines.append("--- catalog entries (pinned handles marked) ---")
        lines.append(catalog.dump_state())
        lines.append("")
        lines.append("--- retry/split counts per operator ---")
        snap = _METRICS.snapshot()
        lines.append(f"total: retries={snap['retryCount']} "
                     f"splits={snap['splitAndRetryCount']} "
                     f"blockTimeNs={snap['retryBlockTime']} "
                     f"spillBytes={snap['retrySpillBytes']}")
        for nm, (r, s) in sorted(_METRICS.per_op.items()):
            lines.append(f"  {nm}: retries={r} splits={s}")
        lines.append("")
        lines.append("--- semaphore holders ---")
        if semaphore is not None:
            holders = semaphore.holders()
            lines.append(f"max_concurrent={semaphore.max_concurrent} "
                         f"wait_time_ns={semaphore.wait_time_ns}")
            for tid, depth in holders.items():
                lines.append(f"  thread {tid}: depth {depth}")
        else:
            lines.append("(no semaphore in scope)")
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        return path
    except Exception:
        return None
