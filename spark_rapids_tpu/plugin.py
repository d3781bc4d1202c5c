"""Runtime bootstrap: the plugin/executor lifecycle.

Reference: SURVEY.md §2.1/§3.1 — SQLPlugin → RapidsDriverPlugin (conf
fixup, heartbeat host) and RapidsExecutorPlugin (device acquire, RMM init,
version handshake, semaphore init, fatal-error exit policy,
Plugin.scala:215-393). The standalone TPU engine folds both roles into one
process; multi-host deployments run one `ExecutorRuntime` per host with
`jax.distributed` supplying the DCN control plane.

Failure policy mirrors the reference (SURVEY.md §5): a fatal device error
marks the runtime unusable and (optionally) exits with a dedicated code so
a scheduler reschedules the executor — the plugin adds fast failure, the
cluster manager supplies recovery (Spark task-retry in the reference).
"""

from __future__ import annotations

import atexit
import logging
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .config import (CONCURRENT_TPU_TASKS, HBM_POOL_FRACTION, HBM_RESERVE,
                     HOST_SPILL_LIMIT, RapidsTpuConf, SPILL_DIR)

log = logging.getLogger("spark_rapids_tpu")

FATAL_EXIT_CODE = 20     # reference: executor exits 20 on fatal CUDA error

#: the release line this tree is built and tested on (jax/jaxlib 0.9.0)
_JAX_LINE = (0, 9)


@dataclass
class DeviceInfo:
    platform: str
    device_kind: str
    num_local: int
    num_global: int
    hbm_bytes: Optional[int] = None


class ExecutorRuntime:
    """Per-process device runtime (reference: RapidsExecutorPlugin.init)."""

    _instance: Optional["ExecutorRuntime"] = None
    _lock = threading.Lock()

    def __init__(self, conf: Optional[RapidsTpuConf] = None,
                 exit_on_fatal: bool = False):
        self.conf = conf or RapidsTpuConf()
        self.exit_on_fatal = exit_on_fatal
        self.fatal_error: Optional[BaseException] = None
        self.started_at = time.time()
        self._heartbeats: Dict[str, float] = {}
        #: executors a transport PROVED unreachable: they need a fresh
        #: register() handshake to count as live again — a stray late
        #: heartbeat must not resurrect a dead block server
        self._dead_executors: set = set()
        #: guards _heartbeats + _dead_executors together: the dead check
        #: and the stamp must be one atomic step, or a concurrent
        #: mark_unreachable between them gets silently undone
        self._hb_lock = threading.Lock()
        self._hb_senders: List[tuple] = []      # (thread, stop event)

        self._version_handshake()
        self.device = self._acquire_device()
        self.semaphore = self._init_semaphore()
        self.catalog = self._init_memory()
        atexit.register(self.shutdown)
        log.info("ExecutorRuntime up: %s", self.device)

    # ------------------------------------------------------------------

    @classmethod
    def get(cls, conf: Optional[RapidsTpuConf] = None) -> "ExecutorRuntime":
        with cls._lock:
            if cls._instance is None:
                cls._instance = ExecutorRuntime(conf)
            return cls._instance

    def _version_handshake(self) -> None:
        """Reference: cudf/JNI version checks (Plugin.scala:300-324)."""
        import jax
        ver = tuple(int(x) for x in jax.__version__.split(".")[:2])
        if ver < _JAX_LINE:
            raise RuntimeError(
                f"jax {jax.__version__} is older than the "
                f"{'.'.join(map(str, _JAX_LINE))} line this tree runs on")
        if not jax.config.jax_enable_x64:
            raise RuntimeError(
                "x64 mode is off — int64/float64 SQL semantics require it "
                "(spark_rapids_tpu enables it at import; something reset it)")

    def _acquire_device(self) -> DeviceInfo:
        """Reference: one GPU per executor (GpuDeviceManager.scala:93-114) —
        one TPU chip per executor process here."""
        import jax
        local = jax.local_devices()
        dev = local[0]
        hbm = (dev.memory_stats() or {}).get("bytes_limit")
        if hbm is None and dev.platform == "tpu":
            raise RuntimeError(
                f"{dev} reports no memory_stats()['bytes_limit']: the HBM "
                f"budget cannot be sized from an unknown device")
        return DeviceInfo(platform=dev.platform,
                          device_kind=getattr(dev, "device_kind", "?"),
                          num_local=len(local),
                          num_global=jax.device_count(), hbm_bytes=hbm)

    def _init_semaphore(self):
        from .memory.semaphore import TpuSemaphore
        return TpuSemaphore(self.conf.get(CONCURRENT_TPU_TASKS.key))

    def _init_memory(self):
        """Reference: initializeRmm pool sizing (GpuDeviceManager:192-317) —
        here the reservation budget is sized from real HBM when known."""
        from .config import LEAK_DETECTION
        from .memory.catalog import BufferCatalog
        frac = self.conf.get(HBM_POOL_FRACTION.key)
        reserve = self.conf.get(HBM_RESERVE.key)
        # only a backend that reports no memory (the CPU test platform)
        # gets a nominal size; on a TPU an unknown size already raised
        hbm = self.device.hbm_bytes or (16 << 30)
        limit = max(int(hbm * frac) - reserve, 1 << 30)
        return BufferCatalog(device_limit=limit,
                             host_limit=self.conf.get(HOST_SPILL_LIMIT.key),
                             spill_dir=self.conf.get(SPILL_DIR.key),
                             track_leaks=self.conf.get(LEAK_DETECTION.key))

    # ------------------------------------------------------------------
    # failure handling (reference: Plugin.scala:370-392 onTaskFailed)
    # ------------------------------------------------------------------

    FATAL_MARKERS = ("DEADLINE_EXCEEDED", "device is in an invalid state",
                     "halted")

    def classify_failure(self, exc: BaseException) -> bool:
        """True if fatal for the device (executor must be replaced).

        The device-OOM family (RESOURCE_EXHAUSTED / HBM OOM — memory/
        retry.py RETRYABLE_OOM_MARKERS, one list so classification and
        retry can never disagree) belongs to the retry state machine:
        release pins, spill, re-run, split — only a post-retry
        FinalOOMError fails the query, and even that leaves the executor
        healthy (the reference's task-level GpuOOM vs executor-fatal
        CUDA errors). An explicit fatal marker wins over an OOM marker
        in the same message: a halted device is gone no matter what
        exhausted it."""
        from .memory.retry import FinalOOMError
        if isinstance(exc, FinalOOMError):
            # the retry framework already released pins and spilled the
            # store; the query died but the device is in a clean state
            return False
        msg = str(exc)
        if any(m in msg for m in self.FATAL_MARKERS):
            return True
        # everything else — including the retryable OOM family
        # (is_retryable_oom) — leaves the device usable
        return False

    def on_task_failed(self, exc: BaseException) -> None:
        if not self.classify_failure(exc):
            return
        self.fatal_error = exc
        log.error("fatal device error; executor unusable: %s", exc)
        self._dump_device_state()
        if self.exit_on_fatal:
            sys.exit(FATAL_EXIT_CODE)

    def _dump_device_state(self) -> None:
        """Reference: nvidia-smi capture on death (Plugin.scala:341-361)."""
        try:
            import jax
            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                log.error("device %s stats: %s", d, stats)
            log.error("catalog:\n%s", self.catalog.dump_state())
        except Exception:
            pass

    def ensure_healthy(self) -> None:
        if self.fatal_error is not None:
            raise RuntimeError(
                f"executor poisoned by earlier fatal error: "
                f"{self.fatal_error}")

    # ------------------------------------------------------------------
    # liveness (reference: RapidsShuffleHeartbeatManager — driver-side
    # registry of executor heartbeats for shuffle peer discovery)
    # ------------------------------------------------------------------

    def register(self, executor_id) -> None:
        """The explicit liveness handshake: clears a dead promotion and
        stamps the executor live. mark_unreachable + register is the
        full suspect→dead→rehabilitated cycle; a bare heartbeat only
        covers the live legs."""
        eid = str(executor_id)
        with self._hb_lock:
            self._dead_executors.discard(eid)
            self._heartbeats[eid] = time.time()

    def heartbeat(self, executor_id) -> bool:
        """Stamp liveness unless the executor was promoted dead; returns
        False (refused) for a dead one — it must register() afresh. The
        dead check and the stamp are ONE atomic step under the lock, so
        a concurrent mark_unreachable cannot be silently undone by a
        heartbeat that already passed the check."""
        # keys normalize to str: the CACHED-shuffle registry path hands
        # the transport INT executor ids (spark.rapids.tpu.executorId)
        # while in-process callers use strings — one table serves both
        eid = str(executor_id)
        with self._hb_lock:
            if eid in self._dead_executors:
                # a transport PROVED this executor's block server dead;
                # a stray late heartbeat must not silently resurrect it
                # into every reader's fetch ordering — rehabilitation
                # requires the explicit register() handshake
                return False
            self._heartbeats[eid] = time.time()
        return True

    def start_heartbeat(self, executor_id: str,
                        interval_s: Optional[float] = None
                        ) -> threading.Event:
        """Background sender: stamp this executor's liveness every
        interval (default: shuffle.cached.heartbeatIntervalMs conf;
        reference: RapidsShuffleHeartbeatEndpoint's executor →
        driver ping loop). Returns the stop event; shutdown() sets it."""
        stop = threading.Event()
        if interval_s is None:
            from .config import CACHED_HEARTBEAT_INTERVAL_MS
            interval_s = self.conf.get(
                CACHED_HEARTBEAT_INTERVAL_MS.key) / 1000.0

        def loop():
            # a FRESH sender is the registration handshake (the executor
            # restating itself); subsequent stamps are plain heartbeats.
            # A refused beat means this executor was promoted dead while
            # its sender is demonstrably alive (transient partition) —
            # perform the explicit re-register handshake, the same
            # rehabilitation RegistryClient._beat does on the wire. A
            # truly dead executor has no sender, so stray late beats
            # from other callers still cannot resurrect it. Re-registers
            # BACK OFF exponentially while refusals keep recurring: a
            # HALF-dead executor (heartbeat thread alive, block server
            # wedged) would otherwise undo its promotion every interval
            # and re-tax every reader's fetch with the very timeouts the
            # promotion exists to remove; the backoff resets only after
            # a sustained healthy stretch.
            self.register(executor_id)
            rereg_backoff = interval_s
            last_rereg = time.time()
            healthy = 0
            while not stop.is_set():
                if self.heartbeat(executor_id):
                    healthy += 1
                    if healthy >= 10:
                        rereg_backoff = interval_s
                else:
                    healthy = 0
                    now = time.time()
                    if now - last_rereg >= rereg_backoff:
                        self.register(executor_id)
                        last_rereg = now
                        rereg_backoff = min(rereg_backoff * 2,
                                            max(60.0, interval_s))
                stop.wait(interval_s)

        t = threading.Thread(target=loop, daemon=True,
                             name=f"heartbeat-{executor_id}")
        with self._lock:
            self._hb_senders.append((t, stop))
        t.start()
        return stop

    def mark_unreachable(self, executor_id) -> None:
        """Transport-report hook (TcpTransport.on_unreachable): a peer
        that exhausted its fetch retry budget stops counting as live
        immediately instead of coasting until its heartbeat ages out —
        subsequent list_blocks calls skip it without paying a socket
        timeout (reference: transport errors feeding the
        RapidsShuffleHeartbeatManager's executor-death bookkeeping).
        The removal is a PROMOTION to dead, not mere staleness: only an
        explicit register() brings the executor back."""
        eid = str(executor_id)
        with self._hb_lock:
            self._dead_executors.add(eid)
            self._heartbeats.pop(eid, None)

    def live_executors(self, timeout_s: Optional[float] = None
                       ) -> List[str]:
        if timeout_s is None:
            from .config import CACHED_HEARTBEAT_TIMEOUT_MS
            timeout_s = self.conf.get(
                CACHED_HEARTBEAT_TIMEOUT_MS.key) / 1000.0
        now = time.time()
        with self._hb_lock:
            # snapshot under the same lock the sender threads stamp
            # under — iterating a dict a register() is inserting into
            # raises "dictionary changed size during iteration"
            return [e for e, t in self._heartbeats.items()
                    if now - t <= timeout_s]

    def shutdown(self) -> None:
        # deterministic teardown: stop AND join the senders so no stamp
        # can land after shutdown returns
        for t, stop in list(getattr(self, "_hb_senders", [])):
            stop.set()
        for t, stop in list(getattr(self, "_hb_senders", [])):
            t.join(timeout=10)
        # the MemoryCleaner-at-shutdown analogue (reference:
        # Plugin.scala:283-298 shutdown-hook ordering): surviving catalog
        # handles at engine shutdown are leaks — log them loudly
        leaks = self.catalog.leak_check()
        if leaks:
            log.error("catalog leak check: %d handle(s) still registered "
                      "at shutdown:\n  %s", len(leaks), "\n  ".join(leaks))


def init(conf_dict: Optional[Dict] = None) -> ExecutorRuntime:
    """Engine entry point (the `spark.plugins=com.nvidia.spark.SQLPlugin`
    moment). Idempotent."""
    conf = RapidsTpuConf(conf_dict) if conf_dict else None
    return ExecutorRuntime.get(conf)
