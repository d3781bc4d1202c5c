"""Thread accounting shared by the fault-injection suites."""

import threading

from spark_rapids_tpu.io import source


def warm_reader_pool() -> None:
    """Make the shared fetch/decode pool (``io/source.reader_pool``) start
    every worker it may have, before a leak check takes its baseline.

    The pool starts workers on demand, so how many exist after a run depends
    on how far that run's fetches overlapped — a faulted run under load
    starts more than the clean run before it did. With the pool full, the
    checks keep counting EVERY thread (pool workers and a second executor
    included) against a baseline that no longer depends on timing."""
    pool = source._POOL
    if pool is None:
        return
    go = threading.Event()
    held = []
    # each blocked task either takes an idle worker or starts a new one
    for _ in range(4 * pool._max_workers):
        if len(pool._threads) >= pool._max_workers:
            break
        held.append(pool.submit(go.wait, 30))
    go.set()
    for f in held:
        f.result(timeout=30)
    assert len(pool._threads) == pool._max_workers
