"""Thread-leak accounting shared by the fault-injection suites."""

import threading


def handler_threads() -> int:
    """Live threads, less the shared fetch/decode pool's workers
    (io/source.reader_pool): that pool grows on demand and never shrinks by
    design, and how far a faulted run grows it depends on timing — a
    handler-thread leak check must not count it."""
    return sum(1 for t in threading.enumerate()
               if not t.name.startswith("multifile-read"))
