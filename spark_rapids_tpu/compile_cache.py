"""The process's two stores of built programs: the persistent XLA compile
cache — one directory, chosen from outside — and the program table in
front of it, which keeps what JAX built from one query to the next.

A cold query compiles every program it runs, and the TPU compiler takes
seconds to minutes for each (tools/aot_compile.py). Every entry point that
owns a device — ``python -m spark_rapids_tpu.server`` (and so each router
worker, which is that command), ``bench.py``, ``chip_smoke.py`` — calls
``enable()`` before its first compile, so that they share what they build.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX itself reads it and nothing
here names another directory; otherwise the cache is
``<checkout>/.jax_compilation_cache``. The path is part of the cache key's
environment: no temp dir, pid or timestamp in it, or nothing ever hits.

The directory saves the XLA compile and nothing else: JAX keys its trace,
its lowering and its loaded executable on the jitted function OBJECT, so a
query whose execs are rebuilt (every collect rebuilds them) traces, lowers
and loads each program again. ``ProgramTable`` is what
``exec/common.jit_named`` shares those function objects through.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, Hashable, Tuple

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_compilation_cache")


def cache_dir() -> str:
    """The directory ``enable()`` uses; touches neither JAX nor the disk."""
    return os.environ.get(_ENV) or DEFAULT_DIR


def enable() -> str:
    """Switch the persistent cache on for this process, every program kept.
    Raises if the directory cannot be created: a run that silently compiles
    everything again is the failure this exists to prevent."""
    import jax
    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def entry_count() -> int:
    """Executables in the cache directory (0 when it does not exist)."""
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


#: programs the table keeps. An entry keeps its executables LOADED, and a
#: loaded program occupies device memory, given back when the entry goes:
#: on a v5e the 12 / 24 / 33 entries of the benchmark's three queries hold
#: 195 / 35 / 252 MB at rest (16.2 / 1.5 / 7.6 MB an entry) and each fresh
#: literal of TPC-H Q1's filter 9.4 MB more (PERF.md §6, PR 29). 128
#: entries are 2.1 GB at the heaviest of those means and 0.9 GB at the
#: mean of all three, inside the 4.4 GB of a 16 GB chip that the buffer
#: catalog's budget leaves alone (``memory.hbm.poolFraction``, ``reserve``),
#: with room for the three query shapes together (69). An evicted program
#: is rebuilt by the next exec that states its key, at the cost every
#: program had before the table.
PROGRAM_TABLE_ENTRIES = 128


class ProgramTable:
    """``(name, key, jit arguments)`` -> jitted callable, least recently
    stated first out: an ``OrderedDict`` and a lock, since the server runs
    sessions on threads. An entry is the jitted function alone: what it
    keeps alive is its closure (``jit_named``'s callers hand it functions
    over an exec's compile-time fields, never over the exec) and what JAX
    built from it, the executables loaded on the device among that."""

    def __init__(self, max_entries: int = PROGRAM_TABLE_ENTRIES):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Callable]" = OrderedDict()
        self._hits = self._misses = self._unkeyed = self._evictions = 0

    def get_or_build(self, key: Hashable, build: Callable[[], Callable]
                     ) -> Tuple[Callable, bool]:
        """The entry under ``key`` and whether the table had it; built by
        ``build()`` (cheap: it wraps, it does not trace) on a miss. Under
        the lock, so that two threads stating one key leave one entry and
        call one function."""
        with self._lock:
            fn = self._entries.get(key)
            hit = fn is not None
            if hit:
                self._entries.move_to_end(key)
                self._hits += 1
            else:
                fn = self._entries[key] = build()
                self._misses += 1
                while len(self._entries) > self.max_entries:
                    self._entries.popitem(last=False)
                    self._evictions += 1
        return fn, hit

    def note_unkeyed(self) -> None:
        with self._lock:
            self._unkeyed += 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries), "hits": self._hits,
                    "misses": self._misses, "unkeyed": self._unkeyed,
                    "evictions": self._evictions}

    def clear(self) -> None:
        """Forget every program (tests that count a first lowering)."""
        with self._lock:
            self._entries.clear()


_PROGRAMS = ProgramTable()


def program_table() -> ProgramTable:
    return _PROGRAMS
