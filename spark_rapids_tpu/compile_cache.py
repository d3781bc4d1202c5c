"""The process's persistent XLA compile cache — one directory, chosen from
outside.

A cold query compiles every program it runs, and the TPU compiler takes
seconds to minutes for each (tools/aot_compile.py). Every entry point that
owns a device — ``python -m spark_rapids_tpu.server`` (and so each router
worker, which is that command), ``bench.py``, ``chip_smoke.py`` — calls
``enable()`` before its first compile, so that they share what they build.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX itself reads it and nothing
here names another directory; otherwise the cache is
``<checkout>/.jax_compilation_cache``. The path is part of the cache key's
environment: no temp dir, pid or timestamp in it, or nothing ever hits.
"""

from __future__ import annotations

import os

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
