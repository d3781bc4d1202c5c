"""Executables in the compile cache's directory after the window minus
before (``compile_cache.entry_count()``): programs compiled inside the
window. 0 is the expectation."""


def read(run):
    return run["entries_after"] - run["entries_before"]
