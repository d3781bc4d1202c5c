"""Mean per query of the ``jit.lower`` events: programs JAX lowered anew
inside the query, whatever became of the compile after (a cache load as a
rule)."""

from rtbench.spantree import counted, profiles


def read(run):
    counts = [counted(p, "jit.lower") for p in profiles(run)]
    if not counts:
        return None
    return sum(counts) / len(counts)
