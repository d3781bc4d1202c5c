"""Mean per query of the summed ``jit.trace``, ``jit.lower`` and
``jit.compile`` spans: host seconds spent tracing, lowering and compiling or
loading programs inside the query."""

from rtbench.spantree import JIT, mean_ms, summed


def read(run):
    return mean_ms(run, lambda p: summed(p, JIT))
