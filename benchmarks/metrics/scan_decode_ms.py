"""Mean per query of the summed ``scan.decode`` spans: thread-seconds of
file decode on the prefetch thread and the reader pool, not wall-clock."""

from rtbench.spantree import mean_ms, summed


def read(run):
    return mean_ms(run, lambda p: summed(p, ("scan.decode",)))
