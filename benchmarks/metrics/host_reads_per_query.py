"""Mean per query of the spans' summed ``hostReads``: the reads of a device
value by the host (``trace.host_read``), each of which waits for everything
queued on the device before it. Nothing where no span of the window carries
the counter: a program without the door, or a query that read nothing."""

from rtbench import loader

COUNTER = "hostReads"


def read(run):
    return loader.metric("join_candidates").counter_mean(run, COUNTER)
