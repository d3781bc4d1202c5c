"""Mean per query of the spans' summed ``hostReadUs``, in milliseconds: the
time the host spent blocked on the device for a value (``trace.host_read``).
Nothing where no span of the window carries the counter: a program without
the door, or a query that read nothing."""

from rtbench import loader

COUNTER = "hostReadUs"


def read(run):
    us = loader.metric("join_candidates").counter_mean(run, COUNTER)
    return None if us is None else us / 1000.0
