"""Mean per query of the summed ``scan.h2d`` spans: Arrow table to padded
device batch, conversion on the host and the transfer's enqueue."""

from rtbench.spantree import mean_ms, summed


def read(run):
    return mean_ms(run, lambda p: summed(p, ("scan.h2d",)))
