"""Mean per query of the summed ``plan.materialize`` spans: the planner's
questions about partition counts, with the exchanges (and all below them)
that had to run to answer. Microseconds where nothing had to run; 0 where
the planner asked nothing."""

from rtbench.spantree import mean_ms, summed


def read(run):
    return mean_ms(run, lambda p: summed(p, ("plan.materialize",)))
