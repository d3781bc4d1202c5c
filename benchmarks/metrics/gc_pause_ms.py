"""Mean per query of the summed ``gc`` spans: full collections on a thread
that worked for the query. 0 where none ran."""

from rtbench.spantree import mean_ms, summed


def read(run):
    return mean_ms(run, lambda p: summed(p, ("gc",)))
