"""Mean per query of the summed ``scan.h2d.decimal`` spans: the decimal
columns of a scanned batch from Arrow's 16-byte values to padded device
columns (a child of ``scan.h2d``). Nothing where no query of the window has
such a span: a program without it, or a query that reads no decimal."""

from rtbench.spantree import counted, mean_ms, profiles, summed

SPAN = "scan.h2d.decimal"


def read(run):
    if not any(counted(p, SPAN) for p in profiles(run)):
        return None
    return mean_ms(run, lambda p: summed(p, (SPAN,)))
