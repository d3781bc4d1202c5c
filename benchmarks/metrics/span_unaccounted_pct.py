"""Self time of the structural spans ``query``, ``execute`` and
``plan.prepare`` over ``query``'s duration, mean over the queries, as a
percentage: the share of a query that no named leaf explains."""

from rtbench.spantree import profiles, self_us, summed

STRUCTURAL = ("query", "execute", "plan.prepare")


def read(run):
    shares = []
    for p in profiles(run):
        whole = summed(p, ("query",))
        if whole > 0:
            shares.append(100.0 * self_us(p, STRUCTURAL) / whole)
    if not shares:
        return None
    return sum(shares) / len(shares)
