"""Mean per query of the operator spans' summed ``expandSlotsOut``: the
slots (capacities, padding included; known on the host from shapes) of the
batches the query's ``ExpandExec`` handed to the aggregate above it, which
pays for slots, not rows. Nothing where no span of the window carries the
counter: a program without it, or a plan with no Expand."""

from rtbench.spantree import profiles

COUNTER = "expandSlotsOut"


def read(run):
    totals = []
    for p in profiles(run):
        seen = [(s.get("attrs") or {}).get(COUNTER) for s in p["spans"]]
        seen = [v for v in seen if v is not None]
        if seen:
            totals.append(sum(seen))
    if not totals:
        return None
    return sum(totals) / len(totals)
