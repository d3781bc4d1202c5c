"""Mean per query of the operator spans' summed ``dec128Bytes``: the device
bytes of the decimal128 limb matrices the query's operators emitted (32
bytes a value in the int64[rows, 4] layout, padding included; known on the
host from shapes). Nothing where no span of the window carries the counter:
a program without it, or a query with no decimal past 18 digits."""

from rtbench.spantree import profiles

COUNTER = "dec128Bytes"


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
