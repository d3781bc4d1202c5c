"""Mean per query of the operator spans' summed ``joinCandidates``: the
candidate pairs the query's hash joins found, probe batch by probe batch
(the total each probe's count program returns, which the host reads anyway
to size the pair batch). A self-join of orders makes many times its input;
an exact-probe semi join counts its matches and expands none. Nothing where
no span of the window carries the counter: a program without it, or a plan
with no hash join.

``expansion_bytes`` is the least an expansion streams, for the roofline
share of ``jit_HashJoinExec_expand`` that a per-program device time will
make possible (PERF.md, Open questions): every candidate pair's row read
once, every kept pair's row written once."""

from rtbench.spantree import profiles

COUNTER = "joinCandidates"


def counter_mean(run, counter):
    """The operator spans' ``counter`` summed a query, averaged over the
    queries whose spans carry it; None where none does."""
    totals = []
    for p in profiles(run):
        seen = [(s.get("attrs") or {}).get(counter) for s in p["spans"]]
        seen = [v for v in seen if v is not None]
        if seen:
            totals.append(sum(seen))
    if not totals:
        return None
    return sum(totals) / len(totals)


def read(run):
    return counter_mean(run, COUNTER)


def expansion_bytes(candidates, kept, pair_row_bytes, out_row_bytes):
    """Bytes a pair expansion has to move at the least: ``candidates``
    pairs of ``pair_row_bytes`` (the probe's and the build's columns at the
    configuration's widths, ``rtbench.touched.column_bytes``) read, and
    ``kept`` pairs of ``out_row_bytes`` written."""
    return candidates * pair_row_bytes + kept * out_row_bytes
