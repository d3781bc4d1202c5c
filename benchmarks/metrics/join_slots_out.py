"""Mean per query of the operator spans' summed ``joinSlotsOut``: the slots
(capacities, padding included; known on the host from the one candidate
total a probe reads) of the pair batches the query's hash joins made. A
pair batch is sized at the bucket of its candidates, so slots over
candidates says what the padding costs; an exact-probe semi join makes
none. Nothing where no span of the window carries the counter: a program
without it, or a plan with no hash join."""

from rtbench import loader

COUNTER = "joinSlotsOut"


def read(run):
    return loader.metric("join_candidates").counter_mean(run, COUNTER)
