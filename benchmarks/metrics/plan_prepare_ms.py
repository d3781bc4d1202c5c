"""Mean per query of the server's ``plan.prepare`` span."""

from rtbench.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "server", "plan.prepare")
