"""Mean per query of the server's ``admission.wait`` span."""

from rtbench.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "server", "admission.wait")
