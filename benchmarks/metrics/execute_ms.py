"""Mean per query of the server's ``execute`` span: the physical plan run
and its result brought to the host."""

from rtbench.spans import mean_span_ms


def read(run):
    return mean_span_ms(run, "server", "execute")
