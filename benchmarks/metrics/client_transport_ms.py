"""Mean per query of the client's ``client.collect`` span minus the server's
``query`` span: serialisation, the socket both ways and the reply's decode."""

from rtbench.spans import mean_ms_per_query, spans


def read(run):
    def one(r):
        outer, inner = spans(r, "client", "client.collect"), \
            spans(r, "server", "query")
        if not outer or not inner:
            return None
        return outer[0] - inner[0]
    return mean_ms_per_query(run, one)
