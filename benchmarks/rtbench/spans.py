"""Shared by the per-layer readers of spans: the spans of one traced query,
by component (``PlanClient.last_trace()``: the client's leg first, then the
server's flight-recorded profile)."""


def spans(record, component, name):
    """Durations in microseconds of the spans called ``name`` in the
    record's profile of ``component``; empty where tracing was off."""
    if not record.trace:
        return []
    return [s["durUs"] for p in record.trace["profiles"]
            if p.get("component") == component
            for s in p["spans"] if s["name"] == name]


def mean_ms_per_query(run, per_record):
    """Mean over the window's completed queries of ``per_record(r)`` in
    microseconds, as milliseconds; None where no query carries spans."""
    values = [v for v in (per_record(r) for r in run["done"])
              if v is not None]
    if not values:
        return None
    return sum(values) / len(values) / 1000.0


def mean_span_ms(run, component, name):
    """Mean per query of the summed spans called ``name``."""
    def one(r):
        s = spans(r, component, name)
        return sum(s) if s else None
    return mean_ms_per_query(run, one)
