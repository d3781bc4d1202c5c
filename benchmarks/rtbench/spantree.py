"""Shared by the per-layer readers of the engine's span tree: the server's
profile of each completed query (``record.trace["profiles"]``, component
``server``), worked on as span dicts alone; nothing of the engine is
imported.

A profile says which tracer wrote it (``tracer``: 2 from the one that scopes
operator spans to their pulls, follows work onto other threads and listens
to JAX and the collector). Under an older tracer "no such span" does not
mean "nothing of the kind happened", so the readers here return nothing for
it and never 0."""

TRACER = 2
JIT = ("jit.trace", "jit.lower", "jit.compile")


def profiles(run, component="server"):
    """One profile a completed query that was traced by ``TRACER``."""
    out = []
    for r in run["done"]:
        if not r.trace:
            continue
        for p in r.trace["profiles"]:
            if p.get("component") == component \
                    and p.get("tracer", 1) >= TRACER:
                out.append(p)
                break
    return out


def mean_ms(run, per_profile):
    """Mean over the window's completed, traced queries of
    ``per_profile(profile)`` in microseconds, as milliseconds; None where
    no query carries such a profile."""
    values = [per_profile(p) for p in profiles(run)]
    if not values:
        return None
    return sum(values) / len(values) / 1000.0


def summed(profile, names):
    """Microseconds of the spans called one of ``names``, with what the
    span cap kept only as a count (``overflow``). A span inside another of
    ``names`` is the outer one's time already."""
    spans = profile["spans"]
    name_of = {s["id"]: s["name"] for s in spans}
    us = sum(s["durUs"] for s in spans if s["name"] in names
             and name_of.get(s["parent"]) not in names)
    over = profile.get("overflow") or {}
    return us + sum(over[n][1] for n in names if n in over)


def counted(profile, name):
    over = profile.get("overflow") or {}
    return sum(1 for s in profile["spans"] if s["name"] == name) \
        + (over[name][0] if name in over else 0)


def inside_us(span):
    """An operator holds its thread inside its pulls (``pullUs``); any
    other span from open to close."""
    if span.get("kind") == "operator":
        return (span.get("attrs") or {}).get("pullUs", 0)
    return span["durUs"]


def self_us(profile, names):
    """Microseconds of the spans called one of ``names`` that no child on
    the same thread explains."""
    spans = profile["spans"]
    own = {s["id"]: inside_us(s) for s in spans if s["name"] in names}
    tid = {s["id"]: s.get("tid") for s in spans}
    for s in spans:
        p = s["parent"]
        if p in own and tid[p] == s.get("tid"):
            own[p] -= inside_us(s)
    return sum(max(v, 0) for v in own.values())
