"""Every idle stretch of device 0 in a profiler trace (``xplane.load``), put
down to what the host was doing: ``attribute(trace)``. Where ``xplane.reduce``
names the ten longest gaps, this charges all of them, and the stretch from
the profiler's start to the first operation and from the last one to its
stop, so that the seconds it returns add up to the slice's idle time,
``window_s - busy_s`` of device 0.

A stretch goes whole to one name:

1. the first ``sync.<what>`` host event (the engine's ``trace.host_read``)
   that ends inside it: the device ran dry while the host waited on that
   read, and stays idle until the host has used the value and dispatched
   again;
2. else the name ``xplane._covering`` gives it: the innermost host event
   that covers over half of it;
3. else ``unattributed``.

Importing this module imports no JAX."""

import bisect
import heapq

from rtbench import xplane

SYNC = "sync."


def stretches(trace):
    """``(start_ns, end_ns)`` of device 0's idle stretches, in order: the
    gaps between its merged operation intervals, the lead from the trace's
    start and the tail to its stop. None where no operation ran."""
    devices = trace["devices"]
    if not devices or not devices[0]["ops"]:
        return None
    if not trace["extent_ns"]:
        raise ValueError("the trace does not say when it started and "
                         "stopped")
    out, at = [], 0.0
    for s, e in xplane.union((s, s + d) for _, s, d in devices[0]["ops"]):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if trace["extent_ns"] > at:
        out.append((at, trace["extent_ns"]))
    return out


def attribute(trace):
    """``{name: seconds}`` of every idle stretch of device 0 (see the module
    docstring); None where no operation ran on it."""
    idle = stretches(trace)
    if idle is None:
        return None
    host = trace["host"]
    reads = sorted((s + d, name) for events in host.values()
                   for name, s, d in events if name.startswith(SYNC))
    read_ends = [end for end, _ in reads]
    named = {}
    unread = []
    for a, b in idle:
        i = bisect.bisect_left(read_ends, a)
        if i < len(reads) and read_ends[i] <= b:
            named[(a, b)] = reads[i][1]
        else:
            unread.append((a, b))
    named.update(_covered(host, unread))
    out = {}
    for a, b in idle:
        name = named[(a, b)]
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def _covered(host, gaps):
    """``{gap: xplane._covering(host, *gap)}`` for many gaps at once. An
    event that covers over half of a gap holds the gap's midpoint, so each
    gap is asked of the few events alive at its midpoint alone, kept in
    the order ``host`` lists them (``_covering`` keeps the first of equally
    short ones)."""
    events = sorted((s, s + d, line, j, (name, s, d))
                    for line, evs in enumerate(host.values())
                    for j, (name, s, d) in enumerate(evs))
    lines = list(host)
    alive, k, out = [], 0, {}
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while k < len(events) and events[k][0] <= mid:
            heapq.heappush(alive, (events[k][1],) + events[k][2:])
            k += 1
        while alive and alive[0][0] < mid:
            heapq.heappop(alive)
        holding = {}
        for _, line, j, ev in sorted(alive, key=lambda x: x[1:3]):
            holding.setdefault(lines[line], []).append(ev)
        out[(a, b)] = xplane._covering(holding, a, b)
    return out
