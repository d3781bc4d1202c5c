"""The reduction from a profiler trace (``.xplane.pb``) to what the
per-layer metrics and the breakdown read. Reads the file with
``jax.profiler.ProfileData`` and nothing else; importing this module imports
no JAX, and reading a file initialises no backend.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, with a line of
program executions (``XLA Modules``) and a line of the operations inside
them (``XLA Ops``); the host's threads are lines of ``/host:CPU``; the
``Task Environment`` plane says when the profiler started and stopped, and
every event's time counts from that start. A CPU trace has no device plane:
it reduces to nothing.
"""

import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SESSION_PLANE = "Task Environment"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_trace(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path):
    """``{"devices": [{"ops": [...], "modules": [...]}], "host": {thread:
    [...]}, "extent_ns": n}``, every event ``(name, start_ns, duration_ns)``
    on the trace's one clock, and the trace's own length, profiler start to
    stop (None where the trace does not say). Every line of the host's
    plane is kept (``host_lines``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, extent = [], {}, None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += _events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] += _events(line)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            host = host_lines((line.name, _events(line))
                              for line in plane.lines)
        elif plane.name == SESSION_PLANE:
            stats = dict(plane.stats)
            if {"profile_start_time", "profile_stop_time"} <= set(stats):
                extent = float(stats["profile_stop_time"]
                               - stats["profile_start_time"])
    return {"devices": devices, "host": host, "extent_ns": extent}


def host_lines(lines):
    """``{key: events}`` of ``(name, events)`` pairs, none lost: a line
    keeps its name as its key, and where two threads share a name
    (``python``, or none at all) the later ones get ``name#2``,
    ``name#3``."""
    out, seen = {}, {}
    for name, events in lines:
        seen[name] = seen.get(name, 0) + 1
        out[name if seen[name] == 1 else f"{name}#{seen[name]}"] = events
    return out


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(trace):
    """What the metrics read. ``window_s`` is the trace's own length,
    profiler start to stop, so every operation counted lies inside it;
    ``busy_s`` is the union of the device's operation intervals, averaged
    over the devices; gaps are the idle stretches between them on device
    0."""
    devices = trace["devices"]
    if not devices or not any(d["ops"] for d in devices):
        return None
    if not trace["extent_ns"]:
        raise ValueError("the trace does not say when it started and "
                         "stopped")
    merged = [union((s, s + dur) for _, s, dur in d["ops"]) for d in devices]
    busy = [sum(e - s for s, e in m) for m in merged]
    by_name = {}
    for d in devices:
        for name, dur in _named_ops(d):
            by_name[name] = by_name.get(name, 0.0) + dur
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged[0], merged[0][1:])),
                  reverse=True)[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": trace["extent_ns"] / 1e9,
        "device_count": len(devices),
        "program_executions": sum(len(d["modules"]) for d in devices),
        "device_ops": [[n, s / 1e9] for n, s in top_ops],
        "idle_gaps": [[_covering(trace["host"], s, e), g / 1e9]
                      for g, s, e in gaps],
    }


def _named_ops(device):
    """``(program/op, duration)`` of every operation: the trace names an
    operation by its whole HLO line and a program execution by its name and
    fingerprint; the short forms are ``jit_f/sort.6``."""
    modules = sorted((s, s + d, n.split("(")[0])
                     for n, s, d in device["modules"])
    starts = [m[0] for m in modules]
    for name, s, dur in device["ops"]:
        short = name.split(" = ")[0].lstrip("%")
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1] and modules[i][2] != short:
            short = f"{modules[i][2]}/{short}"
        yield short, dur


def _covering(host, start, end):
    """What the host was doing in ``[start, end]``, by name: of the events
    that each cover over half of it, the innermost, which is the shortest
    (a pull holds its leaf; the leaf names the gap even where the gap began
    a little before it)."""
    best, best_dur = "unattributed", float("inf")
    for events in host.values():
        for name, s, dur in events:
            cover = min(end, s + dur) - max(start, s)
            if cover > 0.5 * (end - start) and dur < best_dur:
                best, best_dur = name, dur
    return best
