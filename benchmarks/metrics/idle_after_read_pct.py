"""The share of device 0's idle time in the traced slice that follows a host
read: the idle stretches that ``rtbench/idle.attribute`` puts down to a
``sync.<what>`` event (``trace.host_read``), over all of them, as a
percentage. Read from the reduced trace's ``idle_by_host``; nothing where it
has none (a rehearsal, a reduction without it) or where no stretch is put
down to a read (a program without the door)."""

from rtbench.idle import SYNC


def read(run):
    by_host = (run["trace"] or {}).get("idle_by_host")
    if not by_host:
        return None
    after_read = [s for name, s in by_host.items() if name.startswith(SYNC)]
    total = sum(by_host.values())
    if not after_read or total <= 0:
        return None
    return 100.0 * sum(after_read) / total
