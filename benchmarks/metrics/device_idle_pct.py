"""1 minus the union of the device's operation intervals over the traced
slice, as a percentage. Not read off a chip in the rehearsal: no number."""


def read(run):
    if not run["trace"] or run["peaks"] is None:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
