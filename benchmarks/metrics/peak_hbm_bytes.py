"""``stats().server.device.peakBytesInUse`` after the window (from
``memory_stats()``)."""


def read(run):
    return run["device_after"].get("peakBytesInUse")
