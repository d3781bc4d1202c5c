"""Rows of every table each completed query scans (fixed per query by the
data), summed over all queries completed in the window, over the window's
seconds, first submit to last reply."""


def read(run):
    if not run["done"] or run["window_s"] <= 0:
        return None
    rows = sum(run["scanned_rows"][r.query] for r in run["done"])
    return rows / run["window_s"]
