"""The slowest submit-to-reply of the window, over ALL its queries; a failed
or timed-out query counts as the timeout. Under twenty queries a window this
is what a nearest-rank 95th percentile reads, under its true name: it decides
no PR (PERF.md section 2)."""


def read(run):
    if not run["seconds"]:
        return None
    return max(run["seconds"])
