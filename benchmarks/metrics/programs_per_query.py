"""Device program executions in the traced slice (events of the trace's
``XLA Modules`` line) over the queries completed in it."""


def read(run):
    if not run["trace"] or not run["slice"] or not run["slice"]["records"]:
        return None
    n = len([r for r in run["slice"]["records"] if r.error is None])
    if not n or not run["trace"]["program_executions"]:
        return None
    return run["trace"]["program_executions"] / n
