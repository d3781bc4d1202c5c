"""Process start to first timed submit: tables, Parquet, server start,
device check, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run["setup_s"]
