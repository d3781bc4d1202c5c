"""The least seconds to stream once, at the chip's peak HBM bytes a second,
the bytes of the columns the slice's queries read (``rtbench/touched.py``),
over the device-busy seconds of the slice, as a percentage. Bandwidth bounds
it: these queries do a few operations a byte. It counts the same work
whatever implements it. Nothing to read off a chip: no peak, no number."""


def read(run):
    if not run["trace"] or not run["slice"] or run["peaks"] is None:
        return None
    done = [r for r in run["slice"]["records"] if r.error is None]
    busy = run["trace"]["busy_s"]
    if not done or busy <= 0:
        return None
    least = sum(run["touched_bytes"][r.query] for r in done) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / busy
