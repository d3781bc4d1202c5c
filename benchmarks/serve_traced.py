"""The traced run's server: the same ``spark_rapids_tpu.server.server.main()``
a deployment runs, in a process that also starts and stops ``jax.profiler``
when the client asks. Only the process that holds the chip can trace it, and
the server has no op that starts a profiler; this file adds nothing else.

The client asks through files in ``--control``: it creates ``start`` (or
``stop``), a thread here acts and answers with an empty ``start.done``
(``stop.done``). Each trace lands under ``<control>/trace``. The Python
tracer is off: it would record every call of the engine's host code and slow
what is measured.
"""

import argparse
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLL_S = 0.01


def _watch(control):
    import jax
    trace_dir = os.path.join(control, "trace")
    tracing = False
    while True:
        for verb in ("start", "stop"):
            ask = os.path.join(control, verb)
            if not os.path.exists(ask):
                continue
            os.remove(ask)
            if verb == "start" and not tracing:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            if verb == "stop" and tracing:
                jax.profiler.stop_trace()
                tracing = False
            with open(ask + ".done", "w"):
                pass
        time.sleep(POLL_S)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--control", required=True)
    args, rest = p.parse_known_args(argv)
    os.makedirs(args.control, exist_ok=True)
    sys.path.insert(0, REPO)
    from spark_rapids_tpu.server import server
    threading.Thread(target=_watch, args=(args.control,), daemon=True,
                     name="trace-control").start()
    return server.main(rest)


if __name__ == "__main__":
    sys.exit(main())
