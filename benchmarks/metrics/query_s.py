"""The window's seconds times its clients, over the queries completed in it:
seconds per query as a closed-loop user feels them, stalls between and
inside queries included. A failed query completes nothing and is charged its
whole timeout, however soon its error came."""


def read(run):
    if not run["done"] or run["window_s"] <= 0:
        return None
    charged = sum(max(run["timeout_s"] - r.seconds, 0.0)
                  for r in run["records"] if r.error is not None)
    return (run["window_s"] * run["clients"] + charged) / len(run["done"])
