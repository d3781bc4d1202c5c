"""Entries of the replies' ``fell_back``, summed over the window: operators
the planner left on the CPU."""


def read(run):
    if not run["done"]:
        return None
    return sum(len(r.fell_back) for r in run["done"])
