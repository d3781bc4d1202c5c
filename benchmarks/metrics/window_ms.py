"""Mean per query of the OWN time of the ``KeyBatchingExec`` and
``WindowExec`` operator spans: what a query's windows cost on the host's
clock, their children's pulls taken out (the aggregate below a window is
not the window's). Nothing where no query of the window has such a span: a
program without them, or a plan with no window."""

from rtbench.spantree import counted, mean_ms, profiles, self_us

SPANS = ("KeyBatchingExec", "WindowExec")


def read(run):
    if not any(counted(p, n) for p in profiles(run) for n in SPANS):
        return None
    return mean_ms(run, lambda p: self_us(p, SPANS))
