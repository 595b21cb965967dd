"""certs_per_s: instances certified over the whole window, per second of
it (a family of 32 counts 32).  An instance counts when the plain
reference finds the sufficiency residual of the strategy its solve
returned at or below the configuration's tolerance.  The window runs until
the request in flight at ``--seconds`` has returned, and all of its time
counts."""

from bench.lib.stats import rate


def read(run):
    return rate(run.certified, run.window_s)
