"""solver.solves_per_cert: ``gp.solve`` calls in the traced window (its
host spans) per instance certified."""

from bench.lib import phases


def read(run):
    if run.trace is None or not run.certified:
        return None
    solves = len(phases.host_spans(run.trace, phases.SOLVE))
    return solves / run.certified if solves else None
