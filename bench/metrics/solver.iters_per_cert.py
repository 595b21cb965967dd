"""solver.iters_per_cert: GP iterations committed in the window (member
iterations for a family) per instance certified (``GPResult.iterations``)."""


def read(run):
    return run.iterations / run.certified if run.certified else None
