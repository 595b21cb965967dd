"""solver.host_idle_ms_per_solve: ms in which the device was idle while
``gp.solve`` built its first carry, dispatched a chunk or trimmed its
result (spans ``gp.solve.init``, ``.dispatch``, ``.trim``), per
``gp.solve`` call in the traced window."""

from bench.lib import phases


def read(run):
    if run.trace is None:
        return None
    solves = len(phases.host_spans(run.trace, phases.SOLVE))
    if not solves:
        return None
    spans = [s for name in phases.HOST_IDLE_PHASES
             for s in phases.host_spans(run.trace, name)]
    return 1e3 * phases.idle_within_s(run.trace, spans) / solves
