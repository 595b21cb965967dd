"""engine.ladder_ms_per_step: device ms of the stepsize ladder per executed
scan step, frozen steps included: the union of the intervals of the
ladder's kernel calls, the LU factor and the chain solve vmapped over its
rungs.  Nothing where no call site stands out as the ladder's
(``bench/lib/phases.py``)."""

from bench.lib import phases


def read(run):
    if run.trace is None:
        return None
    steps = phases.executed_steps(run.trace)
    return 1e3 * phases.ladder_s(run.trace) / steps if steps else None
