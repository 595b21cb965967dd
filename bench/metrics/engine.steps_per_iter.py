"""engine.steps_per_iter: scan steps the device executed (one call of the
ladder's chain solve each) per GP iteration committed in the window
(``GPResult.iterations``).  Above 1 is work the scan did after a solve's
stop latch froze its carry, to the end of the chunk.  Nothing where no call
site stands out as the ladder's (``bench/lib/phases.py``)."""

from bench.lib import phases


def read(run):
    if run.trace is None or not run.iterations:
        return None
    steps = phases.executed_steps(run.trace)
    return steps / run.iterations if steps else None
