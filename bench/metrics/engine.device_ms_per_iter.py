"""engine.device_ms_per_iter: device busy time of the traced window, in
ms, per GP member-iteration completed in it."""

from bench.lib import xtrace


def read(run):
    if run.trace is None or not run.iterations:
        return None
    busy = xtrace.busy_s(run.trace, run.chips)
    return 1e3 * busy / run.iterations if busy > 0 else None
