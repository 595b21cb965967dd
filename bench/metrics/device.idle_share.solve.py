"""device.idle_share.solve: 100 * (1 - busy / window) over the traced
window, busy being the union of device operation intervals."""

from bench.lib import xtrace


def read(run):
    if run.trace is None:
        return None
    busy = xtrace.busy_s(run.trace, run.chips)
    return 100.0 * (1.0 - busy / run.window_s) if busy > 0 else None
