"""batch.device_ms_per_lane_step: device busy ms of the traced window per
lane-step the batched driver executed in it (``gp.GPScan.lane_steps``):
the cost of one member's step at the kernels' padded size, with the
window's checks and initial carries spread over the steps.  Nothing where
the program reports no lane-steps."""

from bench.lib import xtrace


def read(run):
    lanes = sum(r.get("lane_steps") or 0 for r in run.records)
    if run.trace is None or not lanes:
        return None
    busy = xtrace.busy_s(run.trace, run.chips)
    return 1e3 * busy / lanes if busy > 0 else None
