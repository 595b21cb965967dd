"""kernels.chain_solve_ms_per_step: device ms of the fused chain-solve
kernel (``%chain_solve``), every call at every call site inside the scan
loop, per executed scan step.  The eager calls outside the loop (a solve's
initial carry, the program's check) are not counted
(``bench/lib/phases.py``)."""

from bench.lib import phases


def read(run):
    if run.trace is None:
        return None
    steps = phases.executed_steps(run.trace)
    if not steps:
        return None
    return 1e3 * phases.kernel_s(run.trace, phases.CHAIN_SOLVE) / steps
