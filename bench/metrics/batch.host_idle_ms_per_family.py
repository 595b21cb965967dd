"""batch.host_idle_ms_per_family: ms in which the device was idle while
``gp.solve_batched`` built a family's first carry, dispatched a chunk and
read its latch back, copied retiring lanes or compacted the batch (spans
``gp.solve_batched.init``, ``.dispatch``, ``.retire``, ``.compact``), per
``gp.solve_batched`` call in the traced window (a request's first family
solve and each re-solve of its uncertified members count one each)."""

from bench.lib import phases

FAMILY = "gp.solve_batched"
PHASES = tuple(f"{FAMILY}.{p}" for p in ("init", "dispatch", "retire",
                                         "compact"))


def read(run):
    if run.trace is None:
        return None
    families = len(phases.host_spans(run.trace, FAMILY))
    if not families:
        return None
    spans = [s for name in PHASES for s in phases.host_spans(run.trace, name)]
    return 1e3 * phases.idle_within_s(run.trace, spans) / families
