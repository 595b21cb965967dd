"""Online adaptation: the GP solver as a long-running service.

    PYTHONPATH=src python examples/online_adaptation.py

Demonstrates the paper's Section IV adaptivity claims through the online
service (``repro.serve.OnlineSolver``, DESIGN.md §16): one application's
rate jumps, then the whole network surges, the busiest link fails, and
the load falls back — each arrives as a typed event
(``repro.core.events``) and the service re-converges incrementally from
its live strategy instead of restarting.

Each event prints the service's :class:`EventReport` next to a cold
``gp.solve`` on the identical post-event instance: warm iterations vs
cold iterations, the per-app skip gate's solved/skipped split (the first
event re-solves ONE app and freezes the other two — their strategies are
provably still optimal), whether phi was repaired (topology events) and
whether the §15 Anderson window survived (small rate deltas).

The service's answer tracks the cold optimum; the headline numbers (cost
excess <= 1e-4, total iterations <= 0.5x cold over a 50-event trace) are
measured by ``benchmarks/online_bench.py``.
"""

import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import events, gp, network, traffic
from repro.serve import OnlineSolver

ALPHA, TOL = 0.1, 1e-4


def report(solver, rep, label):
    inst = solver.member(rep.member)
    cold = gp.solve(inst, alpha=ALPHA, tol=TOL, accel=True)
    print(f"{label:28s} cost {rep.cost:8.3f}  "
          f"iters {rep.iterations:3d} (cold {int(cold.iterations):3d})  "
          f"solved/skipped {rep.solved_apps}/{rep.skipped_apps}  "
          f"repaired={rep.repaired} kept_window={rep.kept_window}")
    # warm and cold runs may latch onto different near-stationary points;
    # the demo only checks the service never loses more than 1% (the
    # 50-event bench pins the one-sided excess at <= 1e-4)
    assert rep.cost <= cold.final_cost * 1.01, (
        f"online answer worse than cold: {rep.cost} vs {cold.final_cost}")


def main():
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=0.5)
    solver = OnlineSolver([inst], alpha=ALPHA, tol=TOL, accel=True)
    print(f"{'initial convergence':28s} cost {float(solver.costs()[0]):8.3f}  "
          f"iters {int(solver.cold_iters[0]):3d}")

    # event 1: one application's input rate jumps; at this load the other
    # two apps' residuals stay below the gate tolerance, so the service
    # re-solves a single app and freezes the rest
    rep = solver.process(events.RateScale(member=0, factor=1.8, app=0))
    report(solver, rep, "after app-0 surge (warm)")

    # event 2: the whole network surges (x2 is inside SMALL_RATE_WINDOW,
    # so the Anderson acceleration window survives the event)
    rep = solver.process(events.RateScale(member=0, factor=2.0))
    report(solver, rep, "after global surge (warm)")

    # event 3: the busiest link fails (topology -> phi repair)
    fl = traffic.flows(solver.member(0), solver.phi(0))
    F = np.asarray(fl.F)
    i, j = np.unravel_index(F.argmax(), F.shape)
    print(f"  -> failing busiest link ({i},{j}) carrying {F[i, j]:.2f} bit/s")
    rep = solver.process(events.LinkDown(member=0, i=int(i), j=int(j)))
    report(solver, rep, "after link failure (warm)")

    # event 4: rates fall back
    rep = solver.process(events.RateScale(member=0, factor=0.5))
    report(solver, rep, "after load returns (warm)")

    print(f"total event iterations: {solver.event_iters} "
          f"(initial cold solve: {int(solver.cold_iters[0])})")
    print("OK: the online service adapted to rate and topology changes, "
          "staying within 1% of the cold optimum at every step.")


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
