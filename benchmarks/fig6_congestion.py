"""Fig. 6: total cost vs exogenous input rate (Abilene).

Paper claim: GP's advantage grows quickly as the network becomes more
congested (the congestion-oblivious baselines blow up first).

The whole rate sweep is one batched scenario family — six Abilene
instances differing only in ``rate_scale`` — and now the iterative
baselines batch too: SPOC and LCOF run the same six-member family through
``scenarios.run_sweep(masks_fn=...)`` (their direction masks are pure jax,
vmapped over the padded batch), with a serial reference per solver for the
batched-vs-serial speedup report.
"""

from __future__ import annotations

from benchmarks.common import (
    bench_record, emit, result_row, save_json, speedup_report,
)
from repro.core import baselines, scenarios

SCALES = scenarios.FIG6_SCALES

SOLVERS = (("GP", None), *baselines.BASELINE_MASKS.items())


def main() -> dict:
    kw = dict(alpha=0.1, max_iters=300)
    sweeps, serials = {}, {}
    for solver, masks_fn in SOLVERS:
        # warm BOTH paths before timing (all six members share one shape,
        # but each solver's mask signature compiles its own programs)
        scenarios.run_sweep("fig6-congestion", masks_fn=masks_fn, **kw)
        scenarios.run_sweep_serial("fig6-congestion", masks_fn=masks_fn, **kw)
        sweeps[solver] = scenarios.run_sweep(
            "fig6-congestion", masks_fn=masks_fn, **kw)
        serials[solver] = scenarios.run_sweep_serial(
            "fig6-congestion", masks_fn=masks_fn, **kw)

    sweep = sweeps["GP"]
    curve = {}
    for i, sc in enumerate(sweep.scenarios):
        s = sc.meta["rate_scale"]
        row = {
            "GP": sweep.results[i].final_cost,
            "SPOC": sweeps["SPOC"].results[i].final_cost,
            "LCOF": sweeps["LCOF"].results[i].final_cost,
            "LPR-SC": baselines.lpr_sc(sc.instance).final_cost,
            "gp": result_row(sweep.results[i]),  # convergence history
        }
        curve[s] = row
        emit(f"fig6_rate{s}", sweep.seconds * 1e6 / len(SCALES),
             f"GP:{row['GP']:.2f}|SPOC:{row['SPOC']:.2f}|"
             f"LCOF:{row['LCOF']:.2f}|LPR:{row['LPR-SC']:.2f}")
    # claim: advantage ratio (best baseline / GP) grows with the rate
    ratios = [min(r["SPOC"], r["LCOF"], r["LPR-SC"]) / max(r["GP"], 1e-9)
              for r in curve.values()]
    grows = ratios[-1] > ratios[0]

    # warm-start chaining: solve the rate ladder sequentially, rate r_k
    # starting from r_{k-1}'s converged phi (scenarios.run_sweep_chained) —
    # the incremental-rate shortcut the ROADMAP flagged.  Compare against
    # the (already warm) serial GP reference above.
    chained = scenarios.run_sweep_chained("fig6-congestion", **kw)
    it_cold = sum(int(r.iterations) for r in serials["GP"].results)
    it_warm = sum(int(r.iterations) for r in chained.results)
    warm_start = {
        "chained_seconds": chained.seconds,
        "serial_seconds": serials["GP"].seconds,
        "chained_iters": it_warm,
        "serial_iters": it_cold,
        "iter_cut": 1 - it_warm / max(it_cold, 1),
        # signed: negative means the warm-started member landed LOWER
        "max_rel_cost_delta": max(
            (w.final_cost - c.final_cost) / max(abs(c.final_cost), 1e-9)
            for w, c in zip(chained.results, serials["GP"].results)),
    }
    bench_record("fig6", scenario="abilene-rates", V=11, solver="GP-chained",
                 seconds=chained.seconds, iters=it_warm, n=len(SCALES),
                 iters_cold=it_cold)
    emit("fig6_gp_chained", chained.seconds * 1e6,
         f"iters:{it_warm}|cold:{it_cold}|"
         f"iter_cut:{warm_start['iter_cut']:.0%}|"
         f"serial_s:{serials['GP'].seconds:.2f}")

    # accelerated batched GP over the same rate ladder (§15 layer): the
    # committed row pairs with GP-batched for the iters-reduction claim
    scenarios.run_sweep("fig6-congestion", accel=True, **kw)
    accel = scenarios.run_sweep("fig6-congestion", accel=True, **kw)
    it_plain = sum(int(r.iterations) for r in sweeps["GP"].results)
    it_accel = sum(int(r.iterations) for r in accel.results)
    bench_record("fig6", scenario="abilene-rates", V=11,
                 solver="GP-accel-batched", seconds=accel.seconds,
                 iters=it_accel, n=len(SCALES), plain_iters=it_plain)
    emit("fig6_gp_accel", accel.seconds * 1e6,
         f"iters:{it_accel}|plain:{it_plain}|"
         f"iter_cut:{1 - it_accel / max(it_plain, 1):.0%}")

    speedups = {}
    for solver, _ in SOLVERS:
        bat, ser = sweeps[solver], serials[solver]
        rel = max(
            abs(b.final_cost - s.final_cost) / max(abs(s.final_cost), 1e-9)
            for b, s in zip(bat.results, ser.results))
        speedups[solver] = {
            "batched_seconds": bat.seconds, "serial_seconds": ser.seconds,
            "speedup": ser.seconds / max(bat.seconds, 1e-9),
            "max_rel_cost_err": rel,
        }
        bench_record("fig6", scenario="abilene-rates", V=11,
                     solver=f"{solver}-batched", seconds=bat.seconds,
                     iters=sum(int(r.iterations) for r in bat.results),
                     n=len(SCALES),
                     speedup=round(speedups[solver]["speedup"], 3))
        bench_record("fig6", scenario="abilene-rates", V=11,
                     solver=f"{solver}-serial", seconds=ser.seconds,
                     iters=sum(int(r.iterations) for r in ser.results),
                     n=len(SCALES))
        emit(f"fig6_{solver.lower()}_speedup", bat.seconds * 1e6,
             speedup_report(ser.seconds, bat.seconds, len(SCALES)))

    save_json("fig6.json", {"curve": curve, "advantage_ratios": ratios,
                            "advantage_grows_with_congestion": grows,
                            "solver_speedups": speedups,
                            "warm_start": warm_start,
                            "accel": {"iters": it_accel,
                                      "plain_iters": it_plain,
                                      "seconds": accel.seconds}})
    emit("fig6_summary", 0.0,
         "ratios=" + "|".join(f"{r:.2f}" for r in ratios) + f" grows={grows}")
    return curve


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
