"""Fig. 5: normalized total cost across the Table II network scenarios,
GP vs SPOC / LCOF / LPR-SC — GP *and* the iterative baselines run as
batched scenario families.

Paper claims to validate:
  * GP achieves the lowest cost in every scenario,
  * up to ~50% improvement over LPR-SC (the joint-optimization baseline),
  * the advantage is larger with queueing (congestion-aware) costs
    (SW-queue vs SW-linear).

Engine claims to validate (this repo's batched scenario engine):
  * batched family solves reproduce per-scenario serial costs — for GP and
    for the mask-restricted SPOC/LCOF baselines (``baselines.spoc_masks`` /
    ``lcof_masks`` threaded through ``scenarios.run_sweep``),
  * per-solver batched-vs-serial wall clock is measured honestly (both
    paths fully warmed) and recorded to BENCH_gp.json.  Note the two
    regimes: *homogeneous* families (seed ensembles, fig6/fig7 sweeps —
    identical member shapes) win 3-5x batched, while the *heterogeneous*
    Table II six pays envelope padding (V and A inflate to each group's
    max) and only LCOF's cheap restricted solves still come out ahead —
    exactly the padding trade-off DESIGN.md §9 / run_sweep's size-class
    grouping predicts.
"""

from __future__ import annotations

from benchmarks.common import (
    Timer, bench_record, emit, save_json, speedup_report,
)
from repro.core import baselines, network, scenarios

GP_ITERS = 250
ENSEMBLE_SEEDS = 32

# (solver label, masks_fn) for the three iterative solvers; masks_fn=None
# is unrestricted GP (baselines are direction-mask restrictions, §11).
SOLVERS = (("GP", None), *baselines.BASELINE_MASKS.items())


def run_fig5(iters: int = GP_ITERS) -> dict:
    """All Table II scenarios: GP, SPOC and LCOF each as one batched
    scenario family (per cost-kind/size-class group); LPR-SC stays serial
    (a single closed-form shortest-path evaluation per scenario)."""
    family = scenarios.expand("fig5")
    sweeps = {}
    seconds = {}
    for solver, masks_fn in SOLVERS:
        with Timer() as t:
            sweeps[solver] = scenarios.run_sweep(
                family, masks_fn=masks_fn, alpha=0.1, max_iters=iters)
        seconds[solver] = t.seconds
    table = {}
    for i, sc in enumerate(family):
        out = {solver: sweeps[solver].results[i].final_cost
               for solver, _ in SOLVERS}
        out["gp_iters"] = int(sweeps["GP"].results[i].iterations)
        out["LPR-SC"] = baselines.lpr_sc(sc.instance).final_cost
        worst = max(out[k] for k in ("GP", "SPOC", "LCOF", "LPR-SC"))
        out["normalized"] = {k: out[k] / worst for k in ("GP", "SPOC", "LCOF", "LPR-SC")}
        table[sc.label] = out
        emit(f"fig5_{sc.label}_GP", seconds["GP"] * 1e6 / len(family),
             "norm=" + "|".join(f"{k}:{v:.3f}" for k, v in out["normalized"].items()))
    return {"table": table, "batched_seconds": seconds,
            "gp_batches": sweeps["GP"].n_batches}


def run_baseline_speedup(iters: int = GP_ITERS) -> dict:
    """Batched-vs-serial wall clock for GP, SPOC and LCOF on the small
    Table II six (one padded batch per solver; the V=100 small-world pair
    is excluded so the serial reference stays minutes, not hours).

    Both paths solve exactly the same restricted problems — serial goes
    through ``run_sweep_serial(masks_fn=...)`` (apples-to-apples).  Rows
    land in BENCH_gp.json (bench="fig5", scenario="small6").  Speedups
    here can be < 1 for GP/SPOC: the six topologies pad to per-group
    (V, A) envelopes, so this row pairs with the homogeneous-ensemble row
    (speedup ~4x) as the two ends of the batching trade-off.
    """
    small = [sc for sc in scenarios.expand("fig5")
             if sc.label in scenarios.SMALL_TABLE_II]
    vmax = max(sc.instance.V for sc in small)
    kw = dict(alpha=0.1, max_iters=iters)
    out = {}
    for solver, masks_fn in SOLVERS:
        # warm both paths: steady-state solving, not XLA compilation.  The
        # serial warm-up must cover the FULL family — gp.solve jit-caches
        # per instance shape and the six topologies all differ, so warming
        # one member would leave five compiles inside the timed window.
        scenarios.run_sweep(small, masks_fn=masks_fn, **kw)
        scenarios.run_sweep_serial(small, masks_fn=masks_fn, **kw)
        batched = scenarios.run_sweep(small, masks_fn=masks_fn, **kw)
        serial = scenarios.run_sweep_serial(small, masks_fn=masks_fn, **kw)
        rel_errs = [
            abs(b.final_cost - s.final_cost) / max(abs(s.final_cost), 1e-9)
            for b, s in zip(batched.results, serial.results)
        ]
        speedup = serial.seconds / max(batched.seconds, 1e-9)
        out[solver] = {
            "batched_seconds": batched.seconds,
            "serial_seconds": serial.seconds,
            "speedup": speedup,
            "max_rel_cost_err": max(rel_errs),
        }
        bench_record("fig5", scenario="small6", V=vmax,
                     solver=f"{solver}-batched", seconds=batched.seconds,
                     iters=sum(int(r.iterations) for r in batched.results),
                     n=len(small), speedup=round(speedup, 3))
        bench_record("fig5", scenario="small6", V=vmax,
                     solver=f"{solver}-serial", seconds=serial.seconds,
                     iters=sum(int(r.iterations) for r in serial.results),
                     n=len(small))
        emit(f"fig5_{solver.lower()}_speedup", batched.seconds * 1e6,
             speedup_report(serial.seconds, batched.seconds, len(small)))
    return out


def run_sw_warmstart(iters: int = GP_ITERS) -> dict:
    """Incremental-rate warm start on the congested V=100 small-world pair.

    The fig5 table solves sw-linear / sw-queue cold at their target rate for
    ``iters`` iterations each — the dominant wall-clock of the whole driver.
    Here each member instead climbs a two-rung rate ladder (half rate, then
    target) with ``scenarios.run_sweep_chained`` threading phi between
    rungs, and we report the target-rate iteration/wall-clock cut vs the
    cold solve (both warmed; rows land in BENCH_gp.json).
    """
    out = {}
    kw = dict(alpha=0.1, max_iters=iters)
    for name in ("sw-linear", "sw-queue"):
        rate = scenarios.FIG5_RATE[name]
        rungs = [
            scenarios.Scenario(
                label=f"{name}@x{s:g}",
                instance=network.table_ii_instance(
                    name, seed=0, rate_scale=s * rate),
                meta={"table_ii": name, "rate_scale": s * rate})
            for s in (0.5, 1.0)
        ]
        # warm the (single) program shape, then time cold vs chained
        scenarios.run_sweep_serial(rungs[-1:], **kw)
        with Timer() as t:
            cold = scenarios.run_sweep_serial(rungs[-1:], **kw)
        t_cold = t.seconds
        with Timer() as t:
            warm = scenarios.run_sweep_chained(rungs, **kw)
        t_warm = t.seconds
        it_cold = int(cold.results[0].iterations)
        it_target = int(warm.results[-1].iterations)
        it_total = sum(int(r.iterations) for r in warm.results)
        rel = ((warm.results[-1].final_cost - cold.results[0].final_cost)
               / max(abs(cold.results[0].final_cost), 1e-9))
        out[name] = {
            "cold_seconds": t_cold, "chained_seconds": t_warm,
            "cold_iters": it_cold, "target_iters": it_target,
            "chained_iters_total": it_total,
            "rel_cost_delta": rel,       # negative: warm landed lower
        }
        bench_record("fig5", scenario=f"{name}-warmstart", V=100,
                     solver="GP-chained", seconds=t_warm, iters=it_total,
                     target_iters=it_target, cold_iters=it_cold)
        bench_record("fig5", scenario=f"{name}-warmstart", V=100,
                     solver="GP-cold", seconds=t_cold, iters=it_cold)
        emit(f"fig5_{name}_warmstart", t_warm * 1e6,
             f"target_iters:{it_target}|cold_iters:{it_cold}|"
             f"cold_s:{t_cold:.1f}|rel_cost_delta:{rel:+.2e}")
    return out


def run_ensemble_speedup(n_seeds: int = ENSEMBLE_SEEDS, iters: int = GP_ITERS) -> dict:
    """Batched-vs-serial wall clock on the seed-ensemble sweep (warm)."""
    kw = dict(alpha=0.1, max_iters=iters)
    skw = {"n_seeds": n_seeds}
    # warm both paths so the comparison measures steady-state solving, not
    # XLA compilation (the batched path compiles one program per compaction
    # bucket size, the serial path one chunk program)
    scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw, **kw)
    scenarios.run_sweep_serial("seed-ensemble", sweep_kwargs={"n_seeds": 2}, **kw)

    batched = scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw, **kw)
    serial = scenarios.run_sweep_serial("seed-ensemble", sweep_kwargs=skw, **kw)
    rel_errs = [
        abs(b.final_cost - s.final_cost) / max(s.final_cost, 1e-9)
        for b, s in zip(batched.results, serial.results)
    ]
    ens = {
        "n_seeds": n_seeds,
        "batched_seconds": batched.seconds,
        "serial_seconds": serial.seconds,
        "speedup": serial.seconds / max(batched.seconds, 1e-9),
        "max_rel_cost_err": max(rel_errs),
        "costs": [r.final_cost for r in batched.results],
    }
    bench_record("fig5", scenario=f"abilene-ensemble{n_seeds}", V=11,
                 solver="GP-batched", seconds=batched.seconds,
                 iters=sum(int(r.iterations) for r in batched.results),
                 n=n_seeds, speedup=round(ens["speedup"], 3))

    # the same family under the §15 acceleration layer (Anderson mixing +
    # adaptive stepsize + residual stopping): same final costs, fewer
    # committed iterations — the iters gate holds this row to the claim
    scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw, accel=True, **kw)
    accel = scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw,
                                accel=True, **kw)
    it_plain = sum(int(r.iterations) for r in batched.results)
    it_accel = sum(int(r.iterations) for r in accel.results)
    ens["accel"] = {
        "seconds": accel.seconds,
        "iters": it_accel, "plain_iters": it_plain,
        "iter_cut": 1 - it_accel / max(it_plain, 1),
        "max_rel_cost_delta": max(
            (a.final_cost - b.final_cost) / max(abs(b.final_cost), 1e-9)
            for a, b in zip(accel.results, batched.results)),
    }
    bench_record("fig5", scenario=f"abilene-ensemble{n_seeds}", V=11,
                 solver="GP-accel-batched", seconds=accel.seconds,
                 iters=it_accel, n=n_seeds, plain_iters=it_plain)
    emit("fig5_ensemble_accel", accel.seconds * 1e6,
         f"iters:{it_accel}|plain:{it_plain}|"
         f"iter_cut:{ens['accel']['iter_cut']:.0%}")
    return ens


def main() -> dict:
    fig5 = run_fig5()
    table = fig5["table"]
    # paper-claim checks (0.5% tolerance: linear-cost scenarios tie exactly
    # at the shortest-path optimum, which IS the global optimum there)
    ok_best = all(
        t["normalized"]["GP"] <= 1.005 * min(
            t["normalized"][k] for k in ("SPOC", "LCOF", "LPR-SC"))
        for t in table.values())
    gain_lpr = max(1 - t["normalized"]["GP"] / max(t["normalized"]["LPR-SC"], 1e-9)
                   for t in table.values())
    sw_gap_queue = 1 - table["sw-queue"]["normalized"]["GP"]
    sw_gap_linear = 1 - table["sw-linear"]["normalized"]["GP"]

    baseline_speedups = run_baseline_speedup()
    ensemble = run_ensemble_speedup()
    warmstart = run_sw_warmstart()
    summary = {
        "sw_warmstart": warmstart,
        "gp_best_everywhere": ok_best,
        "max_gain_vs_lpr_sc": gain_lpr,
        "sw_queue_gain": sw_gap_queue,
        "sw_linear_gain": sw_gap_linear,
        "queue_gain_exceeds_linear": sw_gap_queue >= sw_gap_linear,
        "baseline_speedups": baseline_speedups,
        "ensemble": ensemble,
    }
    save_json("fig5.json", {"table": table, "summary": summary})
    emit("fig5_summary", 0.0,
         f"gp_best={ok_best} max_gain_vs_LPR={gain_lpr:.2f} "
         f"queue>{sw_gap_linear:.2f}linear={summary['queue_gain_exceeds_linear']}")
    emit("fig5_ensemble_speedup", ensemble["batched_seconds"] * 1e6,
         speedup_report(ensemble["serial_seconds"], ensemble["batched_seconds"],
                        ensemble["n_seeds"]))
    return {"table": table, "summary": summary}


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
