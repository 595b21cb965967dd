"""Online-service benchmark: OnlineSolver vs per-event cold solves.

    PYTHONPATH=src python benchmarks/online_bench.py [--smoke] [--events N]

Drives a scripted event trace (rates, failures, arrivals — DESIGN.md §16)
over the fig6 fleet (abilene at the FIG6_SCALES rate ladder) and measures
the service's incremental re-convergence against two cold baselines solved
per event on the identical post-event instances:

  * ``cold-accel`` — ``gp.solve(..., accel=True)``: the §15-accelerated
    cold restart, the honest baseline (same solver configuration the
    service itself uses);
  * ``cold-plain`` — ``gp.solve(...)`` without acceleration, the legacy
    restart-from-scratch reference.

Asserts the paper-level claims the service is sold on (hard failures, not
just recorded numbers):

  * **cost parity** — no post-event online cost exceeds the cold-accel
    optimum by more than 1e-4 (relative); events where the warm start
    lands *below* the cold answer (cold ground into its iteration cap)
    are counted separately as ``n_online_better``;
  * **iteration cut** — total online iterations <= 0.5x the cold-accel
    total (warm starts + skip gates do real work);
  * **skip gate** — at least one event is skipped outright (0 iterations)
    or solves a strict subset of the member's live apps.

Rows land in BENCH_gp.json keyed (online, fig6-trace{N}, 11, *): the
``online`` solver row carries total seconds/iters plus the iteration ratio
and worst parity; the two cold rows carry their own totals so future PRs
can diff all three trajectories.

``--chaos`` runs the §17 fault-tolerance leg instead: a seeded
``faults.chaos_trace`` (flapping, destination-area node bursts,
over-capacity surges, event storms) with a ``faults.FaultInjector``
corrupting solver state at the solve boundary, under ``debug=True`` so the
runtime invariant checker screens every event.  Asserts survival — every
member ends feasible and finite, and no served cost ever exceeds the
member's last-known-good incumbent on the current instance — and records a
(online, chaos-trace{N}, 11, online-chaos) row with degradation-ladder hit
counts, status tallies, injection/quarantine counts.

``--trace-out PREFIX`` arms the §19 observability layer on either leg:
the solver runs with device telemetry + a metrics registry + a span
tracer, and four artifacts land next to PREFIX —

  * ``PREFIX.trace.json``   — Chrome-trace/perfetto span timeline
  * ``PREFIX.events.jsonl`` — one line per HealthReport
  * ``PREFIX.iters.jsonl``  — per-iteration device telemetry records
  * ``PREFIX.metrics.json`` — counters/gauges/histograms snapshot

``python -m repro.obs.report --trace PREFIX`` turns them into the
per-member timeline + fleet summary (and ``--check-bench`` cross-checks
the event iteration totals against the committed BENCH_gp.json row).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")

import numpy as np

from benchmarks.common import bench_record, save_json
from repro import obs
from repro.core import events, faults, gp, network
from repro.core.scenarios import FIG6_SCALES
from repro.serve.online import OnlineSolver

ALPHA, TOL = 0.1, 1e-4
# LKG bound used by the chaos assertions: the service's own rollback
# margin (serve/online.py default) plus float32 re-costing headroom.
LKG_MARGIN = 2e-4


def _obs_kit(trace_out: str | None):
    """(solver kwargs, metrics, tracer) for ``--trace-out`` — all empty/None
    when tracing is off so the measured path stays exactly the shipped one."""
    if not trace_out:
        return {}, None, None
    metrics, tracer = obs.Metrics(), obs.Tracer()
    return dict(telemetry=True, metrics=metrics, tracer=tracer), metrics, tracer


def _jsonable(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def export_obs(prefix: str, solver: OnlineSolver, reports, metrics,
               tracer) -> dict:
    """Write the four ``--trace-out`` artifacts; returns {name: path}."""
    d = os.path.dirname(os.path.abspath(prefix))
    os.makedirs(d, exist_ok=True)
    obs.collect_compile_caches(metrics)
    paths = {"trace": prefix + ".trace.json",
             "events": prefix + ".events.jsonl",
             "iters": prefix + ".iters.jsonl",
             "metrics": prefix + ".metrics.json"}
    tracer.export_chrome(paths["trace"],
                         tid_names={b: f"member-{b}"
                                    for b in range(solver.B)})
    with open(paths["events"], "w") as f:
        for t, r in enumerate(reports):
            row = {fld.name: getattr(r, fld.name)
                   for fld in dataclasses.fields(r) if fld.name != "event"}
            row["t"] = t
            row["event"] = type(r.event).__name__
            f.write(json.dumps(row, default=_jsonable) + "\n")
    with open(paths["iters"], "w") as f:
        for rec in solver.iter_trace:
            f.write(json.dumps(rec, default=_jsonable) + "\n")
    metrics.export_json(paths["metrics"])
    return paths


def run_trace(scales, n_events: int, seed: int, spare_apps: int = 2,
              trace_out: str | None = None) -> dict:
    insts = [network.table_ii_instance("abilene", seed=seed, rate_scale=s)
             for s in scales]
    members = events.pad_fleet(insts, spare_apps=spare_apps)
    trace = events.random_trace(members, n_events=n_events, seed=seed)
    snaps = events.replay(members, trace)

    # --- online service ---
    obs_kw, metrics, tracer = _obs_kit(trace_out)
    solver = OnlineSolver(insts, spare_apps=spare_apps, alpha=ALPHA, tol=TOL,
                          accel=True, **obs_kw)
    t0 = time.perf_counter()
    reports = solver.step(trace)
    online_s = time.perf_counter() - t0
    online_iters = solver.event_iters

    # --- cold baselines on the identical post-event instances ---
    cold = {"cold-accel": dict(accel=True), "cold-plain": dict(accel=None)}
    cold_res, cold_s, cold_iters = {}, {}, {}
    for name, kw in cold.items():
        t0 = time.perf_counter()
        res = [gp.solve(inst, alpha=ALPHA, tol=TOL, **kw)
               for _ev, inst, _eff in snaps]
        cold_s[name] = time.perf_counter() - t0
        cold_res[name] = res
        cold_iters[name] = sum(r.iterations for r in res)

    # --- the three claims ---
    # parity is one-sided: the online cost must never EXCEED the cold
    # optimum by more than the tolerance.  Landing *below* cold is a win,
    # not a violation — on heavy members the cold baseline can grind into
    # its iteration cap while the warm start descends past it.
    signed = np.array([
        (rep.cost - ref.final_cost) / max(1.0, abs(ref.final_cost))
        for rep, ref in zip(reports, cold_res["cold-accel"])])
    parity = np.maximum(signed, 0.0)
    ratio = online_iters / max(cold_iters["cold-accel"], 1)
    gate_hits = sum(1 for r in reports
                    if r.iterations == 0 or r.skipped_apps > 0)
    per_event = [
        {"t": t, "event": type(r.event).__name__, "member": r.member,
         "iters": r.iterations,
         "cold_accel_iters": cold_res["cold-accel"][t].iterations,
         "cold_plain_iters": cold_res["cold-plain"][t].iterations,
         "cost": r.cost, "rel_dcost": float(signed[t]),
         "solved": r.solved_apps, "skipped": r.skipped_apps,
         "cold_restart": r.cold_restart, "kept_window": r.kept_window}
        for t, r in enumerate(reports)]
    trace_files = (export_obs(trace_out, solver, reports, metrics, tracer)
                   if trace_out else None)
    return {
        "trace_files": trace_files,
        "n_events": n_events, "seed": seed, "scales": list(scales),
        "online_s": online_s, "online_iters": online_iters,
        "cold_s": cold_s, "cold_iters": cold_iters,
        "max_rel_dcost": float(parity.max()),
        "n_online_better": int((signed < -1e-4).sum()),
        "iter_ratio": float(ratio), "gate_hits": gate_hits,
        "per_event": per_event,
    }


def run_chaos(scales, n_events: int, seed: int, spare_apps: int = 2,
              trace_out: str | None = None) -> dict:
    """The §17 survival leg: chaos trace + fault injection + debug checks."""
    insts = [network.table_ii_instance("abilene", seed=seed, rate_scale=s)
             for s in scales]
    members = events.pad_fleet(insts, spare_apps=spare_apps)
    steps = faults.chaos_trace(members, n_events=n_events, seed=seed)
    obs_kw, metrics, tracer = _obs_kit(trace_out)
    injector = faults.FaultInjector(seed=seed + 1, p_inject=0.15,
                                    metrics=metrics)
    solver = OnlineSolver(insts, spare_apps=spare_apps, alpha=ALPHA, tol=TOL,
                          accel=True, debug=True, fault_injector=injector,
                          **obs_kw)

    t0 = time.perf_counter()
    reports = []
    for batch in steps:
        reports.extend(solver.step(batch))
    chaos_s = time.perf_counter() - t0

    # --- survival claims (hard failures, not recorded numbers) ---
    # 1. every member's final served strategy is feasible and finite
    final = solver.verify_fleet()
    for h in final:
        assert not h.corrupt, f"member {h.member} ends corrupt: {h}"
        assert np.isfinite(h.cost), f"member {h.member} ends non-finite"
    # 2. no served cost ever exceeded the member's last-known-good
    #    incumbent re-costed on the SAME post-event instance ("rejected"
    #    means nothing finite existed, incumbent included — nothing to bound)
    for t, r in enumerate(reports):
        if r.status == "rejected" or not np.isfinite(r.incumbent_cost):
            continue
        assert r.cost <= r.incumbent_cost * (1 + LKG_MARGIN), (
            f"event {t}: served {r.cost} above incumbent {r.incumbent_cost}")

    statuses: dict[str, int] = {}
    for r in reports:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    n_events_run = len(reports)
    trace_files = (export_obs(trace_out, solver, reports, metrics, tracer)
                   if trace_out else None)
    return {
        "trace_files": trace_files,
        "n_events": n_events_run, "n_steps": len(steps), "seed": seed,
        "scales": list(scales), "chaos_s": chaos_s,
        "online_iters": solver.event_iters,
        "statuses": statuses,
        "ladder_hits": dict(solver.ladder_hits),
        "injections": len(injector.log),
        "injected_members": sorted({i.member for i in injector.log}),
        "quarantines": solver.quarantines,
        "rollbacks": sum(1 for r in reports if r.rolled_back),
        "shed_apps": sum(len(r.shed) for r in reports),
        "final_costs": [h.cost for h in final],
        "final_slack": [h.capacity_slack for h in final],
    }


def chaos_main(args) -> dict:
    scales = FIG6_SCALES[:3] if args.smoke else FIG6_SCALES
    n_events = 30 if args.smoke else args.events
    out = run_chaos(scales, n_events, args.seed, trace_out=args.trace_out)

    label = f"chaos-trace{n_events}"
    bench_record("online", scenario=label, V=11, solver="online-chaos",
                 seconds=out["chaos_s"], iters=out["online_iters"],
                 events=out["n_events"], members=len(scales),
                 statuses=out["statuses"], ladder_hits=out["ladder_hits"],
                 injections=out["injections"],
                 quarantines=out["quarantines"],
                 rollbacks=out["rollbacks"], shed_apps=out["shed_apps"])
    save_json(f"online_{label}.json", out)

    print(f"chaos: events={out['n_events']} steps={out['n_steps']} "
          f"members={len(scales)} seed={args.seed}")
    print(f"online:      {out['online_iters']:5d} iters  "
          f"{out['chaos_s']:.2f}s")
    print(f"statuses:    {out['statuses']}")
    print(f"ladder hits: {out['ladder_hits'] or '(none needed)'}")
    print(f"injections:  {out['injections']} "
          f"(members {out['injected_members']}), "
          f"quarantines: {out['quarantines']}, "
          f"rollbacks: {out['rollbacks']}, shed: {out['shed_apps']}")
    print("OK: all members end feasible+finite; "
          "served costs never exceeded the LKG incumbent")
    if out["trace_files"]:
        print("trace artifacts: "
              + " ".join(sorted(out["trace_files"].values())))
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small trace (10 events, 3 members) for CI")
    ap.add_argument("--chaos", action="store_true",
                    help="run the §17 chaos/fault-injection survival leg")
    ap.add_argument("--trace-out", default=None, metavar="PREFIX",
                    help="write §19 observability artifacts "
                         "(PREFIX.trace.json, .events.jsonl, .iters.jsonl, "
                         ".metrics.json)")
    args = ap.parse_args(argv)
    if args.chaos:
        if args.events == 50:
            args.events = 100       # chaos default: the 100-event criterion
        return chaos_main(args)

    scales = FIG6_SCALES[:3] if args.smoke else FIG6_SCALES
    n_events = 10 if args.smoke else args.events
    out = run_trace(scales, n_events, args.seed, trace_out=args.trace_out)

    label = f"fig6-trace{n_events}"
    bench_record("online", scenario=label, V=11, solver="online",
                 seconds=out["online_s"], iters=out["online_iters"],
                 events=n_events, members=len(scales),
                 iter_ratio=round(out["iter_ratio"], 4),
                 max_rel_dcost=out["max_rel_dcost"],
                 gate_hits=out["gate_hits"])
    for name in ("cold-accel", "cold-plain"):
        bench_record("online", scenario=label, V=11, solver=name,
                     seconds=out["cold_s"][name],
                     iters=out["cold_iters"][name], events=n_events,
                     members=len(scales))
    save_json(f"online_{label}.json", out)

    print(f"events={n_events} members={len(scales)} seed={args.seed}")
    print(f"online:      {out['online_iters']:5d} iters  "
          f"{out['online_s']:.2f}s")
    for name in ("cold-accel", "cold-plain"):
        print(f"{name}:  {out['cold_iters'][name]:5d} iters  "
              f"{out['cold_s'][name]:.2f}s")
    print(f"iter ratio (online/cold-accel): {out['iter_ratio']:.3f}")
    print(f"max relative cost excess:       {out['max_rel_dcost']:.2e}")
    print(f"events online beat cold by >1e-4: {out['n_online_better']}")
    print(f"skip-gate hits:                 {out['gate_hits']}/{n_events}")

    # the <=0.5x iteration-cut claim is defined on the 50-event trace; a
    # 10-event smoke trace is too short to amortize warm-up events, so CI
    # smoke only sanity-checks that warm starts never LOSE to cold
    ratio_cap = 1.0 if args.smoke else 0.5
    assert out["max_rel_dcost"] <= 1e-4, (
        f"cost parity broken: {out['max_rel_dcost']:.2e} > 1e-4")
    assert out["iter_ratio"] <= ratio_cap, (
        f"iteration cut missed: {out['iter_ratio']:.3f} > {ratio_cap}")
    assert out["gate_hits"] > 0, "skip gate never fired"
    print(f"OK: parity <= 1e-4, iters <= {ratio_cap}x cold-accel, "
          "skip gate active")
    if out["trace_files"]:
        print("trace artifacts: "
              + " ".join(sorted(out["trace_files"].values())))
    return out


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
