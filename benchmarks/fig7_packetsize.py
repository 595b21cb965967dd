"""Fig. 7: average hop count of data vs result packets, as a function of
the input packet size L_(a,0).

Paper claim: when input packets are larger (relative to results), GP
offloads computation closer to the requester — data packets travel fewer
hops, result packets more.

The L0 sweep runs as one batched scenario family (``fig7-packetsize``).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import bench_record, emit, save_json, speedup_report
from repro.core import scenarios, traffic

L0_VALUES = scenarios.FIG7_L0


def hop_counts(inst, phi) -> tuple[float, float]:
    """Average hops traveled by a data packet (stage 0) and a result packet
    (final stage), flow-weighted: total link crossings / packets injected."""
    fl = traffic.flows(inst, phi)
    f = np.asarray(fl.f)                 # (A,K1,V,V)
    r_tot = float(np.asarray(inst.r).sum())
    data_hops = f[:, 0].sum() / max(r_tot, 1e-9)
    last = np.asarray(inst.n_tasks)
    res_hops = sum(f[a, int(last[a])].sum() for a in range(inst.A)) / max(r_tot, 1e-9)
    return float(data_hops), float(res_hops)


def main() -> dict:
    kw = dict(alpha=0.1, max_iters=300)
    cold = scenarios.run_sweep("fig7-packetsize", **kw)       # compiles
    scenarios.run_sweep_serial("fig7-packetsize", **kw)       # warm serial too
    sweep = scenarios.run_sweep("fig7-packetsize", **kw)      # warm timing
    serial = scenarios.run_sweep_serial("fig7-packetsize", **kw)

    out = {}
    for sc, res in zip(sweep.scenarios, sweep.results):
        L0 = sc.meta["L0"]
        dh, rh = hop_counts(sc.instance, res.phi)
        out[L0] = {"data_hops": dh, "result_hops": rh, "cost": res.final_cost}
        emit(f"fig7_L0_{L0}", 0.0, f"data_hops:{dh:.2f}|result_hops:{rh:.2f}")
    # claim: data hop count decreases as L0 grows (offload near requester)
    dhs = [out[L]["data_hops"] for L in L0_VALUES]
    monotone_trend = dhs[-1] < dhs[0]
    save_json("fig7.json", {"curve": out, "data_hops_shrink": monotone_trend,
                            "gp_batched_seconds_warm": sweep.seconds,
                            "gp_batched_seconds_cold": cold.seconds,
                            "gp_serial_seconds": serial.seconds})
    emit("fig7_summary", 0.0,
         "data_hops=" + "|".join(f"{d:.2f}" for d in dhs) + f" shrink={monotone_trend}")
    emit("fig7_gp_speedup", sweep.seconds * 1e6,
         speedup_report(serial.seconds, sweep.seconds, len(L0_VALUES)))
    for solver, sw, it in (("GP-batched", sweep, sweep.results),
                           ("GP-serial", serial, serial.results)):
        bench_record("fig7", scenario="abilene-L0", V=11, solver=solver,
                     seconds=sw.seconds, n=len(L0_VALUES),
                     iters=sum(int(r.iterations) for r in it))
    return out


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
