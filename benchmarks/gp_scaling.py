"""GP algorithm scaling: per-iteration wall time vs network/application
count (complexity table of Section IV), the shard_map variant, and the
batched scenario engine's per-member iteration cost vs batch size."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, bench_record, emit, save_json, speedup_report
from repro.core import batch, compat, distributed, gp, network, scenarios


def time_gp_iteration(inst, reps: int = 5, solver: str = "auto") -> float:
    phi = gp.init_phi(inst)
    state = gp._jit_step(inst, phi, 0.05, None, None, False, solver)  # warm
    with Timer() as t:
        for _ in range(reps):
            state = gp._jit_step(inst, state.phi, 0.05, None, None, False,
                                 solver)
        jax.block_until_ready(state.phi.e)
    return t.us / reps


def time_batched_iteration(name: str, B: int, chunk: int = 32) -> float:
    """us per iteration per member of the device-resident batched scan."""
    insts = [network.table_ii_instance(name, seed=s, rate_scale=2.0)
             for s in range(B)]
    binst = batch.pad_instances(insts)
    phi = jax.vmap(gp.init_phi)(binst)
    carry = jax.vmap(gp._init_carry)(binst, phi)
    args = (jnp.float32(0.05), jnp.float32(1e-4), jnp.int32(10**6),
            jnp.int32(10**6), None, None)
    carry, _ = gp._scan_chunk_batched(binst, carry, *args, length=chunk)  # warm
    jax.block_until_ready(carry.cost)
    with Timer() as t:
        carry, _ = gp._scan_chunk_batched(binst, carry, *args, length=chunk)
        jax.block_until_ready(carry.cost)
    return t.us / chunk / B


# Metro-scale leg (DESIGN.md §18): per-iteration cost of the sparse
# neighbor-list solve path vs the dense batched-LU path on O(V)-edge metro
# graphs.  Above _DENSE_MAX_V the dense path is not measured — a V=1000
# dense iteration factors (ladder * A * K1) 1000^3 LUs per step, which this
# box cannot complete in a sane budget — and is recorded as an explicit
# status="timeout" row at the budget wall clock instead (the honest
# "dense is not viable here" data point the scale claim rests on).
_METRO_VS = (300, 600, 1000)
_DENSE_MAX_V = 600
_DENSE_BUDGET_S = 600.0


def metro_leg(rows: dict, *, smoke: bool = False) -> None:
    """Sparse-vs-dense per-iteration rows on metro-scale graphs.

    ``smoke`` runs only the V=60 small-world point (the CI sparse bench
    row); the full leg covers V in ``_METRO_VS`` on both metro builders.
    The V=60 point is always included so the committed baseline carries a
    pair for the CI smoke run to gate against.
    """
    specs = [("sw", 60)]
    if not smoke:
        specs += [(t, v) for t in ("sw", "geant") for v in _METRO_VS]
    out = {}
    for topo, V in specs:
        inst = network.metro_instance(topo, V)
        E = network.n_edges(inst)
        reps = 3 if V <= 300 else 1
        us_sparse = time_gp_iteration(inst, reps=reps, solver="sparse")
        row = {"V": V, "edges": E, "sparse_us": us_sparse}
        extra = {}
        if V <= _DENSE_MAX_V:
            us_dense = time_gp_iteration(network.without_sparse(inst),
                                         reps=reps, solver="batched_lu")
            row["dense_us"] = us_dense
            row["speedup"] = us_dense / max(us_sparse, 1e-9)
            extra["speedup"] = round(row["speedup"], 3)
            bench_record("gp_scaling", scenario=f"metro-{topo}", V=V,
                         solver="batched_lu", seconds=us_dense / 1e6,
                         iters=1)
        else:
            row["dense_us"] = None
            bench_record("gp_scaling", scenario=f"metro-{topo}", V=V,
                         solver="batched_lu", seconds=_DENSE_BUDGET_S,
                         status="timeout")
        bench_record("gp_scaling", scenario=f"metro-{topo}", V=V,
                     solver="sparse", seconds=us_sparse / 1e6, iters=1,
                     edges=E, **extra)
        dense_str = ("timeout" if row["dense_us"] is None
                     else f"{row['dense_us']:.0f}us")
        emit(f"gp_metro_{topo}_V{V}", us_sparse, f"E:{E}|dense:{dense_str}")
        out[f"{topo}-{V}"] = row
    rows["metro"] = out


def main(argv=()):
    # argv defaults to () — NOT sys.argv — because benchmarks/run.py calls
    # mod.main() programmatically with run.py's own flags still on sys.argv.
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.gp_scaling")
    ap.add_argument("--sparse", action="store_true",
                    help="run only the metro-scale sparse-vs-dense leg")
    ap.add_argument("--smoke", action="store_true",
                    help="with --sparse: only the V=60 CI smoke point")
    args = ap.parse_args(list(argv))
    if args.sparse:
        rows = {}
        metro_leg(rows, smoke=args.smoke)
        save_json("gp_metro.json", rows)
        return

    rows = {}
    for name in ["abilene", "balanced-tree", "fog", "geant", "sw-queue"]:
        inst = network.table_ii_instance(name, seed=0)
        us = time_gp_iteration(inst)
        rows[name] = {"V": inst.V, "A": inst.A, "S": inst.A * inst.K1,
                      "us_per_iter": us}
        emit(f"gp_iter_{name}", us, f"V:{inst.V}|stages:{inst.A * inst.K1}")

    # stage-solver comparison: the batched-LU kernel path (shared
    # factorization, kernels/batched_solve.py) vs the seed's per-stage
    # dense solves, across node counts.  "auto" picks dense below
    # traffic.AUTO_MIN_V on CPU — these rows are where that threshold
    # comes from (DESIGN.md §12).
    solver_rows = {}
    for name in ("connected-er", "geant", "sw-queue"):
        inst = network.table_ii_instance(name, seed=0, rate_scale=1.5)
        us_dense = time_gp_iteration(inst, reps=3, solver="dense")
        us_lu = time_gp_iteration(inst, reps=3, solver="batched_lu")
        solver_rows[name] = {"V": inst.V, "dense_us": us_dense,
                             "batched_lu_us": us_lu,
                             "speedup": us_dense / max(us_lu, 1e-9)}
        emit(f"gp_iter_solver_{name}", us_lu,
             f"V:{inst.V}|dense:{us_dense:.0f}us|"
             f"speedup:{us_dense / max(us_lu, 1e-9):.2f}x")
        bench_record("gp_scaling", scenario=name, V=inst.V,
                     solver="batched_lu", seconds=us_lu / 1e6, iters=1,
                     speedup=round(us_dense / max(us_lu, 1e-9), 3))
        bench_record("gp_scaling", scenario=name, V=inst.V,
                     solver="dense", seconds=us_dense / 1e6, iters=1)
    rows["stage_solver"] = solver_rows

    # batched engine: per-member iteration cost vs batch size (the
    # vectorization win the scenario layer exploits)
    batched = {}
    for B in (1, 8, 32):
        us = time_batched_iteration("abilene", B)
        batched[B] = us
        emit(f"gp_batched_iter_B{B}", us, f"us_per_iter_per_member|V:11")
    rows["batched_abilene"] = {str(b): u for b, u in batched.items()}

    # end-to-end ensemble: batched engine vs one-at-a-time (warm)
    kw = dict(alpha=0.1, max_iters=200)
    skw = {"n_seeds": 16}
    scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw, **kw)          # warm
    scenarios.run_sweep_serial("seed-ensemble", sweep_kwargs={"n_seeds": 2}, **kw)
    bat = scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw, **kw)
    ser = scenarios.run_sweep_serial("seed-ensemble", sweep_kwargs=skw, **kw)
    emit("gp_ensemble16_speedup", bat.seconds * 1e6,
         speedup_report(ser.seconds, bat.seconds, 16))
    rows["ensemble16"] = {"batched_s": bat.seconds, "serial_s": ser.seconds,
                          "speedup": ser.seconds / max(bat.seconds, 1e-9)}

    # shard_map distributed GP on the unified step engine (however many host
    # devices are present; the collective pattern is what the multi-device
    # CI job exercises).  Warm once so the row measures solving, not XLA.
    inst = network.table_ii_instance("abilene", seed=0)
    n_sh = min(len(jax.devices()), 2)
    mesh = compat.make_mesh((n_sh,), ("stage",))
    skw_sh = dict(alpha=0.05, max_iters=30, patience=10**6, tol=0.0)
    distributed.solve_sharded(inst, mesh, **skw_sh)                    # warm
    with Timer() as t:
        res = distributed.solve_sharded(inst, mesh, **skw_sh)
    emit("gp_sharded_30iters", t.us,
         f"shards:{n_sh}|final_cost:{float(res.cost_history[-1]):.3f}")
    bench_record("gp_scaling", scenario="abilene-sharded", V=inst.V,
                 solver=f"sharded-fused{n_sh}", seconds=t.seconds,
                 iters=int(res.iterations))
    rows["sharded"] = {"shards": n_sh, "seconds": t.seconds,
                       "iters": int(res.iterations)}

    # mesh-composed ensemble: the batch axis vmapped INSIDE each app shard
    # (scenarios.run_sweep(mesh=...), vmap-of-shard_map — DESIGN.md §14)
    skw8 = {"n_seeds": 8}
    scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw8, mesh=mesh, **kw)  # warm
    msweep = scenarios.run_sweep("seed-ensemble", sweep_kwargs=skw8,
                                 mesh=mesh, **kw)
    m_iters = sum(int(r.iterations) for r in msweep.results)
    emit("gp_ensemble8_mesh", msweep.seconds * 1e6,
         f"shards:{n_sh}|iters:{m_iters}|n:8")
    bench_record("gp_scaling", scenario="ensemble8-mesh", V=11,
                 solver=f"sharded-vmap{n_sh}", seconds=msweep.seconds,
                 iters=m_iters, n=8)
    rows["ensemble8_mesh"] = {"shards": n_sh, "seconds": msweep.seconds,
                              "iters": m_iters}
    save_json("gp_scaling.json", rows)


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    import sys

    main(sys.argv[1:])
