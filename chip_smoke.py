"""Drive the GP solver's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # the sharded-fleet path on 4 chips

Phases run in order in this one process; any failed check exits non-zero.

  sw-queue    ``gp.solve`` on Table II sw-queue (V=100) until the Theorem-1
              sufficiency residual is at or below ``CERT_TOL``; final cost
              within 1e-4 (relative) of a ``solver="dense"`` solve, the plain
              per-stage ``jnp.linalg.solve`` reference.
  ensemble    ``scenarios.run_sweep("seed-ensemble")`` over 32 abilene seeds:
              every member finite, two members equal to their serial
              ``gp.solve`` within 1e-4.
  metro       metro-sw V=1000 on the "auto" (sparse) path for 20 iterations
              with a finite, non-increasing cost; metro-sw V=300 sparse
              against batched-LU, cost histories within 1e-4.  Source rates
              are scaled by ``METRO_RATE`` so that GP has work to do.
  online      the fig6 fleet of ``benchmarks/online_bench.py`` served by
              ``OnlineSolver`` over 5 random events: every served cost at
              most 1e-4 (relative, one-sided) above the cold optimum.

``--four-chips`` runs only the sharded path (``distributed.solve_sharded``)
and its single-device comparison: a 4-way app mesh on sw-queue and a 2x2
app x node mesh on metro-sw V=300, cost histories within 1e-4 of
``gp.solve``, and a strategy whose sharding spans four devices.

Each phase prints its wall time: a cold run with its compiles, not a
benchmark number.  The last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ALPHA = 0.1
# Certification tolerance of the sufficiency residual (absolute marginal
# excess; a float32 solve of sw-queue stalls near 3e-3 on CPU).
CERT_TOL = 1e-2
PARITY = 1e-4
# fixed-length runs: no early stop, so histories align entry for entry
FIXED = dict(alpha=ALPHA, patience=10**6, tol=0.0)
# Metro source rates are scaled by this factor: at the default rates the
# shortest-path start is already optimal and GP would have nothing to do.
METRO_RATE = 8.0
# distinct Pallas kernels one GP step must hold on each stage-solver path:
# LU factor + traffic and marginal chain solves; the two BSR chain solves
STEP_KERNELS = {"batched_lu": 3, "sparse": 2}


def _rel(a, b) -> float:
    import numpy as np
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-9)))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _metro(V: int):
    import dataclasses
    from repro.core import network

    inst = network.metro_instance("sw", V)
    return dataclasses.replace(inst, r=inst.r * METRO_RATE)


def _kernels_in_step(inst, solver: str) -> int:
    """Count the Pallas kernels (``tpu_custom_call``) in one compiled GP
    step of ``inst`` on the stage-solver path ``solver``."""
    import jax
    from repro.core import gp

    step = jax.jit(lambda i, p: gp.gp_step(i, p, ALPHA, solver=solver).cost)
    return step.lower(inst, gp.init_phi(inst)).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


def phase_sw_queue() -> None:
    from repro.core import conditions, gp, network, traffic

    inst = network.table_ii_instance("sw-queue")
    path = traffic.resolve_solver("auto", inst.V, inst)
    n_kernels = _kernels_in_step(inst, path)
    print(f"  stage solver (auto): {path}; tpu_custom_call in step: "
          f"{n_kernels}")
    _check(n_kernels >= STEP_KERNELS[path],
           f"sw-queue step holds {n_kernels} Pallas kernels")
    res = gp.solve(inst, alpha=ALPHA, tol=CERT_TOL, max_iters=1000,
                   accel=True)
    resid = float(conditions.sufficiency_residual(inst, res.phi))
    print(f"  auto: {res.iterations} iterations, cost {res.final_cost!r}, "
          f"sufficiency residual {resid!r}")
    _check(resid <= CERT_TOL, f"residual {resid} > {CERT_TOL}")
    ref = gp.solve(inst, alpha=ALPHA, tol=CERT_TOL, max_iters=1000,
                   accel=True, solver="dense")
    rel = abs(res.final_cost - ref.final_cost) / abs(ref.final_cost)
    print(f"  dense: {ref.iterations} iterations, cost {ref.final_cost!r}; "
          f"relative gap {rel!r}")
    _check(rel <= PARITY, f"sw-queue cost gap to dense {rel} > {PARITY}")


def phase_ensemble() -> None:
    import numpy as np
    from repro.core import gp, scenarios

    kw = dict(alpha=ALPHA, tol=1e-4, max_iters=400, accel=True)
    sweep = scenarios.run_sweep(
        "seed-ensemble", sweep_kwargs={"scenario": "abilene", "n_seeds": 32},
        **kw)
    costs = np.array([r.final_cost for r in sweep.results])
    print(f"  32 members in {sweep.n_batches} batch(es); costs "
          f"{float(costs.min())!r}..{float(costs.max())!r}")
    _check(bool(np.all(np.isfinite(costs))), "non-finite ensemble member")
    for i in (0, len(sweep.results) - 1):
        serial = gp.solve(sweep.scenarios[i].instance, **kw)
        rel = _rel(sweep.results[i].final_cost, serial.final_cost)
        print(f"  member {i}: batched {sweep.results[i].final_cost!r} vs "
              f"serial {serial.final_cost!r} (relative {rel!r})")
        _check(rel <= PARITY, f"ensemble member {i} gap {rel} > {PARITY}")


def phase_metro() -> None:
    import numpy as np
    from repro.core import gp, traffic

    big = _metro(1000)
    path = traffic.resolve_solver("auto", big.V, big)
    n_kernels = _kernels_in_step(big, path)
    print(f"  metro-sw V=1000 stage solver (auto): {path}; "
          f"tpu_custom_call in step: {n_kernels}")
    _check(n_kernels >= STEP_KERNELS[path],
           f"metro step holds {n_kernels} Pallas kernels")
    hist = np.asarray(gp.solve(big, max_iters=20, **FIXED).cost_history)
    print(f"  V=1000: {len(hist) - 1} iterations, cost {float(hist[0])!r} "
          f"-> {float(hist[-1])!r}")
    _check(len(hist) == 21 and bool(np.all(np.isfinite(hist))),
           "metro V=1000 history not 20 finite iterations")
    _check(bool(np.all(np.diff(hist) <= 0.0)), "metro V=1000 cost increased")

    mid = _metro(300)
    phi0 = gp.init_phi(mid)
    sparse = gp.solve(mid, phi0, max_iters=20, solver="sparse", **FIXED)
    dense = gp.solve(mid, phi0, max_iters=20, solver="batched_lu", **FIXED)
    rel = _rel(sparse.cost_history, dense.cost_history)
    print(f"  V=300: sparse vs batched_lu over {sparse.iterations} "
          f"iterations, max relative gap {rel!r}")
    _check(sparse.iterations == dense.iterations == 20,
           "metro V=300 runs stopped early")
    _check(rel <= PARITY, f"metro V=300 gap {rel} > {PARITY}")


def phase_online() -> None:
    from benchmarks.online_bench import run_trace
    from repro.core.scenarios import FIG6_SCALES

    out = run_trace(FIG6_SCALES, n_events=5, seed=0)
    worst = out["max_rel_dcost"]
    print(f"  5 events, {out['online_iters']} online iterations "
          f"(cold-accel {out['cold_iters']['cold-accel']}); worst "
          f"served-cost excess {worst!r}")
    _check(worst <= PARITY, f"served cost excess {worst} > {PARITY}")


def _phi_devices(run):
    """Call ``run()``; return its result and the number of devices holding
    the strategy that the mesh chunk program hands back (the solver itself
    gathers its final strategy to the host)."""
    from repro.core import distributed

    build, seen = distributed._chunk_program, set()

    def spy(*args):
        chunk = build(*args)

        def recorded(*xs):
            out = chunk(*xs)
            seen.update(out[0].sharding.device_set)
            return out
        return recorded

    distributed._chunk_program = spy
    try:
        return run(), len(seen)
    finally:
        distributed._chunk_program = build


def phase_four_chips() -> None:
    import jax
    from repro.core import compat, distributed, gp, network

    _check(len(jax.devices()) >= 4, "--four-chips needs four devices")
    cases = [
        ("sw-queue, 4-way app mesh", network.table_ii_instance("sw-queue"),
         (4,), ("stage",), {}),
        ("metro-sw V=300, 2x2 app x node mesh", _metro(300), (2, 2),
         ("stage", "node"),
         {"node_axis": "node"}),
    ]
    for label, inst, shape, axes, kw in cases:
        phi0 = gp.init_phi(inst)
        ref = gp.solve(inst, phi0, max_iters=20, **FIXED)
        mesh = compat.make_mesh(shape, axes)
        res, n_dev = _phi_devices(lambda: distributed.solve_sharded(
            inst, mesh, phi0=phi0, max_iters=20, **kw, **FIXED))
        rel = _rel(res.cost_history, ref.cost_history)
        print(f"  {label}: {res.iterations} iterations, max relative gap "
              f"{rel!r}, phi spans {n_dev} devices")
        _check(res.iterations == ref.iterations == 20,
               f"{label}: runs stopped early")
        _check(rel <= PARITY, f"{label}: gap {rel} > {PARITY}")
        _check(n_dev == 4, f"{label}: phi spans {n_dev} devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.join(here, "src"), here]
    from repro.runtime import use_compile_cache

    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **_: hits.append(1)
        if name == "/jax/compilation_cache/cache_hits" else None)
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {dev.device_kind} x{len(jax.devices())}")

    phases = ([("four-chips", phase_four_chips)] if args.four_chips else
              [("sw-queue", phase_sw_queue), ("ensemble", phase_ensemble),
               ("metro", phase_metro), ("online", phase_online)])
    for name, run in phases:
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        run()
        print(f"phase {name}: ok, {time.perf_counter() - t0!r} s wall "
              f"(cold run, compiles included)", flush=True)
    print(f"compile cache hits: {len(hits)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
