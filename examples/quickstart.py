"""Quickstart: solve a CEC service-chain instance with GP and inspect it.

    PYTHONPATH=src python examples/quickstart.py

Builds the paper's Abilene scenario, runs the distributed gradient-projection
algorithm (Algorithm 1), verifies the sufficiency optimality condition (6),
compares against the three baselines of Section V — then solves a 32-seed
ensemble of the same scenario in ONE batched call via the scenario engine.
"""

import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import baselines, conditions, gp, network, scenarios, traffic


def main():
    # the paper's Abilene scenario (Table II), moderately congested
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    print(f"network: |V|={inst.V} |E|={int(np.asarray(inst.adj).sum())} "
          f"|A|={inst.A} stages={inst.A * inst.K1}")

    res = gp.solve(inst, alpha=0.1, max_iters=400)
    print(f"GP: cost {res.final_cost:.3f} after {res.iterations} iterations")
    print(f"    sufficiency residual {float(conditions.sufficiency_residual(inst, res.phi)):.2e}"
          f"  (0 => provably global optimum, Theorem 1)")

    for name, fn in baselines.ALL_BASELINES.items():
        b = fn(inst) if name == "LPR-SC" else fn(inst, alpha=0.1, max_iters=250)
        print(f"{name:7s}: cost {b.final_cost:10.3f} "
              f"(GP is {b.final_cost / res.final_cost:5.2f}x better)")

    # where did computation land?
    fl = traffic.flows(inst, res.phi)
    G = np.asarray(fl.G)
    caps = np.asarray(inst.comp_param)
    print("\nper-node CPU load (workload / capacity):")
    for i in range(inst.V):
        bar = "#" * int(30 * G[i] / caps[i])
        print(f"  node {i:2d}: {G[i]:6.2f} / {caps[i]:5.2f} {bar}")

    # the batched scenario engine: a 32-seed ensemble of the same scenario,
    # padded into one pytree and solved by a single vmapped device program
    print("\n32-seed ensemble (one batched call):")
    sweep = scenarios.run_sweep(
        "seed-ensemble",
        sweep_kwargs={"scenario": "abilene", "n_seeds": 32, "rate_scale": 2.0},
        alpha=0.1, max_iters=250,
    )
    costs = np.array([r.final_cost for r in sweep.results])
    iters = np.array([r.iterations for r in sweep.results])
    print(f"  solved {len(costs)} seeds in {sweep.seconds:.2f}s "
          f"({sweep.n_batches} device program{'s' if sweep.n_batches > 1 else ''})")
    print(f"  cost  mean {costs.mean():.3f}  std {costs.std():.3f}  "
          f"min {costs.min():.3f}  max {costs.max():.3f}")
    print(f"  iters mean {iters.mean():.0f}  max {int(iters.max())}")


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
