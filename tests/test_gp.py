"""Algorithm 1 behaviour: descent, convergence, loop-freedom, adaptivity,
and dominance over the Section V baselines."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines, conditions, gp, network, traffic
from tests.helpers import random_loopfree_phi, small_instances


def test_descent_is_monotone():
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    res = gp.solve(inst, alpha=0.1, max_iters=150)
    hist = np.asarray(res.cost_history)
    assert np.all(np.diff(hist) <= 1e-4 * np.maximum(hist[:-1], 1.0))


@pytest.mark.parametrize("scenario", ["abilene", "balanced-tree"])
def test_converges_to_sufficiency(scenario):
    inst = network.table_ii_instance(scenario, seed=0)
    res = gp.solve(inst, alpha=0.1, max_iters=500)
    assert float(conditions.sufficiency_residual(inst, res.phi, active_eps=1e-3)) < 5e-2


@pytest.mark.parametrize("scenario", ["abilene", "balanced-tree", "fog"])
@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_gp_beats_baselines(scenario, scale):
    inst = network.table_ii_instance(scenario, seed=0, rate_scale=scale)
    res = gp.solve(inst, alpha=0.1, max_iters=400)
    for name, fn in baselines.ALL_BASELINES.items():
        if name == "LPR-SC":
            b = fn(inst)
        else:
            b = fn(inst, alpha=0.1, max_iters=250)
        assert res.final_cost <= b.final_cost * 1.02, (name, res.final_cost, b.final_cost)


@given(seed=st.integers(0, 500))
@settings(max_examples=8, deadline=None)
def test_iterates_stay_loopfree_and_feasible(seed):
    """The blocked-set mechanism preserves loop-freedom from any loop-free
    start (the paper's key invariant)."""
    inst = small_instances()[0]
    phi = random_loopfree_phi(inst, seed)
    for _ in range(15):
        state = gp.gp_step(inst, phi, 0.2)
        phi = state.phi
        fl = traffic.flows(inst, phi)
        assert bool(traffic.traffic_is_valid(inst, fl.t))
        assert float(traffic.feasibility_violation(inst, phi)) < 1e-4


def test_adapts_to_input_rate_change():
    """Online adaptivity: after r_i(a) changes, continuing from the current
    phi re-converges (no restart needed)."""
    inst = network.table_ii_instance("abilene", seed=0)
    res1 = gp.solve(inst, alpha=0.1, max_iters=300)
    inst2 = dataclasses.replace(inst, r=inst.r * 2.5)
    res2 = gp.solve(inst2, phi0=res1.phi, alpha=0.1, max_iters=300)
    fresh = gp.solve(inst2, alpha=0.1, max_iters=300)
    assert res2.final_cost <= fresh.final_cost * 1.05
    # residual threshold is scale-aware: marginals grow with congestion
    res = float(conditions.sufficiency_residual(inst2, res2.phi, active_eps=1e-3))
    assert res < 0.05 * max(1.0, res2.final_cost)


def test_adapts_to_link_removal():
    """Topology change: removing a link, the strategy re-normalizes and GP
    re-converges on the reduced graph."""
    inst = network.table_ii_instance("abilene", seed=0)
    res1 = gp.solve(inst, alpha=0.1, max_iters=300)
    adj = np.asarray(inst.adj).copy()
    links = np.argwhere(adj)
    i, j = links[0]
    adj[i, j] = False
    lp = np.asarray(inst.link_param).copy()
    lp[i, j] = 0.0
    inst2 = dataclasses.replace(
        inst, adj=jnp.asarray(adj), link_param=jnp.asarray(lp)
    )
    phi0 = traffic.renormalize(inst2, res1.phi)
    # the removed link's mass may leave a row empty; re-seed those rows
    tot = phi0.e.sum(-1) + phi0.c
    empty = (tot < 0.5) & ~inst2.degenerate_mask()
    if bool(empty.any()):
        sp = gp.init_phi(inst2)
        phi0 = traffic.Phi(
            e=jnp.where(empty[..., None], sp.e, phi0.e),
            c=jnp.where(empty, sp.c, phi0.c),
        )
    res2 = gp.solve(inst2, phi0=phi0, alpha=0.1, max_iters=300)
    assert np.isfinite(res2.final_cost)
    assert float(conditions.sufficiency_residual(inst2, res2.phi, active_eps=1e-3)) < 0.1


def test_multi_source_applications():
    """The paper allows multiple data sources per application (footnote 1)."""
    inst = network.table_ii_instance("geant", seed=2)
    assert int((np.asarray(inst.r) > 0).sum(axis=1).max()) >= 2
    res = gp.solve(inst, alpha=0.1, max_iters=200)
    assert np.isfinite(res.final_cost)
