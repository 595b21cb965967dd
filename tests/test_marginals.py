"""Validate closed-form marginals (eqs. 3-4) against autodiff and FD.

This is the central theory check: the paper's distributed marginal-cost
broadcast must compute exactly dD/dphi — otherwise nothing downstream
(conditions, GP, Theorem 1) holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.marginals as M
from repro.core import network, traffic
from tests.helpers import random_loopfree_phi, small_instances


def _autodiff_grads(inst, phi):
    fn = lambda e, c: traffic.total_cost(inst, traffic.Phi(e, c))
    return jax.grad(fn, argnums=(0, 1))(phi.e, phi.c)


@pytest.mark.parametrize("inst", small_instances(seeds=(0, 1)),
                         ids=["abilene0", "tree0", "abilene1", "tree1"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_closed_form_equals_autodiff(inst, seed):
    phi = random_loopfree_phi(inst, seed)
    ge, gc = M.dD_dphi(inst, phi)
    age, agc = _autodiff_grads(inst, phi)
    # relative tolerance: float32 noise amplifies near queue knees
    # (D'' ~ 1/(cap-F)^3, so a 1-ulp flow difference moves the marginal by
    # orders of magnitude more); the closed form is exact in exact
    # arithmetic (verified against finite differences below).  Scale by the
    # LARGEST marginal so saturated instances don't fail on f32 noise.
    scale = max(1.0, float(jnp.max(jnp.abs(age))),
                float(jnp.max(jnp.abs(agc))))
    err_e = float(jnp.max(jnp.abs(jnp.where(inst.adj[None, None], ge - age, 0.0))))
    err_c = float(jnp.max(jnp.abs(jnp.where(inst.cpu_allowed()[:, :, None], gc - agc, 0.0))))
    assert err_e / scale < 5e-3
    assert err_c / scale < 5e-3


def test_closed_form_matches_finite_difference():
    inst = small_instances()[0]
    phi = random_loopfree_phi(inst, 42)
    ge, _ = M.dD_dphi(inst, phi)
    rng = np.random.default_rng(0)
    adj = np.asarray(inst.adj)
    links = np.argwhere(adj)
    cost0 = float(traffic.total_cost(inst, phi))
    for _ in range(5):
        i, j = links[rng.integers(len(links))]
        a = rng.integers(inst.A)
        k = rng.integers(inst.K1)
        eps = 1e-3
        e2 = phi.e.at[a, k, i, j].add(eps)
        cost1 = float(traffic.total_cost(inst, traffic.Phi(e2, phi.c)))
        fd = (cost1 - cost0) / eps
        assert fd == pytest.approx(float(ge[a, k, i, j]), rel=0.05, abs=5e-3)


def test_pdt_zero_at_destination_final_stage():
    """dD/dt_{d_a}(a, K_a) == 0 — final results exit for free."""
    for inst in small_instances():
        phi = random_loopfree_phi(inst, 5)
        m = M.marginals(inst, phi)
        for a in range(inst.A):
            d = int(inst.dst[a])
            k = int(inst.n_tasks[a])
            assert float(m.pdt[a, k, d]) == pytest.approx(0.0, abs=1e-6)


def test_pdt_decreases_downstream_at_optimum():
    """At a (6)-satisfying point, pdt decreases along any flow path."""
    from repro.core import gp

    inst = network.table_ii_instance("abilene", seed=1)
    res = gp.solve(inst, alpha=0.1, max_iters=300)
    m = M.marginals(inst, res.phi)
    pdt = np.asarray(m.pdt)
    e = np.asarray(res.phi.e)
    viol = 0
    for a in range(inst.A):
        for k in range(inst.K1):
            carried = np.argwhere(e[a, k] > 1e-3)
            for i, j in carried:
                if pdt[a, k, j] > pdt[a, k, i] + 1e-2:
                    viol += 1
    assert viol == 0
