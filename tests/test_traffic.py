"""Traffic fixed-point invariants (Section II flow model)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import network, traffic
from tests.helpers import random_loopfree_phi, small_instances


@pytest.mark.parametrize("inst", small_instances(), ids=["abilene", "tree"])
@given(seed=st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_flow_conservation(inst, seed):
    """t_i(a,k) = sum_j t_j phi_ji + injection  (definition of traffic)."""
    phi = random_loopfree_phi(inst, seed)
    fl = traffic.flows(inst, phi)
    t, g = np.asarray(fl.t), np.asarray(fl.g)
    r = np.asarray(inst.r)
    for a in range(inst.A):
        for k in range(inst.K1):
            inject = r[a] if k == 0 else g[a, k - 1]
            incoming = np.asarray(phi.e)[a, k].T @ t[a, k]
            np.testing.assert_allclose(t[a, k], incoming + inject, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("inst", small_instances(), ids=["abilene", "tree"])
@given(seed=st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_traffic_bounded_and_valid(inst, seed):
    """Loop-free traffic never exceeds the injected totals (no amplification)."""
    phi = random_loopfree_phi(inst, seed)
    fl = traffic.flows(inst, phi)
    assert bool(traffic.traffic_is_valid(inst, fl.t))
    total_in = float(jnp.sum(inst.r, axis=1).max())
    assert float(fl.t.max()) <= total_in + 1e-3
    assert float(fl.t.min()) >= -1e-4


@pytest.mark.parametrize("inst", small_instances(), ids=["abilene", "tree"])
def test_all_input_reaches_destination(inst):
    """Constraint (1): everything injected exits as final results at d_a."""
    phi = random_loopfree_phi(inst, seed=123)
    fl = traffic.flows(inst, phi)
    t = np.asarray(fl.t)
    for a in range(inst.A):
        k_last = int(inst.n_tasks[a])
        d = int(inst.dst[a])
        injected = float(np.asarray(inst.r)[a].sum())
        # traffic absorbed at (d_a, K) = arriving final results + local conv
        phi_row = np.asarray(phi.e)[a, k_last][d]
        assert phi_row.sum() == pytest.approx(0.0, abs=1e-6)
        # total final-stage production equals total input (packet conversion
        # is one-in-one-out): sum of stage-K injections == r_total
        produced = float(np.asarray(fl.g)[a, k_last - 1].sum())
        assert produced == pytest.approx(injected, rel=1e-4)


def test_renormalize_fixes_violations():
    inst = small_instances()[0]
    phi = random_loopfree_phi(inst, 7)
    broken = traffic.Phi(e=phi.e * 1.7 + 0.01 * inst.adj[None, None], c=phi.c * 0.3)
    fixed = traffic.renormalize(inst, broken)
    assert float(traffic.feasibility_violation(inst, fixed)) < 1e-5


def test_total_cost_positive_and_finite():
    for inst in small_instances():
        phi = random_loopfree_phi(inst, 3)
        c = float(traffic.total_cost(inst, phi))
        assert np.isfinite(c) and c > 0
