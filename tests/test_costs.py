"""Property tests for the cost families (Section II requirements)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import costs


@pytest.mark.parametrize("kind", [costs.LINEAR, costs.QUEUE])
@given(cap=st.floats(0.5, 100.0), f=st.floats(0.0, 200.0))
@settings(max_examples=60, deadline=None)
def test_cost_zero_nonneg_increasing(kind, cap, f):
    cap_a = jnp.float32(cap)
    assert float(costs.cost(kind, jnp.float32(0.0), cap_a)) == 0.0
    c = float(costs.cost(kind, jnp.float32(f), cap_a))
    assert np.isfinite(c) and c >= 0.0
    m = float(costs.marginal(kind, jnp.float32(f), cap_a))
    assert np.isfinite(m) and m > 0.0


@pytest.mark.parametrize("kind", [costs.LINEAR, costs.QUEUE])
@given(cap=st.floats(0.5, 100.0), f1=st.floats(0.0, 150.0), df=st.floats(0.01, 50.0))
@settings(max_examples=60, deadline=None)
def test_cost_convex_monotone(kind, cap, f1, df):
    cap_a = jnp.float32(cap)
    c1 = float(costs.cost(kind, jnp.float32(f1), cap_a))
    c2 = float(costs.cost(kind, jnp.float32(f1 + df), cap_a))
    assert c2 >= c1 - 1e-5 * max(1.0, abs(c1))          # increasing
    m1 = float(costs.marginal(kind, jnp.float32(f1), cap_a))
    m2 = float(costs.marginal(kind, jnp.float32(f1 + df), cap_a))
    assert m2 >= m1 - 1e-4 * max(1.0, m1)               # convex (D' increasing)


@pytest.mark.parametrize("kind", [costs.LINEAR, costs.QUEUE])
@given(cap=st.floats(0.5, 50.0), f=st.floats(0.001, 120.0))
@settings(max_examples=60, deadline=None)
def test_marginal_matches_autodiff(kind, cap, f):
    cap_a = jnp.float32(cap)
    g = float(jax.grad(lambda x: costs.cost(kind, x, cap_a))(jnp.float32(f)))
    m = float(costs.marginal(kind, jnp.float32(f), cap_a))
    assert g == pytest.approx(m, rel=2e-3, abs=1e-5)


def test_queue_matches_mm1_inside_capacity():
    """Below the knee the queue cost is exactly F/(cap-F) (M/M/1)."""
    cap = jnp.float32(10.0)
    for f in [0.0, 1.0, 5.0, 9.0, 9.7]:
        expect = f / (10.0 - f)
        got = float(costs.cost(costs.QUEUE, jnp.float32(f), cap))
        assert got == pytest.approx(expect, rel=1e-5)


def test_queue_extension_is_c1_at_knee():
    cap = jnp.float32(10.0)
    knee = 0.98 * 10.0
    below = float(costs.marginal(costs.QUEUE, jnp.float32(knee - 1e-4), cap))
    above = float(costs.marginal(costs.QUEUE, jnp.float32(knee + 1e-4), cap))
    assert above == pytest.approx(below, rel=1e-2)
