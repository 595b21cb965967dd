"""Property-based invariants of the online layer's primitives (§16).

The online service leans on three mechanical guarantees:

  * ``traffic.repair_phi`` / ``traffic.renormalize`` always return a
    strategy on the simplex constraints (1) with zero mass on dead links
    and disallowed CPU rows — for ANY live strategy and ANY surviving
    topology, not just the ones the benches happen to hit;
  * ``gp_step`` with the §15 accel safeguards commits only feasible
    strategies and never increases the objective (the stepsize ladder
    always holds the alpha=0 rung);
  * the bitset blocked-set kernel is bit-equal to the dense reference
    scan on randomized congested strategies (the fused hot path cannot
    silently diverge from Section IV's definition).

Randomization goes through ``hypothesis`` under the derandomized profile
that ``conftest.py`` loads, so tier-1 runs the same examples everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gp, marginals, network, traffic
from hypothesis import given, settings, strategies as st


# One compile each (shapes are fixed across examples): the eager accel
# ladder runs op-by-op and would dominate tier-1 wall clock otherwise.
_accel_step = jax.jit(lambda inst, phi, alpha: gp.gp_step(
    inst, phi, alpha, accel=True))
_blocked_both = jax.jit(lambda inst, phi: (
    lambda pdt: (gp.blocked_sets(inst, phi, pdt, method="bitset"),
                 gp.blocked_sets(inst, phi, pdt, method="scan"))
)(marginals.marginals(inst, phi).pdt))


def _random_strategy(inst, seed: int) -> traffic.Phi:
    """A feasible but arbitrary live strategy (cycles, improper links)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    e = jax.random.uniform(k1, (inst.A, inst.K1, inst.V, inst.V))
    e = e * inst.adj[None, None]
    c = jax.random.uniform(k2, (inst.A, inst.K1, inst.V))
    return traffic.renormalize(inst, traffic.Phi(e=e, c=c))


def _fail_link(inst, rank: int):
    """Drop the ``rank``-th live link (mod link count) from the instance."""
    import dataclasses

    links = np.argwhere(np.asarray(inst.adj))
    i, j = links[rank % len(links)]
    adj = np.asarray(inst.adj).copy()
    lp = np.asarray(inst.link_param).copy()
    adj[i, j] = False
    lp[i, j] = 0.0
    return dataclasses.replace(
        inst, adj=jnp.asarray(adj), link_param=jnp.asarray(lp)), (int(i), int(j))


# ---------------------------------------------------------------------------
# repair_phi / renormalize
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(scale=st.floats(min_value=0.4, max_value=2.5),
       seed=st.integers(min_value=0, max_value=10_000),
       rank=st.integers(min_value=0, max_value=27))
def test_repair_phi_simplex_and_zero_dead_mass(scale, seed, rank):
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=scale)
    phi = _random_strategy(inst, seed)
    new_inst, (i, j) = _fail_link(inst, rank)

    repaired = traffic.repair_phi(new_inst, phi, gp.init_phi(new_inst))
    # constraint (1) holds exactly on the new instance
    assert float(traffic.feasibility_violation(new_inst, repaired)) <= 1e-5
    # zero mass on every dead direction, not just the newly failed link
    dead = ~np.asarray(new_inst.adj)[None, None]
    assert float(np.abs(np.asarray(repaired.e) * dead).max()) == 0.0
    assert float(np.asarray(repaired.e)[:, :, i, j].max()) == 0.0
    # zero CPU mass where offloading is disallowed
    cpu_dead = ~np.asarray(new_inst.cpu_allowed())[:, :, None]   # (A,K1,1)
    assert float(np.abs(np.asarray(repaired.c) * cpu_dead).max()) == 0.0


@settings(max_examples=6, deadline=None)
@given(scale=st.floats(min_value=0.4, max_value=2.5),
       seed=st.integers(min_value=0, max_value=10_000))
def test_renormalize_projects_onto_simplex(scale, seed):
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=scale)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    # drifted strategy: negative entries, off-graph mass, unnormalized rows
    e = jax.random.uniform(k1, (inst.A, inst.K1, inst.V, inst.V),
                           minval=-0.5, maxval=2.0)
    c = jax.random.uniform(k2, (inst.A, inst.K1, inst.V),
                           minval=-0.5, maxval=2.0)
    out = traffic.renormalize(inst, traffic.Phi(e=e, c=c))
    # contract: every row is either exactly on the simplex or exactly zero
    # (a row whose mass clipped away entirely is repair_phi's job, not
    # renormalize's), and degenerate rows are forced to zero
    tot = np.asarray(out.e.sum(-1) + out.c)
    degen = np.asarray(inst.degenerate_mask())
    assert (np.isclose(tot, 1.0, atol=1e-5) | (tot == 0.0)).all()
    assert (tot[degen] == 0.0).all()
    assert float(np.abs(np.asarray(out.e) *
                        ~np.asarray(inst.adj)[None, None]).max()) == 0.0
    assert float(np.asarray(out.e).min()) >= 0.0
    assert float(np.asarray(out.c).min()) >= 0.0


# ---------------------------------------------------------------------------
# gp_step: feasibility + monotone descent under the accel safeguards
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(scale=st.floats(min_value=0.5, max_value=3.0),
       alpha=st.floats(min_value=0.02, max_value=0.4))
def test_gp_step_commits_feasible_never_worse_strategies(scale, alpha):
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=scale)
    phi = gp.init_phi(inst)
    prev = float(traffic.total_cost(inst, phi))
    assert np.isfinite(prev)
    for _ in range(3):
        state = _accel_step(inst, phi, jnp.float32(alpha))
        phi = state.phi
        cost = float(state.cost)
        # committed strategy is feasible and its cost is the reported cost
        assert float(traffic.feasibility_violation(inst, phi)) <= 1e-5
        assert cost == pytest.approx(float(traffic.total_cost(inst, phi)),
                                     rel=1e-5)
        # the ladder holds an alpha=0 rung: the step can never lose ground
        assert cost <= prev * (1 + 1e-6) + 1e-6, (scale, alpha, cost, prev)
        prev = cost


# ---------------------------------------------------------------------------
# bitset blocked sets == dense reference scan (randomized)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(scale=st.floats(min_value=0.5, max_value=3.0),
       seed=st.integers(min_value=0, max_value=10_000))
def test_blocked_bitset_bit_equal_to_dense_scan(scale, seed):
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=scale)
    phi = _random_strategy(inst, seed)
    b_bit, b_scan = _blocked_both(inst, phi)
    np.testing.assert_array_equal(np.asarray(b_bit), np.asarray(b_scan))
