"""The benchmark's copy of the Table II draws gives the program's instances,
and the plain reference agrees with the program on them."""

import numpy as np
import pytest

from bench import reference, registry
from bench.traffic import tableii

FIELDS = ("adj", "link_param", "comp_param", "L", "w", "wnode", "r", "dst",
          "n_tasks", "stage_mask")


@pytest.mark.parametrize("config,name,scale", [
    ("abilene", "abilene", 2.0), ("sw-queue", "sw-queue", 1.5)])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_copy_draws_the_programs_instances(config, name, scale, seed):
    from repro.core import network

    ours = tableii.to_instance(tableii.draw(registry.config(config), seed,
                                            scale))
    theirs = network.table_ii_instance(name, seed=seed, rate_scale=scale)
    for f in FIELDS:
        a, b = np.asarray(getattr(ours, f)), np.asarray(getattr(theirs, f))
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert (ours.link_kind, ours.comp_kind) == (theirs.link_kind,
                                                theirs.comp_kind)


def _spread_strategy(d, cpu=0.4):
    """Every node splits its traffic evenly over its out-links, and offloads
    a share ``cpu`` where computing is allowed: loop-carrying but absorbing,
    so every stage system is nonsingular."""
    adj = np.asarray(d["adj"], float)
    A, K1 = d["L"].shape
    V = adj.shape[0]
    e = np.broadcast_to(adj / adj.sum(1, keepdims=True), (A, K1, V, V)).copy()
    c = np.zeros((A, K1, V))
    c[:, :-1] = cpu
    e[:, :-1] *= 1 - cpu
    for a in range(A):
        e[a, -1, d["dst"][a]] = 0.0
    return e, c


def test_relabelled_deployment_has_the_same_cost_and_residual():
    d = tableii.draw(registry.config("abilene"), 3, 2.0)
    e, c = _spread_strategy(d)
    perm = np.random.default_rng(11).permutation(11)
    dp = tableii.relabel(d, perm)
    ep, cp = e[:, :, perm][:, :, :, perm], c[:, :, perm]
    assert reference.cost(dp, ep, cp) == pytest.approx(
        reference.cost(d, e, c), rel=1e-12)
    assert reference.residual(dp, ep, cp) == pytest.approx(
        reference.residual(d, e, c), rel=1e-9)
    assert reference.feasibility(dp, ep, cp) < 1e-12


def test_reference_agrees_with_the_programs_cost_and_residual():
    from repro.core import conditions, gp, traffic

    d = tableii.draw(registry.config("abilene"), 5, 2.0)
    inst = tableii.to_instance(d)
    phi = gp.init_phi(inst)
    e, c = np.asarray(phi.e), np.asarray(phi.c)
    assert reference.cost(d, e, c) == pytest.approx(
        float(traffic.total_cost(inst, phi, solver="dense")), rel=1e-5)
    assert reference.residual(d, e, c) == pytest.approx(
        float(conditions.sufficiency_residual(inst, phi)), rel=1e-4,
        abs=1e-5)
    assert reference.feasibility(d, e, c) < 1e-6
    bad = e.copy()
    bad[0, 0, 0, 0] += 0.5                 # mass on a self-loop, off the graph
    assert reference.feasibility(d, bad, c) >= 0.5


def test_lower_bound_is_exact_for_linear_costs():
    """With linear link and CPU costs the linearised problem is the problem,
    so the bound is the optimum whatever strategy it is read at."""
    d = dict(tableii.draw(registry.config("abilene"), 4, 2.0), link_kind=0,
             comp_kind=0)
    e, c = _spread_strategy(d)
    e2, c2 = _spread_strategy(d, cpu=0.9)
    lb = reference.lower_bound(d, e, c)
    assert lb == pytest.approx(reference.lower_bound(d, e2, c2), rel=1e-12)
    assert 0 < lb < min(reference.cost(d, e, c), reference.cost(d, e2, c2))


def test_lower_bound_closes_on_a_solved_strategy():
    from repro.core import gp

    d = tableii.draw(registry.config("abilene"), 2, 2.0)
    inst = tableii.to_instance(d)
    phi0 = gp.init_phi(inst)
    e0, c0 = np.asarray(phi0.e), np.asarray(phi0.c)
    res = gp.solve(inst, phi0, alpha=0.1, tol=1e-2, max_iters=200,
                   accel=True)
    e, c = np.asarray(res.phi.e), np.asarray(res.phi.c)
    f0, f = reference.cost(d, e0, c0), reference.cost(d, e, c)
    lb0, lb = reference.lower_bound(d, e0, c0), reference.lower_bound(d, e, c)
    assert lb0 <= f and lb <= f < f0
    assert (f - lb) / f < 1e-2 < (f0 - lb0) / f0
