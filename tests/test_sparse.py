"""Sparse solve path (DESIGN.md §18): neighbor-list / BSR chain solves,
neighbor blocked-set sweeps, hetero-degree batch padding, and the 2-D
(app x node-space) mesh.

Parity targets come from the nilpotency argument: loop-free strategies make
every stage matrix strictly triangular under a topological order, so the
fixed-point sweep terminates EXACTLY — the sparse paths are the same
arithmetic as the dense solves up to summation order (<= 1e-5 on cost-scale
quantities), and the tagged sweep is bit-equal (pure boolean lattice).

The 2-D mesh cases skip below 4 devices; CI runs this module a second time
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import jax
import numpy as np
import pytest

from repro.core import batch, compat, distributed, engine, gp, network
from repro.core import marginals as marginals_mod
from repro.core import traffic
from repro.kernels import ops

KW = dict(alpha=0.1, max_iters=40, patience=10**6, tol=0.0)

need4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)

SCENARIOS = ["abilene", "geant", "sw-queue"]


def _sparse_inst(name, rate_scale=2.0):
    return network.with_sparse(
        network.table_ii_instance(name, seed=0, rate_scale=rate_scale))


def _rel(a, b):
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-9)))


def _mid_solve_phi(inst, iters=10):
    """A congested mid-solve iterate (nontrivial routing splits, traffic
    well away from the init point) — the regime the parity claim must hold
    in, not just at phi0."""
    res = gp.solve(inst, gp.init_phi(inst), alpha=0.1, max_iters=iters,
                   patience=10**6, tol=0.0, solver="batched_lu")
    return res.phi


@pytest.mark.parametrize("name", SCENARIOS)
def test_stage_traffic_sparse_matches_dense(name):
    inst = _sparse_inst(name)
    for phi in (gp.init_phi(inst), _mid_solve_phi(inst)):
        t_s, g_s = traffic.stage_traffic(inst, phi, solver="sparse")
        t_d, g_d = traffic.stage_traffic(inst, phi, solver="batched_lu")
        assert _rel(t_d, t_s) <= 1e-5
        assert _rel(g_d, g_s) <= 1e-5


@pytest.mark.parametrize("name", SCENARIOS)
def test_pdt_recursion_sparse_matches_dense(name):
    inst = _sparse_inst(name)
    phi = _mid_solve_phi(inst)
    fl = traffic.flows(inst, phi)
    Dp = marginals_mod.link_marginals(inst, fl.F)
    Cp = marginals_mod.comp_marginals(inst, fl.G)
    pdt_s = marginals_mod.pdt_recursion(inst, phi, Dp, Cp, solver="sparse")
    pdt_d = marginals_mod.pdt_recursion(inst, phi, Dp, Cp,
                                        solver="batched_lu")
    assert _rel(pdt_d, pdt_s) <= 1e-5


@pytest.mark.parametrize("trans,reverse,clamp",
                         [(1, False, False), (0, True, True)],
                         ids=["traffic", "marginals"])
@pytest.mark.parametrize("name", ["geant", "sw-queue"])
def test_bsr_chain_solve_matches_nbr(name, trans, reverse, clamp):
    """The partition-blocked Pallas chain solve (interpret mode) computes
    the same chain as the neighbor-list jnp sweeps, for both sweep shapes."""
    inst = _sparse_inst(name)
    phi = gp.init_phi(inst)
    topo = ops.sparse_topo(inst)
    k1, k2 = jax.random.split(jax.random.PRNGKey(inst.V))
    base = jax.random.uniform(k1, phi.c.shape) - (0.5 if clamp else 0.0)
    mult = jax.random.uniform(k2, phi.c.shape)
    kw = dict(trans=trans, reverse=reverse, clamp=clamp)
    got = ops.sparse_chain_solve(topo, phi.e, base, mult, use_pallas=True,
                                 **kw)
    want = ops.sparse_chain_solve(topo, phi.e, base, mult, use_pallas=False,
                                  **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("name", SCENARIOS)
def test_blocked_nbr_bit_equal(name):
    """The neighbor-list tagged sweep is a monotone boolean fixed point —
    bit-equal to both the bit-packed kernel and the dense scan."""
    inst = _sparse_inst(name)
    phi = _mid_solve_phi(inst)
    m = marginals_mod.marginals(inst, phi)
    ref = engine.blocked_sets(inst, phi, m.pdt, method="scan")
    bit = engine.blocked_sets(inst, phi, m.pdt, method="bitset")
    nbr = engine.blocked_sets(inst, phi, m.pdt, method="nbr")
    assert np.array_equal(np.asarray(ref), np.asarray(bit))
    assert np.array_equal(np.asarray(ref), np.asarray(nbr))


@pytest.mark.parametrize("name", ["abilene", "geant"])
def test_full_solve_sparse_matches_dense(name):
    """Whole-trajectory parity: identical committed iterations, cost
    histories <= 1e-5 (the acceptance bound on the Table II scenarios)."""
    inst = _sparse_inst(name)
    phi0 = gp.init_phi(inst)
    ref = gp.solve(inst, phi0, solver="batched_lu", **KW)
    res = gp.solve(inst, phi0, solver="sparse", **KW)
    assert int(res.iterations) == int(ref.iterations)
    assert _rel(ref.cost_history, res.cost_history) <= 1e-5


def test_auto_dispatch():
    """"auto" resolves to sparse only with the topology attached AND at
    metro scale (SPARSE_MIN_V); stripping the fields restores dense."""
    inst = _sparse_inst("abilene")
    assert traffic.resolve_solver("auto", traffic.SPARSE_MIN_V, inst) == "sparse"
    assert traffic.resolve_solver("auto", traffic.SPARSE_MIN_V - 1,
                                  inst) != "sparse"
    bare = network.without_sparse(inst)
    assert traffic.resolve_solver("auto", traffic.SPARSE_MIN_V,
                                  bare) != "sparse"
    # explicit solver choices pass through untouched
    assert traffic.resolve_solver("dense", 10**4, inst) == "dense"


def _star(n_leaves):
    V = n_leaves + 1
    adj = np.zeros((V, V), dtype=bool)
    adj[0, 1:] = adj[1:, 0] = True
    return adj


def _ring(V):
    adj = np.zeros((V, V), dtype=bool)
    for i in range(V):
        adj[i, (i + 1) % V] = adj[(i + 1) % V, i] = True
    return adj


def test_pad_instances_hetero_degree():
    """Batching a degree-12 star with degree-2 rings must not silently
    densify the padded neighbor lists: default raises, "pad" opts into the
    family-max degree, "strip" falls back to the dense-only batch."""
    star = network.with_sparse(
        network.build_instance(_star(12), n_apps=2, seed=0))
    ring = network.with_sparse(
        network.build_instance(_ring(13), n_apps=2, seed=1))
    assert int(star.max_degree) > 4 * int(ring.max_degree)

    with pytest.raises(ValueError, match="degree"):
        batch.pad_instances([star, ring])

    padded = batch.pad_instances([star, ring], hetero_degree="pad")
    assert padded.has_sparse
    assert padded.out_nbr.shape[0] == 2
    assert padded.out_nbr.shape[-1] >= int(star.max_degree)

    stripped = batch.pad_instances([star, ring], hetero_degree="strip")
    assert not stripped.has_sparse

    with pytest.raises(ValueError):
        batch.pad_instances([star, network.without_sparse(ring)])

    # near-equal degrees stay sparse under the default policy
    ok = batch.pad_instances([ring, network.with_sparse(
        network.build_instance(_ring(13), n_apps=2, seed=2))])
    assert ok.has_sparse


def test_pad_instance_rederives_sparse():
    """Single-instance V-padding re-derives the topology on the padded
    adjacency: dead nodes are isolated, live neighbors unchanged."""
    inst = _sparse_inst("abilene")
    out = batch.pad_instance(inst, inst.V + 5, inst.A, inst.K1)
    assert out.has_sparse
    assert out.out_nbr.shape[0] == inst.V + 5
    assert not bool(np.asarray(out.out_mask[inst.V:]).any())
    np.testing.assert_array_equal(
        np.asarray(out.out_mask[:inst.V]), np.asarray(inst.out_mask))


# ---------------------------------------------------------------------------
# 2-D app x node-space mesh
# ---------------------------------------------------------------------------

def _metro60():
    return network.metro_instance("sw", 60)


@need4
def test_2d_mesh_matches_single_device():
    """2x2 stage x node mesh == single-device sparse solve (<= 1e-4; the
    node axis storage-shards phi rows and runs the tagged sweep
    node-parallel, so trajectories agree to summation order)."""
    inst = _metro60()
    phi0 = gp.init_phi(inst)
    ref = gp.solve(inst, phi0, solver="sparse", **KW)
    mesh = compat.make_mesh((2, 2), ("stage", "node"))
    res = distributed.solve_sharded(inst, mesh, node_axis="node",
                                    phi0=phi0, solver="sparse", **KW)
    assert int(res.iterations) == int(ref.iterations)
    assert _rel(ref.cost_history, res.cost_history) <= 1e-4


@need4
def test_node_only_mesh_matches_single_device():
    """1x4 mesh: all parallelism on the node axis (V=60 % 4 == 0 takes the
    genuinely sharded tagged-sweep path)."""
    inst = _metro60()
    phi0 = gp.init_phi(inst)
    ref = gp.solve(inst, phi0, solver="sparse", alpha=0.1, max_iters=15,
                   patience=10**6, tol=0.0)
    mesh = compat.make_mesh((1, 4), ("stage", "node"))
    res = distributed.solve_sharded(inst, mesh, node_axis="node",
                                    phi0=phi0, solver="sparse", alpha=0.1,
                                    max_iters=15, patience=10**6, tol=0.0)
    assert _rel(ref.cost_history, res.cost_history) <= 1e-4
