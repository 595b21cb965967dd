"""Batched-LU kernel (kernels/batched_solve.py) vs oracles, and the GP
integration contract: shared stage factorization == the seed dense path.

Covers the PR's kernel deliverables:
  * parity vs ``vmap(jnp.linalg.solve)`` across dtypes and batch shapes,
    for both the reference (LAPACK) path and the Pallas interpret path;
  * a singular / near-singular member raises the per-member flag without
    poisoning the rest of the batch;
  * loops that stop at the logical V give the bits of the full padded
    trip count;
  * end-to-end ``gp.solve`` cost parity vs the seed per-stage solver.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gp, network, traffic
from repro.core.marginals import marginals
from repro.core.traffic import Phi, flows, stage_factors, traffic_is_valid
from repro.kernels import ops
from repro.kernels import batched_solve as bs


def _mk_systems(key, B, V, dtype=jnp.float32, spread=0.5):
    """Well-conditioned stage-like systems I - c*row-substochastic."""
    k1, k2 = jax.random.split(key)
    P = jax.random.uniform(k1, (B, V, V), dtype=jnp.float32)
    P = spread * P / jnp.sum(P, axis=-1, keepdims=True)
    mats = (jnp.eye(V) - P).astype(dtype)
    rhs = jax.random.uniform(k2, (B, V), dtype=jnp.float32).astype(dtype)
    return mats, rhs


def _oracle(mats, rhs, trans=0):
    m = mats.astype(jnp.float32)
    m = m.transpose(0, 2, 1) if trans else m
    return jnp.linalg.solve(m, rhs.astype(jnp.float32)[..., None])[..., 0]


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref_lapack", "pallas_interpret"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("B,V", [(1, 3), (4, 11), (7, 33), (3, 100)])
def test_batched_solve_parity(B, V, dtype, tol, use_pallas):
    mats, rhs = _mk_systems(jax.random.PRNGKey(B * 1000 + V), B, V, dtype)
    want = _oracle(mats, rhs)
    x, resid = ops.batched_solve(mats, rhs, use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want),
                               atol=tol, rtol=tol)
    assert np.all(np.asarray(resid) < tol)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref_lapack", "pallas_interpret"])
@pytest.mark.parametrize("trans", [0, 1])
def test_factor_solve_trans(trans, use_pallas):
    mats, rhs = _mk_systems(jax.random.PRNGKey(7), 5, 37)
    fact = ops.batched_factor(mats, use_pallas=use_pallas)
    x = ops.batched_solve_factored(fact, rhs, trans=trans,
                                   use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(_oracle(mats, rhs, trans)),
                               atol=1e-5, rtol=1e-5)
    assert bool(jnp.all(fact.ok))


@pytest.mark.parametrize("trans", [0, 1])
def test_factors_are_path_portable(trans):
    """Pivoted reference factors solve correctly through the kernel path
    (perm is honored there) and vice versa within the kernel's M-matrix
    domain — mixing use_pallas between factor and solve is valid."""
    mats, rhs = _mk_systems(jax.random.PRNGKey(5), 4, 29)
    # force non-trivial pivoting for the reference factorization
    mats = mats[:, ::-1, :] + 0.0
    want = _oracle(mats, rhs, trans)
    fact = ops.batched_factor(mats, use_pallas=False)
    assert bool(jnp.any(fact.perm != jnp.arange(29)))
    x = ops.batched_solve_factored(fact, rhs, trans=trans, use_pallas=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    mats_dd, rhs_dd = _mk_systems(jax.random.PRNGKey(6), 4, 29)
    want_dd = _oracle(mats_dd, rhs_dd, trans)
    fact_p = ops.batched_factor(mats_dd, use_pallas=True)
    x2 = ops.batched_solve_factored(fact_p, rhs_dd, trans=trans,
                                    use_pallas=False)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(want_dd),
                               atol=1e-5, rtol=1e-5)


def test_leading_batch_dims_and_vmap():
    """(A, K1, V, V) leading dims flatten/restore, and vmap composes."""
    mats, rhs = _mk_systems(jax.random.PRNGKey(3), 12, 9)
    mats4, rhs4 = mats.reshape(3, 4, 9, 9), rhs.reshape(3, 4, 9)
    fact = ops.batched_factor(mats4)
    assert fact.lu.shape == (3, 4, 9, 9) and fact.ok.shape == (3, 4)
    x = ops.batched_solve_factored(fact, rhs4)
    np.testing.assert_allclose(np.asarray(x.reshape(12, 9)),
                               np.asarray(_oracle(mats, rhs)), atol=1e-5)
    xv = jax.vmap(lambda m, b: ops.batched_solve(m, b)[0])(mats4, rhs4)
    np.testing.assert_allclose(np.asarray(xv), np.asarray(x), atol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref_lapack", "pallas_interpret"])
def test_singular_member_flags_without_poisoning(use_pallas):
    """One singular member -> its ok flag drops and its residual is inf,
    while every other member still solves to oracle accuracy."""
    mats, rhs = _mk_systems(jax.random.PRNGKey(11), 6, 23)
    bad = 2
    mats = mats.at[bad].set(mats[bad].at[:, 5].set(0.0).at[5, :].set(0.0))
    want = _oracle(mats, rhs)

    fact = ops.batched_factor(mats, use_pallas=use_pallas)
    ok = np.asarray(fact.ok)
    assert not ok[bad] and ok[np.arange(6) != bad].all()

    x, resid = ops.batched_solve(mats, rhs, use_pallas=use_pallas)
    resid = np.asarray(resid)
    assert not np.isfinite(resid[bad]) or resid[bad] > 1e3
    good = np.arange(6) != bad
    np.testing.assert_allclose(np.asarray(x)[good], np.asarray(want)[good],
                               atol=1e-5, rtol=1e-5)
    assert np.all(resid[good] < 1e-5)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref_lapack", "pallas_interpret"])
@pytest.mark.parametrize("trans,reverse,clamp",
                         [(1, False, False), (0, True, True)])
def test_fused_chain_solve_matches_per_stage_loop(trans, reverse, clamp,
                                                  use_pallas):
    """ops.fused_chain_solve == the per-stage batched_solve_factored loop
    it replaced, for both GP sweep shapes (traffic: trans=1 forward;
    marginals: trans=0 reverse clamped)."""
    Bf, K, V = 3, 5, 22
    keys = jax.random.split(jax.random.PRNGKey(K * V), 3)
    P = jax.random.uniform(keys[0], (Bf, K, V, V))
    mats = jnp.eye(V) - 0.5 * P / jnp.sum(P, axis=-1, keepdims=True)
    base = jax.random.uniform(keys[1], (Bf, K, V)) - (0.5 if clamp else 0.0)
    mult = jax.random.uniform(keys[2], (Bf, K, V))
    fact = ops.batched_factor(mats, use_pallas=use_pallas)

    carry = jnp.zeros((Bf, V))
    want = [None] * K
    for k in (range(K - 1, -1, -1) if reverse else range(K)):
        b = base[:, k] + mult[:, k] * carry
        fk = jax.tree_util.tree_map(lambda x: x[:, k], fact)
        x = ops.batched_solve_factored(fk, b, trans=trans,
                                       use_pallas=use_pallas)
        if clamp:
            x = jnp.maximum(x, 0.0)
        want[k] = x
        carry = x
    got = ops.fused_chain_solve(fact, base, mult, trans=trans,
                                reverse=reverse, clamp=clamp,
                                use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(want, 1)),
                               atol=1e-5, rtol=1e-5)


def test_stage_factors_serve_both_sweeps():
    """One ``stage_factors`` factorization reproduces BOTH the traffic
    (transposed) and marginal (plain) sweeps of the dense seed path."""
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    phi = gp.init_phi(inst)
    fact = stage_factors(phi.e)
    assert bool(jnp.all(fact.ok))

    fl_lu = flows(inst, phi, fact, solver="batched_lu")
    fl_dense = flows(inst, phi, solver="dense")
    np.testing.assert_allclose(np.asarray(fl_lu.t), np.asarray(fl_dense.t),
                               atol=1e-5, rtol=1e-5)

    m_lu = marginals(inst, phi, fl_lu, fact, solver="batched_lu")
    m_dense = marginals(inst, phi, fl_dense, solver="dense")
    np.testing.assert_allclose(np.asarray(m_lu.pdt), np.asarray(m_dense.pdt),
                               atol=1e-5, rtol=1e-5)


def test_loopy_strategy_divergence_still_detected():
    """DESIGN.md §2 contract: a routing loop must keep tripping
    ``traffic_is_valid`` under the factored path (no exception, no silent
    garbage) — the per-solve-exception-free flag contract of §12."""
    inst = network.table_ii_instance("abilene", seed=0)
    phi = gp.init_phi(inst)
    adj = np.asarray(inst.adj)
    i = int(np.flatnonzero(adj.any(1))[0])
    j = int(np.flatnonzero(adj[i])[0])
    assert adj[j, i], "abilene links are bidirectional"
    e = np.zeros_like(np.asarray(phi.e))
    e[:, :, i, j] = 1.0
    e[:, :, j, i] = 1.0          # i <-> j cycle: I - Phi singular
    loopy = Phi(e=jnp.asarray(e), c=jnp.zeros_like(phi.c))

    fact = stage_factors(loopy.e)
    assert not bool(jnp.all(fact.ok))
    fl = flows(inst, loopy, fact, solver="batched_lu")
    assert not bool(traffic_is_valid(inst, fl.t))


def test_resolve_solver_policy():
    """"auto" is backend/size-aware and static; explicit choices pass
    through untouched."""
    assert traffic.resolve_solver("dense", 100) == "dense"
    assert traffic.resolve_solver("batched_lu", 4) == "batched_lu"
    big = traffic.resolve_solver("auto", traffic.AUTO_MIN_V)
    assert big == "batched_lu"
    if ops.INTERPRET:      # CPU: small instances keep the dense fast path
        assert traffic.resolve_solver("auto", 11) == "dense"
    else:                  # accelerator: always the kernel path
        assert traffic.resolve_solver("auto", 11) == "batched_lu"


def test_gp_step_dense_vs_batched_lu():
    """One full projection step agrees across solvers (same argmin rung)."""
    inst = network.table_ii_instance("geant", seed=0, rate_scale=2.0)
    phi = gp.init_phi(inst)
    s_lu = gp.gp_step(inst, phi, 0.1, solver="batched_lu")
    s_dense = gp.gp_step(inst, phi, 0.1, solver="dense")
    np.testing.assert_allclose(float(s_lu.cost), float(s_dense.cost),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s_lu.phi.e),
                               np.asarray(s_dense.phi.e), atol=1e-4)


def test_gp_solve_end_to_end_cost_parity():
    """Whole-solve parity vs the seed path: final cost within 1e-5 rel."""
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    r_lu = gp.solve(inst, alpha=0.1, max_iters=400, solver="batched_lu")
    r_dense = gp.solve(inst, alpha=0.1, max_iters=400, solver="dense")
    rel = abs(r_lu.final_cost - r_dense.final_cost) / abs(r_dense.final_cost)
    assert rel <= 1e-5, (r_lu.final_cost, r_dense.final_cost)


def test_pallas_blocked_path_crosses_panel_boundary():
    """V > NB exercises the panel Neumann sweep + MXU trailing update."""
    V = bs.DEFAULT_NB * 2 + 5
    mats, rhs = _mk_systems(jax.random.PRNGKey(42), 2, V)
    lu = bs.lu_factor(mats, interpret=True)
    x = bs.lu_solve(lu, rhs, interpret=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(_oracle(mats, rhs)),
                               atol=1e-5, rtol=1e-5)
    assert np.asarray(bs.factor_ok(lu)).all()


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _same_answers(got, want):
    """Bit-identical where finite; non-finite in the same places."""
    got, want = np.asarray(got), np.asarray(want)
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_array_equal(_bits(got[fin]), _bits(want[fin]))


def _pad_vec(x, Vp):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Vp - x.shape[-1])])


@pytest.mark.parametrize("op,V,nb", [
    *[(op, V, bs.DEFAULT_NB) for op in ("factor", "solve0", "solve1",
                                         "traffic", "marginals", "singular")
      for V in (11, 33, 100)],
    ("factor", 2 * bs.DEFAULT_NB + 5, bs.DEFAULT_NB),  # three panels
    ("factor", 33, 8),    # the last panel holds V: its loop stops inside it
    ("factor", 11, 4),    # a panel past V runs no column step at all
])
def test_loops_at_logical_V_match_full_trip_count(op, V, nb):
    """The kernels' sequential loops stop at the logical V.  Feeding the
    same systems pre-padded with the kernels' own identity tail to their Vp
    (so V = Vp and every pad step runs, as a full-length loop would) gives
    the same bits on [:V]."""
    Vp = bs._pad_dim(V, interpret=True)
    B, K = 3, 3
    mats, rhs = _mk_systems(jax.random.PRNGKey(V * 10 + nb), B * K, V)
    if op == "singular":
        bad = mats[1].at[:, 5].set(0.0).at[5, :].set(0.0)
        mats = mats.at[1].set(bad)
    f = functools.partial(bs.lu_factor, nb=nb, interpret=True)
    lu = f(mats)
    if op in ("factor", "singular"):
        full = f(bs._pad_square(mats, Vp))[:, :V, :V]
        _same_answers(lu, full)
        np.testing.assert_array_equal(np.asarray(bs.factor_ok(lu)),
                                      np.asarray(bs.factor_ok(full)))
    if op == "singular":
        assert not np.asarray(bs.factor_ok(lu))[1]
    if op in ("solve0", "solve1", "singular"):
        for trans in ((0, 1) if op == "singular" else (int(op[-1]),)):
            s = functools.partial(bs.lu_solve, trans=trans, interpret=True)
            _same_answers(s(lu, rhs),
                          s(bs._pad_square(lu, Vp), _pad_vec(rhs, Vp))[:, :V])
    if op in ("traffic", "marginals"):
        trans, reverse, clamp = ((1, False, False) if op == "traffic"
                                 else (0, True, True))
        base = rhs.reshape(B, K, V) - (0.5 if clamp else 0.0)
        mult = jnp.flip(rhs, 0).reshape(B, K, V)
        c = functools.partial(bs.chain_solve, trans=trans, reverse=reverse,
                              clamp=clamp, interpret=True)
        _same_answers(c(lu.reshape(B, K, V, V), base, mult),
                      c(bs._pad_square(lu, Vp).reshape(B, K, Vp, Vp),
                        _pad_vec(base, Vp), _pad_vec(mult, Vp))[..., :V])
