"""Closed-form marginal costs and modified marginals (eqs. (3), (4), (7)).

``pdt[a,k,i] = dD/dt_i(a,k)`` satisfies the backward recursion (4):

    pdt_k(i) = sum_j phi_ij(k) (L_k D'_ij + pdt_k(j))
             + phi_i0(k) (w(a,k) C'_i + pdt_{k+1}(i))

with pdt_{K}(d_a) = 0 at the destination.  For each stage this is a linear
system in ``pdt_k`` whose matrix is ``I - Phi_k`` (NOT transposed — the
recursion runs along outgoing links), solved exactly; the chain coupling is
a *reverse* ``lax.scan`` over k.  This realizes the paper's distributed
marginal-cost broadcast protocol as a synchronous fixed-point computation:
identical limit, synchronous schedule (DESIGN.md §4).

The modified marginals (7) drop the ``t_i(a,k)`` prefactor of (3):

    delta_ij(a,k) = L_k D'_ij + pdt[a,k,j]                     (j != 0)
    delta_i0(a,k) = w(a,k) C'_i + pdt[a,k+1,i]                 (j == 0)

and are the quantities both the sufficiency condition (6) and the GP update
(9) operate on.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.network import Instance
from repro.core.traffic import (
    HIGHEST, Flows, Phi, comp_marginals, flows, link_marginals,
    resolve_solver, stage_factors,
)
from repro.kernels import ops

# Marginal assigned to non-existent directions ((i,j) not in E, or CPU at the
# final stage) — the paper's "infinity" (footnote 4).
BIG = jnp.float32(1e9)


class Marginals(NamedTuple):
    pdt: jnp.ndarray       # (A, K1, V)     dD/dt_i(a,k)
    delta_e: jnp.ndarray   # (A, K1, V, V)  delta_ij(a,k); BIG on non-links
    delta_c: jnp.ndarray   # (A, K1, V)     delta_i0(a,k); BIG when k == K_a
    Dp: jnp.ndarray        # (V, V)         D'_ij(F_ij)
    Cp: jnp.ndarray        # (V,)           C'_i(G_i)


def pdt_recursion(
    inst: Instance,
    phi: Phi,
    Dp: jnp.ndarray,
    Cp: jnp.ndarray,
    fact: Optional[ops.BatchedLU] = None,
    *,
    solver: str = "auto",
) -> jnp.ndarray:
    """Solve recursion (4) for all stages: reverse scan over k, vmap over a.

    Each stage's matrix ``I - Phi_k`` is independent of the chain coupling
    (only the RHS carries pdt_{k+1}), so the default path factors all
    (a, k) systems in ONE batched LU (``traffic.stage_factors`` — shareable
    with the traffic sweep, which solves the transposed system) and runs
    the whole reverse recursion as ONE fused chain-substitution call
    (``ops.fused_chain_solve``, DESIGN.md §13).
    """
    solver = resolve_solver(solver, phi.e.shape[-1], inst)
    if solver not in ("batched_lu", "sparse"):
        return jax.vmap(
            lambda pe, pc, L_a, w_a: _per_app_dense(inst, Dp, Cp, pe, pc, L_a, w_a)
        )(phi.e, phi.c, inst.L, inst.w)

    # One fused call consumes the whole (A, K1, V, V) stage stack, walking
    # k in reverse: pdt_k = (I - Phi_k)^-1 (base_k + phi_c_k * pdt_{k+1})
    # with base_k = [link term] + phi_c_k * w_k * wnode * C' and the
    # nonnegativity clamp applied inside the fused sweep.
    link_term = jnp.einsum(
        "akij,akij->aki", phi.e, inst.L[:, :, None, None] * Dp[None, None],
        precision=HIGHEST)  # (A, K1, V): sum_j phi_ij L_k D'_ij
    base = link_term + phi.c * (
        inst.w[:, :, None] * inst.wnode[None, None] * Cp[None, None])
    if solver == "sparse":
        return ops.sparse_chain_solve(
            ops.sparse_topo(inst), phi.e, base, phi.c, trans=0,
            reverse=True, clamp=True)
    if fact is None:
        fact = stage_factors(phi.e)
    return ops.fused_chain_solve(fact, base, phi.c, trans=0, reverse=True,
                                 clamp=True)


def _per_app_dense(inst, Dp, Cp, phi_e_a, phi_c_a, L_a, w_a):
    """Seed-path per-app recursion (dense per-stage solves) — the
    differential reference for solver="batched_lu"."""
    link_term = jnp.einsum(
        "kij,kij->ki", phi_e_a, L_a[:, None, None] * Dp[None],
        precision=HIGHEST)

    def step(pdt_next, xs):
        phi_e_k, phi_c_k, lt_k, w_k = xs
        b = lt_k + phi_c_k * (w_k * inst.wnode * Cp + pdt_next)
        V = phi_e_k.shape[0]
        pdt_k = jnp.linalg.solve(jnp.eye(V, dtype=b.dtype) - phi_e_k, b)
        pdt_k = jnp.maximum(pdt_k, 0.0)
        return pdt_k, pdt_k

    zero = jnp.zeros(inst.V, dtype=phi_e_a.dtype)
    _, pdt_a = jax.lax.scan(
        step, zero, (phi_e_a, phi_c_a, link_term, w_a), reverse=True
    )
    return pdt_a


def marginals(
    inst: Instance,
    phi: Phi,
    fl: Flows | None = None,
    fact: Optional[ops.BatchedLU] = None,
    *,
    solver: str = "auto",
    axis: Optional[str] = None,
) -> Marginals:
    """All marginal quantities for strategy phi.

    The marginal recursion itself is local to an application shard (stage
    coupling never crosses applications); only the measured ``F``/``G``
    inside ``fl`` are network-wide.  Callers under ``shard_map`` either
    pass a pre-reduced ``fl`` (``core/engine.py`` does) or set ``axis`` so
    the internally computed flows psum-reduce over the app shards.
    """
    if fl is None:
        fl = flows(inst, phi, fact, solver=solver, axis=axis)
    Dp = link_marginals(inst, fl.F)
    Cp = comp_marginals(inst, fl.G)
    pdt = pdt_recursion(inst, phi, Dp, Cp, fact, solver=solver)

    # delta_ij (7), j != 0
    delta_e = inst.L[:, :, None, None] * Dp[None, None] + pdt[:, :, None, :]
    delta_e = jnp.where(inst.adj[None, None], delta_e, BIG)

    # delta_i0 (7): needs pdt at stage k+1 (zero beyond the last stage)
    pdt_next = jnp.concatenate(
        [pdt[:, 1:, :], jnp.zeros_like(pdt[:, :1, :])], axis=1
    )
    delta_c = inst.w[:, :, None] * inst.wnode[None, None] * Cp[None, None] + pdt_next
    delta_c = jnp.where(inst.cpu_allowed()[:, :, None], delta_c, BIG)

    return Marginals(pdt=pdt, delta_e=delta_e, delta_c=delta_c, Dp=Dp, Cp=Cp)


def dD_dphi(inst: Instance, phi: Phi) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Closed-form dD/dphi_ij(a,k) of eq. (3): t_i(a,k) * delta_ij(a,k).

    Returns (grad_e (A,K1,V,V), grad_c (A,K1,V)).  Cross-validated against
    jax.grad in tests/test_marginals.py.
    """
    fl = flows(inst, phi)
    m = marginals(inst, phi, fl)
    grad_e = fl.t[..., None] * jnp.where(inst.adj[None, None], m.delta_e, 0.0)
    grad_c = fl.t * jnp.where(inst.cpu_allowed()[:, :, None], m.delta_c, 0.0)
    return grad_e, grad_c
