"""Stage traffic, link flows and computation workloads (Section II).

Given a forwarding/offloading strategy ``phi`` the stage traffics
``t_i(a,k)`` satisfy the linear fixed points

    t(a,0) = Phi_0^T t(a,0) + r(a)
    t(a,k) = Phi_k^T t(a,k) + g(a,k-1),       g(a,k) = t(a,k) * phi_c(a,k)

(one next-stage packet per computed packet).  For loop-free strategies
``I - Phi^T`` is nonsingular (spectral radius < 1), so each stage is a dense
linear solve; the chain coupling is a ``lax.scan`` over k, and applications
are vmapped.  This is the synchronous, vectorized equivalent of the paper's
per-packet flow propagation.

The default solver path batches all (app, stage) factorizations into ONE
``(A*K1, V, V)`` LU (``stage_factors`` -> ``kernels.ops.batched_factor``)
and then consumes the whole factor stack in ONE fused chain-substitution
call (``ops.fused_chain_solve`` — per-stage padding/transpose/permutation
costs hoisted out of the scan, DESIGN.md §13).  The same factors serve the
marginal recursion (``core/marginals.py``) because its matrix
``I - Phi_k`` is this one un-transposed — one factorization per GP step
covers both sweeps (DESIGN.md §12).  ``solver="dense"`` keeps the seed's
per-stage ``jnp.linalg.solve`` as the differential reference.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.network import Instance
from repro.kernels import ops

# Full float32 contractions on every backend: the TPU's default matmul
# precision is one bfloat16 pass, which would change flows and costs.
HIGHEST = jax.lax.Precision.HIGHEST


class Phi(NamedTuple):
    """Forwarding/offloading strategy (the optimization variable).

    e: (A, K1, V, V)  phi_{ij}(a,k) link-forwarding fractions
    c: (A, K1, V)     phi_{i0}(a,k) local-CPU offloading fractions
    """

    e: jnp.ndarray
    c: jnp.ndarray


class Flows(NamedTuple):
    t: jnp.ndarray   # (A, K1, V)    stage traffic t_i(a,k)
    g: jnp.ndarray   # (A, K1, V)    CPU rates g_i(a,k)
    f: jnp.ndarray   # (A, K1, V, V) link rates f_ij(a,k)
    F: jnp.ndarray   # (V, V)        total link bit-rates
    G: jnp.ndarray   # (V,)          total computation workloads


def _solve_stage(phi_e_k: jnp.ndarray, inject: jnp.ndarray) -> jnp.ndarray:
    """Solve t = Phi_k^T t + inject for one (application, stage)."""
    V = phi_e_k.shape[0]
    mat = jnp.eye(V, dtype=phi_e_k.dtype) - phi_e_k.T
    return jnp.linalg.solve(mat, inject)


# Below this node count the CPU fallback's batched factor+substitution is
# dispatch-bound and loses to the per-stage dense solve.  On TPU the Pallas
# kernel path is always preferred.  The historical hand-measured value;
# used whenever BENCH_gp.json rows are unavailable.
_AUTO_MIN_V_FALLBACK = 48


def _derive_auto_min_v(rows: Optional[list] = None,
                       backend: Optional[str] = None) -> int:
    """Dense-vs-batched crossover V, derived from committed bench rows.

    Reads the repo's BENCH_gp.json ``gp_scaling``/``batched_lu`` rows
    (each carries the measured batched-over-dense ``speedup`` at one V)
    and linearly interpolates the V where the speedup crosses 1.0.  The
    committed measurements put the crossover well below the old hardcoded
    48 (0.95x already at V=22), so deriving it here fixes the small-V
    dispatch regression without baking in another magic constant.

    The crossover is *per backend*: rows carry a ``backend`` key (rows
    recorded before the key existed count as ``"cpu"``), and only rows
    measured on the current backend (default ``jax.default_backend()``)
    enter the interpolation — a CPU-measured crossover says nothing about
    GPU/TPU dispatch.  Any failure — file missing (installed package), no
    rows for this backend, no crossing bracketed — falls back to
    :data:`_AUTO_MIN_V_FALLBACK`.  ``rows`` injects a row list directly
    (tests); default None reads the file.
    """
    import json
    import os

    if backend is None:
        backend = jax.default_backend()
    if rows is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "..", "..", "BENCH_gp.json")
        try:
            with open(path) as fh:
                rows = json.load(fh)["rows"]
        except (OSError, ValueError, KeyError):
            return _AUTO_MIN_V_FALLBACK
    pts = sorted(
        {int(r["V"]): float(r["speedup"])
         for r in rows
         if r.get("bench") == "gp_scaling"
         and r.get("solver") == "batched_lu"
         and r.get("backend", "cpu") == backend
         and "V" in r and "speedup" in r}.items())
    if len(pts) < 2:
        return _AUTO_MIN_V_FALLBACK
    if pts[0][1] >= 1.0:
        return pts[0][0]          # batched wins from the smallest measured V
    for (v1, s1), (v2, s2) in zip(pts, pts[1:]):
        if s1 < 1.0 <= s2:
            frac = (1.0 - s1) / (s2 - s1)
            return max(2, int(-(-(v1 + frac * (v2 - v1)) // 1)))
    return _AUTO_MIN_V_FALLBACK   # never crosses in the measured range


AUTO_MIN_V = _derive_auto_min_v()

# Minimum node count for "auto" to prefer the sparse fixed-point solver when
# an instance carries a sparse topology (network.with_sparse).  Below this
# the dense paths win — the sweeps are dispatch-bound and the V^3/V^2 work
# they avoid is small; at metro scale (V >= several hundred at O(V) edges)
# the sparse path is the only viable one (DESIGN.md §18).  Parity tests
# force solver="sparse" explicitly, so the threshold only steers "auto".
SPARSE_MIN_V = 128


def resolve_solver(solver: str, V: int, inst: Optional[Instance] = None
                   ) -> str:
    """Resolve the "auto" stage-solver policy to a concrete method.

    V is a static (shape-derived) quantity — and whether ``inst`` carries a
    sparse topology is static pytree structure — so the choice is made at
    trace time and each jitted program contains exactly one solver path.
    "auto" resolves to "sparse" when the instance carries a sparse topology
    and V >= :data:`SPARSE_MIN_V`; otherwise to "batched_lu"/"dense" by the
    per-backend bench-derived crossover :data:`AUTO_MIN_V`.
    """
    if solver != "auto":
        return solver
    if inst is not None and inst.has_sparse and V >= SPARSE_MIN_V:
        return "sparse"
    return "batched_lu" if (not ops.INTERPRET or V >= AUTO_MIN_V) else "dense"


def stage_factors(phi_e: jnp.ndarray) -> ops.BatchedLU:
    """Batched LU of every stage system ``I - Phi_k`` in one device call.

    phi_e (A, K1, V, V) -> BatchedLU with leading dims (A, K1).  The factors
    serve BOTH linear sweeps of a GP iteration: the traffic fixed point
    solves the transposed system (``trans=1``) and the marginal recursion
    the plain one (``trans=0``), so ``gp.gp_step`` factors once and shares
    (DESIGN.md §12).  Per-member condition flags live in ``.ok``; singular
    members (loopy candidates) yield non-finite solves that
    ``traffic_is_valid`` rejects, exactly like the dense path.
    """
    V = phi_e.shape[-1]
    mats = jnp.eye(V, dtype=phi_e.dtype) - phi_e
    return ops.batched_factor(mats)


def stage_traffic(
    inst: Instance,
    phi: Phi,
    fact: Optional[ops.BatchedLU] = None,
    *,
    solver: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compute t (A,K1,V) and g (A,K1,V) by scanning the chain.

    solver="batched_lu" consumes ``fact`` (or factors all stages in one
    batched LU) and runs O(V^2) triangular solves per scan step;
    solver="sparse" runs the factorization-free neighbor-list fixed-point
    sweeps (requires ``inst.has_sparse``; O(E) per sweep, DESIGN.md §18);
    solver="dense" is the seed's per-stage ``jnp.linalg.solve`` reference;
    solver="auto" (default) picks per backend/size (``resolve_solver``).
    """
    solver = resolve_solver(solver, phi.e.shape[-1], inst)
    if solver in ("batched_lu", "sparse"):
        # One fused call consumes the whole (A, K1, V, V) stage stack:
        # t_k = (I - Phi_k)^-T (base_k + mult_k * t_{k-1}) with base_0 = r,
        # base_{k>0} = 0 and mult_k = phi_c_{k-1} (each computed packet of
        # stage k-1 injects one next-stage packet).  NOTE: no clamping — the
        # map phi -> t must stay exactly linear so closed-form marginals
        # (3)-(4) match autodiff and finite differences
        # (tests/test_marginals.py); divergent solutions from loopy
        # candidate strategies are rejected by ``traffic_is_valid`` instead.
        base = jnp.concatenate(
            [inst.r[:, None, :], jnp.zeros_like(phi.c[:, 1:])], axis=1)
        mult = jnp.concatenate(
            [jnp.zeros_like(phi.c[:, :1]), phi.c[:, :-1]], axis=1)
        if solver == "sparse":
            t = ops.sparse_chain_solve(
                ops.sparse_topo(inst), phi.e, base, mult, trans=1)
        else:
            if fact is None:
                fact = stage_factors(phi.e)
            t = ops.fused_chain_solve(fact, base, mult, trans=1)
        return t, t * phi.c

    def per_app(phi_e_a, phi_c_a, r_a):
        def step(inject, xs):
            phi_e_k, phi_c_k = xs
            t_k = _solve_stage(phi_e_k, inject)
            g_k = t_k * phi_c_k
            return g_k, (t_k, g_k)

        _, (t_a, g_a) = jax.lax.scan(step, r_a, (phi_e_a, phi_c_a))
        return t_a, g_a

    return jax.vmap(per_app)(phi.e, phi.c, inst.r)


def flows(
    inst: Instance,
    phi: Phi,
    fact: Optional[ops.BatchedLU] = None,
    *,
    solver: str = "auto",
    axis: Optional[str] = None,
) -> Flows:
    """All flow quantities induced by strategy phi (Table I).

    ``axis`` parameterizes the ONE network-wide measurement of the model:
    total link flows ``F_ij`` and workloads ``G_i`` are sums over *all*
    applications, so when the application axis is sharded over a mesh axis
    (core/distributed.py) the local partial sums are all-reduced with
    ``lax.psum(_, axis)`` — the paper's implicit all-reduce of locally
    measured flows.  ``axis=None`` (default, single device) keeps the
    plain einsum sums.  Per-application quantities ``t``/``g``/``f`` stay
    local to the shard either way.
    """
    t, g = stage_traffic(inst, phi, fact, solver=solver)
    f = t[..., None] * phi.e                                  # (A,K1,V,V)
    F = jnp.einsum("ak,akij->ij", inst.L, f, precision=HIGHEST)
    G = jnp.einsum("ak,aki->i", inst.w, g, precision=HIGHEST) * inst.wnode
    if axis is not None:
        F = jax.lax.psum(F, axis)
        G = jax.lax.psum(G, axis)
    return Flows(t=t, g=g, f=f, F=F, G=G)


def traffic_is_valid(inst: Instance, t: jnp.ndarray, *,
                     axis: Optional[str] = None) -> jnp.ndarray:
    """Scalar bool: t is a physical (loop-free) traffic solution.

    For a loop-free strategy, flow conservation bounds every stage traffic
    by the application's total injected rate; a routing loop makes the
    Neumann series diverge and the linear solve returns values far outside
    that bound (or non-finite).

    Under app sharding (``axis`` names the mesh axis) the bound uses the
    globally maximal injected rate (``pmax``) and the verdict is the
    all-shard AND, so the sharded vote matches the single-device check on
    the full application set.
    """
    rmax = jnp.max(jnp.sum(inst.r, axis=1))
    if axis is not None:
        rmax = jax.lax.pmax(rmax, axis)
    bound = 4.0 * rmax + 1.0
    finite = jnp.all(jnp.isfinite(t))
    ok = finite & jnp.all(t > -1e-3) & jnp.all(t < bound)
    if axis is not None:
        ok = jax.lax.pmax(jnp.where(ok, 0, 1), axis) == 0
    return ok


def total_cost(inst: Instance, phi: Phi, *, solver: str = "auto",
               axis: Optional[str] = None) -> jnp.ndarray:
    """Objective of problem (2): D(phi) = sum D_ij(F_ij) + sum C_i(G_i).

    With ``axis`` set, F/G are psum-reduced over the app shards first, so
    every shard returns the identical replicated global objective.
    """
    fl = flows(inst, phi, solver=solver, axis=axis)
    D_links = jnp.where(inst.adj, costs.cost(inst.link_kind, fl.F, inst.link_param), 0.0)
    C_nodes = costs.cost(inst.comp_kind, fl.G, inst.comp_param)
    return jnp.sum(D_links) + jnp.sum(C_nodes)


def link_marginals(inst: Instance, F: jnp.ndarray) -> jnp.ndarray:
    """D'_ij(F_ij), zero on non-links."""
    m = costs.marginal(inst.link_kind, F, inst.link_param)
    return jnp.where(inst.adj, m, 0.0)


def comp_marginals(inst: Instance, G: jnp.ndarray) -> jnp.ndarray:
    """C'_i(G_i)."""
    return costs.marginal(inst.comp_kind, G, inst.comp_param)


def renormalize(inst: Instance, phi: Phi) -> Phi:
    """Project phi back onto the simplex constraints (1), fixing drift.

    Non-negative clip then rescale each (a,k,i) row to sum 1, except
    degenerate rows (stage K_a at the destination / invalid stages) which
    are forced to zero; CPU fractions at the final stage are forced to zero.
    """
    e = jnp.where(inst.adj[None, None], jnp.maximum(phi.e, 0.0), 0.0)
    c = jnp.maximum(phi.c, 0.0) * inst.cpu_allowed()[:, :, None]
    tot = e.sum(-1) + c                                       # (A,K1,V)
    degen = inst.degenerate_mask()
    scale = jnp.where(degen | (tot <= 0), 0.0, 1.0 / jnp.maximum(tot, 1e-30))
    return Phi(e=e * scale[..., None], c=c * scale)


def repair_phi(inst: Instance, phi: Phi, seed_phi: Optional[Phi] = None,
               *, min_mass: float = 1e-3) -> Phi:
    """Project a live strategy onto a (possibly changed) instance.

    The online repair primitive (Section IV adaptivity; DESIGN.md §16):
    after a topology event — link/node failure, application
    arrival/departure — a previously feasible ``phi`` may carry mass on
    directions that no longer exist.  ``repair_phi``

      1. zeroes mass on dead links (``~adj``) and disallowed CPU rows,
      2. reseeds rows that lost (almost) all their mass — total remaining
         mass ``<= min_mass`` on a non-degenerate row — from ``seed_phi``,
      3. renormalizes back onto the simplex constraints (1).

    ``seed_phi`` should be a loop-free strategy valid for the NEW instance
    (callers use ``gp.init_phi(new_inst)``, the uncongested shortest-path
    strategy).  Without one, the fallback seeds full local offloading where
    the CPU direction exists and a uniform spread over the surviving
    out-links at final stages; the fallback keeps the output on the simplex
    but — unlike a shortest-path seed — cannot guarantee loop-freedom of
    the seeded rows, so prefer passing ``seed_phi``.

    The threshold matters: a row that kept only a sliver of mass (say
    ``1e-4`` on one surviving link) would be rescaled to route *everything*
    there, which is feasible but can be a terrible (even invalid-traffic)
    starting point; reseeding such rows instead costs nothing and keeps the
    warm start loop-free.  Rows above the threshold rescale as usual —
    that is exactly the renormalize repair the paper's adaptivity argument
    relies on.

    Invariants (property-tested in tests/test_online_properties.py): the
    output satisfies constraint (1) exactly (``feasibility_violation`` ~ 0),
    carries zero mass on non-links, and zero CPU mass where offloading is
    not allowed.
    """
    e = jnp.where(inst.adj[None, None], jnp.maximum(phi.e, 0.0), 0.0)
    c = jnp.maximum(phi.c, 0.0) * inst.cpu_allowed()[:, :, None]
    tot = e.sum(-1) + c
    empty = (tot <= min_mass) & ~inst.degenerate_mask()        # (A,K1,V)
    if seed_phi is None:
        cpu_ok = inst.cpu_allowed()[:, :, None]                # (A,K1,1)
        out_deg = jnp.maximum(inst.adj.sum(-1, keepdims=True), 1)
        uniform = inst.adj.astype(e.dtype) / out_deg           # (V,V)
        seed_e = jnp.where(cpu_ok[..., None], 0.0,
                           jnp.broadcast_to(uniform[None, None], e.shape))
        seed_c = jnp.broadcast_to(cpu_ok.astype(c.dtype), c.shape)
        seed_phi = Phi(e=seed_e, c=seed_c)
    e = jnp.where(empty[..., None], seed_phi.e, e)
    c = jnp.where(empty, seed_phi.c, c)
    return renormalize(inst, Phi(e=e, c=c))


def feasibility_violation(inst: Instance, phi: Phi) -> jnp.ndarray:
    """Max violation of constraint (1) — for tests and invariant checks."""
    tot = phi.e.sum(-1) + phi.c
    degen = inst.degenerate_mask()
    want = jnp.where(degen, 0.0, 1.0)
    return jnp.max(jnp.abs(tot - want))


class StrategyViolations(NamedTuple):
    """Per-invariant maxima of a live strategy (the §17 guardrail checks).

    Every field is a scalar; an exactly-feasible strategy reports 0 (or
    ``False``) everywhere.  ``nonfinite`` is the hard-corruption flag: any
    nan/inf entry in phi poisons every downstream flow measurement, so it
    is reported separately from the magnitude checks (whose comparisons a
    nan would silently pass).
    """

    simplex: jnp.ndarray         # max |row sum - expected| over (a,k,i)
    dead_link_mass: jnp.ndarray  # max phi.e mass on (i,j) not in E
    dead_app_mass: jnp.ndarray   # max mass on rows of dead/padded apps
    cpu_mass: jnp.ndarray        # max phi.c where offloading is disallowed
    nonfinite: jnp.ndarray       # bool: any non-finite entry in phi


def strategy_violations(inst: Instance, phi: Phi) -> StrategyViolations:
    """Measure every runtime strategy invariant in one jittable call.

    The numeric core of ``serve.online.OnlineSolver.verify_fleet``
    (DESIGN.md §17): simplex rows (constraint (1)), zero mass on dead
    links, zero mass on dead/padded application rows, zero CPU mass where
    offloading is disallowed, and finiteness of every entry.  Pure and
    vmappable, so fleet-wide checks batch into one device program.
    """
    live_app = inst.stage_mask.any(axis=1)                   # (A,)
    dead_e = jnp.where(inst.adj[None, None], 0.0, jnp.abs(phi.e))
    dead_rows = jnp.where(live_app[:, None, None], 0.0,
                          jnp.abs(phi.e).sum(-1) + jnp.abs(phi.c))
    bad_c = jnp.where(inst.cpu_allowed()[:, :, None], 0.0, jnp.abs(phi.c))
    finite = jnp.all(jnp.isfinite(phi.e)) & jnp.all(jnp.isfinite(phi.c))
    return StrategyViolations(
        simplex=feasibility_violation(inst, phi),
        dead_link_mass=jnp.max(dead_e),
        dead_app_mass=jnp.max(dead_rows),
        cpu_mass=jnp.max(bad_c),
        nonfinite=~finite,
    )


def capacity_slack(inst: Instance, F: jnp.ndarray) -> jnp.ndarray:
    """Min over links of ``theta * capacity - F`` (the M/M/1 headroom).

    Negative slack means some link operates beyond the modelled queueing
    region (``costs.saturated``) — the strategy is still *feasible* (the
    quadratic cost extension keeps costs finite) but the served delay no
    longer tracks the M/M/1 model, which is the "capacity slack" guardrail
    of DESIGN.md §17.  LINEAR links have no capacity; instances whose link
    family is LINEAR report ``+inf``.
    """
    if inst.link_kind == costs.LINEAR:
        return jnp.asarray(jnp.inf, dtype=F.dtype)
    slack = jnp.where(inst.adj, costs._THETA * inst.link_param - F, jnp.inf)
    return jnp.min(slack)
