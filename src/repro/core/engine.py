"""One GP step engine: the fused Algorithm-1 iteration, shared by all drivers.

The paper's iteration is node-parallel with exactly ONE network-wide
coupling: the measured total link flows ``F_ij`` and workloads ``G_i``.
This module owns the full fused iteration — stage factorization (one
batched LU per step, ``traffic.stage_factors``), the fused forward/reverse
chain sweeps (``ops.fused_chain_solve``), the bitset blocked sets
(``ops.blocked_tagged``), the blocked-node fallback, the stepsize-ladder
projection + renormalize, and the cost/residual bookkeeping — and is
parameterized over how that one measurement is reduced:

  * ``axis=None``   — plain sums over the whole application axis; this is
                      the single-device path ``gp.gp_step`` / ``gp.solve*``
                      wrap.
  * ``axis="name"`` — ``lax.psum`` over the named mesh axis the application
                      dimension is sharded on; this is the ``shard_map``
                      path ``distributed.solve_sharded*`` wraps (the paper's
                      implicit all-reduce of locally measured flows).

Everything except the F/G reduction, the traffic-validity vote and the
residual max is local to an application shard, so both paths execute the
same fused kernels and produce matching cost trajectories (DESIGN.md §14,
tests/test_distributed.py).

``scan_chunk`` is the shared chunked-scan loop body with the on-device
early-stop latch (DESIGN.md §10); the single-device drivers jit it
directly, the mesh driver runs it inside ``shard_map`` (optionally under
``jax.vmap`` for mesh-composed scenario families).

The engine additionally owns the *convergence-acceleration layer*
(DESIGN.md §15), toggled per mechanism by an :class:`AccelConfig` carried
as a static argument so every driver — single-device, vmapped-batched,
shard_map-sharded and warm-start-chained — gets it for free:

  * **Anderson mixing over phi** — a small (x, f) history window in the
    scan carry, least-squares residual combination, safeguarded by the
    existing projection + cost check (a mixed iterate that leaves the
    flow-conservation simplex or increases cost falls back to the plain
    GP step);
  * **adaptive per-member stepsize** — the fixed 12-rung ladder is
    replaced by a short ladder centered on a carry-resident alpha that
    grows/shrinks with the observed winning rung;
  * **sufficiency-residual stopping** — the residual latch uses the exact
    ``conditions.sufficiency_residual`` form, with a phi-delta fixed-point
    latch as the fallback stop.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core import traffic as traffic_mod
from repro.core.marginals import BIG, marginals
from repro.core.network import Instance
from repro.core.traffic import (
    Phi, flows, renormalize, total_cost, traffic_is_valid,
)
from repro.kernels import blocked_sets as blocked_sets_mod
from repro.kernels import ops
from repro.obs.device import (
    COL_ALPHA, COL_ANDERSON, COL_BS_ROUNDS, COL_COST, COL_ITER,
    COL_PHI_DELTA, COL_RESIDUAL, COL_RUNG, TEL_WIDTH, TelemetryConfig,
    empty_ring, resolve_telemetry, ring_record,
)

TIE_EPS = 1e-6      # directions within this of the min-delta receive mass
BLOCK_EPS = 1e-7    # strictness slack for pdt comparisons

# Backtracking multipliers tried each iteration (vmapped inside the jitted
# step).  The paper assumes a "sufficiently small" fixed alpha (Theorem 2 /
# [11]); with congestion-level queue marginals (D' ~ 1e6 near saturation) a
# fixed alpha either diverges or crawls, so we evaluate the same projection
# direction at several stepsizes and keep the best — a monotone-descent
# safeguard that preserves the convergence argument (descent + stationarity
# of condition (6)).  Multiplier 0 is included so the cost never increases.
ALPHA_LADDER = tuple(4.0 ** (1 - k) for k in range(11)) + (0.0,)


class AccelConfig(NamedTuple):
    """Static toggles of the §15 convergence-acceleration layer.

    Hashable (ints/floats/bools only) so it rides as a jit static argument
    and an ``lru_cache`` key for the mesh chunk programs; each distinct
    config compiles its own program, exactly like ``solver=``/``blocked=``.

      anderson_m     history window of the Anderson mixer (0 disables it)
      adaptive_alpha per-member adaptive stepsize replacing the fixed ladder
      residual_stop  exact sufficiency residual + phi-delta fixed-point stop
      phi_tol        phi-delta latch: a committed positive-stepsize move of
                     max|dphi| <= phi_tol means the projection map reached
                     its fixed point (set < 0 to disable)
      anderson_reg   relative Tikhonov regularization of the LS Gram matrix
      alpha_grow / alpha_shrink / alpha_min / alpha_max
                     the short adaptive ladder evaluates multipliers
                     (grow, 1, shrink, 0) on the carry alpha; the winner
                     becomes the next alpha (clipped), a 0-rung win shrinks

    Defaults are tuned on the fig5/fig6 families: Anderson(m=5) + the
    exact-residual/fixed-point stop cut total iterations ~2.4-3.4x at
    matching costs.  ``adaptive_alpha`` defaults OFF: the short 4-rung
    ladder saves 8 candidate evaluations per iteration but on congested
    instances (fig6 r>=1.5) it chases the stepsize instead of line-searching
    the full 12-rung ladder, costing more iterations than it saves — opt in
    per call when per-iteration cost dominates.
    """

    anderson_m: int = 5
    adaptive_alpha: bool = False
    residual_stop: bool = True
    phi_tol: float = 1e-6
    anderson_reg: float = 1e-8
    alpha_grow: float = 2.0
    alpha_shrink: float = 0.25
    alpha_min: float = 1e-6
    alpha_max: float = 64.0


# The tuned default config callers opt into with accel=True/"default".
DEFAULT_ACCEL = AccelConfig()


def resolve_accel(accel) -> Optional[AccelConfig]:
    """None/False -> None (legacy exact path); True/"default"/"on" ->
    :data:`DEFAULT_ACCEL`; an :class:`AccelConfig` passes through."""
    if accel is None or accel is False:
        return None
    if accel is True or accel in ("default", "on"):
        return DEFAULT_ACCEL
    if isinstance(accel, AccelConfig):
        return accel
    raise TypeError(f"accel must be None/bool/'default'/AccelConfig, got {accel!r}")


class GPState(NamedTuple):
    phi: Phi
    cost: jnp.ndarray
    residual: jnp.ndarray    # sufficiency-condition residual (0 => optimal)
    alpha: jnp.ndarray | float = 0.0   # stepsize the winning ladder rung used
    rung: jnp.ndarray | int = 0        # winning ladder-rung index
    bs_rounds: jnp.ndarray | int = -1  # blocked-set sweep rounds (§19; -1 off)


class ScanCarry(NamedTuple):
    """Carry of the chunked GP scan (DESIGN.md §10, accel fields §15,
    telemetry ring §19).

    The accel fields and the telemetry ring are zero-size placeholders
    when the matching mechanism is off (the carry pytree structure is
    fixed per static config, so the scan body simply never touches them):

      alpha    f32 scalar, the member's adaptive stepsize (0 = unseeded —
               the first iteration adopts the driver's ``alpha`` argument)
      ax / af  (m, N) ring buffers of the last m flattened iterates and
               plain-step residuals (newest last); under ``jax.vmap`` these
               gain the member axis, under ``shard_map`` the N axis holds
               the shard-local app slab (opaque, roundtripped per shard)
      ak       int32, #history pairs pushed so far
      tb       (R, TEL_WIDTH) f32 iteration-telemetry ring (§19): one row
               per committed iteration, write index = ``iters`` (both are
               masked by the ``done`` freeze and zeroed together by
               ``reset_carry``), truncating — not wrapping — past R.
               Every column is replicated under ``shard_map`` (values
               derive from the psum-reduced F/G or are pmax-reduced), so
               the ring travels with a replicated spec.
    """

    phi: Phi
    best_cost: jnp.ndarray   # float32, monotone-descent tracker
    stall: jnp.ndarray       # int32, iterations without improvement
    done: jnp.ndarray        # bool, early-stop latch
    iters: jnp.ndarray       # int32, #iterations committed so far
    cost: jnp.ndarray        # float32, last committed cost
    residual: jnp.ndarray    # float32, last committed residual
    alpha: jnp.ndarray       # float32, adaptive stepsize carry (§15)
    ax: jnp.ndarray          # (m, N) Anderson iterate history (§15)
    af: jnp.ndarray          # (m, N) Anderson residual history (§15)
    ak: jnp.ndarray          # int32, Anderson history count (§15)
    tb: jnp.ndarray          # (R, TEL_WIDTH) telemetry ring (§19)


def _pmax(x: jnp.ndarray, axis: Optional[str]) -> jnp.ndarray:
    return x if axis is None else jax.lax.pmax(x, axis)


# ---------------------------------------------------------------------------
# Blocked node sets
# ---------------------------------------------------------------------------

# Node count above which "bitset" auto-upgrades to the padded-neighbor-list
# tagged sweep when the instance carries a sparse topology.  The two are
# bit-equal (the sweep is the same monotone fixed point); the neighbor form
# does O(E) work per round instead of O(V^2), which is what matters at metro
# scale.  Matches traffic.SPARSE_MIN_V in spirit but kept separate — the
# tagged sweep's crossover is independent of the stage-solver crossover.
_NBR_AUTO_MIN_V = 128


def _tagged_nbr_sharded(route: jnp.ndarray, improper: jnp.ndarray,
                        nbr: jnp.ndarray, mask: jnp.ndarray,
                        node_axis: str, node_shards: int, *,
                        with_rounds: bool = False):
    """Node-parallel tagged sweep: each node shard owns a V/n row slab.

    The category-3 fixed point tagged[p] = ∃d: route[p,d] & (improper[p,d]
    | tagged[nbr[p,d]]) reads arbitrary *columns* (successor nodes) but
    writes only its own rows, so under a node-space mesh axis each shard
    sweeps its contiguous row slab (O(E/n) per round) and the slabs are
    re-assembled with one ``all_gather`` of the (A,K1,V) boolean frontier
    per round — the §18 2-D-mesh realization of the paper's node-parallel
    broadcast.  Monotone fixed point ⇒ bit-equal to the dense/replicated
    sweeps; the exact-settle loop exits at the shared fixed point.

    ``with_rounds=True`` also returns the loop's round counter (§19
    telemetry).  The exit test reads the all-gathered full-V frontier, so
    the counter is identical on every node shard by construction.
    """
    V = route.shape[-1]
    rl = V // node_shards
    i0 = jax.lax.axis_index(node_axis) * rl
    route_l = jax.lax.dynamic_slice_in_dim(route, i0, rl, axis=-2)
    imp_l = jax.lax.dynamic_slice_in_dim(improper, i0, rl, axis=-2)
    nbr_l = jax.lax.dynamic_slice_in_dim(nbr, i0, rl, axis=0)
    mask_l = jax.lax.dynamic_slice_in_dim(mask, i0, rl, axis=0)
    idx = jnp.broadcast_to(nbr_l, route_l.shape[:-1] + nbr_l.shape[-1:])
    rv = jnp.take_along_axis(route_l, idx, axis=-1) & mask_l
    iv = jnp.take_along_axis(imp_l, idx, axis=-1)
    seed_l = jnp.any(rv & iv, axis=-1)                       # (A,K1,rl)

    def sweep(t):
        tl = seed_l | jnp.any(rv & t[..., nbr_l], axis=-1)
        return jax.lax.all_gather(tl, node_axis, axis=-1, tiled=True)

    def cond(c):
        i, t, prev = c
        return jnp.any(t != prev) & (i < V + 1)

    def body(c):
        i, t, _ = c
        return i + 1, sweep(t), t

    t0 = jax.lax.all_gather(seed_l, node_axis, axis=-1, tiled=True)
    rounds, t, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), t0, jnp.zeros_like(t0) | True))
    if with_rounds:
        return t, rounds
    return t


def blocked_sets(inst: Instance, phi: Phi, pdt: jnp.ndarray,
                 method: str = "bitset", *,
                 node_axis: Optional[str] = None,
                 node_shards: int = 1,
                 with_rounds: bool = False):
    """(A,K1,V,V) bool: j in B_i(a,k).

    j is blocked for i at stage (a,k) if (Section IV "Blocked node set"):
      1) (i,j) not in E, or
      2) dD/dt_j(a,k) > dD/dt_i(a,k), or
      3) j's routing subtree for (a,k) contains an improper link (p,q)
         with dD/dt_q > dD/dt_p.

    Category 3 ("tagged" nodes) is a monotone boolean fixed point along the
    routing DAG.  method="bitset" (default) runs it through the bit-packed
    kernel — uint32-packed successor words, while-loop frontier early exit
    at the DAG diameter (kernels/blocked_sets.py, DESIGN.md §13);
    method="nbr" gathers along the instance's padded out-neighbor lists so
    each round costs O(E) (requires ``inst.has_sparse``; DESIGN.md §18) —
    "bitset" auto-upgrades to it at V >= 128 when the topology is attached,
    since the two are bit-equal; method="scan" keeps the seed's dense
    V-sweep ``lax.scan`` as the differential reference
    (tests/test_blocked_sets.py asserts bit-exact agreement — the early
    exit stops precisely at the shared fixed point).

    Entirely local to an application shard: the routing DAG of stage (a,k)
    never couples applications, so the mesh path calls this unchanged.

    ``with_rounds=True`` additionally returns the tagged sweep's settled
    round count (int32; -1 on paths without a counter — the dense scan and
    the pallas kernel).  The telemetry ring records it as the frontier-depth
    column (DESIGN.md §19); requesting it changes no blocking arithmetic.
    """
    route = phi.e > 0.0                                         # (A,K1,V,V)
    worse = pdt[:, :, None, :] > pdt[:, :, :, None] + BLOCK_EPS  # pdt_q > pdt_p
    improper = route & worse

    rounds = jnp.int32(-1)
    if (method == "bitset" and inst.has_sparse
            and inst.V >= _NBR_AUTO_MIN_V):
        method = "nbr"
    if method == "nbr":
        if (node_axis is not None and node_shards > 1
                and inst.V % node_shards == 0):
            res = _tagged_nbr_sharded(route, improper, inst.out_nbr,
                                      inst.out_mask, node_axis,
                                      node_shards, with_rounds=with_rounds)
            tagged, rounds = res if with_rounds else (res, rounds)
        elif with_rounds:
            tagged, rounds = ops.blocked_tagged_nbr(
                route, improper, inst.out_nbr, inst.out_mask,
                with_rounds=True)
        else:
            tagged = ops.blocked_tagged_nbr(route, improper,
                                            inst.out_nbr, inst.out_mask)
    elif method == "bitset":
        if with_rounds:
            tagged, rounds = ops.blocked_tagged(route, improper,
                                                with_rounds=True)
        else:
            tagged = ops.blocked_tagged(route, improper)
    else:
        tagged = blocked_sets_mod.tagged_scan_dense(route, improper)

    blocked = (~inst.adj[None, None]) | improper | worse | tagged[:, :, None, :]
    if with_rounds:
        return blocked, rounds
    return blocked


# ---------------------------------------------------------------------------
# One GP iteration (eqs. 8-10)
# ---------------------------------------------------------------------------

def _strategy_cost(inst: Instance, phi: Phi, solver: str,
                   axis: Optional[str]) -> jnp.ndarray:
    """Objective of a candidate strategy; inf when its traffic is invalid.

    Shared by the stepsize ladder and the Anderson safeguard: with ``axis``
    set, F/G psum-reduce over the app shards first, so every shard sees the
    identical replicated candidate cost (deterministic tie-breaks).
    """
    fl = flows(inst, phi, solver=solver, axis=axis)
    valid = traffic_is_valid(inst, fl.t, axis=axis)
    c_links = jnp.where(inst.adj, costs.cost(inst.link_kind, fl.F,
                                             inst.link_param), 0.0)
    c_nodes = costs.cost(inst.comp_kind, fl.G, inst.comp_param)
    cost = jnp.sum(c_links) + jnp.sum(c_nodes)
    return jnp.where(valid, cost, jnp.inf)


def gp_step(
    inst: Instance,
    phi: Phi,
    alpha: float,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    scaled: bool = False,
    solver: str = "auto",
    *,
    blocked: str = "bitset",
    axis: Optional[str] = None,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    accel: Optional[AccelConfig] = None,
    app_mask: Optional[jnp.ndarray] = None,
    telemetry: Optional[TelemetryConfig] = None,
) -> GPState:
    """One fused GP iteration; ``axis`` selects the F/G reduction (above).

    ``node_axis``/``node_shards`` name the second (node-space) mesh axis of
    the 2-D mesh (DESIGN.md §18): when set, the blocked-set tagged sweep
    runs node-parallel over row slabs (``_tagged_nbr_sharded``); all other
    per-iteration compute is replicated across the node shards, so the
    iteration stays bit-equal to the 1-D mesh and single-device paths.

    ``app_mask`` ((A,) bool, optional) freezes applications: where False,
    the committed strategy rows are the *incoming* ``phi`` rows regardless
    of what the projection proposes, and the reported residual ignores the
    frozen applications' directions.  The freeze is applied *inside* each
    ladder candidate before its flows are measured, so the evaluated costs
    are exactly the costs of the committed strategies — frozen applications
    still contribute their (unchanged) flows to the shared F/G measurement,
    which is what makes the restricted solve exact for the active set.
    This is the §16 residual skip gate (``serve/online.py``): applications
    whose sufficiency residual an event left below tolerance are frozen,
    re-checked after the active set converges, and unfrozen only if the
    active set's movement pushed them back above tolerance.
    """
    # One batched LU of every (app, stage) system per iteration: the traffic
    # sweep solves the transposed systems and the marginal recursion the
    # plain ones from the SAME factors (traffic.stage_factors, DESIGN.md
    # §12).  The sparse path is factorization-free — both sweeps run the
    # neighbor-list fixed point directly (§18).  The ladder's candidate
    # evaluations below factor their own (ladder, A, K1)-stacked batch
    # inside the vmap.  "auto" resolves per backend/size/topology at trace
    # time (traffic.resolve_solver).
    solver = traffic_mod.resolve_solver(solver, inst.V, inst)
    fact = traffic_mod.stage_factors(phi.e) if solver == "batched_lu" else None
    fl = flows(inst, phi, fact, solver=solver, axis=axis)
    m = marginals(inst, phi, fl, fact, solver=solver)

    want_rounds = telemetry is not None and telemetry.bs_rounds
    if want_rounds:
        bset, bs_rounds = blocked_sets(
            inst, phi, m.pdt, method=blocked,
            node_axis=node_axis, node_shards=node_shards, with_rounds=True)
        # per-app-shard sweeps may settle at different depths; report the
        # fleet-wide maximum so the ring column is replicated (§19)
        bs_rounds = _pmax(bs_rounds, axis)
    else:
        bset = blocked_sets(inst, phi, m.pdt, method=blocked,
                            node_axis=node_axis, node_shards=node_shards)
        bs_rounds = jnp.int32(-1)
    avail_e = inst.adj[None, None] & ~bset
    if allowed_e is not None:
        avail_e = avail_e & allowed_e
    avail_c = inst.cpu_allowed()[:, :, None]
    if allowed_c is not None:
        avail_c = avail_c & allowed_c

    delta_e = jnp.where(avail_e, m.delta_e, BIG)
    delta_c = jnp.where(avail_c, m.delta_c, BIG)
    min_delta = jnp.minimum(delta_e.min(-1), delta_c)           # (A,K1,V)

    # Fallback guard: if blocking removed every direction at a row that must
    # forward (can happen transiently on congested iterates), fall back to
    # the unblocked-by-topology direction set for that row.
    stuck = min_delta >= BIG / 2
    fb_e = jnp.where(inst.adj[None, None] & (allowed_e if allowed_e is not None else True), m.delta_e, BIG)
    fb_c = jnp.where(inst.cpu_allowed()[:, :, None] & (allowed_c if allowed_c is not None else True), m.delta_c, BIG)
    delta_e = jnp.where(stuck[..., None], fb_e, delta_e)
    delta_c = jnp.where(stuck, fb_c, delta_c)
    min_delta = jnp.minimum(delta_e.min(-1), delta_c)

    e_e = delta_e - min_delta[..., None]                        # e_ij >= 0
    e_c = delta_c - min_delta
    if scaled:
        # quasi-Newton diagonal scaling (the second-order speedup the paper
        # attributes to [5]): normalize the projection step by a curvature
        # surrogate so stepsizes are comparable across congestion levels.
        # D'' of the M/M/1 cost ~ 2 D'/(cap-F) ~ D'^2-scale; we use the
        # per-row marginal magnitude as the diagonal preconditioner.
        scale_row = jnp.maximum(jnp.abs(min_delta), 1e-6)
        e_e = e_e / scale_row[..., None]
        e_c = e_c / scale_row

    is_min_e = (e_e <= TIE_EPS) & (delta_e < BIG / 2)
    is_min_c = (e_c <= TIE_EPS) & (delta_c < BIG / 2)
    N = is_min_e.sum(-1) + is_min_c                             # (A,K1,V)

    # reductions: blocked directions surrender everything; positive-e
    # directions surrender min(phi, alpha * e)   (eq. 9)
    def apply(a):
        red_e = jnp.where(
            delta_e >= BIG / 2, phi.e,
            jnp.where(is_min_e, 0.0, jnp.minimum(phi.e, a * e_e)),
        )
        red_c = jnp.where(
            delta_c >= BIG / 2, phi.c,
            jnp.where(is_min_c, 0.0, jnp.minimum(phi.c, a * e_c)),
        )
        share = (red_e.sum(-1) + red_c) / jnp.maximum(N, 1)     # (A,K1,V)
        cand = renormalize(inst, Phi(
            e=phi.e - red_e + share[..., None] * is_min_e,
            c=phi.c - red_c + share * is_min_c,
        ))
        if app_mask is not None:
            # frozen apps keep their incoming rows; applied BEFORE the flow
            # measurement so the ladder costs what it would actually commit
            cand = Phi(
                e=jnp.where(app_mask[:, None, None, None], cand.e, phi.e),
                c=jnp.where(app_mask[:, None, None], cand.c, phi.c),
            )
        cand_fl = flows(inst, cand, solver=solver, axis=axis)
        valid = traffic_is_valid(inst, cand_fl.t, axis=axis)
        c_links = jnp.where(inst.adj, costs.cost(inst.link_kind, cand_fl.F, inst.link_param), 0.0)
        c_nodes = costs.cost(inst.comp_kind, cand_fl.G, inst.comp_param)
        cost = jnp.sum(c_links) + jnp.sum(c_nodes)
        return cand, jnp.where(valid, cost, jnp.inf)

    if accel is not None and accel.adaptive_alpha:
        # short adaptive ladder centered on the carry alpha (§15): probe one
        # growth rung, the current stepsize, one shrink rung, and 0 (the
        # monotone-descent floor); the caller feeds the winner back in.
        mults = (accel.alpha_grow, 1.0, accel.alpha_shrink, 0.0)
    else:
        mults = ALPHA_LADDER
    ladder = alpha * jnp.asarray(mults, dtype=jnp.float32)
    cands, cand_costs = jax.vmap(apply)(ladder)
    # a too-aggressive candidate can form a routing loop -> divergent traffic
    # fixed point -> inf/NaN cost; such candidates must lose the argmin.
    # cand_costs derive from the psum-reduced F/G, so every shard computes
    # the identical replicated ladder and picks the same argmin.
    cand_costs = jnp.where(jnp.isnan(cand_costs), jnp.inf, cand_costs)
    best = jnp.argmin(cand_costs)
    new_phi = jax.tree_util.tree_map(lambda x: x[best], cands)

    # residual of sufficiency condition (6) at the *incoming* iterate
    # ``phi``, from its marginals ``m`` (already at hand), not at the
    # ``new_phi`` this step returns; so a solve that stops on it returns the
    # strategy one step past the one it certified
    if accel is not None and accel.residual_stop:
        # exact conditions.sufficiency_residual form: the minimum is taken
        # over *all* directions, not the blocked-masked set, so the latch
        # agrees with the checker callers use to certify optimality.
        min_margin = jnp.minimum(m.delta_e.min(-1), m.delta_c)
        exc_e = jnp.where(phi.e > 1e-6, m.delta_e - min_margin[..., None], 0.0)
        exc_c = jnp.where(phi.c > 1e-6, m.delta_c - min_margin, 0.0)
    else:
        exc_e = jnp.where(phi.e > 1e-6, m.delta_e - min_delta[..., None], 0.0)
        exc_c = jnp.where(phi.c > 1e-6, m.delta_c - min_delta, 0.0)
    if app_mask is not None:
        # the stop latch must not wait on frozen apps: their drift is
        # re-checked by the caller's outer gate, not by this solve
        exc_e = jnp.where(app_mask[:, None, None, None], exc_e, 0.0)
        exc_c = jnp.where(app_mask[:, None, None], exc_c, 0.0)
    residual = _pmax(jnp.maximum(jnp.max(exc_e), jnp.max(exc_c)), axis)

    return GPState(phi=new_phi, cost=cand_costs[best], residual=residual,
                   alpha=ladder[best], rung=best, bs_rounds=bs_rounds)


# ---------------------------------------------------------------------------
# Anderson mixing helpers (§15)
# ---------------------------------------------------------------------------

def _flat_phi(phi: Phi) -> jnp.ndarray:
    """Flatten a (possibly shard-local) strategy into one f32 vector."""
    return jnp.concatenate(
        [phi.e.reshape(-1), phi.c.reshape(-1)]).astype(jnp.float32)


def _unflat_phi(vec: jnp.ndarray, like: Phi) -> Phi:
    ne = like.e.size
    return Phi(e=vec[:ne].reshape(like.e.shape).astype(like.e.dtype),
               c=vec[ne:].reshape(like.c.shape).astype(like.c.dtype))


def _anderson_mix(ax, af, ak, x_k, f_k, reg: float,
                  axis: Optional[str]) -> jnp.ndarray:
    """Type-II windowed Anderson combination of the fixed-point map g.

    Given the current evaluated pair ``(x_k, f_k)`` (``f = g(x) - x``, the
    plain GP step's displacement) and ring buffers of the last ``m`` pairs,
    solve the regularized least-squares problem

        min_gamma || f_k - sum_j gamma_j (f_k - f_j) ||

    via the (m, m) normal equations and return the mixed iterate

        x_mix = g_k - sum_j gamma_j (g_k - g_j),  g = x + f.

    Slots never written (``j < m - ak``) contribute zero rows; Tikhonov
    regularization keeps the Gram matrix invertible, and their gamma is
    masked to exactly 0.  Under ``axis`` the feature dimension N is the
    shard-local app slab, so the Gram matrix and right-hand side psum over
    the mesh axis — every shard then solves the identical (m, m) system
    and applies the identical gamma to its own slab.
    """
    m = ax.shape[0]
    valid = jnp.arange(m) >= (m - jnp.minimum(ak, m))            # (m,)
    dF = jnp.where(valid[:, None], f_k[None, :] - af, 0.0)       # (m, N)
    gram = jnp.dot(dF, dF.T, precision=traffic_mod.HIGHEST)          # (m, m)
    b = jnp.dot(dF, f_k, precision=traffic_mod.HIGHEST)              # (m,)
    if axis is not None:
        gram = jax.lax.psum(gram, axis)
        b = jax.lax.psum(b, axis)
    lam = reg * (jnp.trace(gram) / m) + 1e-12
    gamma = jnp.linalg.solve(gram + lam * jnp.eye(m, dtype=gram.dtype), b)
    gamma = jnp.where(valid, gamma, 0.0)
    g_k = x_k + f_k
    g_hist = ax + af                                             # (m, N)
    return g_k - jnp.dot(gamma, g_k[None, :] - g_hist,
                         precision=traffic_mod.HIGHEST)


def _push_history(buf: jnp.ndarray, row: jnp.ndarray) -> jnp.ndarray:
    """Drop the oldest ring-buffer row and append ``row`` (newest last)."""
    return jnp.roll(buf, -1, axis=0).at[-1].set(row)


# ---------------------------------------------------------------------------
# Chunked scan loop body (shared by gp.solve* and distributed.solve_sharded*)
# ---------------------------------------------------------------------------

def init_carry(inst: Instance, phi: Phi, *, solver: str = "auto",
               axis: Optional[str] = None,
               accel: Optional[AccelConfig] = None,
               telemetry: Optional[TelemetryConfig] = None) -> ScanCarry:
    cost0 = jnp.asarray(total_cost(inst, phi, solver=solver, axis=axis),
                        jnp.float32)
    m = accel.anderson_m if accel is not None else 0
    n = (phi.e.size + phi.c.size) if m > 0 else 0
    return ScanCarry(
        phi=phi,
        best_cost=cost0,
        stall=jnp.int32(0),
        done=jnp.asarray(False),
        iters=jnp.int32(0),
        cost=cost0,
        residual=jnp.float32(jnp.inf),
        alpha=jnp.float32(0.0),
        ax=jnp.zeros((m, n), jnp.float32),
        af=jnp.zeros((m, n), jnp.float32),
        ak=jnp.int32(0),
        tb=empty_ring(telemetry),
    )


def reset_carry(inst: Instance, phi: Phi, carry: ScanCarry, *,
                keep_window: bool = False, solver: str = "auto",
                axis: Optional[str] = None) -> ScanCarry:
    """Re-arm a converged carry for a new re-convergence (online events).

    Rebuilds the bookkeeping fields around the (possibly repaired) live
    strategy ``phi`` — fresh cost/best-cost at the *current* instance,
    cleared stall/done/iters latches — while optionally carrying the §15
    acceleration state across the event:

      * ``keep_window=True`` keeps the Anderson ring buffers and the
        adaptive stepsize.  Correct for *small rate deltas*: the stored
        (x, f) pairs were evaluated under the old rates, so the mixer's
        extrapolation is approximate, but the scan body's safeguard
        (projected-feasible AND no-worse-than-the-plain-step, costed under
        the NEW instance) rejects any mix the stale history misleads —
        descent is preserved, and on small deltas the stale window still
        cuts the re-convergence (DESIGN.md §16).
      * ``keep_window=False`` (default) zeroes the window — required after
        topology events (failures, arrivals), where the fixed-point map
        itself changed shape and stale pairs are pure noise.

    The carry's pytree structure (accel slab sizes) is preserved either
    way, so re-armed carries keep hitting the compiled chunk programs.
    """
    cost0 = jnp.asarray(total_cost(inst, phi, solver=solver, axis=axis),
                        jnp.float32)
    keep = jnp.asarray(keep_window)
    return carry._replace(
        phi=phi,
        best_cost=cost0,
        stall=jnp.int32(0),
        done=jnp.asarray(False),
        iters=jnp.int32(0),
        cost=cost0,
        residual=jnp.float32(jnp.inf),
        alpha=jnp.where(keep, carry.alpha, jnp.float32(0.0)),
        ax=jnp.where(keep, carry.ax, jnp.zeros_like(carry.ax)),
        af=jnp.where(keep, carry.af, jnp.zeros_like(carry.af)),
        ak=jnp.where(keep, carry.ak, jnp.int32(0)),
        # the ring restarts with iters: callers drain it *before* resetting
        # (serve/online.py) — the valid prefix is always rows [0, iters)
        tb=jnp.zeros_like(carry.tb),
    )


def scan_chunk(
    inst: Instance,
    carry: ScanCarry,
    alpha, tol, patience, max_iters,
    allowed_e: Optional[jnp.ndarray], allowed_c: Optional[jnp.ndarray],
    *,
    length: int,
    scaled: bool = False,
    solver: str = "auto",
    blocked: str = "bitset",
    axis: Optional[str] = None,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    accel: Optional[AccelConfig] = None,
    app_mask: Optional[jnp.ndarray] = None,
    telemetry: Optional[TelemetryConfig] = None,
):
    """Advance the solve by up to ``length`` iterations entirely on device.

    Once ``done`` latches (residual below tol, ladder-stationary for
    ``patience`` iterations, the ``max_iters`` budget spent, or — with
    ``accel.residual_stop`` — a committed positive-stepsize move below
    ``accel.phi_tol``) the carry is frozen: the chunk's step loop (a
    ``lax.while_loop`` that tests the latch) exits, so no step after the
    latch runs, and those steps re-emit the converged (cost, residual),
    which keeps history shapes static.  Under ``jax.vmap`` (batched
    families, the mesh driver's member axis) the latch is batched: the loop
    runs while any member is live, and latched members keep their carry.

    With ``accel`` set the body additionally runs the §15 layer: the plain
    step seeds an Anderson candidate from the carry's history window, the
    candidate is accepted only if it is projected-feasible and at least as
    cheap as the plain step (otherwise the plain step commits — the
    safeguard that preserves monotone descent), and the adaptive stepsize
    carry adopts the winning rung.

    Not jitted here — the single-device drivers wrap it in ``jax.jit``
    (``gp._scan_chunk``) and the mesh driver traces it inside
    ``shard_map`` (``distributed._chunk_program``), where the ``axis``
    collectives bind to the mesh.
    """
    use_anderson = accel is not None and accel.anderson_m > 0
    use_adaptive = accel is not None and accel.adaptive_alpha
    use_phistop = (accel is not None and accel.residual_stop
                   and accel.phi_tol >= 0)

    def body(c: ScanCarry) -> ScanCarry:
        if use_adaptive:
            # carry alpha 0 = unseeded (first iteration / legacy warm
            # start): adopt the driver's alpha argument.
            alpha_eff = jnp.where(c.alpha > 0, c.alpha,
                                  jnp.float32(alpha))
        else:
            alpha_eff = alpha
        state = gp_step(inst, c.phi, alpha_eff, allowed_e, allowed_c, scaled,
                        solver, blocked=blocked, axis=axis,
                        node_axis=node_axis, node_shards=node_shards,
                        accel=accel, app_mask=app_mask, telemetry=telemetry)

        new_phi, new_cost = state.phi, state.cost
        ax, af, ak = c.ax, c.af, c.ak
        if use_anderson:
            x_k = _flat_phi(c.phi)
            f_k = _flat_phi(state.phi) - x_k
            mix = _anderson_mix(ax, af, ak, x_k, f_k,
                                accel.anderson_reg, axis)
            phi_mix = renormalize(inst, _unflat_phi(mix, c.phi))
            if app_mask is not None:
                # the mixer extrapolates over the full flattened phi; frozen
                # apps must stay exactly frozen (applied before costing, so
                # the safeguard evaluates the committed strategy)
                phi_mix = Phi(
                    e=jnp.where(app_mask[:, None, None, None],
                                phi_mix.e, c.phi.e),
                    c=jnp.where(app_mask[:, None, None], phi_mix.c, c.phi.c),
                )
            cost_mix = _strategy_cost(inst, phi_mix, solver, axis)
            cost_mix = jnp.where(jnp.isnan(cost_mix), jnp.inf, cost_mix)
            feas = _pmax(
                traffic_mod.feasibility_violation(inst, phi_mix), axis)
            # safeguard: accept only a feasible, no-worse mixed iterate
            # (rejection falls back to the already-committed plain step)
            accept = (ak >= 1) & (cost_mix <= state.cost) & (feas <= 1e-5)
            new_phi = jax.tree_util.tree_map(
                lambda mx, pl: jnp.where(accept, mx, pl),
                phi_mix, state.phi)
            new_cost = jnp.where(accept, cost_mix, state.cost)
            # history holds genuinely *evaluated* pairs of the plain map
            ax = _push_history(ax, x_k)
            af = _push_history(af, f_k)
            ak = jnp.minimum(ak + 1, jnp.int32(accel.anderson_m))

        # The loop below exits at the latch, so a step never runs frozen
        # (under vmap the loop's own select keeps latched members).  These
        # selects stay all the same: without them the TPU compiler builds
        # a different step, whose answers differ in their last bits.
        frz = c.done
        phi = jax.tree_util.tree_map(
            lambda new, old: jnp.where(frz, old, new), new_phi, c.phi)
        cost = jnp.where(frz, c.cost, new_cost)
        residual = jnp.where(frz, c.residual, state.residual)
        improved = new_cost < c.best_cost * (1 - 1e-6)
        best = jnp.where(frz | ~improved, c.best_cost, new_cost)
        stall = jnp.where(frz, c.stall, jnp.where(improved, 0, c.stall + 1))
        iters = c.iters + jnp.where(frz, 0, 1).astype(jnp.int32)
        done = frz | (residual <= tol) | (stall >= patience) | (iters >= max_iters)

        if use_adaptive:
            chosen = state.alpha
            na = jnp.where(chosen > 0,
                           jnp.clip(chosen, accel.alpha_min, accel.alpha_max),
                           jnp.maximum(alpha_eff * accel.alpha_shrink,
                                       accel.alpha_min))
            new_alpha = jnp.where(frz, c.alpha, jnp.float32(na))
        else:
            new_alpha = c.alpha
        if use_anderson:
            ax = jax.tree_util.tree_map(
                lambda new, old: jnp.where(frz, old, new), ax, c.ax)
            af = jax.tree_util.tree_map(
                lambda new, old: jnp.where(frz, old, new), af, c.af)
            ak = jnp.where(frz, c.ak, ak)
        if use_phistop or telemetry is not None:
            # phi-delta of the committed move; pmax-replicated across app
            # shards.  Shared by the §15 fixed-point latch and the §19
            # telemetry column (computed once when both are on).
            moved = jnp.maximum(jnp.max(jnp.abs(new_phi.e - c.phi.e)),
                                jnp.max(jnp.abs(new_phi.c - c.phi.c)))
            moved = _pmax(moved, axis)
        if use_phistop:
            # phi-delta fixed point: a committed move at positive stepsize
            # that left phi (numerically) unchanged means the projection
            # map is stationary.  Gate on chosen > 0 so a 0-rung win (the
            # ladder rejecting every positive step) doesn't latch early.
            fixed = (state.alpha > 0) & (moved <= accel.phi_tol)
            done = done | (~frz & fixed)

        tb = c.tb
        if telemetry is not None:
            # every operand is already replicated across the mesh (cost,
            # residual, alpha and rung derive from the psum-reduced ladder;
            # bs_rounds and moved were pmax'd above), so the ring rides the
            # carry with a replicated spec and costs no extra collectives.
            if use_anderson:
                anders = jnp.where(accept, 1.0, 0.0).astype(jnp.float32)
            else:
                anders = jnp.float32(-1.0)
            row = jnp.stack([
                c.iters.astype(jnp.float32),           # COL_ITER
                new_cost.astype(jnp.float32),          # COL_COST
                state.residual.astype(jnp.float32),    # COL_RESIDUAL
                state.alpha.astype(jnp.float32),       # COL_ALPHA
                jnp.asarray(state.rung, jnp.float32),  # COL_RUNG
                anders,                                # COL_ANDERSON
                jnp.asarray(state.bs_rounds, jnp.float32),  # COL_BS_ROUNDS
                moved.astype(jnp.float32),             # COL_PHI_DELTA
            ])
            tb = ring_record(tb, c.iters, row, ~frz)

        return ScanCarry(phi=phi, best_cost=best, stall=stall, done=done,
                         iters=iters, cost=cost, residual=residual,
                         alpha=new_alpha, ax=ax, af=af, ak=ak, tb=tb)

    def running(st):
        i, c, _, _ = st
        return (i < length) & ~c.done

    def step(st):
        i, c, cs, rs = st
        c = body(c)
        return i + 1, c, cs.at[i].set(c.cost), rs.at[i].set(c.residual)

    hist = jnp.zeros((length,), jnp.float32)
    n, carry, cs, rs = jax.lax.while_loop(
        running, step, (jnp.int32(0), carry, hist, hist))
    # steps after the latch re-emit the converged (cost, residual)
    after = jnp.arange(length) >= n
    return carry, (jnp.where(after, carry.cost, cs),
                   jnp.where(after, carry.residual, rs))
