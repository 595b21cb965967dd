"""Algorithm 1: distributed Gradient Projection (GP) for problem (2).

Per iteration (time slot), every node i and stage (a,k):

  1. obtains dD/dt via the marginal-cost broadcast (here: the synchronous
     fixed-point sweep in ``marginals.pdt_recursion``),
  2. computes modified marginals delta_ij(a,k) (eq. 7),
  3. computes the blocked node set B_i(a,k) (loop-freedom),
  4. moves phi mass from blocked/high-delta directions onto the min-delta
     direction(s) with stepsize alpha (eqs. 8-10).

The update is a masked, vectorized computation over the whole (A,K1,V,V(+1))
strategy tensor — jit-compiled, and shard_mappable over stages
(``core/distributed.py``).  ``allowed_e`` / ``allowed_c`` masks restrict the
direction set, which is how the SPOC / LCOF baselines reuse this machinery
(``core/baselines.py``).

The iteration itself lives in :mod:`repro.core.engine` (the ONE fused step
core, parameterized over the F/G measurement reduction — DESIGN.md §14);
this module is the single-device driver layer: initial strategies, the
chunked/vmapped solve drivers, and thin ``axis=None`` wrappers that keep
the historical ``gp.gp_step`` / ``gp.blocked_sets`` entry points.  The
mesh drivers (``distributed.solve_sharded*``) consume the same engine
under ``shard_map``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch as batch_mod
from repro.core import costs
from repro.core import engine
from repro.core.engine import GPState, ScanCarry as _ScanCarry
from repro.core.network import Instance
from repro.core.traffic import Phi, renormalize, total_cost
from repro.obs.spans import span

# Historical spellings, re-exported for call sites and differential tests
# that predate the engine extraction.
_TIE_EPS = engine.TIE_EPS      # directions within this of min-delta get mass
_BLOCK_EPS = engine.BLOCK_EPS  # strictness slack for pdt comparisons
_ALPHA_LADDER = engine.ALPHA_LADDER
blocked_sets = engine.blocked_sets


class GPScan(NamedTuple):
    """Result of :func:`solve_scan` (a strict superset of GPState), on the
    device; :func:`solve_batched` returns the same fields as host (numpy)
    arrays.

    Histories are dense ``(max_iters[+1],)`` arrays: entries past
    ``iterations`` repeat the converged value (the carry is frozen once the
    early-stop predicate fires), so the arrays are safe to consume without
    trimming and stack cleanly under ``jax.vmap``.
    """

    phi: Phi
    cost: jnp.ndarray              # final cost
    residual: jnp.ndarray          # final sufficiency residual
    cost_history: jnp.ndarray      # (max_iters + 1,), [0] = initial cost
    residual_history: jnp.ndarray  # (max_iters,)
    iterations: jnp.ndarray        # int32, #iterations actually committed
    # (R, TEL_WIDTH) per-iteration telemetry ring ((B, R, TEL_WIDTH) for the
    # batched driver) when the solve ran with telemetry on; rows past
    # ``iterations`` (clamped to R) are zero.  Decode with
    # ``repro.obs.ring_valid(telemetry, iterations)`` (DESIGN.md §19).
    telemetry: Optional[jnp.ndarray] = None
    # lane-steps the batched driver's vmapped chunk programs executed: per
    # chunk, the lanes of its bucket times the steps its loop ran
    # (:func:`solve_batched` only; None elsewhere)
    lane_steps: Optional[int] = None


def _as_array(x):
    """Host and device arrays as they are; anything else (a list) as a
    device array."""
    return x if isinstance(x, (np.ndarray, jax.Array)) else jnp.asarray(x)


@dataclasses.dataclass
class GPResult:
    """Host-side solve summary.

    ``cost_history`` / ``residual_history`` are dense arrays (NOT python
    lists; device arrays from :func:`solve`, host arrays from the family
    drivers): ``cost_history[0]`` is the initial cost and entry ``i`` is
    the cost after iteration ``i``.  Results from :func:`solve` are
    already trimmed; un-trimmed dense results (e.g. assembled from
    :func:`solve_scan`) repeat the converged value past ``iterations`` —
    ``trim()`` cuts them back to the committed prefix.
    """

    phi: Phi
    cost_history: jnp.ndarray
    residual_history: jnp.ndarray
    iterations: int
    # raw (R, TEL_WIDTH) iteration ring when the solve ran with telemetry
    # (``repro.obs.ring_valid`` trims it to the committed prefix); None
    # when telemetry was off.  ``trim()`` preserves it untouched.
    telemetry: Optional[jnp.ndarray] = None

    def __post_init__(self):
        self.cost_history = _as_array(self.cost_history)
        self.residual_history = _as_array(self.residual_history)

    def trim(self) -> "GPResult":
        """Cut dense histories back to the committed iteration prefix.

        ``cost_history`` shrinks to ``(iterations + 1,)`` (entry 0 is the
        initial cost) and ``residual_history`` to ``(iterations,)``.
        Idempotent; on host arrays it does no device work.

        Example::

            >>> res = gp.GPResult(phi=phi, cost_history=jnp.ones(401),
            ...                   residual_history=jnp.zeros(400),
            ...                   iterations=57)
            >>> res.trim().cost_history.shape
            (58,)
        """
        n = int(self.iterations)
        return dataclasses.replace(
            self,
            cost_history=self.cost_history[: n + 1],
            residual_history=self.residual_history[:n],
        )

    @property
    def final_cost(self) -> float:
        return float(self.cost_history[-1])


# ---------------------------------------------------------------------------
# One GP iteration (eqs. 8-10) — thin wrapper over the shared step engine
# ---------------------------------------------------------------------------

def gp_step(
    inst: Instance,
    phi: Phi,
    alpha: float,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    scaled: bool = False,
    solver: str = "auto",
    blocked: str = "bitset",
    accel=None,
) -> GPState:
    """One fused GP iteration on a single device.

    Delegates to :func:`engine.gp_step` with ``axis=None`` (plain-sum F/G
    measurement).  ``solver`` picks the stage solver (``"auto"`` |
    ``"batched_lu"`` | ``"dense"``, DESIGN.md §12) and ``blocked`` the
    blocked-set method (``"bitset"`` | ``"scan"``, DESIGN.md §13); the mesh
    path (``distributed.solve_sharded``) runs the same engine under
    ``shard_map`` with ``axis`` bound to the app-shard mesh axis.
    ``accel`` toggles the §15 step-level acceleration (adaptive ladder /
    exact residual) — see :func:`engine.resolve_accel`.
    """
    return engine.gp_step(inst, phi, alpha, allowed_e, allowed_c, scaled,
                          solver, blocked=blocked, axis=None,
                          accel=engine.resolve_accel(accel))


# ---------------------------------------------------------------------------
# Initial strategies (loop-free, finite cost)
# ---------------------------------------------------------------------------

def _zero_flow_weights(inst: Instance) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Link and CPU marginals at zero flow (the 'uncongested' metrics)."""
    Dp0 = jnp.where(
        inst.adj,
        costs.marginal(inst.link_kind, jnp.zeros_like(inst.link_param), inst.link_param),
        jnp.inf,
    )
    Cp0 = costs.marginal(inst.comp_kind, jnp.zeros_like(inst.comp_param), inst.comp_param)
    return Dp0, Cp0


def expanded_shortest_path(inst: Instance) -> tuple[jnp.ndarray, Phi]:
    """Stage-expanded single-destination shortest paths at zero flow.

    Returns (dist, phi) where dist[a,k,i] is the min uncongested cost-to-go
    from (i, stage k) to (d_a, stage K_a), and phi routes integrally along
    the argmin successors.  This is simultaneously:
      * the LPR-SC baseline (joint uncongested routing + offloading), and
      * the default loop-free initialization for GP.
    """
    Dp0, Cp0 = _zero_flow_weights(inst)
    V, K1 = inst.V, inst.K1
    INF = jnp.float32(1e18)

    def per_app(L_a, w_a, dst_a, ntask_a):
        def stage(dist_next, xs):
            k, L_k, w_k = xs
            is_last = k == ntask_a
            # absorbing cost: at the last stage, reaching dst ends the chain
            comp = jnp.where(is_last, INF, w_k * inst.wnode * Cp0 + dist_next)
            at_dst = jnp.arange(V) == dst_a
            base = jnp.where(is_last & at_dst, 0.0, comp)
            # tiny per-hop epsilon: breaks ties toward fewer hops so the
            # argmin successor graph is acyclic even at zero packet size
            wmat = L_k * Dp0 + 1e-5              # (V,V) link weights, inf off-graph

            def relax(dist, _):
                via = jnp.min(wmat + dist[None, :], axis=1)
                return jnp.minimum(dist, via), None

            dist, _ = jax.lax.scan(relax, base, None, length=V)
            return dist, dist

        ks = jnp.arange(K1)
        _, dists = jax.lax.scan(
            stage, jnp.full((V,), INF), (ks, L_a, w_a), reverse=True
        )
        return dists                              # (K1, V)

    dist = jax.vmap(per_app)(inst.L, inst.w, inst.dst, inst.n_tasks)  # (A,K1,V)

    # successor choice: CPU (cost w*C'0 + dist[k+1,i]) vs each link
    dist_next = jnp.concatenate([dist[:, 1:], jnp.full_like(dist[:, :1], 1e18)], axis=1)
    cand_c = jnp.where(
        inst.cpu_allowed()[:, :, None],
        inst.w[:, :, None] * inst.wnode[None, None] * Cp0[None, None] + dist_next,
        INF,
    )
    cand_e = jnp.where(
        inst.adj[None, None],
        inst.L[:, :, None, None] * Dp0[None, None] + 1e-5 + dist[:, :, None, :],
        INF,
    )
    all_cand = jnp.concatenate([cand_c[..., None], cand_e], axis=-1)  # (A,K1,V,1+V)
    best = jnp.argmin(all_cand, axis=-1)
    phi_c = (best == 0).astype(jnp.float32)
    phi_e = jax.nn.one_hot(best - 1, V, dtype=jnp.float32) * (best > 0)[..., None]
    phi = renormalize(inst, Phi(e=phi_e, c=phi_c))
    return dist, phi


def init_phi(inst: Instance) -> Phi:
    """Default loop-free initial strategy with finite cost."""
    _, phi = expanded_shortest_path(inst)
    return phi


# ---------------------------------------------------------------------------
# Solver drivers
# ---------------------------------------------------------------------------
#
# Three entry points share one device-resident iteration (DESIGN.md §10):
#
#   * solve_scan  — the whole loop as ONE jitted device loop of static
#                   length with an on-device early-stop latch at which
#                   it exits; composes with jax.vmap for batched
#                   scenario families (core/batch.py, core/scenarios.py),
#                   where the loop runs until every member has latched.
#   * solve       — the user-facing driver: runs the same scan in chunks and
#                   checks the early-stop flag on host once per chunk, so a
#                   run that converges in 50 iterations does not pay for
#                   max_iters=400 worth of steps.
#   * solve_loop  — the original per-iteration host-sync python loop, kept
#                   as the semantic reference (tests/test_batch.py asserts
#                   scan == loop on every Table II scenario).

@functools.partial(jax.jit,
                   static_argnames=("scaled", "solver", "blocked", "accel"))
def _jit_step(inst, phi, alpha, allowed_e, allowed_c, scaled=False,
              solver="auto", blocked="bitset", accel=None):
    return engine.gp_step(inst, phi, alpha, allowed_e, allowed_c, scaled,
                          solver, blocked=blocked, axis=None, accel=accel)


_init_carry = engine.init_carry


@functools.partial(jax.jit,
                   static_argnames=("length", "scaled", "solver", "blocked",
                                    "accel", "telemetry"))
def _scan_chunk(
    inst, carry, alpha, tol, patience, max_iters, allowed_e, allowed_c,
    *, length: int, scaled: bool = False, solver: str = "auto",
    blocked: str = "bitset", accel=None, app_mask=None, telemetry=None,
):
    """Jitted single-device wrapper over :func:`engine.scan_chunk`.

    Once the ``done`` latch is set the chunk's step loop exits, and the
    steps it did not run re-emit the converged (cost, residual), keeping
    history shapes static (see the engine docstring; under ``jax.vmap``, as
    in :func:`_scan_chunk_batched`, the loop runs while any member is live
    and latched members keep their carry by a select).  ``accel``
    is a resolved :class:`engine.AccelConfig` (or None) riding as a static
    argument — each distinct config compiles its own program.  ``app_mask``
    ((A,) bool or None) freezes applications (the §16 skip gate).
    ``telemetry`` (a resolved :class:`engine.TelemetryConfig` or None) is
    likewise static: with None the carry's ring is (0, TEL_WIDTH) and the
    compiled program is identical to the pre-telemetry one (§19).
    """
    return engine.scan_chunk(
        inst, carry, alpha, tol, patience, max_iters, allowed_e, allowed_c,
        length=length, scaled=scaled, solver=solver, blocked=blocked,
        axis=None, accel=accel, app_mask=app_mask, telemetry=telemetry)


def solve_scan(
    inst: Instance,
    phi0: Optional[Phi] = None,
    *,
    alpha: float = 0.02,
    max_iters: int = 400,
    tol: float = 1e-4,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    patience: int = 40,
    scaled: bool = False,
    solver: str = "auto",
    blocked: str = "bitset",
    accel=None,
    app_mask: Optional[jnp.ndarray] = None,
    telemetry=None,
) -> GPScan:
    """Algorithm 1 as a single device-resident loop (``engine.scan_chunk``).

    No host syncs inside the loop; returns dense histories (see
    :class:`GPScan`).  This is the vmap/jit-composable primitive — batched
    families go through ``jax.vmap(solve_scan)`` (``core/scenarios.py``).

    Shapes: with ``inst`` of extent (V nodes, A apps, K1 = K+1 stages),
    the result carries ``phi.e (A, K1, V, V)``, ``phi.c (A, K1, V)``,
    scalar ``cost``/``residual``/``iterations``, ``cost_history
    (max_iters + 1,)`` and ``residual_history (max_iters,)``.

    Example::

        >>> inst = network.table_ii_instance("abilene", seed=0)
        >>> scan = gp.solve_scan(inst, alpha=0.1, max_iters=200)
        >>> float(scan.cost) <= float(scan.cost_history[0])
        True
        >>> scan.cost_history.shape, int(scan.iterations) <= 200
        ((201,), True)

    solver="batched_lu" runs the shared-factorization stage solver
    (kernels/batched_solve.py); solver="dense" keeps the seed's per-stage
    ``jnp.linalg.solve`` for differential testing; solver="auto" (default)
    picks per backend/size (``traffic.resolve_solver``).

    accel=True (or an :class:`engine.AccelConfig`) enables the §15
    convergence-acceleration layer — Anderson mixing, per-member adaptive
    stepsize, sufficiency-residual stopping; default None keeps the legacy
    exact iteration.
    """
    accel = engine.resolve_accel(accel)
    telemetry = engine.resolve_telemetry(telemetry)
    phi = phi0 if phi0 is not None else init_phi(inst)
    carry0 = _init_carry(inst, phi, accel=accel, telemetry=telemetry)
    carry, (cs, rs) = _scan_chunk(
        inst, carry0, jnp.float32(alpha), jnp.float32(tol),
        jnp.int32(patience), jnp.int32(max_iters), allowed_e, allowed_c,
        length=max_iters, scaled=scaled, solver=solver, blocked=blocked,
        accel=accel, app_mask=app_mask, telemetry=telemetry,
    )
    return GPScan(
        phi=carry.phi, cost=carry.cost, residual=carry.residual,
        cost_history=jnp.concatenate([carry0.cost[None], cs]),
        residual_history=rs, iterations=carry.iters,
        telemetry=carry.tb if telemetry is not None else None,
    )


_SOLVE_CHUNK = 32    # host checks the early-stop latch once per chunk

# Adaptive chunk schedule for batched ensembles (gp.solve_batched): start
# short so early-converging members retire (and the batch compacts) after 8
# iterations, then double up to 64 as the long tail sets in.  All lengths
# stay powers of two — {8, 16, 32, 64} — so the schedule adds no XLA cache
# entries beyond those four per compaction bucket size.
_CHUNK_MIN = 8
_CHUNK_MAX = 64


def _prev_pow2(n: int) -> int:
    """Largest power of two <= n (n >= 1)."""
    return 1 << (n.bit_length() - 1)


def _chunk_lengths(max_iters: int):
    """The batched driver's chunk schedule, in order: pow2 lengths only
    (min with the largest pow2 <= the remaining budget), so the whole
    schedule draws from {8, 16, 32, 64} plus the pow2 ladder of any sub-8
    tail."""
    steps, chunk = 0, _CHUNK_MIN
    while steps < max_iters:
        length = min(chunk, _prev_pow2(max_iters - steps))
        yield length
        steps += length
        chunk = min(chunk * 2, _CHUNK_MAX)


def solve(
    inst: Instance,
    phi0: Optional[Phi] = None,
    *,
    alpha: float = 0.02,
    max_iters: int = 400,
    tol: float = 1e-4,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    track_every: int = 1,   # accepted for API compat; histories are dense now
    patience: int = 40,
    scaled: bool = False,
    solver: str = "auto",
    blocked: str = "bitset",
    accel=None,
    app_mask: Optional[jnp.ndarray] = None,
    telemetry=None,
) -> GPResult:
    """Run Algorithm 1 until the sufficiency residual falls below tol.

    Thin chunked driver over the :func:`solve_scan` iteration: the loop body
    never syncs to host — only the ``done`` latch is read back, once every
    ``_SOLVE_CHUNK`` iterations — so converged runs stop early while the
    per-iteration cost stays identical to the fully device-resident scan.
    The last chunk's step loop exits at the latch
    (:func:`engine.scan_chunk`), so no step after it runs.

    scaled=True enables the quasi-Newton diagonal preconditioner (paper
    Section IV remark on second-order methods).  accel=True (or an
    :class:`engine.AccelConfig`) enables the §15 acceleration layer.
    ``app_mask`` ((A,) bool) freezes applications (the §16 skip gate):
    frozen apps keep their phi rows and still contribute their flows to
    the shared F/G measurement, and the residual stop ignores them.

    Under a running ``jax.profiler`` trace the call and its host phases are
    named spans on the device's clock (``repro.obs.spans.span``):
    ``gp.solve``, and inside it ``gp.solve.init`` (initial strategy and
    carry), one ``gp.solve.dispatch`` per chunk, and ``gp.solve.trim``."""
    del track_every
    with span("gp.solve"):
        accel = engine.resolve_accel(accel)
        telemetry = engine.resolve_telemetry(telemetry)
        with span("gp.solve.init"):
            phi = phi0 if phi0 is not None else init_phi(inst)
            carry = _init_carry(inst, phi, accel=accel, telemetry=telemetry)
        cost0 = carry.cost
        alpha_, tol_ = jnp.float32(alpha), jnp.float32(tol)
        patience_, max_iters_ = jnp.int32(patience), jnp.int32(max_iters)
        cost_chunks, res_chunks = [], []
        steps = 0
        while steps < max_iters:
            with span("gp.solve.dispatch"):
                carry, (cs, rs) = _scan_chunk(
                    inst, carry, alpha_, tol_, patience_, max_iters_,
                    allowed_e, allowed_c,
                    length=min(_SOLVE_CHUNK, max_iters - steps),
                    scaled=scaled, solver=solver, blocked=blocked,
                    accel=accel, app_mask=app_mask, telemetry=telemetry,
                )
            cost_chunks.append(cs)
            res_chunks.append(rs)
            steps += len(cs)
            if bool(carry.done):
                break
        with span("gp.solve.trim"):
            return GPResult(
                phi=carry.phi,
                cost_history=jnp.concatenate([cost0[None], *cost_chunks]),
                residual_history=(jnp.concatenate(res_chunks) if res_chunks
                                  else jnp.zeros((0,))),
                iterations=int(carry.iters),
                telemetry=carry.tb if telemetry is not None else None,
            ).trim()


@functools.partial(jax.jit,
                   static_argnames=("length", "scaled", "solver", "blocked",
                                    "accel", "telemetry"))
def _scan_chunk_batched(
    inst, carry, alpha, tol, patience, max_iters, allowed_e, allowed_c,
    *, length: int, scaled: bool = False, solver: str = "auto",
    blocked: str = "bitset", accel=None, app_mask=None, telemetry=None,
):
    def one(i, c, ae, ac, am):
        return _scan_chunk(i, c, alpha, tol, patience, max_iters, ae, ac,
                           length=length, scaled=scaled, solver=solver,
                           blocked=blocked, accel=accel, app_mask=am,
                           telemetry=telemetry)

    return jax.vmap(one)(inst, carry, allowed_e, allowed_c, app_mask)


def _gather(tree, idx: jnp.ndarray):
    return jax.tree_util.tree_map(lambda x: x[idx], tree)


def _host_gather(tree, idx: np.ndarray):
    """Lanes ``idx`` of every leaf, gathered on the host and put back on
    the device: no program, whatever the source's size."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(np.asarray(x)[idx]),
                                  tree)


@functools.partial(jax.jit, static_argnames=("accel", "telemetry"))
def _init_family(binst, phi0, n_live, *, accel=None, telemetry=None):
    """The first carry of a family bucket, as one program: the initial
    strategies (``init_phi`` where ``phi0`` is None) and their carries;
    lanes from ``n_live`` on are padding and start latched."""
    if phi0 is None:
        phi0 = jax.vmap(init_phi)(binst)
    carry = jax.vmap(
        lambda i, p: _init_carry(i, p, accel=accel, telemetry=telemetry)
    )(binst, phi0)
    pad = jnp.arange(carry.done.shape[0]) >= n_live
    return carry._replace(done=carry.done | pad)


def _chunk_runner(alpha, tol, patience, max_iters, **static):
    """``run(inst, carry, allowed_e, allowed_c, length)``: one chunk of the
    batched driver, with the scalars and static arguments fixed as
    :func:`solve_batched` passes them."""
    scalars = (jnp.float32(alpha), jnp.float32(tol), jnp.int32(patience),
               jnp.int32(max_iters))

    def run(inst, carry, allowed_e, allowed_c, length):
        return _scan_chunk_batched(inst, carry, *scalars, allowed_e,
                                   allowed_c, length=length, **static)

    return run


@jax.jit
def _repack(inst, carry, allowed_e, allowed_c, sel, n_live):
    """Compaction as one program per (source, target) bucket: lanes ``sel``
    of the instances, carries and masks; lanes from ``n_live`` on are
    padding (copies of a live lane) and start latched."""
    inst, carry, allowed_e, allowed_c = _gather(
        (inst, carry, allowed_e, allowed_c), sel)
    pad = jnp.arange(sel.shape[0]) >= n_live
    return inst, carry._replace(done=carry.done | pad), allowed_e, allowed_c


def solve_batched(
    binst: Instance,
    phi0: Optional[Phi] = None,
    *,
    alpha: float = 0.02,
    max_iters: int = 400,
    tol: float = 1e-4,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    patience: int = 40,
    scaled: bool = False,
    compact: bool = True,
    solver: str = "auto",
    blocked: str = "bitset",
    accel=None,
    telemetry=None,
) -> GPScan:
    """Solve a whole scenario family (a ``batch.pad_instances`` pytree with
    a leading batch axis) in one vmapped device program.

    Semantically ``jax.vmap(solve_scan)`` with two wall-clock refinements
    (DESIGN.md §10):

      * **chunked early stop, adaptive lengths** — the loop body never
        syncs to host; only the batched ``done`` latch is read back at
        chunk boundaries, and the sweep ends when every member has
        converged.  Chunks start at ``_CHUNK_MIN`` = 8 iterations and
        double up to ``_CHUNK_MAX`` = 64, so early-converging members
        retire (and the batch compacts) quickly while long tails amortize
        the host sync — with only pow2 chunk lengths, bounding XLA cache
        entries;
      * **convergence compaction** (``compact=True``) — at chunk boundaries,
        converged members retire and the active set is re-packed into the
        next power-of-two bucket, so a long-tailed ensemble does not keep
        paying for members that finished early.  Bucket sizes are quantized
        to powers of two to bound XLA recompiles (one per bucket size).

    Histories are dense ``(B, max_iters[+1])`` arrays repeating each
    member's converged values past its own stop point; ``iterations``
    reports each member's stop point.  Every result is a host (numpy)
    array, copied from the device as lanes retire, so splitting the family
    into members costs no device program; ``lane_steps`` counts the
    lane-steps the chunk programs executed (latched and padding lanes
    included).

    The device work compiles once per bucket: the family's first carry
    (:func:`_init_family`), each chunk length of the schedule, and each
    compaction from one bucket to a smaller one (:func:`_repack`); a family
    whose size is not a power of two is padded to its bucket on the host
    first.  Under a running ``jax.profiler`` trace the call and its host
    phases are named spans (``repro.obs.spans.span``):
    ``gp.solve_batched``, and inside it ``gp.solve_batched.init``, one
    ``gp.solve_batched.dispatch`` per chunk (the chunk's call through
    reading its latch, iteration counts and histories back),
    ``gp.solve_batched.retire`` (copying the finals of retiring lanes) and
    ``gp.solve_batched.compact``.

    Shapes: for a batch of B members padded to (V, A, K1), returns
    ``phi.e (B, A, K1, V, V)``, ``phi.c (B, A, K1, V)``, ``cost``/
    ``residual``/``iterations (B,)``, ``cost_history (B, max_iters + 1)``
    and ``residual_history (B, max_iters)``, all indexed by the ORIGINAL
    member order (compaction is internal).  The stage systems of the whole
    batch run through the batched-LU kernel path as one
    ``(B * ladder * A * K1, V, V)`` factorization per chunk iteration
    (vmap over scenarios x batch over stages — DESIGN.md §12).

    Example::

        >>> insts = [network.table_ii_instance("abilene", seed=s)
        ...          for s in range(4)]
        >>> binst = batch.pad_instances(insts)
        >>> scan = gp.solve_batched(binst, alpha=0.1, max_iters=200)
        >>> scan.cost.shape, scan.cost_history.shape
        ((4,), (4, 201))
    """
    with span("gp.solve_batched"):
        accel = engine.resolve_accel(accel)
        telemetry = engine.resolve_telemetry(telemetry)
        B = int(binst.adj.shape[0])
        # lane -> original member (-1: pad); the lanes of a bucket that is not
        # a power of two are padded on the host, so every chunk program in this
        # solve (and any other solve over same-shaped members) hits the same
        # XLA cache entries as the compaction buckets
        bucket0 = batch_mod.next_pow2(B) if compact else B
        ids = np.concatenate([np.arange(B), np.full(bucket0 - B, -1)])
        inst_p, ae_p, ac_p = binst, allowed_e, allowed_c
        with span("gp.solve_batched.init"):
            if bucket0 > B:
                sel = np.concatenate([np.arange(B),
                                      np.zeros(bucket0 - B, np.int64)])
                inst_p, phi0, ae_p, ac_p = _host_gather(
                    (inst_p, phi0, ae_p, ac_p), sel)
            carry = _init_family(inst_p, phi0, B, accel=accel,
                                 telemetry=telemetry)
            # host-side result buffers, indexed by original member id
            cost0 = np.asarray(carry.cost)[:B]
            out_phi_e = np.asarray(carry.phi.e)[:B].copy()
            out_phi_c = np.asarray(carry.phi.c)[:B].copy()
        run_chunk = _chunk_runner(alpha, tol, patience, max_iters,
                                  scaled=scaled, solver=solver,
                                  blocked=blocked, accel=accel,
                                  telemetry=telemetry)

        cost_hist = np.zeros((B, max_iters + 1), np.float32)
        cost_hist[:, 0] = cost0
        res_hist = np.zeros((B, max_iters), np.float32)
        out_cost = cost0.copy()
        out_res = np.full((B,), np.inf, np.float32)
        out_iters = np.zeros((B,), np.int32)
        ring = telemetry.ring if telemetry is not None else 0
        out_tb = np.zeros((B, ring, engine.TEL_WIDTH), np.float32)
        written = np.zeros((B,), np.int64)     # history filled up to this step

        lane_iters = np.zeros(len(ids), np.int64)   # iterations at chunk start
        lane_steps = 0
        steps = 0
        for length in _chunk_lengths(max_iters):
            valid = ids >= 0
            vids = ids[valid]
            with span("gp.solve_batched.dispatch"):
                carry, (cs, rs) = run_chunk(inst_p, carry, ae_p, ac_p, length)
                done = np.asarray(carry.done)
                iters = np.asarray(carry.iters)
                cost_hist[vids, steps + 1: steps + 1 + length] = (
                    np.asarray(cs)[valid])
                res_hist[vids, steps: steps + length] = np.asarray(rs)[valid]
            # the loop ran while any lane was live: as many steps as the lane
            # that committed most, for every lane of the bucket
            lane_steps += len(ids) * int((iters - lane_iters).max())
            steps += length
            written[vids] = steps

            # snapshot finals only for lanes retiring this chunk (done, or the
            # iteration budget just ran out) — phi is the expensive transfer,
            # (B, A, K1, V, V), and active lanes would overwrite it anyway
            retiring = valid & (done | (steps >= max_iters))
            if retiring.any():
                with span("gp.solve_batched.retire"):
                    rids = ids[retiring]
                    out_phi_e[rids] = np.asarray(carry.phi.e)[retiring]
                    out_phi_c[rids] = np.asarray(carry.phi.c)[retiring]
                    out_cost[rids] = np.asarray(carry.cost)[retiring]
                    out_res[rids] = np.asarray(carry.residual)[retiring]
                    out_iters[rids] = iters[retiring]
                    if telemetry is not None:
                        # rings snapshot at retirement only (same rationale as
                        # phi: active lanes would overwrite, and compaction
                        # re-packs lanes — original-id indexing happens here)
                        out_tb[rids] = np.asarray(carry.tb)[retiring]

            active = valid & ~done
            n_act = int(active.sum())
            if n_act == 0:
                break
            bucket = batch_mod.next_pow2(n_act)
            if compact and bucket < len(ids):
                with span("gp.solve_batched.compact"):
                    keep = np.flatnonzero(active)
                    # pad lanes duplicate a live member but start frozen
                    sel = np.concatenate(
                        [keep, np.full(bucket - n_act, keep[0], np.int64)])
                    inst_p, carry, ae_p, ac_p = _repack(
                        inst_p, carry, ae_p, ac_p, jnp.asarray(sel), n_act)
                    ids = np.where(np.arange(bucket) < n_act, ids[sel], -1)
                    iters = iters[sel]
            lane_iters = iters

        # dense-history contract: repeat converged values past each member's
        # retirement chunk
        for m in range(B):
            w = int(written[m])
            cost_hist[m, w + 1:] = cost_hist[m, w]
            if w > 0:
                res_hist[m, w:] = res_hist[m, w - 1]

        return GPScan(
            phi=Phi(e=out_phi_e, c=out_phi_c), cost=out_cost, residual=out_res,
            cost_history=cost_hist, residual_history=res_hist,
            iterations=out_iters,
            telemetry=out_tb if telemetry is not None else None,
            lane_steps=lane_steps,
        )


def warm_batched(
    binst: Instance,
    *,
    alpha: float = 0.02,
    max_iters: int = 400,
    tol: float = 1e-4,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    patience: int = 40,
    scaled: bool = False,
    compact: bool = True,
    solver: str = "auto",
    blocked: str = "bitset",
    accel=None,
    telemetry=None,
) -> None:
    """Build every program :func:`solve_batched` runs, with the same keyword
    arguments and with or without ``phi0``, for a family of at most
    ``binst``'s members of its shapes: each bucket's first carry, its chunk
    programs at every length of the schedule, and its compactions to every
    smaller bucket.  Every lane starts latched, so no step is committed.

    A family solved afterwards compiles nothing, whatever its members'
    iteration counts::

        >>> gp.warm_batched(binst, alpha=0.1, max_iters=256)
        >>> scan = gp.solve_batched(binst, alpha=0.1, max_iters=256)
    """
    accel = engine.resolve_accel(accel)
    telemetry = engine.resolve_telemetry(telemetry)
    B = int(binst.adj.shape[0])
    top = batch_mod.next_pow2(B) if compact else B
    buckets = [top >> i for i in range(top.bit_length())] if compact else [B]
    run_chunk = _chunk_runner(alpha, tol, patience, max_iters, scaled=scaled,
                              solver=solver, blocked=blocked, accel=accel,
                              telemetry=telemetry)
    lengths = sorted(set(_chunk_lengths(max_iters)))
    family = _host_gather((binst, allowed_e, allowed_c),
                          np.arange(top) % B)
    for n, b in enumerate(buckets):
        inst_b, ae_b, ac_b = _host_gather(family, np.arange(b))
        carry = _init_family(inst_b, None, 0, accel=accel,
                             telemetry=telemetry)
        carry = _init_family(inst_b, _host_gather(carry.phi, np.arange(b)),
                             0, accel=accel, telemetry=telemetry)
        for length in lengths:
            run_chunk(inst_b, carry, ae_b, ac_b, length)
        for to in buckets[n + 1:]:
            _repack(inst_b, carry, ae_b, ac_b, jnp.asarray(np.arange(to)), to)


def solve_loop(
    inst: Instance,
    phi0: Optional[Phi] = None,
    *,
    alpha: float = 0.02,
    max_iters: int = 400,
    tol: float = 1e-4,
    allowed_e: Optional[jnp.ndarray] = None,
    allowed_c: Optional[jnp.ndarray] = None,
    patience: int = 40,
    scaled: bool = False,
    solver: str = "auto",
    blocked: str = "bitset",
) -> GPResult:
    """Reference driver: the original per-iteration host-sync python loop.

    Semantically equivalent to :func:`solve` / :func:`solve_scan` (asserted
    by tests/test_batch.py); kept for differential testing and debugging —
    use :func:`solve` everywhere else."""
    phi = phi0 if phi0 is not None else init_phi(inst)
    cost0 = jnp.asarray(total_cost(inst, phi), jnp.float32)
    cost_hist = [float(cost0)]
    res_hist = []
    it = 0
    # bookkeeping stays in float32 so the stop iteration is bit-identical
    # to the device-resident scan (which cannot use python float64)
    best_cost, stall = cost0, 0
    shrink = jnp.float32(1 - 1e-6)
    tol32 = jnp.float32(tol)
    for it in range(1, max_iters + 1):
        state = _jit_step(inst, phi, alpha, allowed_e, allowed_c, scaled,
                          solver, blocked)
        phi = state.phi
        cost_hist.append(float(state.cost))
        res_hist.append(float(state.residual))
        if bool(state.residual <= tol32):
            break
        if bool(state.cost < best_cost * shrink):
            best_cost, stall = state.cost, 0
        else:
            stall += 1
            if stall >= patience:
                break   # ladder-stationary: no stepsize makes progress
    return GPResult(phi=phi, cost_history=cost_hist, residual_history=res_hist, iterations=it)
