"""jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic; on the CPU a kernel that a caller
asks for runs with interpret=True.  ``INTERPRET`` follows the backend JAX
starts with — there is no run-time fallback between the two.

Shard-map contract (relied on by ``core/engine.py``, DESIGN.md §14): every
wrapper here is *collective-free and per-member* — leading batch dims are
flattened into the kernel grid and no wrapper ever reduces across them —
so the GP step engine may call them unchanged inside ``shard_map`` (each
app shard runs the kernels on its local slab) and under ``jax.vmap`` of a
shard (mesh-composed scenario families).  Keep new wrappers collective-free
too; network-wide reductions belong to the engine's ``axis`` plumbing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import batched_solve as _bs
from repro.kernels import blocked_sets as _bset
from repro.kernels import chain_propagate as _cp
from repro.kernels import flash_attention as _fa
from repro.kernels import sparse_solve as _ss
from repro.kernels import ssd_chunk as _sc

INTERPRET = jax.default_backend() == "cpu"


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal=True, window=None):
    """(B,S,H,hd) layout public API (matches models.attention.sdpa)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qt, S = _pad_to(qt, 2, _fa.DEFAULT_BQ)
    kt, _ = _pad_to(kt, 2, _fa.DEFAULT_BK)
    vt, _ = _pad_to(vt, 2, _fa.DEFAULT_BK)
    out = _fa.flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                                  interpret=INTERPRET)
    return out[:, :, :S].transpose(0, 2, 1, 3)


@jax.jit
def propagate_step(t, M, src):
    return _cp.propagate_step(t, M, src, interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("sweeps",))
def solve_fixed_point(M, src, *, sweeps: int):
    return _cp.solve_fixed_point(M, src, sweeps=sweeps, interpret=INTERPRET)


@jax.jit
def ssd_chunk(xh, dt, dtA, cum, BH, CH):
    """Adapter matching models.ssm.ssd_chunked's kernel call signature."""
    return _sc.ssd_chunk_fwd(xh, dt, cum, BH, CH, interpret=INTERPRET)


# ---------------------------------------------------------------------------
# Batched LU solve (the GP stage-system hot path — DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# Dispatch: on TPU the blocked Pallas kernels compile to Mosaic; on CPU
# interpret mode is engaged only when explicitly requested
# (``use_pallas=True`` — tests and kernel parity sweeps), because the
# default CPU path should hit native batched LAPACK (``jax.lax.linalg.lu``)
# rather than the Pallas interpreter.  Both paths share the packed-LU
# (B, V, V) layout and the per-member ``ok`` flag contract.

class BatchedLU(NamedTuple):
    """Packed LU factors of a batch of stage systems.

    lu:   (..., V, V) packed L\\U (unit diagonal of L implicit)
    perm: (..., V) int32 row permutation (``mats[perm] = L @ U``; identity
          for the Pallas path, which factors without pivoting — valid for
          the M-matrices ``I - Phi`` of loop-free strategies)
    linv: (..., nblk, nb, nb) inverses of L's diagonal blocks
    uinv: (..., nblk, nb, nb) inverses of U's diagonal blocks (the
          substitution prework of the reference path — DESIGN.md §12)
    ok:   (...,) bool per-member condition flag (False: singular /
          non-finite factor — the member's solves will carry inf/nan)
    """

    lu: jnp.ndarray
    perm: jnp.ndarray
    linv: jnp.ndarray
    uinv: jnp.ndarray
    ok: jnp.ndarray


# The batched-LU kernels are written for Mosaic (TPU): VMEM-resident
# lane-padded blocks, rows read from refs with ``pl.ds``.  On GPU the
# reference path (cuBLAS/cuSOLVER batched LU via lax.linalg) is both safe
# and fast, so Pallas engages by default only on TPU; interpret mode is
# for tests.
_PALLAS_DEFAULT = jax.default_backend() == "tpu"


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    return _PALLAS_DEFAULT if use_pallas is None else use_pallas


def _flatten_batch(x, core_ndim):
    lead = x.shape[: x.ndim - core_ndim]
    flat = x.reshape((-1,) + x.shape[x.ndim - core_ndim:])
    return flat, lead


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def batched_factor(mats: jnp.ndarray, *, use_pallas: Optional[bool] = None
                   ) -> BatchedLU:
    """Factor a batch of dense systems: mats (..., V, V) -> BatchedLU.

    Any number of leading batch dims is accepted (they are flattened into
    the kernel grid and restored on return); composes with jax.vmap/scan.
    """
    flat, lead = _flatten_batch(mats, 2)
    V = flat.shape[-1]
    if _use_pallas(use_pallas):
        lu = _bs.lu_factor(flat, interpret=INTERPRET)
        perm = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32),
                                flat.shape[:1] + (V,))
        linv, uinv = _bs.block_inverses(lu)
    else:
        lu, perm, linv, uinv = _bs.ref_factor(flat)
    ok = _bs.factor_ok(lu)
    return BatchedLU(
        lu=lu.reshape(lead + (V, V)),
        perm=perm.reshape(lead + (V,)),
        linv=linv.reshape(lead + linv.shape[1:]),
        uinv=uinv.reshape(lead + uinv.shape[1:]),
        ok=ok.reshape(lead),
    )


@functools.partial(jax.jit, static_argnames=("trans", "use_pallas"))
def batched_solve_factored(fact: BatchedLU, rhs: jnp.ndarray, *,
                           trans: int = 0,
                           use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Solve A x = rhs (trans=0) or A^T x = rhs (trans=1) from factors.

    fact.lu (..., V, V), rhs (..., V) -> (..., V).  O(V^2) per member —
    the factorization cost is paid once per GP step, not once per stage
    sweep (core/traffic.py, core/marginals.py).
    """
    lu_flat, lead = _flatten_batch(fact.lu, 2)
    rhs_flat, _ = _flatten_batch(rhs, 1)
    if _use_pallas(use_pallas):
        # Honor the row permutation even for kernel solves, so factors are
        # path-portable: Pallas factors carry an identity perm (no-op
        # gather), while reference (LAPACK-pivoted) factors solve
        # correctly here too.
        perm_flat, _ = _flatten_batch(fact.perm.astype(jnp.int32), 1)
        if trans == 0:
            rhs_flat = jnp.take_along_axis(rhs_flat, perm_flat, axis=1)
        x = _bs.lu_solve(lu_flat, rhs_flat, trans=trans, interpret=INTERPRET)
        if trans != 0:
            inv_perm = jnp.argsort(perm_flat, axis=1)
            x = jnp.take_along_axis(x, inv_perm, axis=1)
    else:
        perm_flat, _ = _flatten_batch(fact.perm, 1)
        linv_flat, _ = _flatten_batch(fact.linv, 3)
        uinv_flat, _ = _flatten_batch(fact.uinv, 3)
        x = _bs.ref_solve(lu_flat, perm_flat, linv_flat, uinv_flat,
                          rhs_flat, trans=trans)
    return x.reshape(rhs.shape)


@functools.partial(jax.jit, static_argnames=("trans", "reverse", "clamp",
                                              "use_pallas"))
def fused_chain_solve(fact: BatchedLU, base: jnp.ndarray, mult: jnp.ndarray,
                      *, trans: int = 0, reverse: bool = False,
                      clamp: bool = False,
                      use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Fused sequential solve along the stage axis of a factor stack.

    fact with leading dims (..., K), base/mult (..., K, V) -> x (..., K, V)
    where, walking k forward (or backward with ``reverse=True``),

        x_k = A_k^{-1(T)} (base_k + mult_k * x_prev),   x_prev(start) = 0,

    optionally clamped at 0 (``clamp=True`` — the marginal recursion's
    nonnegativity).  This is the chain-scan substitution of BOTH GP sweeps
    (traffic: trans=1 forward, marginals: trans=0 reverse) issued as ONE
    call consuming the whole (K, V, V) factor stack: per-stage fixed costs
    (padding, transposes, permutation sorts, dispatch) are paid once per GP
    step instead of once per stage (DESIGN.md §13).

    The Pallas path runs each member's chain inside one kernel invocation
    (factor stack VMEM-resident) and assumes the identity row permutation
    of the unpivoted Pallas factors; LAPACK-pivoted reference factors are
    handled by the reference path.
    """
    lu_flat, lead = _flatten_batch(fact.lu, 3)         # (Bf, K, V, V)
    base_flat, _ = _flatten_batch(base, 2)
    mult_flat, _ = _flatten_batch(mult, 2)
    if _use_pallas(use_pallas):
        x = _bs.chain_solve(lu_flat, base_flat, mult_flat, trans=trans,
                            reverse=reverse, clamp=clamp, interpret=INTERPRET)
    else:
        perm_flat, _ = _flatten_batch(fact.perm, 2)
        linv_flat, _ = _flatten_batch(fact.linv, 4)
        uinv_flat, _ = _flatten_batch(fact.uinv, 4)
        x = jax.vmap(
            functools.partial(_bs.ref_chain_solve, trans=trans,
                              reverse=reverse, clamp=clamp)
        )(lu_flat, perm_flat, linv_flat, uinv_flat, base_flat, mult_flat)
    return x.reshape(base.shape)


@functools.partial(jax.jit, static_argnames=("trans", "use_pallas"))
def batched_solve(mats: jnp.ndarray, rhs: jnp.ndarray, *, trans: int = 0,
                  use_pallas: Optional[bool] = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-shot factor + solve with per-member residual flags.

    Returns (x (..., V), resid (...,)) where resid is the relative
    residual ``|A x - b|_inf / (|b|_inf + 1)`` (inf for non-finite
    members).  A singular member flags itself without poisoning the rest
    of the batch — the contract the GP loop's loopy-candidate rejection
    relies on (DESIGN.md §2, §12).
    """
    fact = batched_factor(mats, use_pallas=use_pallas)
    x = batched_solve_factored(fact, rhs, trans=trans, use_pallas=use_pallas)
    mats_flat, lead = _flatten_batch(mats, 2)
    x_flat, _ = _flatten_batch(x, 1)
    rhs_flat, _ = _flatten_batch(rhs, 1)
    resid = _bs.residuals(mats_flat, x_flat, rhs_flat, trans=trans)
    return x, resid.reshape(lead)


# ---------------------------------------------------------------------------
# Sparse stage solves on padded neighbor lists (kernels/sparse_solve.py, §18)
# ---------------------------------------------------------------------------

class SparseTopo(NamedTuple):
    """The sparse-topology arrays of an Instance, as one hashable-shape
    bundle the sparse kernels consume (``network.with_sparse`` attaches the
    fields; ``sparse_topo`` extracts them).

    out_nbr/out_mask, in_nbr/in_mask: (V, D) padded neighbor lists
    blk_nbr/blk_mask: (NB, BD) block-level neighbor lists (BSR structure)
    """

    out_nbr: jnp.ndarray
    out_mask: jnp.ndarray
    in_nbr: jnp.ndarray
    in_mask: jnp.ndarray
    blk_nbr: jnp.ndarray
    blk_mask: jnp.ndarray


def sparse_topo(inst) -> SparseTopo:
    """Extract the SparseTopo bundle of an instance (raises if absent)."""
    if inst.out_nbr is None:
        raise ValueError(
            "instance carries no sparse topology; attach one with "
            "network.with_sparse(inst) before solver='sparse'")
    return SparseTopo(out_nbr=inst.out_nbr, out_mask=inst.out_mask,
                      in_nbr=inst.in_nbr, in_mask=inst.in_mask,
                      blk_nbr=inst.blk_nbr, blk_mask=inst.blk_mask)


@functools.partial(jax.jit, static_argnames=("trans", "reverse", "clamp",
                                              "use_pallas"))
def sparse_chain_solve(topo: SparseTopo, phi_e: jnp.ndarray,
                       base: jnp.ndarray, mult: jnp.ndarray, *,
                       trans: int = 0, reverse: bool = False,
                       clamp: bool = False,
                       use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Sparse drop-in for ``fused_chain_solve``: solve the whole stage chain

        x_k = (I - M_k)^{-1} (base_k + mult_k * x_prev),
        M_k = Phi_k (trans=0) or Phi_k^T (trans=1),

    by O(E)-per-sweep fixed-point iteration on the padded neighbor lists —
    exact for loop-free (nilpotent) strategies, divergent (and rejected by
    ``traffic_is_valid``) for loopy candidates, mirroring the dense
    contract (kernels/sparse_solve.py).

    phi_e (..., K, V, V), base/mult (..., K, V) -> x (..., K, V).  No
    factorization object: the topology bundle replaces ``BatchedLU``.  The
    Pallas path (TPU default, interpret on request) runs the
    partition-blocked BSR kernel over the nonzero blocks only; the jnp path
    gathers per-edge values.  Collective-free and per-member, like every
    wrapper here (shard_map safe).
    """
    phi_flat, lead = _flatten_batch(phi_e, 3)          # (Bf, K, V, V)
    base_flat, _ = _flatten_batch(base, 2)
    mult_flat, _ = _flatten_batch(mult, 2)
    if _use_pallas(use_pallas):
        M = phi_flat if trans == 0 else jnp.swapaxes(phi_flat, -1, -2)
        bvals = _ss.block_values(M, topo.blk_nbr, topo.blk_mask,
                                 _ss.SPARSE_BLOCK)
        x = _ss.chain_solve_bsr(bvals, topo.blk_nbr, base_flat, mult_flat,
                                reverse=reverse, clamp=clamp,
                                interpret=INTERPRET)
    else:
        nbr, mask = ((topo.out_nbr, topo.out_mask) if trans == 0
                     else (topo.in_nbr, topo.in_mask))
        vals = _ss.neighbor_values(phi_flat, nbr, mask, trans=trans)
        x = _ss.chain_solve_nbr(vals, nbr, base_flat, mult_flat,
                                reverse=reverse, clamp=clamp)
    return x.reshape(base.shape)


@functools.partial(jax.jit, static_argnames=("with_rounds",))
def blocked_tagged_nbr(route: jnp.ndarray, improper: jnp.ndarray,
                       nbr: jnp.ndarray, mask: jnp.ndarray, *,
                       with_rounds: bool = False):
    """Neighbor-list variant of ``blocked_tagged``: O(E) per round.

    route/improper (..., V, V) bool, nbr/mask (V, D) -> tagged (..., V)
    bool, bit-equal to ``blocked_tagged`` and the dense scan (the fixed
    point is the same monotone map; see kernels/sparse_solve.py).
    ``with_rounds=True`` also returns the sweep's round counter (§19
    telemetry — the counter already exists in the while-loop).
    """
    flat, lead = _flatten_batch(route, 2)
    V = flat.shape[-1]
    idx = jnp.broadcast_to(nbr, flat.shape[:-1] + nbr.shape[-1:])
    rv = jnp.take_along_axis(flat, idx, axis=-1) & mask
    iv = jnp.take_along_axis(improper.reshape(flat.shape), idx, axis=-1)
    if with_rounds:
        tagged, rounds = _ss.tagged_nbr(rv, iv, nbr, with_rounds=True)
        return tagged.reshape(lead + (V,)), rounds
    tagged = _ss.tagged_nbr(rv, iv, nbr)
    return tagged.reshape(lead + (V,))


# ---------------------------------------------------------------------------
# Bit-packed blocked-set propagation (kernels/blocked_sets.py — DESIGN.md §13)
# ---------------------------------------------------------------------------

# The Pallas tagged kernel keeps packed successor words on the lane axis
# (W = ceil(V/32) lanes), which only fills real-TPU lanes at V >= 4096; below
# that the packed-jnp path wins even on TPU, so the Pallas path engages by
# default only for very large graphs (interpret mode on request, for tests).
_BITSET_PALLAS_MIN_V = 4096


@functools.partial(jax.jit, static_argnames=("use_pallas", "with_rounds"))
def blocked_tagged(route: jnp.ndarray, improper: jnp.ndarray, *,
                   use_pallas: Optional[bool] = None,
                   with_rounds: bool = False):
    """Category-3 "tagged node" flags of the blocked sets B_i(a,k).

    route, improper (..., V, V) bool -> tagged (..., V) bool: node p is
    tagged iff its routing subtree contains an improper link, i.e. the
    monotone fixed point of

        tagged[p] = exists q: route[p, q] and (improper[p, q] or tagged[q]).

    Both matrices are bit-packed into uint32 lanes once and the fixed point
    is reached by word-wise OR-AND rounds with a while-loop frontier early
    exit at the routing-DAG diameter — exactly equal to the seed's dense
    V-round sweep, at ~1/32 the traffic and ~diameter/V the rounds
    (kernels/blocked_sets.py).

    ``with_rounds=True`` additionally returns the sweep's round counter
    (§19 telemetry).  The Pallas path runs its loop in-kernel and does not
    expose the counter — it reports -1 (not measured).
    """
    flat, lead = _flatten_batch(route, 2)
    V = flat.shape[-1]
    Vp, _ = _bset.padded_nodes(V)
    imp_flat = improper.reshape(flat.shape)
    row_pad = ((0, 0), (0, Vp - V), (0, 0))
    route_bits = jnp.pad(_bset.pack_bits(flat), row_pad)
    imp_bits = jnp.pad(_bset.pack_bits(imp_flat), row_pad)
    pallas = (_PALLAS_DEFAULT and V >= _BITSET_PALLAS_MIN_V
              if use_pallas is None else use_pallas)
    rounds = jnp.int32(-1)
    if pallas:
        tagged = _bset.tagged_pallas(route_bits, imp_bits, V,
                                     interpret=INTERPRET)
    elif with_rounds:
        tagged, rounds = _bset.tagged_packed(route_bits, imp_bits, V,
                                             with_rounds=True)
    else:
        tagged = _bset.tagged_packed(route_bits, imp_bits, V)
    tagged = tagged.reshape(lead + (V,))
    if with_rounds:
        return tagged, rounds
    return tagged
