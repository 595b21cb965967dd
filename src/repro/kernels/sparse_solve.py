"""Sparse stage-system solves on padded neighbor lists (DESIGN.md §18).

The GP stage systems are ``(I - Phi_k^T) t = b`` (traffic, trans=1) and
``(I - Phi_k) pdt = b`` (marginals, trans=0).  For loop-free strategies
``Phi_k`` restricted to its support is *nilpotent* — routing follows a DAG —
so the Neumann series terminates and the fixed-point sweep

    x <- b + M x,        M = Phi_k (trans=0) or Phi_k^T (trans=1)

converges EXACTLY after (DAG depth + 1) sweeps: once every dependency of a
node has settled, recomputing its value is bit-deterministic, so the
``x != prev`` early exit stops precisely at the fixed point (the same
argument as the bitset sweep's monotone early exit, DESIGN.md §13).  Loopy
candidate strategies make the sweep diverge — values blow past the
``traffic_is_valid`` bound (or are frozen at +inf by the divergence latch)
and the candidate is rejected, exactly like the dense path's singular-solve
contract.

Each sweep costs O(E) instead of the dense path's O(V^2) substitution (and
no O(V^3) factorization at all), which is what makes metro-scale graphs
(V >= several hundred at O(V) edges) viable.

Two executable paths, dispatched by ``kernels.ops.sparse_chain_solve``:

  * :func:`chain_solve_nbr`  — gather/scatter-free jnp sweeps on the padded
    neighbor lists (``x[..., nbr]`` is one gather per sweep); CPU/GPU path.
  * :func:`chain_solve_bsr`  — the partition-blocked Pallas kernel: the
    stage matrices are gathered into BSR-style ``(NB, BD, bs, bs)`` blocks
    (``network.block_neighbors``) and the kernel iterates ONLY the nonzero
    blocks — one ``(bs, BD*bs)`` block-row matmul per block row per sweep
    on TPU (Mosaic; interpret mode for tests).

Both compute the same linear map, so they agree to float tolerance; parity
with the dense LU path on loop-free strategies is exact up to roundoff
(tests/test_sparse.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Edge length of the partition blocks (``network.block_neighbors`` re-exports
# this as ``network.SPARSE_BLOCK``): 32 matches both the bitset word width
# and the TPU sublane tile.
SPARSE_BLOCK = 32

# Iterates beyond this magnitude are frozen at +inf: the lane has provably
# diverged (every physical traffic/marginal is orders of magnitude smaller),
# and freezing makes the while-loop exit instead of chasing a runaway
# geometric series to the sweep cap.
_DIVERGE = 1e12

_LANE = 128  # TPU lane width


def neighbor_values(phi_e: jnp.ndarray, nbr: jnp.ndarray, mask: jnp.ndarray,
                    *, trans: int) -> jnp.ndarray:
    """Gather the sparse matrix entries aligned to the padded neighbor lists.

    phi_e (..., V, V), nbr/mask (V, D) -> vals (..., V, D) with

        trans=0:  vals[..., i, d] = phi_e[..., i, out_nbr[i, d]]
        trans=1:  vals[..., j, d] = phi_e[..., in_nbr[j, d], j]

    i.e. row p of ``vals`` holds the nonzero entries of row p of ``M``
    (``M = Phi`` or ``Phi^T``), so the sweep ``b + sum_d vals * x[nbr]`` is
    the sparse matvec ``b + M x``.  Masked columns are zeroed.
    """
    M = phi_e if trans == 0 else jnp.swapaxes(phi_e, -1, -2)
    idx = jnp.broadcast_to(nbr, M.shape[:-1] + nbr.shape[-1:])
    vals = jnp.take_along_axis(M, idx, axis=-1)
    return jnp.where(mask, vals, 0.0)


def _fixed_point(vals: jnp.ndarray, nbr: jnp.ndarray, b: jnp.ndarray,
                 cap: int) -> jnp.ndarray:
    """Solve x = b + M x by sweeps with an exact-settle early exit.

    vals (..., V, D), nbr (V, D), b (..., V) -> x (..., V).  The loop exits
    when no entry changed (exact for nilpotent M, see module docstring) or
    after ``cap`` sweeps; diverging entries latch at +inf.
    """
    def sweep(x):
        y = b + jnp.sum(vals * x[..., nbr], axis=-1)
        bad = ~jnp.isfinite(y) | (jnp.abs(y) > _DIVERGE)
        return jnp.where(bad, jnp.inf, y)

    def cond(carry):
        x, prev, i = carry
        return jnp.any(x != prev) & (i < cap)

    def body(carry):
        x, _, i = carry
        return sweep(x), x, i + 1

    x0 = sweep(jnp.zeros_like(b))
    prev0 = jnp.full_like(b, jnp.inf)
    x, _, _ = jax.lax.while_loop(cond, body, (x0, prev0, jnp.int32(1)))
    return x


def chain_solve_nbr(vals: jnp.ndarray, nbr: jnp.ndarray,
                    base: jnp.ndarray, mult: jnp.ndarray, *,
                    reverse: bool = False, clamp: bool = False) -> jnp.ndarray:
    """Fused chain of sparse stage solves (the neighbor-list jnp path).

    vals (B, K, V, D) row-aligned stage matrices (``neighbor_values``),
    nbr (V, D), base/mult (B, K, V) -> x (B, K, V) where, walking k forward
    (or backward with ``reverse=True``),

        x_k = (I - M_k)^{-1} (base_k + mult_k * x_prev),  x_prev(start) = 0,

    optionally clamped at 0 after each stage — exactly the
    ``ops.fused_chain_solve`` contract, with the dense triangular
    substitutions replaced by O(E) fixed-point sweeps.
    """
    V = base.shape[-1]
    cap = V + 2
    # scan over the stage axis: move K in front of the member axis
    vals_t = jnp.moveaxis(vals, 1, 0)      # (K, B, V, D)
    base_t = jnp.moveaxis(base, 1, 0)      # (K, B, V)
    mult_t = jnp.moveaxis(mult, 1, 0)

    def step(x_prev, xs):
        vals_k, base_k, mult_k = xs
        x = _fixed_point(vals_k, nbr, base_k + mult_k * x_prev, cap)
        if clamp:
            x = jnp.maximum(x, 0.0)
        return x, x

    _, xs = jax.lax.scan(step, jnp.zeros_like(base_t[0]),
                         (vals_t, base_t, mult_t), reverse=reverse)
    return jnp.moveaxis(xs, 0, 1)


# ---------------------------------------------------------------------------
# Partition-blocked (BSR) Pallas kernel
# ---------------------------------------------------------------------------

def block_values(M: jnp.ndarray, blk_nbr: jnp.ndarray, blk_mask: jnp.ndarray,
                 block: int) -> jnp.ndarray:
    """Gather the nonzero ``block x block`` blocks of a stage matrix stack.

    M (..., V, V), blk_nbr/blk_mask (NB, BD) -> bvals (..., NB, BD, bs, bs)
    with ``bvals[..., I, d] = M[rows of I, cols of blk_nbr[I, d]]`` (zero
    where masked).  V is zero-padded to NB*bs — exact for the fixed-point
    form, which needs no diagonal.
    """
    NB, BD = blk_nbr.shape
    Vp = NB * block
    V = M.shape[-1]
    if Vp != V:
        widths = [(0, 0)] * (M.ndim - 2) + [(0, Vp - V), (0, Vp - V)]
        M = jnp.pad(M, widths)
    Mb = M.reshape(M.shape[:-2] + (NB, block, NB, block))
    Mb = jnp.swapaxes(Mb, -3, -2)                        # (..., NB, NB, bs, bs)
    idx = jnp.broadcast_to(blk_nbr[:, :, None, None],
                           Mb.shape[:-3] + (BD, block, block))
    bvals = jnp.take_along_axis(Mb, idx, axis=-3)        # (..., NB, BD, bs, bs)
    return jnp.where(blk_mask[:, :, None, None], bvals, 0.0)


def _bsr_chain_kernel(nbr_ref, rows_ref, base_ref, mult_ref, out_ref,
                      xprev_ref, x_ref, y_ref, b_ref, xc_ref, *,
                      clamp: bool, cap: int):
    """One (member, stage) per grid step, stages walked in chain order.

    rows (1, 1, NB, bs, BDp*bs): block row I of the stage matrix with its
    BDp nonzero (bs, bs) blocks side by side; nbr (NB, BDp) in SMEM names
    the column block of each.  Vectors are (Vp, 1) columns in VMEM scratch,
    so every gather is a sublane-aligned row slice.  One sweep stacks the
    BDp neighbour slices of x and takes ONE (bs, BDp*bs) @ (BDp*bs, 1)
    matmul per block row.  ``xprev`` carries x_{k-1} across the stage axis
    of the grid.
    """
    NB, bs, W = rows_ref.shape[2:]
    BDp = W // bs

    @pl.when(pl.program_id(1) == 0)
    def _():
        xprev_ref[...] = jnp.zeros_like(xprev_ref)

    b_ref[...] = base_ref[0, 0] + mult_ref[0, 0] * xprev_ref[...]

    def sweep():
        """y <- b + M x, with diverging entries latched at +inf."""
        def row_block(I, carry):
            for d in range(BDp):
                j0 = pl.multiple_of(nbr_ref[I, d] * bs, bs)
                xc_ref[d * bs:(d + 1) * bs, :] = x_ref[pl.ds(j0, bs), :]
            r0 = pl.multiple_of(I * bs, bs)
            y = b_ref[pl.ds(r0, bs), :] + jax.lax.dot(
                rows_ref[0, 0, I], xc_ref[...],
                precision=jax.lax.Precision.HIGHEST)
            bad = ~jnp.isfinite(y) | (jnp.abs(y) > _DIVERGE)
            y_ref[pl.ds(r0, bs), :] = jnp.where(bad, jnp.inf, y)
            return carry

        jax.lax.fori_loop(0, NB, row_block, 0)

    def changed(prev):
        return jnp.max(jnp.where(y_ref[...] != prev, 1, 0)) > 0

    def cond(carry):
        moved, i = carry
        return moved & (i < cap)

    def body(carry):
        _, i = carry
        x_ref[...] = y_ref[...]
        sweep()
        return changed(x_ref[...]), i + 1

    # x_0 = sweep(0), compared against an all-inf "previous" iterate
    x_ref[...] = jnp.zeros_like(x_ref)
    sweep()
    moved0 = changed(jnp.full(x_ref.shape, jnp.inf, jnp.float32))
    jax.lax.while_loop(cond, body, (moved0, jnp.int32(1)))
    x = y_ref[...]
    if clamp:
        x = jnp.maximum(x, 0.0)
    out_ref[0, 0] = x
    xprev_ref[...] = x


def chain_solve_bsr(bvals: jnp.ndarray, blk_nbr: jnp.ndarray,
                    base: jnp.ndarray, mult: jnp.ndarray, *,
                    reverse: bool = False, clamp: bool = False,
                    interpret: bool = False) -> jnp.ndarray:
    """Blocked-sparse fused chain solve (the Pallas path).

    bvals (B, K, NB, BD, bs, bs) from :func:`block_values`, blk_nbr (NB, BD),
    base/mult (B, K, V) -> x (B, K, V); same semantics as
    :func:`chain_solve_nbr`.  The block degree is padded with zero blocks
    to BDp so that a block row (bs, BDp*bs) spans whole 128-lane tiles.
    """
    B, K, NB, BD, bs = bvals.shape[:5]
    Vp = NB * bs
    V = base.shape[-1]
    BDp = -(-BD * bs // _LANE) * _LANE // bs
    if BDp != BD:
        bvals = jnp.pad(bvals, ((0, 0),) * 3 + ((0, BDp - BD), (0, 0), (0, 0)))
        blk_nbr = jnp.pad(blk_nbr, ((0, 0), (0, BDp - BD)))
    rows = jnp.swapaxes(bvals, 3, 4).reshape(B, K, NB, bs, BDp * bs)
    widths = ((0, 0), (0, 0), (0, Vp - V))
    base = jnp.pad(base.astype(jnp.float32), widths)[..., None]
    mult = jnp.pad(mult.astype(jnp.float32), widths)[..., None]

    def stage(b, k, nbr):
        return (b, K - 1 - k) if reverse else (b, k)

    vec = pl.BlockSpec((1, 1, Vp, 1),
                       lambda b, k, nbr: stage(b, k, nbr) + (0, 0))
    out = pl.pallas_call(
        functools.partial(_bsr_chain_kernel, clamp=clamp, cap=V + 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K),
            in_specs=[
                pl.BlockSpec((1, 1, NB, bs, BDp * bs),
                             lambda b, k, nbr: stage(b, k, nbr) + (0, 0, 0)),
                vec, vec,
            ],
            out_specs=vec,
            scratch_shapes=[pltpu.VMEM((Vp, 1), jnp.float32)] * 4
            + [pltpu.VMEM((BDp * bs, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, Vp, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="chain_solve_bsr",
    )(blk_nbr.astype(jnp.int32), rows.astype(jnp.float32), base, mult)
    return out[..., :V, 0]


# ---------------------------------------------------------------------------
# Neighbor-list blocked-set ("tagged node") sweep
# ---------------------------------------------------------------------------

def tagged_nbr(route_vals: jnp.ndarray, improper_vals: jnp.ndarray,
               nbr: jnp.ndarray, *, with_rounds: bool = False):
    """Category-3 tagged flags by O(E)-per-round sweeps on neighbor lists.

    route_vals/improper_vals (..., V, D) bool — ``route``/``improper``
    gathered onto the padded out-neighbor lists (masked columns False),
    nbr (V, D) -> tagged (..., V) bool: the monotone fixed point of

        tagged[p] = exists d: route[p, d] and (improper[p, d] or
                                               tagged[nbr[p, d]])

    The map is monotone (tagged only grows), so the ``!=`` early exit is
    exact: the result is bit-equal to the dense V-round scan and the bitset
    sweep, at O(E) per round instead of O(V^2)(/32) (DESIGN.md §18).

    ``with_rounds=True`` additionally returns the sweep's existing round
    counter (rounds until the fixed point settled — telemetry, §19);
    propagation arithmetic is unchanged.
    """
    V = route_vals.shape[-2]
    seed = jnp.any(route_vals & improper_vals, axis=-1)       # (..., V)

    def cond(carry):
        t, prev, i = carry
        return jnp.any(t != prev) & (i < V + 1)

    def body(carry):
        t, _, i = carry
        hit = seed | jnp.any(route_vals & t[..., nbr], axis=-1)
        return hit, t, i + 1

    prev0 = jnp.zeros_like(seed)
    t, _, rounds = jax.lax.while_loop(
        cond, body, (seed, prev0, jnp.int32(1)))
    if with_rounds:
        return t, rounds
    return t
