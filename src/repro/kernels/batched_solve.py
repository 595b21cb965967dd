"""Blocked batched-LU Pallas kernel for the GP hot loop's stage systems.

Every GP iteration solves O(ladder x apps x stages) small dense systems

    (I - Phi_k)   pdt = b      (marginal recursion (4), row form)
    (I - Phi_k)^T t   = inject (traffic fixed point, Section II)

whose matrices differ only by a transpose.  This module provides the batched
factorization + triangular-solve pair that turns that pile of tiny solves
into ONE ``(B, V, V)`` device program:

  * :func:`lu_factor` — unpivoted blocked LU, one batch member per grid
    step.  Loop-free strategies make ``I - Phi`` a nonsingular M-matrix
    (unit diagonal, row-diagonally dominant), for which LU without pivoting
    exists and is stable; near-singular members (loopy candidate
    strategies) produce ~0 pivots whose non-finite quotients are surfaced
    through per-member ``ok`` flags rather than exceptions — the contract
    DESIGN.md §2 and §12 rely on to keep divergence detectable under vmap.
  * :func:`lu_solve` — the companion two-sweep triangular solve, with
    ``trans=1`` reusing the same factors for the transposed system.
  * :func:`ref_factor` / :func:`ref_solve` — the ``jax.lax.linalg`` (LAPACK
    partial-pivoting) reference path; also the CPU dispatch target of
    ``kernels.ops`` since interpret-mode Pallas cannot beat native LAPACK.

Blocking scheme (§12): the (Vp, Vp) matrix is resident in VMEM; a static
python loop walks column panels of width ``NB`` (= 128, so every static
slice is lane-aligned on TPU).  Within a panel, columns are eliminated by
masked rank-1 updates (VPU); the panel's trailing block row is
``U12 = (I + L11s)^{-1} A12`` with the inverse of the nilpotent
strictly-lower panel from the log-depth Neumann product (MXU matmuls); the
trailing submatrix update ``A22 -= L21 @ U12`` is a single MXU matmul —
the O(V^3) bulk of the factorization.  Both are written through the
output ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128       # lane-dim alignment on real TPU
SUBLANE = 8      # cheaper alignment used under interpret mode (tests/CPU)
DEFAULT_NB = LANE  # column-panel width: panels start lane-aligned on TPU

# |U_ii| below this is treated as a structurally singular member.
PIVOT_TINY = 1e-30

# Every contraction here runs at full float32 precision: on TPU the default
# matmul precision is a single bfloat16 pass, which would change the solves.
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Reference path (jax.lax.linalg factorization + block substitution)
# ---------------------------------------------------------------------------
#
# The factorization is LAPACK's batched partial-pivoting getrf
# (``jax.lax.linalg.lu``).  The SOLVE phase deliberately avoids XLA's
# ``triangular_solve``: on CPU its batched lowering is orders of magnitude
# slower than the O(B V^2) flop count (measured ~50ms for 90 single-rhs
# V=100 solves).  Instead, factor time precomputes the inverses of the
# nb x nb diagonal blocks of L and U — a log-depth Neumann product over
# ONE (B * nblk, nb, nb) matmul stack, valid because the strict triangle
# of a triangular block is nilpotent — and each solve is then a short
# static chain of batched matvecs (one per block row), which XLA:CPU maps
# to well-optimized batched GEMV.  This is the same blocking scheme the
# Pallas kernel uses on TPU, expressed at the XLA level (DESIGN.md §12).

# Substitution block width.  The diag-block inverse prework costs
# O(log(nb) * V * nb^2) flops per member and the solve sweeps O(V/nb)
# dispatches — nb=16 balances the two on CPU (nb=32 triples factor-time
# flops for one fewer solve dispatch per sweep).
REF_NB = 16


def _pad_square(a: jnp.ndarray, Vp: int) -> jnp.ndarray:
    """Pad (B, V, V) to (B, Vp, Vp) with an identity tail block."""
    V = a.shape[-1]
    if Vp == V:
        return a
    a = jnp.pad(a, ((0, 0), (0, Vp - V), (0, Vp - V)))
    tail = (jnp.arange(Vp) >= V).astype(a.dtype)
    return a + jnp.diag(tail)[None]


def _diag_blocks(a: jnp.ndarray, nb: int) -> jnp.ndarray:
    """(B, Vp, Vp) -> (B, nblk, nb, nb) diagonal blocks."""
    B, Vp, _ = a.shape
    nblk = Vp // nb
    d = jnp.diagonal(a.reshape(B, nblk, nb, nblk, nb), axis1=1, axis2=3)
    return jnp.moveaxis(d, -1, 1)


def _nilpotent_inv(X: jnp.ndarray) -> jnp.ndarray:
    """inv(I - X) for strictly-triangular (nilpotent) X, any leading dims.

    Uses the log-depth product identity sum_{k<2^m} X^k =
    prod_j (I + X^(2^j)) — ceil(log2 nb) batched matmul rounds instead of
    nb substitution steps.  The leading dims are flattened into ONE batch
    dim around the matmuls: XLA:CPU refuses to compile the multi-batch-dim
    dot that the same einsum gives under ``vmap``.
    """
    lead, nb = X.shape[:-2], X.shape[-1]
    X = X.reshape((-1, nb, nb))
    eye = jnp.eye(nb, dtype=X.dtype)
    mm = functools.partial(jnp.einsum, "bij,bjk->bik", precision=_HIGHEST)
    acc = eye + X
    span = 2
    while span < nb:
        X = mm(X, X)
        acc = mm(acc, eye + X)
        span *= 2
    return acc.reshape(lead + (nb, nb))


def block_inverses(lu: jnp.ndarray, nb: int = REF_NB
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inverses of the diagonal nb-blocks of packed factors.

    lu (B, V, V) -> (linv, uinv), each (B, nblk, nb, nb), where
    linv[b, i] = inv(L_ii) (unit lower) and uinv[b, i] = inv(U_ii).
    Padding blocks are identity, so padded solves are exact.
    """
    V = lu.shape[-1]
    Vp = -(-V // nb) * nb
    lup = _pad_square(lu.astype(jnp.float32), Vp)
    tri = jnp.tril(jnp.ones((nb, nb), jnp.float32), -1)
    Lb = _diag_blocks(lup, nb) * tri                     # strict lower
    linv = _nilpotent_inv(-Lb)
    Ub = _diag_blocks(lup, nb) * (1.0 - tri)             # upper incl diag
    d = jnp.diagonal(Ub, axis1=-2, axis2=-1)             # (B, nblk, nb)
    dinv = 1.0 / d
    Nu = dinv[..., :, None] * Ub * tri.T                 # row-scaled strict upper
    uinv = _nilpotent_inv(-Nu) * dinv[..., None, :]
    return linv, uinv


def ref_factor(mats: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched LAPACK LU + substitution prework.

    mats (B, V, V) -> (lu, perm (B, V) int32 row permutation with
    ``mats[perm] = L @ U``, linv, uinv).
    """
    lu, _, perm = jax.lax.linalg.lu(mats.astype(jnp.float32))
    linv, uinv = block_inverses(lu)
    return lu, perm, linv, uinv


def _block_subst(mat: jnp.ndarray, dinv: jnp.ndarray, b: jnp.ndarray,
                 nb: int, *, lower: bool) -> jnp.ndarray:
    """Solve T x = b for block-triangular T given diag-block inverses.

    mat (B, Vp, Vp) carries T in its lower (or upper) triangle; coupling
    to already-solved blocks is a masked batched matvec per block row —
    the intra-block triangle is folded into ``dinv``.
    """
    B, Vp = b.shape
    nblk = Vp // nb
    cols = jnp.arange(Vp)
    x = jnp.zeros_like(b)
    order = range(nblk) if lower else range(nblk - 1, -1, -1)
    for i in order:
        sl = slice(i * nb, (i + 1) * nb)
        panel = mat[:, sl, :]
        mask = (cols < i * nb) if lower else (cols >= (i + 1) * nb)
        s = jnp.einsum("brv,bv->br", panel * mask, x, precision=_HIGHEST)
        x_i = jnp.einsum("brc,bc->br", dinv[:, i], b[:, sl] - s,
                         precision=_HIGHEST)
        x = x.at[:, sl].set(x_i)
    return x


def _subst_single(mat: jnp.ndarray, dinv: jnp.ndarray, b: jnp.ndarray,
                  nb: int, *, lower: bool) -> jnp.ndarray:
    """Single-system variant of :func:`_block_subst`: mat (Vp, Vp),
    dinv (nblk, nb, nb), b (Vp,) -> x (Vp,).  Batches by jax.vmap.

    The block-row loop is static, so the coupling to already-solved blocks
    is a *statically sliced* matvec (``mat[sl, :i*nb] @ x[:i*nb]``) rather
    than the masked full-row product of ``_block_subst`` — half the flops
    and no mask materialization, which matters inside the fused chain scan.
    """
    Vp = b.shape[0]
    nblk = Vp // nb
    x = jnp.zeros_like(b)
    order = range(nblk) if lower else range(nblk - 1, -1, -1)
    for i in order:
        sl = slice(i * nb, (i + 1) * nb)
        done = slice(0, i * nb) if lower else slice((i + 1) * nb, Vp)
        s = (jnp.dot(mat[sl, done], x[done], precision=_HIGHEST)
             if done.stop != done.start else 0.0)
        x = x.at[sl].set(jnp.dot(dinv[i], b[sl] - s, precision=_HIGHEST))
    return x


def ref_chain_solve(lu: jnp.ndarray, perm: jnp.ndarray,
                    linv: jnp.ndarray, uinv: jnp.ndarray,
                    base: jnp.ndarray, mult: jnp.ndarray,
                    *, trans: int = 0, reverse: bool = False,
                    clamp: bool = False, nb: int = REF_NB) -> jnp.ndarray:
    """Fused sequential solve over a whole factor stack (one chain).

    Solves, along the stage axis k (forward, or backward with
    ``reverse=True``),

        x_k = A_k^{-1(T)} (base_k + mult_k * x_prev),     x_prev(start) = 0

    for lu (K, V, V), perm (K, V), linv/uinv (K, nblk, nb, nb) and
    base/mult (K, V), returning x (K, V) — the shared recurrence shape of
    the traffic fixed point (trans=1, forward, mult = shifted phi_c) and
    the marginal recursion (trans=0, reverse, mult = phi_c, clamp >= 0).

    Compared with calling :func:`ref_solve` once per stage inside a scan,
    every per-stage fixed cost — factor padding, the trans transpose, the
    permutation (arg)sort, dtype casts — is hoisted out of the loop and
    paid ONCE for the whole (K, V, V) stack; the scan body is only the two
    block-substitution sweeps plus the O(V) affine RHS.  This is what
    moves the CPU dense-vs-batched crossover (traffic.AUTO_MIN_V) down
    (DESIGN.md §13).
    """
    K, V = base.shape
    Vp = linv.shape[-3] * nb
    lup = _pad_square(lu.astype(jnp.float32), Vp)
    basep = jnp.pad(base.astype(jnp.float32), ((0, 0), (0, Vp - V)))
    multp = jnp.pad(mult.astype(jnp.float32), ((0, 0), (0, Vp - V)))
    tail = jnp.broadcast_to(jnp.arange(V, Vp, dtype=perm.dtype), (K, Vp - V))
    permp = jnp.concatenate([perm, tail], axis=1).astype(jnp.int32)
    if trans == 0:
        mats, d1, d2 = lup, linv, uinv
        pre, post = permp, None
    else:
        # A^T = U^T L^T P: sweep the transposed pack, un-permute the result
        mats = lup.transpose(0, 2, 1)
        d1 = uinv.transpose(0, 1, 3, 2)
        d2 = linv.transpose(0, 1, 3, 2)
        pre, post = None, jnp.argsort(permp, axis=1)

    def step(carry, xs):
        mat_k, d1_k, d2_k, base_k, mult_k, pre_k, post_k = xs
        b = base_k + mult_k * carry
        if pre is not None:
            b = b[pre_k]
        y = _subst_single(mat_k, d1_k, b, nb, lower=True)
        x = _subst_single(mat_k, d2_k, y, nb, lower=False)
        if post is not None:
            x = x[post_k]
        if clamp:
            x = jnp.maximum(x, 0.0)
        return x, x

    zeros_i = jnp.zeros((K, 1), jnp.int32)  # placeholder for the unused perm
    xs = (mats, d1, d2, basep, multp,
          pre if pre is not None else zeros_i,
          post if post is not None else zeros_i)
    _, x = jax.lax.scan(step, jnp.zeros((Vp,), jnp.float32), xs,
                        reverse=reverse)
    return x[:, :V]


def ref_solve(lu: jnp.ndarray, perm: jnp.ndarray,
              linv: jnp.ndarray, uinv: jnp.ndarray, rhs: jnp.ndarray,
              *, trans: int = 0, nb: int = REF_NB) -> jnp.ndarray:
    """Solve A x = rhs (trans=0) or A^T x = rhs (trans=1) from ref_factor."""
    B, V = rhs.shape
    Vp = linv.shape[1] * nb
    lup = _pad_square(lu.astype(jnp.float32), Vp)
    b = rhs.astype(jnp.float32)
    if trans == 0:
        # A = P^T L U:  L U x = b[perm]
        bp = jnp.take_along_axis(b, perm.astype(jnp.int32), axis=1)
        bp = jnp.pad(bp, ((0, 0), (0, Vp - V)))
        y = _block_subst(lup, linv, bp, nb, lower=True)
        x = _block_subst(lup, uinv, y, nb, lower=False)
        return x[:, :V]
    # A^T = U^T L^T P:  solve U^T y = b, L^T z = y, then undo the row perm
    lupT = lup.transpose(0, 2, 1)
    uinvT = uinv.transpose(0, 1, 3, 2)
    linvT = linv.transpose(0, 1, 3, 2)
    bp = jnp.pad(b, ((0, 0), (0, Vp - V)))
    y = _block_subst(lupT, uinvT, bp, nb, lower=True)
    z = _block_subst(lupT, linvT, y, nb, lower=False)[:, :V]
    inv_perm = jnp.argsort(perm, axis=1)
    return jnp.take_along_axis(z, inv_perm, axis=1)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _pad_dim(V: int, interpret: bool) -> int:
    mult = SUBLANE if interpret else LANE
    return -(-V // mult) * mult


def _lu_kernel(a_ref, lu_ref, *, nb: int):
    """Unpivoted blocked LU of one (Vp, Vp) matrix.

    Column panels of width ``nb`` start at multiples of ``nb`` (= the lane
    width on TPU), so every static slice below is tile-aligned; the panel's
    trailing block row and submatrix are written through the output ref.
    """
    a = a_ref[0].astype(jnp.float32)
    Vp = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Vp, Vp), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Vp, Vp), 1)
    rid = jax.lax.broadcasted_iota(jnp.int32, (Vp, 1), 0)
    cid = jax.lax.broadcasted_iota(jnp.int32, (1, Vp), 1)

    for p0 in range(0, Vp, nb):
        p1 = min(p0 + nb, Vp)

        def col_step(k, a):
            # Masked rank-1 elimination of column k, update restricted to
            # the panel's columns (the trailing block is updated once per
            # panel by the MXU matmul below).
            rowk = jnp.sum(jnp.where(row == k, a, 0.0), axis=0, keepdims=True)
            colk = jnp.sum(jnp.where(col == k, a, 0.0), axis=1, keepdims=True)
            piv = jnp.sum(jnp.where(cid == k, rowk, 0.0), axis=1,
                          keepdims=True)
            l = jnp.where(rid > k, colk / piv, 0.0)                  # (Vp, 1)
            u = jnp.where((cid > k) & (cid < p1), rowk, 0.0)         # (1, Vp)
            a = a - l * u
            # store the multipliers below the diagonal of column k
            return jnp.where((col == k) & (row > k), l, a)

        a = jax.lax.fori_loop(p0, p1, col_step, a)

        if p1 < Vp:
            L11 = a[p0:p1, p0:p1]
            rloc = jax.lax.broadcasted_iota(jnp.int32, L11.shape, 0)
            cloc = jax.lax.broadcasted_iota(jnp.int32, L11.shape, 1)
            # U12 = (I + L11s)^{-1} A12, with the inverse of the unit-lower
            # panel from the log-depth Neumann product (L11s is nilpotent).
            inv = _nilpotent_panel_inv(-jnp.where(rloc > cloc, L11, 0.0))
            U12 = jax.lax.dot(inv, a[p0:p1, p1:], precision=_HIGHEST)
            L21 = a[p1:, p0:p1]
            lu_ref[0] = a
            lu_ref[0, p0:p1, p1:] = U12
            lu_ref[0, p1:, p1:] = a[p1:, p1:] - jax.lax.dot(
                L21, U12, precision=_HIGHEST)
            a = lu_ref[0]

    lu_ref[0] = a.astype(lu_ref.dtype)


def _nilpotent_panel_inv(X: jnp.ndarray) -> jnp.ndarray:
    """In-kernel inv(I - X) of one nilpotent (n, n) panel (log-depth)."""
    n = X.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
           ).astype(jnp.float32)
    acc = eye + X
    span = 2
    while span < n:
        X = jax.lax.dot(X, X, precision=_HIGHEST)
        acc = jax.lax.dot(acc, eye + X, precision=_HIGHEST)
        span *= 2
    return acc


def _two_sweep(row, b: jnp.ndarray, *, trans: int) -> jnp.ndarray:
    """In-kernel two-sweep substitution on a packed L\\U factor.

    ``row(i)`` reads row i of the packed factor, (1, Vp), from its ref;
    ``b`` is (1, Vp).  Solves L U x = b (trans=0) or (L U)^T x = b
    (trans=1).  Every sweep reads rows only: trans=0 takes the dot form
    (row i of L, then of U), trans=1 the column-oriented (axpy) form, whose
    column i of U^T / L^T is row i of U / L — so no transposed copy of the
    factor is needed.
    """
    Vp = b.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Vp), 1)

    def pick(v, i):
        return jnp.sum(jnp.where(lane == i, v, 0.0), axis=1, keepdims=True)

    if trans == 0:
        def fwd(i, y):        # unit-lower L, dot form
            s = jnp.sum(jnp.where(lane < i, row(i), 0.0) * y, axis=1,
                        keepdims=True)
            return jnp.where(lane == i, y - s, y)

        def bwd(j, x):        # upper U with diagonal, dot form
            i = Vp - 1 - j
            r = row(i)
            s = jnp.sum(jnp.where(lane > i, r, 0.0) * x, axis=1,
                        keepdims=True)
            return jnp.where(lane == i, (x - s) / pick(r, i), x)
    else:
        def fwd(i, v):        # U^T (lower, diagonal of U), axpy form
            r = row(i)
            vi = pick(v, i) / pick(r, i)
            return jnp.where(lane == i, vi,
                             jnp.where(lane > i, v - vi * r, v))

        def bwd(j, v):        # L^T (unit upper), axpy form
            i = Vp - 1 - j
            vi = pick(v, i)
            return jnp.where(lane < i, v - vi * row(i), v)

    y = jax.lax.fori_loop(0, Vp, fwd, b)
    return jax.lax.fori_loop(0, Vp, bwd, y)


def _solve_kernel(lu_ref, b_ref, x_ref, *, trans: int):
    """Two-sweep substitution for one packed-LU system (trans=0: L U x = b,
    trans=1: (L U)^T x = b)."""
    def row(i):
        return lu_ref[0, pl.ds(i, 1), :].astype(jnp.float32)

    b = b_ref[0].astype(jnp.float32)                             # (1, Vp)
    x_ref[0] = _two_sweep(row, b, trans=trans).astype(x_ref.dtype)


def _chain_solve_kernel(lu_ref, base_ref, mult_ref, x_ref, *, trans: int,
                        reverse: bool, clamp: bool, K: int):
    """Fused chain of substitutions over one (K, Vp, Vp) factor stack.

    One batch member (= one app's whole stage chain) per grid step; the
    factor stack stays VMEM-resident and a single ``fori_loop`` walks the
    stages, so the sequential chain never leaves the core:

        x_k = A_k^{-1(T)} (base_k + mult_k * x_prev)

    Assumes identity row permutation (the unpivoted Pallas factors of
    :func:`lu_factor`); LAPACK-pivoted reference factors must go through
    :func:`ref_chain_solve` instead (kernels/ops.py dispatches).
    """
    Vp = lu_ref.shape[-1]

    def body(j, carry):
        k = (K - 1 - j) if reverse else j

        def row(i):
            return lu_ref[0, k, pl.ds(i, 1), :].astype(jnp.float32)

        base_k = base_ref[0, pl.ds(k, 1), :].astype(jnp.float32)
        mult_k = mult_ref[0, pl.ds(k, 1), :].astype(jnp.float32)
        x = _two_sweep(row, base_k + mult_k * carry, trans=trans)
        if clamp:
            x = jnp.maximum(x, 0.0)
        x_ref[0, pl.ds(k, 1), :] = x.astype(x_ref.dtype)
        return x

    jax.lax.fori_loop(0, K, body, jnp.zeros((1, Vp), jnp.float32))


# ---------------------------------------------------------------------------
# Wrappers (padding + pallas_call plumbing)
# ---------------------------------------------------------------------------

def lu_factor(mats: jnp.ndarray, *, nb: int = DEFAULT_NB,
              interpret: bool = False) -> jnp.ndarray:
    """Unpivoted blocked LU of a (B, V, V) batch -> packed (B, V, V) factors.

    The pad region is an identity block, whose LU is itself, so padding and
    slicing commute with the factorization.
    """
    B, V, _ = mats.shape
    Vp = _pad_dim(V, interpret)
    a = _pad_square(mats.astype(jnp.float32), Vp)

    out = pl.pallas_call(
        functools.partial(_lu_kernel, nb=min(nb, Vp)),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Vp, Vp), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, Vp, Vp), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Vp, Vp), jnp.float32),
        interpret=interpret,
        name="lu_factor",
    )(a)
    return out[:, :V, :V]


def lu_solve(lu: jnp.ndarray, rhs: jnp.ndarray, *, trans: int = 0,
             interpret: bool = False) -> jnp.ndarray:
    """Solve packed-LU systems: lu (B, V, V), rhs (B, V) -> (B, V)."""
    B, V, _ = lu.shape
    Vp = _pad_dim(V, interpret)
    a = _pad_square(lu.astype(jnp.float32), Vp)
    b = jnp.pad(rhs.astype(jnp.float32), ((0, 0), (0, Vp - V)))

    out = pl.pallas_call(
        functools.partial(_solve_kernel, trans=trans),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Vp, Vp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, Vp), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Vp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Vp), jnp.float32),
        interpret=interpret,
        name="lu_solve",
    )(a, b[:, None, :])
    return out[:, 0, :V]


def chain_solve(lu: jnp.ndarray, base: jnp.ndarray, mult: jnp.ndarray,
                *, trans: int = 0, reverse: bool = False, clamp: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    """Fused chain solve: lu (B, K, V, V), base/mult (B, K, V) -> (B, K, V).

    Each grid step runs one member's whole stage chain inside the kernel
    (see :func:`_chain_solve_kernel`); identity row permutation assumed.
    """
    B, K, V, _ = lu.shape
    Vp = _pad_dim(V, interpret)
    a = _pad_square(lu.reshape(B * K, V, V).astype(jnp.float32), Vp)
    a = a.reshape(B, K, Vp, Vp)
    pad = ((0, 0), (0, 0), (0, Vp - V))
    basep = jnp.pad(base.astype(jnp.float32), pad)
    multp = jnp.pad(mult.astype(jnp.float32), pad)

    out = pl.pallas_call(
        functools.partial(_chain_solve_kernel, trans=trans, reverse=reverse,
                          clamp=clamp, K=K),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, Vp, Vp), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, Vp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, K, Vp), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, Vp), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, Vp), jnp.float32),
        interpret=interpret,
        name="chain_solve",
    )(a, basep, multp)
    return out[:, :, :V]


def factor_ok(lu: jnp.ndarray) -> jnp.ndarray:
    """(B,) bool condition flags from packed factors (either pivot scheme).

    A member is flagged not-ok when its factors contain non-finite entries
    or a ~zero U pivot — the batched analogue of LAPACK's ``info`` return,
    evaluated without host sync so flagged members cannot poison the batch
    (their lanes simply carry inf/nan forward to ``traffic_is_valid``).
    """
    diag = jnp.diagonal(lu, axis1=-2, axis2=-1)
    finite = jnp.all(jnp.isfinite(lu), axis=(-2, -1))
    return finite & (jnp.min(jnp.abs(diag), axis=-1) > PIVOT_TINY)


def residuals(mats: jnp.ndarray, x: jnp.ndarray, rhs: jnp.ndarray,
              *, trans: int = 0) -> jnp.ndarray:
    """(B,) relative residuals ``|A x - b|_inf / (|b|_inf + 1)``.

    Non-finite solutions report ``inf`` — the per-member divergence signal
    the GP loop consumes instead of per-solve exceptions (DESIGN.md §12).
    """
    op = jnp.einsum("bji,bj->bi" if trans else "bij,bj->bi",
                    mats.astype(jnp.float32), x.astype(jnp.float32),
                    precision=_HIGHEST)
    r = jnp.max(jnp.abs(op - rhs), axis=-1) / (jnp.max(jnp.abs(rhs), axis=-1) + 1.0)
    return jnp.where(jnp.isfinite(r), r, jnp.inf)
