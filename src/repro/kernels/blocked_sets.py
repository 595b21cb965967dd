"""Bit-packed blocked-set ("tagged node") propagation kernel.

Algorithm 1 needs, per (app, stage), the blocked node sets B_i(a,k):
category 3 of Section IV tags every node whose routing subtree contains an
improper link (p, q) with dD/dt_q > dD/dt_p.  The seed computed this with a
dense boolean sweep — ``lax.scan`` of V rounds over the full (A, K1, V, V)
``route``/``improper`` tensors:

    tagged'[p] = exists q: route[p, q] and (improper[p, q] or tagged[q])

i.e. O(V) rounds of O(V^2) bool traffic per (a, k), always, even though the
propagation stabilizes after the routing-DAG diameter (a handful of hops on
Table II topologies).  After PR 2 batched the linear solves this sweep was
the co-dominant per-iteration cost at V = 100 (ROADMAP).

This module packs the successor axis into uint32 lanes:

  * ``route``/``improper`` (B, V, V) bool  ->  (B, Vp, W) uint32 with
    W = ceil(V / 32) — one word ANDs/ORs 32 successor bits at once;
  * ``tagged`` lives as a (B, W) node bitset, re-packed from the per-row
    ``any`` reduction each round;
  * rounds run under a ``lax.while_loop`` that exits as soon as the bitset
    stops changing — the fixed point is reached after (diameter + 1)
    rounds, not V.  The map is monotone (tagged only grows), so the early
    exit is *exact*: the result equals the V-round dense scan bit for bit.

Two executable paths, dispatched by ``kernels.ops.blocked_tagged`` exactly
like the batched-LU solver (DESIGN.md §13):

  * :func:`tagged_packed`  — packed jnp, the CPU/GPU path;
  * :func:`tagged_pallas`  — one batch member per grid step, the (Vp, W)
    bit matrices VMEM-resident, the while-loop sweep in-kernel (Mosaic on
    TPU, interpret mode for tests).
  * :func:`tagged_scan_dense` — the seed's dense V-round sweep, kept as
    the differential reference for parity tests and benchmarks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WORD = 32  # bits per packed lane word


def padded_nodes(V: int) -> tuple[int, int]:
    """(Vp, W): node count padded to a word multiple, and the word count."""
    W = -(-V // WORD)
    return W * WORD, W


def pack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Pack a bool array along its last axis: (..., V) -> (..., W) uint32.

    Bit ``q % 32`` of word ``q // 32`` is ``x[..., q]``; the pad tail is 0.
    """
    V = x.shape[-1]
    Vp, W = padded_nodes(V)
    if Vp != V:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, Vp - V)]
        x = jnp.pad(x, widths)
    xw = x.reshape(x.shape[:-1] + (W, WORD)).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(WORD, dtype=jnp.uint32))
    return jnp.sum(xw * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(w: jnp.ndarray, V: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: (..., W) uint32 -> (..., V) bool."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = jnp.bitwise_and(
        jnp.right_shift(w[..., None], shifts), jnp.uint32(1))
    return bits.reshape(w.shape[:-1] + (w.shape[-1] * WORD,))[..., :V] != 0


# ---------------------------------------------------------------------------
# Reference: the seed's dense V-round boolean sweep
# ---------------------------------------------------------------------------

def tagged_scan_dense(route: jnp.ndarray, improper: jnp.ndarray
                      ) -> jnp.ndarray:
    """Dense fixed point by V unconditional sweeps: (..., V, V) -> (..., V).

    This is the seed implementation of ``gp.blocked_sets``'s category-3
    propagation, kept verbatim as the parity reference for the packed
    kernels (tests/test_blocked_sets.py, kernel_bench).
    """
    V = route.shape[-1]

    def sweep(tagged, _):
        hit = improper | (route & tagged[..., None, :])
        return jnp.any(hit, axis=-1), None

    tagged0 = jnp.zeros(route.shape[:-1], dtype=bool)
    tagged, _ = jax.lax.scan(sweep, tagged0, None, length=V)
    return tagged


# ---------------------------------------------------------------------------
# Packed jnp path (CPU/GPU)
# ---------------------------------------------------------------------------

def tagged_packed(route_bits: jnp.ndarray, improper_bits: jnp.ndarray,
                  V: int, *, with_rounds: bool = False):
    """Packed frontier propagation: (B, Vp, W) uint32 x2 -> (B, V) bool.

    Runs word-wise OR-AND rounds under a ``while_loop`` that stops when the
    tagged bitset reaches its (monotone) fixed point — after at most
    ``diameter + 1`` rounds of the routing DAG instead of always V.  The
    round cap V + 1 is unreachable for any input (each round before the
    fixed point tags >= 1 new node) but bounds the loop for the compiler.

    ``with_rounds=True`` additionally returns the loop's round counter —
    the number of sweeps until the whole batch settled (the frontier-depth
    telemetry column, DESIGN.md §19).  The counter already drives the
    early exit; returning it changes no propagation arithmetic.
    """
    B, Vp, W = route_bits.shape

    def round_(tagged_bits):
        # hit[p] = exists word w: improper[p,w] | (route[p,w] & tagged[w])
        hit = improper_bits | (route_bits & tagged_bits[:, None, :])
        return pack_bits(jnp.any(hit != 0, axis=-1))

    def cond(carry):
        tb, prev, i = carry
        return jnp.any(tb != prev) & (i < Vp + 1)

    def body(carry):
        tb, _, i = carry
        return round_(tb), tb, i + 1

    tb0 = jnp.zeros((B, W), jnp.uint32)
    sentinel = jnp.full((B, W), jnp.uint32(0xFFFFFFFF))
    tb, _, rounds = jax.lax.while_loop(
        cond, body, (tb0, sentinel, jnp.int32(0)))
    tagged = unpack_bits(tb, V)
    if with_rounds:
        return tagged, rounds
    return tagged


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _tagged_kernel(route_ref, imp_ref, out_ref):
    """One batch member per grid step; bit matrices VMEM-resident.

    The layout keeps nodes on the sublane axis and packed successor words
    on the lane axis — sized for large V (the lane dim fills at V >= 4096);
    below that the packed-jnp path is preferred even on TPU, which
    ``kernels.ops.blocked_tagged`` encodes (DESIGN.md §13).

    Words are int32 with the same bits as the uint32 packing (Mosaic reduces
    signed integers only).  Re-packing the per-node flags (a sublane column)
    into words (a lane row) is a masked sum over nodes: node p contributes
    bit p % 32 to word p // 32, and a sum of distinct bits is their OR.
    """
    route = route_ref[0]          # (Vp, W) int32
    imp = imp_ref[0]
    Vp, W = route.shape
    node = jax.lax.broadcasted_iota(jnp.int32, (Vp, W), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (Vp, W), 1)
    place = jnp.where(node // WORD == word,
                      jnp.left_shift(jnp.int32(1), node % WORD), 0)

    def round_(tb):
        hit = imp | (route & tb)
        tagged = jnp.max(jnp.where(hit != 0, 1, 0), axis=1, keepdims=True)
        return jnp.sum(place * tagged, axis=0, keepdims=True)       # (1, W)

    # the bitset lives in the output block; the loop carries scalars only
    def cond(carry):
        moved, i = carry
        return moved & (i < Vp + 1)

    def body(carry):
        _, i = carry
        tb = out_ref[0]
        new = round_(tb)
        out_ref[0] = new
        return jnp.max(jnp.where(new != tb, 1, 0)) > 0, i + 1

    out_ref[0] = jnp.zeros((1, W), jnp.int32)
    jax.lax.while_loop(cond, body, (jnp.bool_(True), jnp.int32(0)))


def tagged_pallas(route_bits: jnp.ndarray, improper_bits: jnp.ndarray,
                  V: int, *, interpret: bool = False) -> jnp.ndarray:
    """Pallas path: (B, Vp, W) uint32 x2 -> (B, V) bool tagged flags."""
    B, Vp, W = route_bits.shape
    as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    out = pl.pallas_call(
        _tagged_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Vp, W), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Vp, W), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, W), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, W), jnp.int32),
        interpret=interpret,
    )(as_i32(route_bits), as_i32(improper_bits))
    words = jax.lax.bitcast_convert_type(out[:, 0, :], jnp.uint32)
    return unpack_bits(words, V)
