"""Instance padding & stacking: heterogeneous scenarios as one batched pytree.

The paper's evaluation (Figs. 5-7) is a statement about *families* of
scenarios — seven Table II topologies x input-rate scalings x random seeds.
To solve a family in one device program we pad every :class:`Instance` to a
common (V, A, K1) envelope and stack the results along a leading batch axis;
``jax.vmap(gp.solve_scan)`` then runs the whole family as one XLA executable.

Padding invariants (DESIGN.md §9 records the full argument):

  * **Dead nodes** (index >= the instance's true V): no adjacency, zero
    input rate, unit CPU capacity.  They receive zero traffic, so with
    D(0) = C(0) = 0 they contribute exactly nothing to the objective, and
    the per-stage linear systems stay nonsingular (their rows reduce to the
    identity).
  * **Dead applications / stages**: zero rate, ``stage_mask`` False, so
    ``renormalize`` forces their strategy rows to zero and ``cpu_allowed``
    excludes them from every direction set.
  * **Cost-family kinds are static metadata** and must agree across the
    batch (they select python-level code paths); mixed-kind families must be
    grouped by kind first (``scenarios.run_sweep`` does this automatically).

Under these invariants ``flows``, ``marginals`` and ``gp_step`` restricted
to the real (node, app, stage) block are identical to the unpadded
computation, so batched solves reproduce serial solves (tests/test_batch.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import network as network_mod
from repro.core.network import Instance
from repro.core.traffic import Phi

# Packet-size fill for padded stages — keep the same positive floor the
# builder applies to real stages (DESIGN.md §8) so padded entries can never
# introduce a zero-size degeneracy if a masked stage is ever touched.
_L_FILL = 0.01

# Heterogeneous-degree guard for sparse batches: padding every member's
# neighbor lists to the family max degree D costs O(V * D) per member, so a
# family mixing a near-regular metro graph with a hub-heavy one would
# silently densify the cheap members' sparse representation.  When the max
# over min member degree exceeds this ratio, ``pad_instances`` refuses
# (hetero_degree="raise", the default) unless the caller explicitly opts
# into padding ("pad") or falls back to dense ("strip").
_HETERO_DEGREE_RATIO = 4


def next_pow2(n: int) -> int:
    """Bucket quantizer shared by solver compaction (gp.solve_batched) and
    sweep size-class grouping (scenarios.run_sweep) — the two must agree."""
    return 1 << max(n - 1, 0).bit_length()


def _pad_axis(x: jnp.ndarray, axis: int, target: int, fill) -> jnp.ndarray:
    cur = x.shape[axis]
    if cur == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_degree(nbr: jnp.ndarray, mask: jnp.ndarray, D: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad neighbor-list columns to degree ``D`` (self-index, mask False)."""
    cur = int(nbr.shape[1])
    if cur == D:
        return nbr, mask
    n = nbr.shape[0]
    self_col = jnp.tile(jnp.arange(n, dtype=nbr.dtype)[:, None],
                        (1, D - cur))
    return (jnp.concatenate([nbr, self_col], axis=1),
            _pad_axis(mask, 1, D, False))


def pad_instance(inst: Instance, V: int, A: int, K1: int) -> Instance:
    """Pad one instance to the (V, A, K1) envelope (no batch axis yet).

    A sparse topology (``inst.has_sparse``) is re-derived from the padded
    adjacency: dead nodes are isolated (self-pointing all-masked neighbor
    rows), so the max degree — and the per-sweep O(E) work — is unchanged,
    only the row count grows to V.
    """
    if V < inst.V or A < inst.A or K1 < inst.K1:
        raise ValueError(
            f"target shape ({V},{A},{K1}) smaller than instance "
            f"({inst.V},{inst.A},{inst.K1})"
        )

    adj = _pad_axis(_pad_axis(inst.adj, 0, V, False), 1, V, False)
    link_param = _pad_axis(_pad_axis(inst.link_param, 0, V, 0.0), 1, V, 0.0)
    # dead nodes get unit CPU capacity: they carry zero workload, and
    # C(0) = 0 for every cost family, but a zero capacity would make the
    # queue family's marginal blow up at exactly 0 flow.
    comp_param = _pad_axis(inst.comp_param, 0, V, 1.0)
    wnode = _pad_axis(inst.wnode, 0, V, 1.0)

    L = _pad_axis(_pad_axis(inst.L, 1, K1, _L_FILL), 0, A, _L_FILL)
    w = _pad_axis(_pad_axis(inst.w, 1, K1, 0.0), 0, A, 0.0)
    r = _pad_axis(_pad_axis(inst.r, 1, V, 0.0), 0, A, 0.0)
    dst = _pad_axis(inst.dst, 0, A, 0)
    n_tasks = _pad_axis(inst.n_tasks, 0, A, 0)
    stage_mask = _pad_axis(_pad_axis(inst.stage_mask, 1, K1, False), 0, A, False)

    out = dataclasses.replace(
        inst, adj=adj, link_param=link_param, comp_param=comp_param,
        wnode=wnode, L=L, w=w, r=r, dst=dst, n_tasks=n_tasks,
        stage_mask=stage_mask,
    )
    if inst.has_sparse:
        out = network_mod.with_sparse(out)
    return out


def batch_envelope(insts: Sequence[Instance]) -> tuple[int, int, int]:
    """Common (V, A, K1) envelope of a scenario family."""
    return (
        max(i.V for i in insts),
        max(i.A for i in insts),
        max(i.K1 for i in insts),
    )


def pad_instances(insts: Sequence[Instance], *,
                  hetero_degree: str = "raise") -> Instance:
    """Stack heterogeneous instances into one Instance with a leading batch
    axis (every array field becomes ``(B, ...)``).

    Each member is first padded to the family envelope ``(V, A, K1) =
    (max V_i, max A_i, max K1_i)`` under the §9 invariants (dead nodes /
    apps / stages contribute exactly nothing), so e.g. ``adj`` becomes
    ``(B, V, V)``, ``r`` becomes ``(B, A, V)`` and ``stage_mask``
    ``(B, A, K1)``.  The result feeds ``jax.vmap(gp.solve_scan)`` or
    ``gp.solve_batched`` directly.

    All instances must share ``link_kind`` / ``comp_kind`` — these are
    static pytree metadata selecting python-level cost code, so they cannot
    vary along a traced batch axis (``scenarios.run_sweep`` groups by kind
    first).

    Sparse topologies (``with_sparse``) must be attached to *every* member
    or none — a mixed family raises (silent stripping would silently change
    solver dispatch).  Sparse members' neighbor lists are padded to the
    family max degree; when the family's degrees are very different
    (max > ``_HETERO_DEGREE_RATIO`` × min) that padding would densify the
    low-degree members' O(E) representation, so ``hetero_degree`` governs
    it explicitly: ``"raise"`` (default) refuses, ``"pad"`` pads anyway
    (opt-in, the batch stays sparse but low-degree members pay the hub
    member's D), ``"strip"`` falls back to the dense-only representation
    for the whole family.

    Example::

        >>> insts = [network.table_ii_instance("abilene", seed=s)
        ...          for s in range(8)]
        >>> binst = batch.pad_instances(insts)
        >>> binst.adj.shape, binst.r.shape
        ((8, 11, 11), (8, 3, 11))
        >>> scan = gp.solve_batched(binst, alpha=0.1)   # one device program
    """
    if not insts:
        raise ValueError("pad_instances needs at least one instance")
    if hetero_degree not in ("raise", "pad", "strip"):
        raise ValueError(
            f"hetero_degree must be 'raise'|'pad'|'strip', got {hetero_degree!r}")
    kinds = {(i.link_kind, i.comp_kind) for i in insts}
    if len(kinds) > 1:
        raise ValueError(
            f"cannot batch across cost families {sorted(kinds)}; group "
            "instances by (link_kind, comp_kind) first"
        )
    flags = {i.has_sparse for i in insts}
    if flags == {True, False}:
        raise ValueError(
            "cannot batch a mix of sparse and dense members; attach "
            "network.with_sparse to every member or strip it from all "
            "(network.without_sparse)"
        )
    sparse = flags == {True}
    if sparse:
        degs = [max(1, int(i.max_degree)) for i in insts]
        if max(degs) > _HETERO_DEGREE_RATIO * min(degs):
            if hetero_degree == "strip":
                insts = [network_mod.without_sparse(i) for i in insts]
                sparse = False
            elif hetero_degree == "raise":
                raise ValueError(
                    f"heterogeneous max degrees {min(degs)}..{max(degs)} "
                    f"(> {_HETERO_DEGREE_RATIO}x spread): padding would "
                    "densify the sparse representation. Pass "
                    "hetero_degree='pad' to pad anyway or 'strip' to fall "
                    "back to dense."
                )
            # "pad": explicit opt-in, fall through to degree padding below
    V, A, K1 = batch_envelope(insts)
    padded = [pad_instance(i, V, A, K1) for i in insts]
    if sparse:
        D = max(int(p.out_nbr.shape[1]) for p in padded)
        BD = max(int(p.blk_nbr.shape[1]) for p in padded)
        padded = [
            dataclasses.replace(
                p,
                **dict(zip(("out_nbr", "out_mask"),
                           _pad_degree(p.out_nbr, p.out_mask, D))),
                **dict(zip(("in_nbr", "in_mask"),
                           _pad_degree(p.in_nbr, p.in_mask, D))),
                **dict(zip(("blk_nbr", "blk_mask"),
                           _pad_degree(p.blk_nbr, p.blk_mask, BD))),
            )
            for p in padded
        ]
    return _stack(padded)


def _stack(trees):
    """Stack same-shaped pytrees along a new leading axis, on the host: one
    transfer per leaf and no device program, whatever the family's size."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(np.stack([np.asarray(x) for x in xs])),
        *trees)


def batch_size(binst: Instance) -> int:
    """Leading batch-axis length of a stacked Instance."""
    return int(binst.adj.shape[0])


def instance_slice(binst: Instance, b: int) -> Instance:
    """Extract padded member ``b`` of a stacked Instance (still padded)."""
    return jax.tree_util.tree_map(lambda x: x[b], binst)


def pad_phi(phi: Phi, V: int, A: int, K1: int,
            inst: Optional[Instance] = None) -> Phi:
    """Pad a strategy to the (V, A, K1) envelope.

    Padded rows are zero, which is exactly right for every degenerate row
    (dead apps/stages, final stages at forwarding-dead nodes).  The one
    non-degenerate padded row class is (real app, non-final stage, dead
    node) — constraint (1) wants those to sum to 1 even though they carry
    zero traffic.  When ``inst`` (the unpadded instance) is given, those
    rows are seeded with full local offloading (phi_c = 1), matching what
    ``init_phi`` converges to there and keeping the padded strategy
    feasible everywhere.
    """
    V0 = phi.e.shape[2]
    e = phi.e
    for axis, tgt in ((0, A), (1, K1), (2, V), (3, V)):
        e = _pad_axis(e, axis, tgt, 0.0)
    c = phi.c
    for axis, tgt in ((0, A), (1, K1), (2, V)):
        c = _pad_axis(c, axis, tgt, 0.0)
    if inst is not None and V > V0:
        dead = jnp.arange(V)[None, None, :] >= V0                 # (1,1,V)
        cpu_ok = _pad_axis(_pad_axis(
            inst.cpu_allowed(), 1, K1, False), 0, A, False)       # (A,K1)
        c = jnp.where(dead & cpu_ok[:, :, None], 1.0, c)
    return Phi(e=e, c=c)


def pad_phis(phis: Sequence[Phi], insts: Sequence[Instance]) -> Phi:
    """Stack per-instance strategies to match ``pad_instances(insts)``."""
    V, A, K1 = batch_envelope(insts)
    return _stack([pad_phi(p, V, A, K1, inst)
                   for p, inst in zip(phis, insts)])


def unpad_phi(phi: Phi, inst: Instance) -> Phi:
    """Strip padding back to an instance's true (V, A, K1)."""
    A, K1, V = inst.A, inst.K1, inst.V
    return Phi(e=phi.e[:A, :K1, :V, :V], c=phi.c[:A, :K1, :V])


def valid_mask(binst: Instance, insts: Sequence[Instance]) -> np.ndarray:
    """(B, V) bool: which nodes of each padded member are real."""
    B, V = batch_size(binst), int(binst.adj.shape[1])
    mask = np.zeros((B, V), dtype=bool)
    for b, inst in enumerate(insts):
        mask[b, : inst.V] = True
    return mask
