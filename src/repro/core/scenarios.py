"""Named scenario sweeps: the paper's figures as batched scenario families.

Fig. 5-7 of the paper are statements about *families* of problem instances —
Table II topologies x input-rate scalings x random seeds.  This module is
the registry that expands a named sweep into a list of :class:`Scenario`
(label + Instance + provenance metadata) and runs whole families through the
device-resident batched solver (``batch.pad_instances`` +
``gp.solve_batched``), grouping members by cost family first because the
cost kinds are static pytree metadata (DESIGN.md §9).

Built-in sweeps:

  * ``fig5``            — the 8 Table II scenarios at their congested-regime
                          rate scalings (GP vs baselines, Fig. 5)
  * ``fig6-congestion`` — Abilene across input-rate scalings (Fig. 6)
  * ``fig7-packetsize`` — Abilene across input packet sizes L_(a,0) (Fig. 7)
  * ``seed-ensemble``   — one topology, many random seeds (confidence bands)
  * ``mixed-topology``  — heterogeneous Table II topologies in ONE padded
                          batch (exercises the V/A padding invariants)

``run_sweep(name)`` solves a family batched (``mesh=`` additionally shards
each member's application axis over a device mesh — vmap-of-shard_map,
DESIGN.md §14); ``run_sweep_serial(name)`` solves it one instance at a time
through ``gp.solve`` — the pair is how the benchmark drivers measure the
batched-vs-serial speedup.  ``run_sweep_chained(name)`` solves an
incremental family sequentially, warm-starting each member from its
predecessor's strategy (the fig6 rate-ladder shortcut).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.core import batch, gp, network
from repro.core.traffic import Phi

# Input-rate scaling per Table II scenario so the networks operate in the
# congested regime the paper targets (its absolute rates depend on
# unpublished simulator units; the *relative* algorithm ordering is the
# claim).  fog's capacities (Table II: s=17, d=20) leave it lightly loaded
# at 2x — every algorithm already sits at the uncongested optimum — so fog
# runs at 3.5x to reach the congested regime Fig. 5 depicts.
FIG5_RATE = {
    "connected-er": 2.0, "balanced-tree": 2.0, "fog": 3.5, "abilene": 2.0,
    "lhc": 2.0, "geant": 2.0, "sw-linear": 1.5, "sw-queue": 1.5,
}

FIG6_SCALES = (0.5, 1.0, 1.5, 2.0, 2.5, 3.0)
FIG7_L0 = (2.0, 5.0, 10.0, 20.0, 40.0)

# Table II members small enough to batch comfortably on one host device
# (excludes the V=100 small-world pair).
SMALL_TABLE_II = ("connected-er", "balanced-tree", "fog", "abilene", "lhc", "geant")


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One member of a sweep: a labeled Instance plus provenance."""

    label: str
    instance: network.Instance
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def kinds(self) -> tuple[int, int]:
        return (self.instance.link_kind, self.instance.comp_kind)


def _fig5(**kw) -> list[Scenario]:
    seed = kw.get("seed", 0)
    return [
        Scenario(
            label=name,
            instance=network.table_ii_instance(name, seed=seed, rate_scale=rate),
            meta={"table_ii": name, "seed": seed, "rate_scale": rate},
        )
        for name, rate in FIG5_RATE.items()
    ]


def _fig6_congestion(**kw) -> list[Scenario]:
    name = kw.get("scenario", "abilene")
    seed = kw.get("seed", 0)
    scales = kw.get("scales", FIG6_SCALES)
    return [
        Scenario(
            label=f"{name}@r{scale:g}",
            instance=network.table_ii_instance(name, seed=seed, rate_scale=scale),
            meta={"table_ii": name, "seed": seed, "rate_scale": scale},
        )
        for scale in scales
    ]


def _fig7_packetsize(**kw) -> list[Scenario]:
    seed = kw.get("seed", 0)
    l0s = kw.get("l0_values", FIG7_L0)
    out = []
    for l0 in l0s:
        inst = network.build_instance(
            network.TOPOLOGIES["abilene"](), n_apps=3, n_tasks=2, n_sources=3,
            link_mean=15.0, comp_mean=10.0, seed=seed,
            packet_sizes=np.array([l0, l0 / 2, 0.01]),
        )
        out.append(Scenario(
            label=f"abilene@L0={l0:g}", instance=inst,
            meta={"topology": "abilene", "seed": seed, "L0": l0},
        ))
    return out


def _seed_ensemble(**kw) -> list[Scenario]:
    name = kw.get("scenario", "abilene")
    n_seeds = kw.get("n_seeds", 32)
    rate = kw.get("rate_scale", 2.0)
    return [
        Scenario(
            label=f"{name}#s{s}",
            instance=network.table_ii_instance(name, seed=s, rate_scale=rate),
            meta={"table_ii": name, "seed": s, "rate_scale": rate},
        )
        for s in range(n_seeds)
    ]


def _mixed_topology(**kw) -> list[Scenario]:
    names = kw.get("scenarios", SMALL_TABLE_II)
    seeds = kw.get("seeds", (0, 1))
    rate = kw.get("rate_scale", 1.5)
    return [
        Scenario(
            label=f"{name}#s{s}",
            instance=network.table_ii_instance(name, seed=s, rate_scale=rate),
            meta={"table_ii": name, "seed": s, "rate_scale": rate},
        )
        for name in names
        for s in seeds
    ]


def _online_trace(**kw) -> list[Scenario]:
    """Event-trace replay family: the fig6 fleet after each event of an
    online trace (DESIGN.md §16).

    Expands to one Scenario per event — the *post-event* instance of the
    event's member — so cold-solving this family with :func:`run_sweep`
    yields the per-event cold baseline the online solver
    (``serve.online.OnlineSolver``) is benchmarked against.  Every member
    is padded to the fleet envelope (plus ``spare_apps`` dead application
    slots for arrivals), so the family always batches into one group.

    kwargs: ``scenario`` (Table II name), ``scales`` (fleet rate ladder),
    ``seed``, ``n_events``, ``spare_apps``, and optionally an explicit
    ``trace`` (list of ``events.Event``) to replay instead of sampling.
    """
    from repro.core import events

    name = kw.get("scenario", "abilene")
    scales = kw.get("scales", FIG6_SCALES)
    seed = kw.get("seed", 0)
    n_events = kw.get("n_events", 50)
    spare = kw.get("spare_apps", 2)
    insts = [network.table_ii_instance(name, seed=seed, rate_scale=s)
             for s in scales]
    members = events.pad_fleet(insts, spare_apps=spare)
    trace = kw.get("trace")
    if trace is None:
        trace = events.random_trace(members, n_events=n_events, seed=seed)
    out = []
    for t, (ev, inst, _eff) in enumerate(events.replay(members, trace)):
        out.append(Scenario(
            label=f"{name}-ev{t:02d}-m{ev.member}",
            instance=inst,
            meta={"event": type(ev).__name__, "member": ev.member, "t": t,
                  "table_ii": name, "seed": seed,
                  "base_scale": scales[ev.member]},
        ))
    return out


SWEEPS: dict[str, Callable[..., list[Scenario]]] = {
    "fig5": _fig5,
    "fig6-congestion": _fig6_congestion,
    "fig7-packetsize": _fig7_packetsize,
    "seed-ensemble": _seed_ensemble,
    "mixed-topology": _mixed_topology,
    "online-trace": _online_trace,
}


def register(name: str, build: Callable[..., list[Scenario]]) -> None:
    """Add a sweep to the registry (used by downstream experiment scripts)."""
    if name in SWEEPS:
        raise ValueError(f"sweep {name!r} already registered")
    SWEEPS[name] = build


def expand(name: str, **kw) -> list[Scenario]:
    """Expand a named sweep into its scenario list."""
    try:
        build = SWEEPS[name]
    except KeyError:
        raise KeyError(f"unknown sweep {name!r}; have {sorted(SWEEPS)}") from None
    return build(**kw)


# ---------------------------------------------------------------------------
# Batched execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepResult:
    scenarios: list[Scenario]
    results: list[gp.GPResult]      # aligned with scenarios, phi un-padded
    seconds: float                  # wall clock for the solve(s)
    n_batches: int                  # #kind-groups the family was split into
    # lane-steps the batched driver executed over every group
    # (``gp.GPScan.lane_steps``); None where a group ran elsewhere (mesh=)
    lane_steps: Optional[int] = None

    def by_label(self) -> dict[str, gp.GPResult]:
        return {s.label: r for s, r in zip(self.scenarios, self.results)}


def solve_family(
    insts: Sequence[network.Instance],
    phi0s: Optional[Sequence[Phi]] = None,
    *,
    masks_fn: Optional[Callable] = None,
    mesh=None,
    mesh_axis: str = "stage",
    **gp_kwargs,
) -> tuple[list[gp.GPResult], Optional[int]]:
    """Solve same-cost-family instances as ONE padded, vmapped batch.

    ``masks_fn`` (e.g. ``baselines.spoc_masks``) maps an Instance to
    (allowed_e, allowed_c, phi0); it is vmapped over the padded batch so
    restricted solvers — the SPOC/LCOF baselines — run through the same
    batched device program as unrestricted GP.  An explicit ``phi0s``
    overrides the masks' initial strategies.

    With ``mesh`` set (a ``jax.sharding.Mesh``), the family runs through
    ``distributed.solve_sharded_batched``: each member's application axis
    is sharded over ``mesh_axis`` and the member axis is vmapped inside
    each shard (vmap-of-shard_map, DESIGN.md §14), so large ensembles
    spread across devices while solving the identical problems.

    Returns per-instance trimmed GPResults with padding stripped from phi
    and histories taken from the batched dense scan outputs, all host
    (numpy) arrays, split from one host copy of the family; and the
    lane-steps the batched driver executed (``gp.GPScan.lane_steps``, None
    on the mesh path).
    """
    binst = batch.pad_instances(insts)
    phi0 = batch.pad_phis(phi0s, insts) if phi0s is not None else None
    if masks_fn is not None:
        allowed_e, allowed_c, mask_phi0 = jax.vmap(masks_fn)(binst)
        gp_kwargs.setdefault("allowed_e", allowed_e)
        gp_kwargs.setdefault("allowed_c", allowed_c)
        if phi0 is None:
            phi0 = mask_phi0
    if mesh is not None:
        from repro.core import distributed

        scan = distributed.solve_sharded_batched(
            binst, mesh, axis=mesh_axis, phi0=phi0, **gp_kwargs)
    else:
        scan = gp.solve_batched(binst, phi0, **gp_kwargs)
    phi_e, phi_c, costs, resids, iters = (np.asarray(x) for x in (
        scan.phi.e, scan.phi.c, scan.cost_history, scan.residual_history,
        scan.iterations))
    out = [gp.GPResult(
        phi=batch.unpad_phi(Phi(e=phi_e[b], c=phi_c[b]), inst),
        cost_history=costs[b], residual_history=resids[b],
        iterations=int(iters[b]),
    ).trim() for b, inst in enumerate(insts)]
    return out, scan.lane_steps


def run_sweep(name_or_scenarios, *, sweep_kwargs: Optional[dict] = None,
              masks_fn: Optional[Callable] = None,
              mesh=None, mesh_axis: str = "stage",
              **gp_kwargs) -> SweepResult:
    """Expand a sweep and solve it batched.

    ``name_or_scenarios`` is a registry name (``"fig5"``,
    ``"fig6-congestion"``, ``"fig7-packetsize"``, ``"seed-ensemble"``,
    ``"mixed-topology"`` — expanded with ``sweep_kwargs``) or an explicit
    ``list[Scenario]``; remaining kwargs go to ``gp.solve_batched`` —
    including ``accel=`` (the §15 convergence-acceleration layer), which
    therefore applies uniformly to every member of the family on both the
    single-device and ``mesh=`` paths.
    ``masks_fn`` restricts the direction set per member (the SPOC/LCOF
    baselines — ``baselines.BASELINE_MASKS``); it is evaluated under
    ``jax.vmap`` on each padded group (see :func:`solve_family`).
    ``mesh`` composes the family with a device mesh: each padded group is
    solved by ``distributed.solve_sharded_batched`` with the app axis
    sharded over ``mesh_axis`` and members vmapped inside each shard.
    Returns a :class:`SweepResult` whose ``results`` align 1:1 with
    ``scenarios`` (trimmed GPResults, phi un-padded back to each member's
    true (A, K1, V, V)).

    Members are grouped by cost family (static metadata, must match within a
    batch) AND by node-count size class (next power of two): padding a
    V=11 Abilene member to a V=100 small-world envelope would multiply its
    per-iteration work ~80x, wiping out the batching win, so differently
    sized members go into separate device programs instead.

    Example::

        >>> sweep = scenarios.run_sweep(
        ...     "seed-ensemble",
        ...     sweep_kwargs={"scenario": "abilene", "n_seeds": 32},
        ...     alpha=0.1, max_iters=250)
        >>> len(sweep.results), sweep.n_batches
        (32, 1)
        >>> sweep.by_label()["abilene#s0"].final_cost  # doctest: +SKIP
        15.19
    """
    if isinstance(name_or_scenarios, str):
        scenarios = expand(name_or_scenarios, **(sweep_kwargs or {}))
    else:
        scenarios = list(name_or_scenarios)
    groups: dict[tuple, list[int]] = {}
    for idx, sc in enumerate(scenarios):
        key = sc.kinds + (batch.next_pow2(sc.instance.V),)
        groups.setdefault(key, []).append(idx)

    results: list[Optional[gp.GPResult]] = [None] * len(scenarios)
    lane_steps = 0
    t0 = time.perf_counter()
    for idxs in groups.values():
        group_res, lanes = solve_family(
            [scenarios[i].instance for i in idxs], masks_fn=masks_fn,
            mesh=mesh, mesh_axis=mesh_axis, **gp_kwargs)
        for i, r in zip(idxs, group_res):
            results[i] = r
        lane_steps = None if lanes is None or lane_steps is None else (
            lane_steps + lanes)
    seconds = time.perf_counter() - t0
    return SweepResult(scenarios=scenarios, results=results, seconds=seconds,
                       n_batches=len(groups), lane_steps=lane_steps)


def run_sweep_serial(name_or_scenarios, *, sweep_kwargs: Optional[dict] = None,
                     masks_fn: Optional[Callable] = None,
                     **gp_kwargs) -> SweepResult:
    """The serial reference: one ``gp.solve`` per scenario (for speedup
    comparisons against :func:`run_sweep`).

    ``masks_fn`` mirrors :func:`run_sweep`'s: per-scenario
    (allowed_e, allowed_c, phi0) direction restrictions are computed on
    each (unpadded) instance and forwarded to ``gp.solve``, so the
    serial-vs-batched baseline comparison is apples-to-apples — both
    paths solve exactly the same restricted problems.
    """
    if isinstance(name_or_scenarios, str):
        scenarios = expand(name_or_scenarios, **(sweep_kwargs or {}))
    else:
        scenarios = list(name_or_scenarios)
    t0 = time.perf_counter()
    results = []
    for sc in scenarios:
        kw = dict(gp_kwargs)
        phi0 = None
        if masks_fn is not None:
            allowed_e, allowed_c, phi0 = masks_fn(sc.instance)
            kw.setdefault("allowed_e", allowed_e)
            kw.setdefault("allowed_c", allowed_c)
        results.append(gp.solve(sc.instance, phi0, **kw))
    seconds = time.perf_counter() - t0
    return SweepResult(scenarios=scenarios, results=results, seconds=seconds,
                       n_batches=len(scenarios))


def run_sweep_chained(name_or_scenarios, *,
                      sweep_kwargs: Optional[dict] = None,
                      masks_fn: Optional[Callable] = None,
                      **gp_kwargs) -> SweepResult:
    """Sequential sweep with warm-start chaining: member k starts from
    member k-1's converged strategy.

    The intended use is *incremental* families — e.g. the fig6 input-rate
    ladder, where rate ``r_k``'s optimum is a small perturbation of
    ``r_{k-1}``'s — so order the scenario list from least to most congested.
    Chaining is inherently sequential (each member needs its predecessor's
    phi), so this trades the batched device program for a much shorter
    iteration count per member; ``benchmarks/fig6_congestion.py`` and the
    fig5 V=100 warm-start report the measured cut.

    A member that cannot legally inherit its predecessor's strategy —
    different graph, destinations or chain structure, not just a different
    array shape (two random topologies can share (A, K1, V, V) while
    disagreeing on which edges exist, and phi mass on a non-edge poisons
    the traffic fixed point) — falls back to a cold start.  ``masks_fn``
    restrictions still apply per member; the chained phi only replaces the
    *initial* strategy.  With ``accel=`` each member's solve builds a fresh
    carry, so the Anderson history and adaptive stepsize never leak across
    chain members (only the warm-started phi does).
    """
    if isinstance(name_or_scenarios, str):
        scenarios = expand(name_or_scenarios, **(sweep_kwargs or {}))
    else:
        scenarios = list(name_or_scenarios)
    t0 = time.perf_counter()
    results: list[gp.GPResult] = []
    phi_prev: Optional[Phi] = None
    inst_prev: Optional[network.Instance] = None
    for sc in scenarios:
        inst = sc.instance
        kw = dict(gp_kwargs)
        phi0 = None
        if masks_fn is not None:
            allowed_e, allowed_c, phi0 = masks_fn(inst)
            kw.setdefault("allowed_e", allowed_e)
            kw.setdefault("allowed_c", allowed_c)
        inheritable = (
            phi_prev is not None
            and tuple(phi_prev.e.shape) == (inst.A, inst.K1, inst.V, inst.V)
            and np.array_equal(np.asarray(inst.adj), np.asarray(inst_prev.adj))
            and np.array_equal(np.asarray(inst.dst), np.asarray(inst_prev.dst))
            and np.array_equal(np.asarray(inst.n_tasks),
                               np.asarray(inst_prev.n_tasks))
        )
        if inheritable:
            phi0 = phi_prev
        res = gp.solve(inst, phi0, **kw)
        phi_prev, inst_prev = res.phi, inst
        results.append(res)
    seconds = time.perf_counter() - t0
    return SweepResult(scenarios=scenarios, results=results, seconds=seconds,
                       n_batches=len(scenarios))
