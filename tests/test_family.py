"""Families through the batched driver (``scenarios.run_sweep`` ->
``solve_family`` -> ``gp.solve_batched``) against the plain reference.

Every family is four Table II Abilene draws at rates x2, each member's
nodes renamed by its own permutation, solved as the benchmark's
``abilene.ensemble32`` cell solves them (``accel=True``, ``solver="auto"``,
tolerance 1e-2).  The results are host arrays; compaction returns each
member's strategy to it in the original order; a family solved again, or
after ``gp.warm_batched``, compiles nothing; ``lane_steps`` counts what the chunk programs ran; the
driver's host phases are named spans.
"""

import contextlib

import jax
import numpy as np
import pytest

from bench import reference, registry
from bench.traffic import tableii
from repro.core import batch, gp, scenarios

KW = dict(alpha=0.1, tol=1e-2, max_iters=256, accel=True, solver="auto")
LIMITS = registry.workload("abilene.ensemble32")["limits"]


def _family(seed, n=4):
    cfg = registry.config("abilene")
    rng = np.random.default_rng(seed)
    deps = [tableii.relabel(tableii.draw(cfg, s, 2.0), rng.permutation(11))
            for s in range(seed, seed + n)]
    return deps, [tableii.to_instance(d) for d in deps]


def _sweep(insts):
    return scenarios.run_sweep(
        [scenarios.Scenario(label=str(m), instance=inst)
         for m, inst in enumerate(insts)], **KW)


class _Compiles:
    """Backend compilations while the context is open."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def _seen(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._seen)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_members_agree_with_the_reference_and_the_single_solve(seed):
    deps, insts = _family(seed)
    sweep = _sweep(insts)
    assert sweep.n_batches == 1
    for d, inst, r in zip(deps, insts, sweep.results):
        e, c = r.phi.e, r.phi.c
        assert isinstance(e, np.ndarray) and isinstance(r.cost_history,
                                                        np.ndarray)
        assert e.shape == (3, 3, 11, 11)
        ref_cost = reference.cost(d, e, c)
        cost = float(r.cost_history[-1])
        assert abs(cost - ref_cost) / ref_cost <= LIMITS["cost_gap"]
        assert (ref_cost - reference.lower_bound(d, e, c)) / ref_cost <= (
            LIMITS["opt_gap"])
        assert reference.feasibility(d, e, c) <= LIMITS["feas_err"]
        single = gp.solve(inst, **KW).final_cost
        assert abs(cost - single) / single <= LIMITS["opt_gap"]


def test_compaction_returns_each_strategy_to_its_member():
    """Three members start at their own optimum and latch in the first
    chunk; the third starts cold and runs on alone, compacted into a
    bucket of one."""
    deps, insts = _family(11)
    warm = {m: gp.solve(inst, **KW) for m, inst in enumerate(insts)
            if m != 2}
    phi0s = [warm[m].phi if m in warm else gp.init_phi(insts[m])
             for m in range(4)]
    results, lanes = scenarios.solve_family(insts, phi0s, **KW)
    its = [r.iterations for r in results]
    assert its[2] > gp._CHUNK_MIN >= max(its[:2] + its[3:])
    # the long member ran alone after the first chunk
    assert lanes < 4 * its[2]
    for m, (d, r) in enumerate(zip(deps, results)):
        single = gp.solve(insts[m], phi0s[m], **KW)
        # a strategy of another (relabelled) member puts mass off this
        # member's graph
        assert reference.feasibility(d, r.phi.e, r.phi.c) <= (
            LIMITS["feas_err"])
        assert float(r.cost_history[-1]) == pytest.approx(
            single.final_cost, rel=1e-4)


def test_family_solved_again_compiles_nothing():
    _, insts = _family(5)
    first = _sweep(insts)
    with _Compiles() as seen:
        again = _sweep(insts)
    assert seen.n == 0
    assert [r.iterations for r in again.results] == [
        r.iterations for r in first.results]


def test_warm_batched_builds_every_program_a_family_runs():
    """After ``gp.warm_batched`` on a family of four, a family of three
    (bucket four, compacted as its members latch) and a re-solve of two of
    its members from their strategies compile nothing."""
    _, insts = _family(17)
    jax.clear_caches()
    gp.warm_batched(batch.pad_instances(insts), **KW)
    with _Compiles() as seen:
        sweep = _sweep(insts[:3])
        scenarios.solve_family(insts[1:3],
                               [r.phi for r in sweep.results[1:3]], **KW)
    assert seen.n == 0
    assert len({r.iterations for r in sweep.results}) > 1


def test_lane_steps_count_what_the_chunks_ran():
    _, insts = _family(7)
    sweep = _sweep(insts)
    committed = sum(r.iterations for r in sweep.results)
    assert sweep.lane_steps >= committed > 0
    # no lane latches before the budget: three members in a bucket of
    # four, every lane steps 8 times
    _, insts3 = _family(7, n=3)
    results, lanes = scenarios.solve_family(
        insts3, alpha=0.1, tol=0.0, patience=1000, max_iters=8)
    assert [r.iterations for r in results] == [8, 8, 8]
    assert lanes == 4 * 8


def test_driver_phases_are_spans(monkeypatch):
    opened, closed = [], []

    @contextlib.contextmanager
    def span(name):
        opened.append(name)
        yield
        closed.append(name)

    _, insts = _family(13)
    # two members start at their optimum: the other two are compacted
    phi0s = [gp.init_phi(i) for i in insts[:2]] + [
        gp.solve(i, **KW).phi for i in insts[2:]]
    monkeypatch.setattr(gp, "span", span)
    scenarios.solve_family(insts, phi0s, **KW)
    names = {"gp.solve_batched"} | {f"gp.solve_batched.{p}" for p in (
        "init", "dispatch", "retire", "compact")}
    assert set(opened) == names
    assert sorted(opened) == sorted(closed)
    assert opened.count("gp.solve_batched") == 1
    assert opened[:2] == ["gp.solve_batched", "gp.solve_batched.init"]
    assert closed[-1] == "gp.solve_batched"
