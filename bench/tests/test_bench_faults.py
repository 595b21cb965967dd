"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell's traffic (set-up, window, the
reference check) on the CPU, past the harness's look for a chip, at a size
a test run can hold: the abilene deployment, a pool of few draws, a short
window.  The faults are planted in the program as the window calls it.
"""

import io
import time

import jax
import numpy as np
import pytest

from bench import registry, run

pytestmark = pytest.mark.filterwarnings("ignore")

SMALL = {
    "sw-queue.certify": dict(base_seeds=[0, 1], requests=4, max_iters=64),
}


def _run(cell, seed=2**31 + 77):
    mix = dict(registry.traffic_mix(registry.workload(cell)["traffic"]),
               **SMALL[cell])
    out = io.StringIO()
    res = run.execute(cell, seed, 0.2, False, jax.devices()[:1],
                      t_start=time.perf_counter(), out=out, err=io.StringIO(),
                      compile_cache=False, cfg=registry.config("abilene"),
                      mix=mix)
    assert out.getvalue().strip().endswith("}")
    return res


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert list(res["checks"]) == ["cost_gap", "resid_gap", "opt_gap",
                                   "feas_err"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_step_that_returns_its_state_unchanged(cell, monkeypatch,
                                               fresh_programs):
    from repro.core import engine

    step = engine.gp_step

    def unchanged(inst, phi, *args, **kw):
        return step(inst, phi, *args, **kw)._replace(phi=phi)

    monkeypatch.setattr(engine, "gp_step", unchanged)
    res = _run(cell)
    assert not res["correct"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_half_the_batch_left_out(cell, monkeypatch, fresh_programs):
    """Half of the deployments are left unsolved (their initial strategy,
    reported with its own cost, however often they are solved again), so
    that the reported costs and residuals stay true: the distance from the
    optimum has to catch it."""
    from repro.core import gp

    solve = gp.solve
    seen = {}

    def half_solve(inst, phi0, **kw):
        res = solve(inst, phi0, **kw)
        if seen.setdefault(id(inst), len(seen)) % 2 == 0:
            return res
        return gp.GPResult(phi=phi0, cost_history=res.cost_history[:1],
                           residual_history=res.residual_history[:0],
                           iterations=0)

    monkeypatch.setattr(gp, "solve", half_solve)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"]["opt_gap"]["value"] > res["checks"]["opt_gap"][
        "limit"]
    assert res["checks"]["cost_gap"]["value"] <= res["checks"]["cost_gap"][
        "limit"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_answer_altered_where_it_is_produced(cell, monkeypatch,
                                             fresh_programs):
    """Each returned strategy moves a tenth of one loaded row's mass onto
    another out-link of the same node: still feasible, another cost."""
    from repro.core import gp

    def alter(phi, adj):
        e, c = np.asarray(phi.e).copy(), np.asarray(phi.c)
        adj = np.broadcast_to(np.asarray(adj), e.shape[:-4] + e.shape[-2:])
        for idx in np.ndindex(*e.shape[:-4]):
            for a, k, i in np.argwhere(e[idx].max(-1) > 0.5):
                row = e[idx][a, k, i]
                idle = np.flatnonzero(adj[idx][i] & (row == 0))
                if idle.size:
                    row[np.argmax(row)] -= 0.1
                    row[idle[0]] += 0.1
                    break
        return type(phi)(e, c)

    solve = gp.solve
    monkeypatch.setattr(gp, "solve", lambda inst, *a, **k: (
        lambda r: r.__class__(**{**r.__dict__,
                                 "phi": alter(r.phi, inst.adj)}))(
            solve(inst, *a, **k)))
    res = _run(cell)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["cost_gap"]["value"] > checks["cost_gap"]["limit"]
    assert checks["feas_err"]["value"] <= checks["feas_err"]["limit"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_certificate_altered_where_it_is_produced(cell, monkeypatch,
                                                  fresh_programs):
    """The program's residual check reports half of what it finds: every
    strategy stays as solved, its certificate says the wrong thing."""
    from repro.core import conditions

    check = conditions.sufficiency_residual
    monkeypatch.setattr(conditions, "sufficiency_residual",
                        lambda inst, phi: 0.5 * check(inst, phi))
    res = _run(cell)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["resid_gap"]["value"] > checks["resid_gap"]["limit"]
