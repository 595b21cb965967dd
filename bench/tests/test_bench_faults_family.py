"""A run of the family cell with the timed path broken underneath comes out
not correct.

Each test drives a whole run of ``abilene.ensemble32``'s traffic (set-up,
window, the reference check) on the CPU, past the harness's look for a
chip, at a size a test run can hold: a pool of eight draws, two requests, a
short window.  The faults are planted in the program as the window calls
it.
"""

import io
import time

import jax
import numpy as np
import pytest

from bench import registry, run

pytestmark = pytest.mark.filterwarnings("ignore")

CELL = "abilene.ensemble32"
SMALL = dict(base_seeds=list(range(8)), requests=2, max_iters=64)


def _run(seed=2**31 + 77):
    mix = dict(registry.traffic_mix(registry.workload(CELL)["traffic"]),
               **SMALL)
    out = io.StringIO()
    res = run.execute(CELL, seed, 0.2, False, jax.devices()[:1],
                      t_start=time.perf_counter(), out=out, err=io.StringIO(),
                      compile_cache=False, mix=mix)
    assert out.getvalue().strip().endswith("}")
    return res


def _over(res, name):
    check = res["checks"][name]
    return check["value"] > check["limit"]


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_family_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8 and res["attempted"] % 8 == 0


def test_family_step_that_returns_its_state_unchanged(monkeypatch,
                                                      fresh_programs):
    from repro.core import engine

    step = engine.gp_step

    def unchanged(inst, phi, *args, **kw):
        return step(inst, phi, *args, **kw)._replace(phi=phi)

    monkeypatch.setattr(engine, "gp_step", unchanged)
    res = _run()
    assert not res["correct"]
    assert _over(res, "opt_gap")


def test_family_answer_altered_where_it_is_produced(monkeypatch):
    """Every member's returned strategy moves a tenth of one loaded row's
    mass onto another out-link of the same node: still feasible, another
    cost."""
    from repro.core import gp

    def alter(phi, adj):
        e, c = np.array(phi.e), np.asarray(phi.c)
        adj = np.asarray(adj)
        for b in range(e.shape[0]):
            for a, k, i in np.argwhere(e[b].max(-1) > 0.5):
                row = e[b, a, k, i]
                idle = np.flatnonzero(adj[b, i] & (row == 0))
                if idle.size:
                    row[np.argmax(row)] -= 0.1
                    row[idle[0]] += 0.1
                    break
        return type(phi)(e, c)

    solve = gp.solve_batched

    def altered(binst, *args, **kw):
        scan = solve(binst, *args, **kw)
        return scan._replace(phi=alter(scan.phi, binst.adj))

    monkeypatch.setattr(gp, "solve_batched", altered)
    res = _run()
    assert not res["correct"]
    assert _over(res, "cost_gap") and not _over(res, "feas_err")


def test_family_certificate_altered_where_it_is_produced(monkeypatch):
    """The program's residual check reports half of what it finds."""
    from repro.core import conditions

    check = conditions.sufficiency_residual
    monkeypatch.setattr(conditions, "sufficiency_residual",
                        lambda inst, phi: 0.5 * check(inst, phi))
    res = _run()
    assert not res["correct"]
    assert _over(res, "resid_gap")


def test_members_swapped_at_compaction(monkeypatch):
    """Compaction gathers the live lanes one place off (the lane that
    should hold member m holds the next live member), so the strategies
    that retire afterwards go to the wrong members."""
    from repro.core import gp

    repack = gp._repack
    swapped = []

    def off_by_one(inst, carry, ae, ac, sel, n_live):
        live = np.asarray(sel)[:n_live]
        # set-up's warm-up compacts lanes that are all latched: leave it be
        if n_live >= 2 and not np.asarray(carry.done)[live].all():
            swapped.append(n_live)
            sel = sel.at[:n_live].set(np.roll(live, 1))
        return repack(inst, carry, ae, ac, sel, n_live)

    monkeypatch.setattr(gp, "_repack", off_by_one)
    res = _run()
    assert swapped
    assert not res["correct"]
