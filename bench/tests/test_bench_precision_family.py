"""The limits of ``abilene.ensemble32`` catch answers computed one precision
below the configuration's, at the cell's size.

The configuration states float32 (every contraction at HIGHEST).  The
control puts in the program's place the cost and the Theorem-1 residual
that the plain reference itself gives in float16, the nearest IEEE format
below float32, for the strategies of a sound family solve of the cell: one
request of the 32 Table II Abilene draws at rates x2, each relabelled,
solved as the cell solves them.  numpy has no half-precision linear solve,
so the reference's stage solves run in float32 and are rounded to float16:
a control a little more accurate than a float16 program would be.

The sound answers are correct; the control's are not, by ``cost_gap`` and
by ``resid_gap`` each.  ``-s`` prints both sets of readings.
"""

import importlib.util
import types

import jax
import numpy as np
import pytest

from bench import reference, registry
from bench.lib import check
from bench.traffic import tableii

pytestmark = pytest.mark.filterwarnings("ignore")

CELL = registry.workload("abilene.ensemble32")
CFG = registry.config(CELL["config"])
MIX = registry.traffic_mix(CELL["traffic"])


class _Numpy:
    """numpy with ``float64`` (the reference's working precision) replaced
    by ``dtype``: arrays are made and computed in it."""

    def __init__(self, dtype):
        self.float64 = dtype
        self.linalg = types.SimpleNamespace(solve=self._solve)

    def __getattr__(self, name):
        return getattr(np, name)

    def zeros(self, shape):
        return np.zeros(shape, self.float64)

    def eye(self, n):
        return np.eye(n, dtype=self.float64)

    def full(self, shape, value):
        return np.full(shape, value, self.float64)

    def _solve(self, a, b):
        return np.linalg.solve(np.asarray(a, np.float32),
                               np.asarray(b, np.float32)).astype(self.float64)


def _reference_in(dtype):
    """A second copy of the plain reference, computing in ``dtype``."""
    spec = importlib.util.spec_from_file_location("_reference_low",
                                                  reference.__file__)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.np = _Numpy(dtype)
    return mod


@pytest.fixture(scope="module", params=[2**31 + 4242, 3 * 10**9 + 17])
def sound(request):
    """(deployment, phi.e, phi.c, reported cost, checked residual) of every
    member of one request of the cell, solved once by the program."""
    from repro.core import conditions, scenarios

    rng = np.random.default_rng(request.param)
    deps = [tableii.relabel(tableii.draw(CFG, s, MIX["rate_scale"]),
                            rng.permutation(11)) for s in MIX["base_seeds"]]
    insts = [tableii.to_instance(d) for d in deps]
    sweep = scenarios.run_sweep(
        [scenarios.Scenario(label=str(m), instance=inst)
         for m, inst in enumerate(insts)],
        alpha=MIX["alpha"], tol=CFG["cert_tol"], max_iters=MIX["max_iters"],
        accel=True, solver="auto")
    resid = jax.jit(conditions.sufficiency_residual)
    return [(d, np.asarray(r.phi.e), np.asarray(r.phi.c),
             float(r.cost_history[-1]), float(resid(inst, r.phi)))
            for d, inst, r in zip(deps, insts, sweep.results)]


def _judge(answers):
    numbers, _ = check.compare(answers, float(CFG["cert_tol"]))
    ok, shown = check.judge(numbers, CELL["limits"])
    print({k: v["value"] for k, v in shown.items()})
    return ok, shown


def test_sound_family_answers_are_correct(sound):
    assert len(sound) == len(MIX["base_seeds"]) == 32
    ok, shown = _judge(sound)
    assert ok, shown


def test_reference_in_float16_is_not_correct(sound):
    low = _reference_in(np.float16)
    answers = [(d, e, c, low.cost(d, e, c), low.residual(d, e, c))
               for d, e, c, _, _ in sound]
    ok, shown = _judge(answers)
    assert not ok
    for k in ("cost_gap", "resid_gap"):
        assert shown[k]["value"] > shown[k]["limit"], shown
