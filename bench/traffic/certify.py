"""Closed loop of certified solves, one deployment at a time.

The mix (``bench/traffic/<mix>.json``) names a fixed pool of Table II draws
(``base_seeds``, at ``rate_scale``).  Request ``i`` is pool member
``i mod P`` with its nodes renamed by a fresh permutation drawn from the
run's seed.  So every seed asks for the same work in the same order, on
arrays that differ from seed to seed, and no result can be reused.

A request is what an operator does to obtain a certified optimum:
``gp.solve(..., accel=True, solver="auto")`` to the configuration's
tolerance, then the program's own Theorem-1 check
(``conditions.sufficiency_residual``) of the strategy it returned; while
that check finds the strategy above the tolerance, another ``gp.solve``
from it, up to ``max_calls`` solves in all.  The solver's stop test reads
the residual of the iterate before its last step, so the strategy it
returns may sit just above the tolerance; the check, not the stop test,
is the certificate.  The next request starts when the check's residual
has been read back.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench.traffic import tableii


class Traffic:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.tol = float(cfg["cert_tol"])
        self.max_iters = int(mix["max_iters"])
        self.rng = np.random.default_rng(seed)

    def _deployments(self, n: int) -> list[dict]:
        base = [tableii.draw(self.cfg, s, self.mix["rate_scale"])
                for s in self.mix["base_seeds"]]
        V = base[0]["adj"].shape[0]
        return [tableii.relabel(base[i % len(base)],
                                self.rng.permutation(V)) for i in range(n)]

    def _solve(self, inst):
        """(strategy, its reported cost, its checked residual, iterations,
        solves) of one request."""
        from repro.core import gp

        phi, iters = self._init(inst), 0
        for calls in range(1, int(self.mix["max_calls"]) + 1):
            res = gp.solve(inst, phi, alpha=self.mix["alpha"], tol=self.tol,
                           max_iters=self.max_iters, accel=True,
                           solver="auto")
            phi, iters = res.phi, iters + int(res.iterations)
            resid = float(self._check(inst, phi))
            if resid <= self.tol:
                break
        return phi, float(np.asarray(res.cost_history)[-1]), resid, iters, \
            calls

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import conditions, gp

        self._init = jax.jit(gp.init_phi)
        self._check = jax.jit(conditions.sufficiency_residual)
        self.deps = self._deployments(int(self.mix["requests"]))
        self.insts = [tableii.to_instance(d) for d in self.deps]
        warm = tableii.draw(self.cfg, self.mix["warmup_seed"],
                            self.mix["rate_scale"])
        self._solve(tableii.to_instance(warm))
        # gp.solve stitches its history from fixed-length chunks and trims
        # it to the iteration count: eager programs whose shapes depend on
        # how many iterations a solve took.  Build each of them once here.
        chunk = getattr(gp, "_SOLVE_CHUNK", 32)
        for k in range(1, math.ceil(self.max_iters / chunk) + 1):
            jnp.concatenate([jnp.zeros(1)] + [jnp.zeros(chunk)] * k)
            jnp.concatenate([jnp.zeros(chunk)] * k)
        for n in range(1, self.max_iters + 1):
            k = math.ceil(n / chunk)
            gp.GPResult(phi=None, cost_history=jnp.zeros(chunk * k + 1),
                        residual_history=jnp.zeros(chunk * k),
                        iterations=n).trim()

    def window(self, seconds: float) -> dict:
        records, kept = [], []
        t_start = time.perf_counter()
        deadline = t_start + seconds
        i = 0
        while not records or time.perf_counter() < deadline:
            t0 = time.perf_counter()
            j = i % len(self.insts)
            phi, cost, resid, iters, calls = self._solve(self.insts[j])
            t1 = time.perf_counter()
            records.append(dict(t0=t0 - t_start, t1=t1 - t_start,
                                iters=iters, calls=calls,
                                claimed=resid <= self.tol, members=1))
            kept.append((j, phi, cost, resid))
            i += 1
        self._kept = kept
        return dict(records=records, seconds=time.perf_counter() - t_start)

    def answers(self):
        """(deployment, phi.e, phi.c, reported cost, checked residual) of
        every request the window served, copied to the host."""
        for j, phi, cost, resid in self._kept:
            yield (self.deps[j], np.asarray(phi.e), np.asarray(phi.c), cost,
                   resid)

    def release(self) -> None:
        """Drop the program's state: device arrays of the window."""
        self._kept = [(j, type(p)(np.asarray(p.e), np.asarray(p.c)), c, r)
                      for j, p, c, r in self._kept]
        self.insts = None
