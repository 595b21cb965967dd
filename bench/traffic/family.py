"""Closed loop of certified family solves, one family at a time.

The mix (``bench/traffic/<mix>.json``) names a pool of Table II draws
(``base_seeds``, at ``rate_scale``).  A request is the whole pool, each
member's nodes renamed by its own permutation drawn from the run's seed:
every seed asks for the same work, on arrays that differ from seed to seed,
and no result can be reused.

A request is what an operator does to certify a family of deployments:
``scenarios.run_sweep(..., accel=True, solver="auto")`` to the
configuration's tolerance (the batched driver, ``gp.solve_batched``), then
the program's own Theorem-1 check (``conditions.sufficiency_residual``) of
every member's strategy; the members the check finds above the tolerance
are solved again, as one smaller family from their own strategies
(``scenarios.solve_family``), up to ``max_calls`` solves per member.  The
next request starts when the last check's residuals have been read back.

Set-up warms every program the window runs: the batched driver's own
``gp.warm_batched`` builds the first carry of each power-of-two bucket up
to the pool's size, its chunk programs at every length of the driver's
schedule and each compaction from one bucket to a smaller one; then one
request on the pool's own draws builds the check.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.traffic import tableii


class Traffic:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.tol = float(cfg["cert_tol"])
        self.kw = dict(alpha=float(mix["alpha"]), tol=self.tol,
                       max_iters=int(mix["max_iters"]), accel=True,
                       solver="auto")
        self.rng = np.random.default_rng(seed)

    def _solve(self, insts):
        """(results, checked residuals, iterations, family solves,
        lane-steps) of one request."""
        from repro.core import scenarios

        sweep = scenarios.run_sweep(
            [scenarios.Scenario(label=str(m), instance=inst)
             for m, inst in enumerate(insts)], **self.kw)
        results, lanes = list(sweep.results), sweep.lane_steps
        resid = self._checks(insts, range(len(insts)), results)
        calls = 1
        while calls < int(self.mix["max_calls"]):
            todo = [m for m in range(len(insts)) if resid[m] > self.tol]
            if not todo:
                break
            again, more = scenarios.solve_family(
                [insts[m] for m in todo], [results[m].phi for m in todo],
                **self.kw)
            lanes += more
            calls += 1
            for m, r in zip(todo, again):
                r.iterations += results[m].iterations
                results[m] = r
            resid.update(self._checks(insts, todo, again))
        iters = sum(r.iterations for r in results)
        return results, resid, iters, calls, lanes

    def _checks(self, insts, members, results) -> dict:
        # dispatch every check before reading any back
        out = {m: self._check(insts[m], r.phi)
               for m, r in zip(members, results)}
        return {m: float(v) for m, v in out.items()}

    def setup(self) -> None:
        import jax
        from repro.core import batch, conditions, gp, scenarios

        if "lane_steps" not in {f.name for f in
                                dataclasses.fields(scenarios.SweepResult)}:
            raise RuntimeError("this program's run_sweep reports no "
                               "lane_steps, which the family cell reads")
        self._check = jax.jit(conditions.sufficiency_residual)
        base = [tableii.draw(self.cfg, s, self.mix["rate_scale"])
                for s in self.mix["base_seeds"]]
        V = base[0]["adj"].shape[0]
        self.deps = [[tableii.relabel(d, self.rng.permutation(V))
                      for d in base]
                     for _ in range(int(self.mix["requests"]))]
        self.insts = [[tableii.to_instance(d) for d in fam]
                      for fam in self.deps]
        pool = [tableii.to_instance(d) for d in base]
        gp.warm_batched(batch.pad_instances(pool), **self.kw)
        self._solve(pool)

    def window(self, seconds: float) -> dict:
        records, kept = [], []
        t_start = time.perf_counter()
        deadline = t_start + seconds
        i = 0
        while not records or time.perf_counter() < deadline:
            t0 = time.perf_counter()
            j = i % len(self.insts)
            results, resid, iters, calls, lanes = self._solve(self.insts[j])
            t1 = time.perf_counter()
            records.append(dict(
                t0=t0 - t_start, t1=t1 - t_start, iters=iters, calls=calls,
                lane_steps=lanes, members=len(results),
                claimed=sum(r <= self.tol for r in resid.values())))
            kept.append((j, results, resid))
            i += 1
        self._kept = kept
        return dict(records=records, seconds=time.perf_counter() - t_start)

    def answers(self):
        """(deployment, phi.e, phi.c, reported cost, checked residual) of
        every member of every request the window served."""
        for j, results, resid in self._kept:
            for m, r in enumerate(results):
                yield (self.deps[j][m], np.asarray(r.phi.e),
                       np.asarray(r.phi.c), float(r.cost_history[-1]),
                       resid[m])

    def release(self) -> None:
        """Drop the program's state: the window's device arrays (its
        results are host arrays already)."""
        self.insts = None
