"""The comparison that decides ``correct``.

Every answer a run's window produced (a strategy, the cost the program
reports for it and the Theorem-1 residual the program's check found for
it) is recomputed by the plain float64 reference (``bench/reference.py``).
The numbers compared, each the worst over the run's answers:

* ``cost_gap``: |reported cost - reference cost| / reference cost;
* ``resid_gap``: |checked residual - reference residual| / the larger of
  the reference residual and the tolerance;
* ``opt_gap``: (reference cost - reference lower bound on the optimum) /
  reference cost, an upper bound on how far the strategy is from optimal;
* ``feas_err``: the largest breach of the simplex constraints (1).

Each has the limit that the cell's file gives (``limits``).  An instance is
certified, and counts towards ``certs_per_s``, when the reference's
sufficiency residual of the strategy the program returned is at or below
the configuration's tolerance; an answer that is not certified is a
certificate not delivered (``failed``), not a wrong answer.
"""

from __future__ import annotations

import sys

import numpy as np

from bench import reference

NUMBERS = ("cost_gap", "resid_gap", "opt_gap", "feas_err")


def compare(answers, tol: float) -> tuple[dict, int]:
    """(the numbers compared, instances certified) over one run's answers."""
    out = dict.fromkeys(NUMBERS, 0.0)
    certified = 0
    for d, e, c, cost, resid in answers:
        e = np.asarray(e)
        c = np.asarray(c)
        ref_cost = reference.cost(d, e, c)
        ref_resid = reference.residual(d, e, c)
        certified += bool(ref_resid <= tol)
        gaps = dict(cost_gap=abs(cost - ref_cost) / abs(ref_cost),
                    resid_gap=abs(resid - ref_resid) / max(ref_resid, tol),
                    opt_gap=(ref_cost - reference.lower_bound(d, e, c))
                    / abs(ref_cost),
                    feas_err=reference.feasibility(d, e, c))
        for k, v in gaps.items():
            # a non-finite gap is a failed comparison, never a pass
            out[k] = max(out[k], v) if np.isfinite(v) else float("inf")
    return out, int(certified)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}})."""
    shown = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
             for k in NUMBERS}
    ok = all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown


def print_checks(shown: dict, file=sys.stderr) -> None:
    for k, v in shown.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=file)
