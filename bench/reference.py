"""Plain reference of problem (2) of arXiv:2310.06141, in float64 numpy.

Independent of the program: it imports nothing of ``repro`` and takes from
the program only the strategy under test.  For a deployment (numpy arrays,
see ``bench/traffic/tableii.py``) and a strategy ``phi = (e, c)`` it gives

* ``cost``: the aggregate delay D(phi) = sum D_ij(F_ij) + sum C_i(G_i),
  with stage traffic from the linear fixed points of Section II;
* ``residual``: the Theorem-1 sufficiency residual, the largest amount by
  which a direction carrying mass (phi > 1e-6) exceeds the least modified
  marginal delta of its (node, application, stage);
* ``feasibility``: the largest breach of the simplex constraints (1);
* ``lower_bound``: a lower bound on the optimum D* of problem (2), from
  the gradient of D at the strategy (the Frank-Wolfe duality bound), so
  that D(phi) - lower_bound bounds how far the strategy is from optimal.

The M/M/1 cost D(F) = F / (d - F) is continued above 0.98 d by its
second-order Taylor polynomial, as the configuration states, so that every
strategy has a finite cost.
"""

from __future__ import annotations

import numpy as np

THETA = 0.98
ACTIVE_EPS = 1e-6
BIG = 1e9


def _queue(F, cap, deriv: bool):
    cap = np.maximum(cap, 1e-6)
    knee = THETA * cap
    u = (F - knee) / cap
    s1 = 1.0 / (1.0 - THETA) ** 2
    s2 = 2.0 / (1.0 - THETA) ** 3
    if deriv:
        inside = cap / np.maximum(cap - F, 1e-12) ** 2
        outside = (s1 + s2 * u) / cap
    else:
        inside = F / np.maximum(cap - F, 1e-12)
        outside = THETA / (1.0 - THETA) + s1 * u + 0.5 * s2 * u * u
    return np.where(F <= knee, inside, outside)


def _cost_fn(kind: int, F, param, deriv: bool = False):
    if kind == 0:                       # linear: D = d F
        return param * (np.ones_like(F) if deriv else F)
    return _queue(F, param, deriv)


def _arrays(d: dict) -> dict:
    f64 = {k: np.asarray(d[k], np.float64)
           for k in ("link_param", "comp_param", "L", "w", "wnode", "r")}
    f64.update(adj=np.asarray(d["adj"], bool),
               dst=np.asarray(d["dst"], np.int64),
               n_tasks=np.asarray(d["n_tasks"], np.int64),
               stage_mask=np.asarray(d["stage_mask"], bool),
               link_kind=int(d["link_kind"]), comp_kind=int(d["comp_kind"]))
    return f64


def flows(d: dict, e, c):
    """Stage traffic t, CPU rates g, link flows F and workloads G."""
    x = _arrays(d)
    e = np.asarray(e, np.float64)
    c = np.asarray(c, np.float64)
    A, K1, V = c.shape
    eye = np.eye(V)
    t = np.zeros((A, K1, V))
    for a in range(A):
        inject = x["r"][a]
        for k in range(K1):
            t[a, k] = np.linalg.solve(eye - e[a, k].T, inject)
            inject = t[a, k] * c[a, k]
    g = t * c
    F = np.einsum("ak,aki,akij->ij", x["L"], t, e)
    G = np.einsum("ak,aki->i", x["w"], g) * x["wnode"]
    return t, g, F, G


def cost(d: dict, e, c) -> float:
    """Aggregate delay D(phi)."""
    x = _arrays(d)
    _, _, F, G = flows(d, e, c)
    links = np.where(x["adj"], _cost_fn(x["link_kind"], F, x["link_param"]),
                     0.0)
    return float(links.sum() + _cost_fn(x["comp_kind"], G,
                                        x["comp_param"]).sum())


def _cpu_allowed(x: dict, K1: int):
    k = np.arange(K1)[None, :]
    return (k < x["n_tasks"][:, None]) & x["stage_mask"]


def residual(d: dict, e, c) -> float:
    """Theorem-1 sufficiency residual (0 at a global optimum)."""
    x = _arrays(d)
    e = np.asarray(e, np.float64)
    c = np.asarray(c, np.float64)
    A, K1, V = c.shape
    _, _, F, G = flows(d, e, c)
    Dp = np.where(x["adj"], _cost_fn(x["link_kind"], F, x["link_param"],
                                     True), 0.0)
    Cp = _cost_fn(x["comp_kind"], G, x["comp_param"], True)
    eye = np.eye(V)
    pdt = np.zeros((A, K1 + 1, V))
    for a in range(A):
        for k in reversed(range(K1)):
            link_term = (e[a, k] * (x["L"][a, k] * Dp)).sum(axis=1)
            b = link_term + c[a, k] * (x["w"][a, k] * x["wnode"] * Cp
                                       + pdt[a, k + 1])
            pdt[a, k] = np.maximum(np.linalg.solve(eye - e[a, k], b), 0.0)
    delta_e = x["L"][:, :, None, None] * Dp[None, None] + pdt[:, :K1, None, :]
    delta_e = np.where(x["adj"][None, None], delta_e, BIG)
    delta_c = (x["w"][:, :, None] * x["wnode"][None, None] * Cp[None, None]
               + pdt[:, 1:, :])
    delta_c = np.where(_cpu_allowed(x, K1)[:, :, None], delta_c, BIG)
    least = np.minimum(delta_e.min(-1), delta_c)
    exc_e = np.where(e > ACTIVE_EPS, delta_e - least[..., None], 0.0)
    exc_c = np.where(c > ACTIVE_EPS, delta_c - least, 0.0)
    return float(max(exc_e.max(), exc_c.max()))


def _shortest_to_go(W, m0):
    """Least of ``m0[i]`` and ``W[i, j] + m[j]`` over paths: Bellman-Ford."""
    m = m0
    for _ in range(len(m0)):
        nxt = np.minimum(m0, (W + m[None, :]).min(axis=1))
        if np.array_equal(nxt, m):
            break
        m = nxt
    return m


def lower_bound(d: dict, e, c) -> float:
    """A lower bound on the optimum D* of problem (2).

    D is a convex function of the link flows F and CPU workloads G, and
    every strategy's (F, G) lies in the convex set of stage-layered
    multicommodity flows.  So for any strategy x, D* >= D(x) + min_y
    <grad D(x), (F, G)(y) - (F, G)(x)>, and the minimum over y routes each
    input along its least marginal-cost path through the stage layers:
    forwarding a unit of stage k over (i, j) costs L_ak D'_ij, computing
    it at i costs w_ak wnode_i C'_i and turns it into stage k + 1, and the
    last stage ends at the destination.  The bound is tight at an optimum.
    """
    x = _arrays(d)
    c = np.asarray(c, np.float64)
    A, K1, V = c.shape
    _, _, F, G = flows(d, e, c)
    Dp = np.where(x["adj"], _cost_fn(x["link_kind"], F, x["link_param"],
                                     True), 0.0)
    Cp = _cost_fn(x["comp_kind"], G, x["comp_param"], True)
    allowed = _cpu_allowed(x, K1)
    linear_min = 0.0
    for a in range(A):
        if not x["r"][a].any():
            continue
        m = np.full(V, np.inf)
        m[x["dst"][a]] = 0.0
        n = int(x["n_tasks"][a])
        for k in range(n, -1, -1):
            if k < n:
                m = np.where(allowed[a, k],
                             x["w"][a, k] * x["wnode"] * Cp + m, np.inf)
            W = np.where(x["adj"], x["L"][a, k] * Dp, np.inf)
            m = _shortest_to_go(W, m)
        linear_min += float((x["r"][a] * m).sum())
    linear_now = float((Dp * F).sum() + (Cp * G).sum())
    return cost(d, e, c) - linear_now + linear_min


def feasibility(d: dict, e, c) -> float:
    """Largest breach of constraints (1): row sums, off-graph, negative mass.

    A row of (application, stage, node) sums to 1, except where the chain
    has left the network (last stage at the destination), at invalid
    stages, and at last-stage nodes without out-links, where it sums to 0.
    """
    x = _arrays(d)
    e = np.asarray(e, np.float64)
    c = np.asarray(c, np.float64)
    A, K1, V = c.shape
    k = np.arange(K1)[None, :, None]
    last = k == x["n_tasks"][:, None, None]
    at_dst = np.arange(V)[None, None, :] == x["dst"][:, None, None]
    no_out = ~x["adj"].any(axis=1)[None, None, :]
    degenerate = (last & at_dst) | (last & no_out) | ~x["stage_mask"][:, :,
                                                                      None]
    want = np.where(degenerate, 0.0, 1.0)
    rows = np.abs(e.sum(-1) + c - want).max()
    off_graph = np.abs(np.where(x["adj"][None, None], 0.0, e)).max()
    cpu_off = np.abs(np.where(_cpu_allowed(x, K1)[:, :, None], 0.0, c)).max()
    negative = max(0.0, -e.min(), -c.min())
    return float(max(rows, off_graph, cpu_off, negative))
