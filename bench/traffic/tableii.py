"""Table II deployments (arXiv:2310.06141), drawn from a seed.

A copy, kept with the benchmark, of the topology constructors and random draws
of ``repro.core.network`` (``abilene``, ``small_world``, ``build_instance``,
``table_ii_instance``) so that a change to the program's constructors cannot
change the benchmark's inputs.  It builds the program's input type,
``network.Instance``.  ``bench/tests/test_bench_traffic.py`` checks that it
gives the same instances as the program's constructors.

``relabel`` renames the nodes of an instance by a permutation: the same
deployment, the same work, other arrays.
"""

from __future__ import annotations

import numpy as np

QUEUE = 1
LINEAR = 0

ABILENE_EDGES = (
    (0, 1), (0, 2), (1, 2), (1, 3), (2, 4), (3, 4), (3, 5), (4, 6),
    (5, 6), (5, 7), (6, 8), (7, 9), (8, 9), (9, 10),
)


def _adjacency(n: int, edges) -> np.ndarray:
    adj = np.zeros((n, n), dtype=bool)
    for u, v in edges:
        adj[u, v] = adj[v, u] = True
    return adj


def abilene() -> np.ndarray:
    """The 11-node, 14-edge Abilene backbone."""
    return _adjacency(11, ABILENE_EDGES)


def small_world(n: int = 100, seed: int = 3) -> np.ndarray:
    """Ring with i+1, i+2, i+3 chords plus n/5 random long-range chords."""
    edges = set()
    for i in range(n):
        for step in (1, 2, 3):
            edges.add(frozenset((i, (i + step) % n)))
    rng = np.random.default_rng(seed)
    added = 0
    while added < n // 5:
        u, v = rng.integers(0, n, size=2)
        if u != v and frozenset((int(u), int(v))) not in edges:
            edges.add(frozenset((int(u), int(v))))
            added += 1
    return _adjacency(n, [tuple(e) for e in edges])


TOPOLOGIES = {"abilene": abilene, "sw": small_world}


def draw(cfg: dict, seed: int, rate_scale: float) -> dict:
    """One deployment of configuration ``cfg`` as numpy arrays.

    The same random sequence as ``network.build_instance``: link and CPU
    parameters u.a.r. in [1-h, 1+h] times their means, then per application
    a destination, ``n_sources`` distinct sources and their rates u.a.r. in
    [0.5, 1.5] times ``rate_scale``.
    """
    rng = np.random.default_rng(seed)
    adj = TOPOLOGIES[cfg["topology"]]()
    V = adj.shape[0]
    A, n_tasks, R = cfg["apps"], cfg["tasks_per_chain"], cfg["sources"]
    K1 = n_tasks + 1
    h = cfg["heterogeneity"]
    link = np.where(adj, cfg["link_mean"] * rng.uniform(1 - h, 1 + h, (V, V)),
                    0.0)
    comp = cfg["comp_mean"] * rng.uniform(1 - h, 1 + h, V)
    sizes = np.maximum(np.array([10.0 - 5.0 * k for k in range(K1)]),
                       cfg["packet_floor"])
    L = np.tile(sizes[None, :], (A, 1))
    w = np.full((A, K1), 1.0)
    w[:, -1] = 0.0
    r = np.zeros((A, V))
    dst = np.zeros(A, dtype=np.int64)
    lo, hi = 0.5 * rate_scale, 1.5 * rate_scale
    for a in range(A):
        dst[a] = rng.integers(V)
        srcs = rng.choice(V, size=min(R, V), replace=False)
        r[a, srcs] = rng.uniform(lo, hi, size=len(srcs))
    return dict(
        adj=adj, link_param=link.astype(np.float32),
        link_kind=cfg["link_kind"], comp_kind=cfg["comp_kind"],
        comp_param=comp.astype(np.float32), L=L.astype(np.float32),
        w=w.astype(np.float32), wnode=np.ones(V, np.float32),
        r=r.astype(np.float32), dst=dst, n_tasks=np.full(A, n_tasks),
        stage_mask=np.ones((A, K1), dtype=bool))


def relabel(d: dict, perm: np.ndarray) -> dict:
    """Rename node ``perm[i]`` to ``i``: the same deployment, permuted."""
    inv = np.argsort(perm)
    out = dict(d)
    out["adj"] = d["adj"][np.ix_(perm, perm)]
    out["link_param"] = d["link_param"][np.ix_(perm, perm)]
    out["comp_param"] = d["comp_param"][perm]
    out["wnode"] = d["wnode"][perm]
    out["r"] = d["r"][:, perm]
    out["dst"] = inv[d["dst"]]
    return out


def to_instance(d: dict):
    """The program's ``network.Instance`` holding the arrays of ``d``."""
    import jax.numpy as jnp
    from repro.core import network

    return network.Instance(
        adj=jnp.asarray(d["adj"]), link_param=jnp.asarray(d["link_param"]),
        link_kind=int(d["link_kind"]),
        comp_param=jnp.asarray(d["comp_param"]),
        comp_kind=int(d["comp_kind"]), L=jnp.asarray(d["L"]),
        w=jnp.asarray(d["w"]), wnode=jnp.asarray(d["wnode"]),
        r=jnp.asarray(d["r"]), dst=jnp.asarray(d["dst"]),
        n_tasks=jnp.asarray(d["n_tasks"]),
        stage_mask=jnp.asarray(d["stage_mask"]))
