"""Kernel microbenchmarks (interpret mode on CPU; structural numbers —
real-TPU wall times come from the roofline, not from this host).

``--smoke`` runs only the GP hot paths (blocked-sets + batched-LU kernels
plus the sharded-vs-single step-engine parity) at V=20 and records rows to
BENCH_gp.json — the CI ``bench-smoke`` job, gated afterwards by
``benchmarks.common --check`` against the committed rows.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, bench_record, emit
from repro.kernels import ops, ref


def _stage_systems(V: int):
    """Per-stage systems (I - Phi_k) and injections for the batched-LU
    bench: real fig5-family matrices where Table II has a member at that
    node count (connected-er V=20, sw-queue V=100), synthetic
    substochastic fill-ins otherwise (V=50)."""
    from repro.core import gp, network, scenarios

    by_v = {20: "connected-er", 100: "sw-queue"}
    if V in by_v:
        name = by_v[V]
        inst = network.table_ii_instance(
            name, seed=0, rate_scale=scenarios.FIG5_RATE[name])
        phi = gp.init_phi(inst)
        A, K1 = inst.A, inst.K1
        mats = (jnp.eye(V) - phi.e).reshape(A * K1, V, V)
        rhs = jnp.broadcast_to(inst.r[:, None, :], (A, K1, V)).reshape(A * K1, V)
        return mats, rhs, f"fig5:{name}"
    B = 90   # match sw-queue's A*K1 stage count
    P = jax.random.uniform(jax.random.PRNGKey(V), (B, V, V))
    P = 0.5 * P / jnp.sum(P, axis=-1, keepdims=True)
    rhs = jax.random.uniform(jax.random.PRNGKey(V + 1), (B, V))
    return jnp.eye(V) - P, rhs, "synthetic"


def _blocked_inputs(V: int):
    """route/improper matrices from a congested mid-solve GP iterate (where
    improper links actually appear), plus the instance label."""
    from repro.core import gp, marginals, network, scenarios

    by_v = {20: "connected-er", 100: "sw-queue"}
    name = by_v.get(V, "connected-er")
    inst = network.table_ii_instance(
        name, seed=0, rate_scale=scenarios.FIG5_RATE[name])
    res = gp.solve(inst, alpha=0.1, max_iters=8, patience=10**6, tol=0.0)
    m = marginals.marginals(inst, res.phi)
    route = res.phi.e > 0.0
    worse = m.pdt[:, :, None, :] > m.pdt[:, :, :, None] + gp._BLOCK_EPS
    return route, route & worse, name


def bench_blocked_sets(sizes=(20, 100)):
    """Bit-packed blocked-set kernel vs the seed's dense V-sweep scan.

    Both paths compute the identical tagged-node fixed point (parity is
    asserted, and tested in tests/test_blocked_sets.py); the bitset path
    packs successors into uint32 words and early-exits at the routing-DAG
    diameter (kernels/blocked_sets.py, DESIGN.md §13).
    """
    from repro.kernels import blocked_sets as bset

    for V in sizes:
        route, improper, src = _blocked_inputs(V)
        f_bit = ops.blocked_tagged          # already jitted at definition
        f_dense = jax.jit(bset.tagged_scan_dense)
        t_bit = _time_med(lambda: f_bit(route, improper))
        t_dense = _time_med(lambda: f_dense(route, improper))
        exact = bool(jnp.all(f_bit(route, improper) == f_dense(route, improper)))
        assert exact, f"bitset kernel diverged from dense scan at V={V}"
        speedup = t_dense / max(t_bit, 1e-9)
        emit(f"blocked_sets_V{V}", t_bit,
             f"fig5:{src}|dense_scan:{t_dense:.0f}us|"
             f"speedup:{speedup:.2f}x|exact:{exact}")
        bench_record("kernel_bench", scenario=f"blocked_sets:{src}", V=V,
                     solver="bitset", seconds=t_bit / 1e6,
                     speedup=round(speedup, 3))
        bench_record("kernel_bench", scenario=f"blocked_sets:{src}", V=V,
                     solver="dense-scan", seconds=t_dense / 1e6)


def bench_batched_solve():
    """Batched (B,V,V) factor+solve vs the looped per-stage LAPACK baseline.

    The baseline is one ``jnp.linalg.solve`` *dispatch* per stage system —
    the pre-batching access pattern the ROADMAP flags ("looped LAPACK on
    CPU ... serializes what is structurally one batched V x V solve").
    The derived field also reports the jit-unrolled variant (all B solves
    as separate HLOs inside one program) for reference.
    """
    bench_batched_solve_sizes((20, 50, 100))


def bench_batched_solve_sizes(sizes):
    for V in sizes:
        mats, rhs, src = _stage_systems(V)
        B = mats.shape[0]

        t_bat = _time_med(lambda: ops.batched_solve(mats, rhs)[0])

        solve1 = jax.jit(lambda m, b: jnp.linalg.solve(m, b))

        def eager_loop():
            return [solve1(mats[i], rhs[i]) for i in range(B)]

        t_loop = _time_med(eager_loop)

        @jax.jit
        def unrolled(mats, rhs):
            return jnp.stack([
                jnp.linalg.solve(mats[i], rhs[i]) for i in range(B)])

        t_unroll = _time_med(lambda: unrolled(mats, rhs))
        x_bat, _ = ops.batched_solve(mats, rhs)
        err = float(jnp.max(jnp.abs(x_bat - unrolled(mats, rhs))))
        emit(f"batched_lu_V{V}", t_bat,
             f"B:{B}|{src}|looped_lapack:{t_loop:.0f}us|"
             f"speedup:{t_loop / max(t_bat, 1e-9):.2f}x|"
             f"jit_unrolled:{t_unroll:.0f}us|max_err:{err:.2e}")
        bench_record("kernel_bench", scenario=f"batched_lu:{src}", V=V,
                     solver="batched_lu", seconds=t_bat / 1e6,
                     speedup=round(t_loop / max(t_bat, 1e-9), 3))
        bench_record("kernel_bench", scenario=f"batched_lu:{src}", V=V,
                     solver="looped-lapack", seconds=t_loop / 1e6)


def bench_sharded_parity(V: int = 20, iters: int = 12):
    """Sharded chunked solve (unified step engine under shard_map) vs
    ``gp.solve`` on one fig5 member: wall time plus the ≤1e-4 cost-history
    parity the engine contract promises (DESIGN.md §14).  Runs on however
    many host devices are available (CI's distributed job forces 4 CPU
    devices; a plain run exercises the 1-shard collective pattern)."""
    import numpy as np

    from repro.core import compat, distributed, gp, network, scenarios

    by_v = {20: "connected-er", 100: "sw-queue"}
    name = by_v[V]
    inst = network.table_ii_instance(
        name, seed=0, rate_scale=scenarios.FIG5_RATE[name])
    phi0 = gp.init_phi(inst)
    kw = dict(alpha=0.1, max_iters=iters, patience=10**6, tol=0.0)
    n = min(len(jax.devices()), 2)
    mesh = compat.make_mesh((n,), ("stage",))
    gp.solve(inst, phi0, **kw)                                  # warm
    distributed.solve_sharded(inst, mesh, phi0=phi0, **kw)      # warm
    with Timer() as t:
        ref = gp.solve(inst, phi0, **kw)
    t_single = t.us
    with Timer() as t:
        res = distributed.solve_sharded(inst, mesh, phi0=phi0, **kw)
    t_shard = t.us
    a = np.asarray(ref.cost_history, dtype=np.float64)
    b = np.asarray(res.cost_history, dtype=np.float64)
    dev = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-9)))
    assert dev <= 1e-4, f"sharded GP diverged from gp.solve: {dev:.2e}"
    emit(f"gp_sharded_V{V}", t_shard,
         f"fig5:{name}|shards:{n}|single:{t_single:.0f}us|"
         f"cost_dev:{dev:.2e}")
    bench_record("kernel_bench", scenario=f"sharded_step:{name}", V=V,
                 solver=f"sharded{n}", seconds=t_shard / 1e6, iters=iters,
                 cost_dev=dev)
    bench_record("kernel_bench", scenario=f"sharded_step:{name}", V=V,
                 solver="single", seconds=t_single / 1e6, iters=iters)


def bench_gp_solver_parity():
    """End-to-end GP on a fig5 member: batched-LU stage solver vs the seed
    dense path — wall time and final-cost parity (acceptance: <= 1e-5)."""
    from repro.core import gp, network, scenarios

    inst = network.table_ii_instance(
        "sw-queue", seed=0, rate_scale=scenarios.FIG5_RATE["sw-queue"])
    kw = dict(alpha=0.1, max_iters=30, patience=10**6, tol=0.0)
    gp.solve(inst, solver="batched_lu", **kw)              # warm compile
    with Timer() as t:
        r_lu = gp.solve(inst, solver="batched_lu", **kw)
    t_lu = t.us
    gp.solve(inst, solver="dense", **kw)                   # warm compile
    with Timer() as t:
        r_dense = gp.solve(inst, solver="dense", **kw)
    t_dense = t.us
    rel = abs(r_lu.final_cost - r_dense.final_cost) / abs(r_dense.final_cost)
    emit("gp_sw_queue_30it_batched_lu", t_lu,
         f"dense:{t_dense:.0f}us|speedup:{t_dense / max(t_lu, 1e-9):.2f}x|"
         f"cost_rel_diff:{rel:.2e}")


def _time(fn, *args, reps=3):
    fn(*args)                        # compile/warm
    with Timer() as t:
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
    return t.us / reps


def _time_med(fn, reps=11):
    """Median single-call time — robust to the multi-x outliers (GC, page
    faults) that skew short-call means on small shared CPUs."""
    jax.block_until_ready(fn())      # compile/warm
    ts = []
    for _ in range(reps):
        with Timer() as t:
            jax.block_until_ready(fn())
        ts.append(t.us)
    return sorted(ts)[len(ts) // 2]


def smoke():
    """CI bench-smoke: GP-hot-path kernels only, V=20, interpret-safe."""
    bench_blocked_sets(sizes=(20,))
    bench_batched_solve_sizes((20,))
    bench_sharded_parity(V=20)


def main():
    # flash attention: kernel (interpret) vs jnp oracle
    B, S, H, KV, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    t_kern = _time(lambda: ops.flash_attention(q, k, v, causal=True))
    tq = lambda x: x.transpose(0, 2, 1, 3)
    rf = jax.jit(lambda q, k, v: ref.flash_attention(q, k, v, causal=True))
    t_ref = _time(lambda: rf(tq(q), tq(k), tq(v)))
    emit("kernel_flash_attention_interp", t_kern, f"jnp_ref:{t_ref:.0f}us")

    # chain propagate: kernel vs jnp on the SW-scale problem (90 stages, 128 nodes)
    Sg, V = 90, 128
    M = jax.random.uniform(jax.random.PRNGKey(1), (Sg, V, V)) * 0.05
    src = jax.random.uniform(jax.random.PRNGKey(2), (Sg, V))
    t0 = jnp.zeros((Sg, V))
    t_kern = _time(lambda: ops.propagate_step(t0, M, src))
    rp = jax.jit(ref.propagate_step)
    t_ref = _time(lambda: rp(t0, M, src))
    emit("kernel_chain_propagate_interp", t_kern, f"jnp_ref:{t_ref:.0f}us")

    # ssd chunk
    Bz, nc, Q, Hh, P, N = 1, 4, 128, 4, 64, 64
    xs = jax.random.split(jax.random.PRNGKey(3), 4)
    xh = jax.random.normal(xs[0], (Bz, nc, Q, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(xs[1], (Bz, nc, Q, Hh)))
    A = -jnp.exp(0.2 * jax.random.normal(xs[2], (Hh,)))
    cum = jnp.cumsum(dt * A[None, None, None], axis=2)
    BH = 0.3 * jax.random.normal(xs[3], (Bz, nc, Q, Hh, N))
    CH = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (Bz, nc, Q, Hh, N))
    t_kern = _time(lambda: ops.ssd_chunk(xh, dt, None, cum, BH, CH))
    rs = jax.jit(ref.ssd_chunk)
    t_ref = _time(lambda: rs(xh, dt, cum, BH, CH))
    emit("kernel_ssd_chunk_interp", t_kern, f"jnp_ref:{t_ref:.0f}us")

    # batched-LU stage solver: kernel-vs-LAPACK speedup + GP parity
    bench_batched_solve()
    # blocked-set propagation: bitset kernel vs the dense V-sweep scan
    bench_blocked_sets()
    bench_gp_solver_parity()
    # unified step engine under shard_map vs the single-device chunked solve
    bench_sharded_parity()


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
