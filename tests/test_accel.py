"""§15 convergence-acceleration layer (engine.AccelConfig).

Covers the ISSUE-6 acceptance criteria:

  * cost parity — accelerated final costs match the plain solver on every
    Table II scenario (within convergence tolerance; acceleration changes
    where the iteration STOPS, never what it converges to);
  * residual-based stopping — the exact sufficiency residual stop and the
    phi-delta fixed-point stop land on the same cost;
  * Anderson safeguard — a forced cost-increasing mix falls back to the
    plain GP step (monotone descent survives a poisoned history), and a
    genuinely better mix is accepted;
  * iteration reduction — the accelerated fig5/fig6 families spend
    >= 1.5x fewer total GP iterations than the committed BENCH_gp.json
    plain rows at equal-or-lower per-member cost (slow tier);
  * sharded parity — accelerated 2-shard trajectories match the
    accelerated single-device ones <= 1e-4 (multi-device only);
  * AUTO_MIN_V derivation from committed gp_scaling rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from repro.core import compat, distributed, engine, gp, network, scenarios
from repro.core import traffic

SMALL = ["abilene", "balanced-tree", "connected-er", "fog", "lhc", "geant"]

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _mesh(n):
    return compat.make_mesh((n,), ("stage",))


def _rel_dev(a, b):
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-9)))


# ---------------------------------------------------------------- config


def test_resolve_accel_forms():
    assert engine.resolve_accel(None) is None
    assert engine.resolve_accel(False) is None
    assert engine.resolve_accel(True) is engine.DEFAULT_ACCEL
    assert engine.resolve_accel("default") is engine.DEFAULT_ACCEL
    cfg = engine.AccelConfig(anderson_m=5)
    assert engine.resolve_accel(cfg) is cfg
    with pytest.raises(TypeError):
        engine.resolve_accel({"anderson_m": 3})


def test_accel_off_is_bit_identical_to_legacy():
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    a = gp.solve(inst, alpha=0.1, max_iters=60)
    b = gp.solve(inst, alpha=0.1, max_iters=60, accel=None)
    assert int(a.iterations) == int(b.iterations)
    assert np.array_equal(np.asarray(a.cost_history),
                          np.asarray(b.cost_history))


# ----------------------------------------------------------- cost parity


@pytest.mark.parametrize("name", SMALL)
def test_accel_cost_parity_table_ii(name):
    inst = network.table_ii_instance(name, seed=0, rate_scale=2.0)
    plain = gp.solve(inst, alpha=0.1, max_iters=600)
    acc = gp.solve(inst, alpha=0.1, max_iters=600, accel=True)
    # acceleration must not land on a worse operating point: equal within
    # the solver's own convergence tolerance (both runs stop at tol=1e-4)
    assert acc.final_cost <= plain.final_cost * (1 + 1e-4)


def test_adaptive_alpha_only_cost_parity():
    # the adaptive-stepsize mechanism alone (Anderson + residual stop off):
    # converges to the same operating point as the full 12-rung ladder
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    acc = engine.AccelConfig(anderson_m=0, adaptive_alpha=True,
                             residual_stop=False)
    plain = gp.solve(inst, alpha=0.1, max_iters=600)
    ada = gp.solve(inst, alpha=0.1, max_iters=600, accel=acc)
    assert ada.final_cost <= plain.final_cost * (1 + 1e-4)


def test_accel_reduces_iterations_single_device():
    # the headline mechanism check on two Table II instances where the
    # plain ladder needs many iterations; the >= 1.5x family-level claim
    # is the slow-tier test below
    for name in ("abilene", "balanced-tree"):
        inst = network.table_ii_instance(name, seed=0, rate_scale=2.0)
        plain = gp.solve(inst, alpha=0.1, max_iters=600)
        acc = gp.solve(inst, alpha=0.1, max_iters=600, accel=True)
        assert int(acc.iterations) < int(plain.iterations)


# ------------------------------------------------------ stopping criteria


@pytest.mark.parametrize("name", ["abilene", "connected-er", "geant"])
def test_residual_stop_matches_phi_delta_stop(name):
    inst = network.table_ii_instance(name, seed=0, rate_scale=2.0)
    # residual latch only (phi-delta disabled via phi_tol < 0)
    res = gp.solve(inst, alpha=0.1, max_iters=600,
                   accel=engine.DEFAULT_ACCEL._replace(phi_tol=-1.0))
    # phi-delta latch only (residual tol disabled via tol < 0); phi_tol
    # tightened one decade so the fixed-point stop is as converged as the
    # tol=1e-4 residual stop — comparable stopping tightness is what makes
    # the 1e-5 cost-agreement contract meaningful
    phid = gp.solve(inst, alpha=0.1, max_iters=600, tol=-1.0,
                    accel=engine.DEFAULT_ACCEL._replace(phi_tol=1e-7))
    rel = abs(res.final_cost - phid.final_cost) / max(abs(res.final_cost),
                                                      1e-9)
    assert rel <= 1e-5


# ------------------------------------------------------ Anderson safeguard


def _poisoned_chunk(inst, phi_k, acc, slot_vec):
    """One accel iteration from ``phi_k`` with ``slot_vec`` planted as the
    sole Anderson history iterate (residual 0 => the mix lands ~on it)."""
    carry = engine.init_carry(inst, phi_k, accel=acc)
    carry = carry._replace(ax=carry.ax.at[-1].set(slot_vec),
                           ak=jnp.int32(1))
    out, _ = engine.scan_chunk(
        inst, carry, jnp.float32(0.1), jnp.float32(-1.0),
        jnp.int32(10 ** 6), jnp.int32(10 ** 6), None, None,
        length=1, accel=acc)
    return out


def test_anderson_safeguard_rejects_cost_increasing_mix():
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    # descend for a while, then poison the history with the (expensive)
    # initial strategy: the single-slot mix reconstructs ~phi0, whose cost
    # is far above the current iterate => the safeguard must commit the
    # plain step instead
    phi0 = gp.init_phi(inst)
    phi_k = gp.solve_scan(inst, alpha=0.1, max_iters=30, tol=0.0,
                          patience=10 ** 6).phi
    acc = engine.DEFAULT_ACCEL._replace(phi_tol=-1.0)
    plain = engine.gp_step(inst, phi_k, 0.1, accel=acc)

    out = _poisoned_chunk(inst, phi_k, acc, engine._flat_phi(phi0))
    assert _rel_dev(engine._flat_phi(out.phi),
                    engine._flat_phi(plain.phi)) <= 1e-6
    assert float(out.cost) <= float(plain.cost) * (1 + 1e-6)


def test_anderson_accepts_cost_decreasing_mix():
    # positive control: plant the CONVERGED strategy in the history slot —
    # the mix reconstructs it, beats the plain step, and is accepted
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    phi_star = gp.solve(inst, alpha=0.1, max_iters=600).phi
    cost_star = float(engine._strategy_cost(inst, phi_star, "auto", None))
    phi_k = gp.solve_scan(inst, alpha=0.1, max_iters=30, tol=0.0,
                          patience=10 ** 6).phi
    acc = engine.DEFAULT_ACCEL._replace(phi_tol=-1.0)
    plain = engine.gp_step(inst, phi_k, 0.1, accel=acc)

    out = _poisoned_chunk(inst, phi_k, acc, engine._flat_phi(phi_star))
    assert float(out.cost) < float(plain.cost)
    assert float(out.cost) <= cost_star * (1 + 1e-5)


# ------------------------------------------------------ exit at the latch


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("accel", [None, True], ids=["plain", "accel"])
def test_frozen_chunk_returns_carry_unchanged(accel):
    # a carry whose done latch is set passes through a chunk bit for bit
    # (phi, bookkeeping, Anderson window, telemetry ring) and re-emits its
    # (cost, residual) at every step
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    acc = engine.resolve_accel(accel)
    tel = engine.resolve_telemetry(True)
    args = (jnp.float32(0.1), jnp.float32(1e-4), jnp.int32(40),
            jnp.int32(400), None, None)
    kw = dict(accel=acc, telemetry=tel)
    carry = gp._init_carry(inst, gp.init_phi(inst), **kw)
    carry, _ = gp._scan_chunk(inst, carry, *args, length=4, **kw)
    assert not bool(carry.done) and int(carry.iters) == 4
    carry = carry._replace(done=jnp.asarray(True))
    out, (cs, rs) = gp._scan_chunk(inst, carry, *args, length=3, **kw)
    assert _bits(out) == _bits(carry)
    assert np.asarray(cs).tobytes() == np.full(3, carry.cost).tobytes()
    assert np.asarray(rs).tobytes() == np.full(3, carry.residual).tobytes()


@pytest.mark.parametrize("accel", [None, True], ids=["plain", "accel"])
def test_solve_latched_mid_chunk_matches_scan_without_frozen_tail(accel):
    # gp.solve latches inside a chunk and runs none of the rest; the scan cut
    # at that iteration count has no frozen tail: same bits everywhere
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    kw = dict(alpha=0.1, accel=accel, telemetry=True)
    res = gp.solve(inst, max_iters=400, **kw)
    n = res.iterations
    assert n % gp._SOLVE_CHUNK != 0 and n < 400
    scan = gp.solve_scan(inst, max_iters=n, **kw)
    assert int(scan.iterations) == n
    for a, b in [(res.phi, scan.phi), (res.telemetry, scan.telemetry),
                 (res.cost_history, scan.cost_history),
                 (res.residual_history, scan.residual_history)]:
        assert _bits(a) == _bits(b)


# ------------------------------------------------- batched / sharded parity


def test_batched_accel_matches_serial_accel():
    kw = dict(alpha=0.1, max_iters=120, accel=True)
    sweep = scenarios.run_sweep(
        "seed-ensemble", sweep_kwargs={"scenario": "abilene", "n_seeds": 4},
        **kw)
    serial = scenarios.run_sweep_serial(
        "seed-ensemble", sweep_kwargs={"scenario": "abilene", "n_seeds": 4},
        **kw)
    for b, s in zip(sweep.results, serial.results):
        assert abs(b.final_cost - s.final_cost) \
            <= 1e-4 * max(abs(s.final_cost), 1e-9)


# pinned-iteration kwargs: every stop latch disabled (tol<0 kills the
# residual stop, phi_tol<0 the fixed-point latch, patience the stall one)
# so single-device and sharded runs commit exactly max_iters iterations
# and their trajectories compare elementwise
PIN = dict(alpha=0.1, max_iters=40, patience=10 ** 6, tol=-1.0,
           accel=engine.DEFAULT_ACCEL._replace(phi_tol=-1.0))


@multi_device
def test_sharded_accel_matches_single_device():
    inst = network.table_ii_instance("abilene", seed=0, rate_scale=2.0)
    phi0 = gp.init_phi(inst)
    ref = gp.solve(inst, phi0, **PIN)
    res = distributed.solve_sharded(inst, _mesh(2), phi0=phi0, **PIN)
    assert _rel_dev(res.cost_history, ref.cost_history) <= 1e-4
    assert abs(res.final_cost - ref.final_cost) \
        <= 1e-4 * abs(ref.final_cost)


@multi_device
def test_sharded_accel_four_shards():
    n = min(4, len(jax.devices()))
    inst = network.table_ii_instance("geant", seed=0, rate_scale=2.0)
    phi0 = gp.init_phi(inst)
    ref = gp.solve(inst, phi0, **PIN)
    res = distributed.solve_sharded(inst, _mesh(n), phi0=phi0, **PIN)
    assert _rel_dev(res.cost_history, ref.cost_history) <= 1e-4


# ------------------------------------------------ iteration-count acceptance


def _committed_iters(bench, scenario, solver):
    rows = common.load_rows(common.BENCH_PATH)
    for r in rows:
        if (r.get("bench"), r.get("scenario"),
                r.get("solver")) == (bench, scenario, solver):
            return int(r["iters"])
    return None


@pytest.mark.slow
def test_fig5_ensemble_iters_reduced_1p5x():
    committed = _committed_iters("fig5", "abilene-ensemble32", "GP-batched")
    if committed is None:
        pytest.skip("no committed fig5 GP-batched iters row")
    kw = dict(sweep_kwargs={"scenario": "abilene", "n_seeds": 32},
              alpha=0.1, max_iters=250)
    plain = scenarios.run_sweep("seed-ensemble", **kw)
    acc = scenarios.run_sweep("seed-ensemble", accel=True, **kw)
    total = sum(int(r.iterations) for r in acc.results)
    assert total * 1.5 <= committed, (total, committed)
    for a, p in zip(acc.results, plain.results):
        assert a.final_cost <= p.final_cost * (1 + 1e-4)


@pytest.mark.slow
def test_fig6_congestion_iters_reduced_1p5x():
    committed = _committed_iters("fig6", "abilene-rates", "GP-batched")
    if committed is None:
        pytest.skip("no committed fig6 GP-batched iters row")
    kw = dict(alpha=0.1, max_iters=300)
    plain = scenarios.run_sweep("fig6-congestion", **kw)
    acc = scenarios.run_sweep("fig6-congestion", accel=True, **kw)
    total = sum(int(r.iterations) for r in acc.results)
    assert total * 1.5 <= committed, (total, committed)
    for a, p in zip(acc.results, plain.results):
        assert a.final_cost <= p.final_cost * (1 + 1e-4)


# -------------------------------------------------------------- AUTO_MIN_V


def _scaling_row(V, speedup):
    return {"bench": "gp_scaling", "scenario": f"V{V}", "V": V,
            "solver": "batched_lu", "seconds": 1.0, "speedup": speedup}


def test_auto_min_v_interpolates_crossing():
    rows = [_scaling_row(20, 0.5), _scaling_row(40, 1.5)]
    # crossing at V = 20 + 0.5/1.0 * 20 = 30
    assert traffic._derive_auto_min_v(rows) == 30


def test_auto_min_v_edge_cases():
    assert traffic._derive_auto_min_v([]) == traffic._AUTO_MIN_V_FALLBACK
    # already >= 1 at the smallest measured size
    rows = [_scaling_row(10, 1.2), _scaling_row(40, 2.0)]
    assert traffic._derive_auto_min_v(rows) == 10
    # never crosses: fall back rather than extrapolate
    rows = [_scaling_row(10, 0.2), _scaling_row(40, 0.8)]
    assert traffic._derive_auto_min_v(rows) == traffic._AUTO_MIN_V_FALLBACK
    # non-scaling rows are ignored
    rows = [{"bench": "fig5", "V": 11, "solver": "GP", "speedup": 9.0}]
    assert traffic._derive_auto_min_v(rows) == traffic._AUTO_MIN_V_FALLBACK


def test_auto_min_v_module_constant_is_sane():
    assert 2 <= traffic.AUTO_MIN_V <= 512
