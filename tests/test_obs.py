"""Observability layer (DESIGN.md §19): device telemetry ring, metrics,
spans, the online drain, the report generator, and the bench-record guard.

The §19 acceptance criteria asserted here:

  * telemetry OFF is bit-identical — same committed trajectories on the
    Table II scenarios, single-device, batched and (when 4 host devices
    are forced) sharded;
  * telemetry ON is trajectory-identical WITHIN each path and the ring
    records exactly the committed per-iteration values (cost column ==
    cost_history), truncating — not wrapping — past capacity;
  * the online service drains per-event segments whose iteration counts
    reproduce the ``HealthReport.iterations`` it serves;
  * telemetry-on overhead <= 5% s_per_iter on the sw-queue scenario
    (skipped on a contended box — same loadavg guard ``bench_record``
    uses).
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import common
from repro import obs
from repro.core import distributed, engine, events, gp, network
from repro.obs import device as obs_device
from repro.obs import report as obs_report
from repro.serve.online import OnlineSolver

# Fixed-length budget (same rationale as tests/test_distributed.py): pin
# the iteration count so parity compares whole trajectories bit-for-bit.
KW = dict(alpha=0.1, max_iters=30, patience=10**6, tol=0.0)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)


def _inst(seed=0, scale=2.0):
    return network.table_ii_instance("abilene", seed=seed, rate_scale=scale)


# ---------------------------------------------------------------------------
# device layer
# ---------------------------------------------------------------------------

def test_resolve_telemetry():
    assert engine.resolve_telemetry(None) is None
    assert engine.resolve_telemetry(False) is None
    assert engine.resolve_telemetry(True) == obs.DEFAULT_TELEMETRY
    assert engine.resolve_telemetry("default") == obs.DEFAULT_TELEMETRY
    cfg = obs.TelemetryConfig(ring=8, bs_rounds=False)
    assert engine.resolve_telemetry(cfg) is cfg
    with pytest.raises(TypeError):
        engine.resolve_telemetry(7)


def test_empty_ring_shapes():
    assert obs_device.empty_ring(None).shape == (0, obs.TEL_WIDTH)
    assert obs_device.empty_ring(obs.TelemetryConfig(ring=5)).shape == (
        5, obs.TEL_WIDTH)


def test_ring_record_truncates_not_wraps():
    tb = obs_device.empty_ring(obs.TelemetryConfig(ring=3))
    for i in range(5):
        row = jax.numpy.full((obs.TEL_WIDTH,), float(i + 1))
        tb = obs_device.ring_record(tb, jax.numpy.int32(i), row,
                                    jax.numpy.bool_(True))
    got = np.asarray(tb)[:, 0]
    np.testing.assert_array_equal(got, [1.0, 2.0, 3.0])   # 4, 5 dropped
    assert obs_device.ring_overflow(tb, 5) == 2
    assert obs_device.ring_valid(tb, 5).shape == (3, obs.TEL_WIDTH)
    assert obs_device.ring_valid(tb, 2).shape == (2, obs.TEL_WIDTH)


def test_ring_record_respects_write_mask():
    tb = obs_device.empty_ring(obs.TelemetryConfig(ring=3))
    row = jax.numpy.full((obs.TEL_WIDTH,), 9.0)
    tb = obs_device.ring_record(tb, jax.numpy.int32(0), row,
                                jax.numpy.bool_(False))
    assert float(np.asarray(tb).sum()) == 0.0


def test_records_to_dicts_columns():
    rows = np.arange(2 * obs.TEL_WIDTH, dtype=np.float32).reshape(2, -1)
    recs = obs.records_to_dicts(rows)
    assert [r["iter"] for r in recs] == [0, 8]
    assert set(recs[0]) == set(obs_device.COLUMNS)
    assert isinstance(recs[0]["rung"], int)
    assert isinstance(recs[0]["cost"], float)


# ---------------------------------------------------------------------------
# solver parity: telemetry off/on bit-identical trajectories
# ---------------------------------------------------------------------------

def test_single_device_parity_and_ring_content():
    inst = _inst()
    phi0 = gp.init_phi(inst)
    off = gp.solve(inst, phi0, **KW)
    on = gp.solve(inst, phi0, telemetry=True, **KW)

    assert off.telemetry is None
    assert int(on.iterations) == int(off.iterations) == KW["max_iters"]
    np.testing.assert_array_equal(np.asarray(on.phi.e), np.asarray(off.phi.e))
    np.testing.assert_array_equal(np.asarray(on.phi.c), np.asarray(off.phi.c))
    np.testing.assert_array_equal(np.asarray(on.cost_history),
                                  np.asarray(off.cost_history))

    rows = obs.ring_valid(on.telemetry, on.iterations)
    assert rows.shape == (KW["max_iters"], obs.TEL_WIDTH)
    # iter column is the committed-iteration index, in order
    np.testing.assert_array_equal(rows[:, obs_device.COL_ITER],
                                  np.arange(KW["max_iters"]))
    # cost column IS the committed cost trajectory (cost_history[0] is the
    # initial cost; record i holds the cost after iteration i)
    np.testing.assert_array_equal(
        rows[:, obs_device.COL_COST],
        np.asarray(on.cost_history)[1:KW["max_iters"] + 1])
    assert obs.ring_overflow(on.telemetry, on.iterations) == 0
    # blocked-set sweep rounds plumb out as small positive counts
    assert (rows[:, obs_device.COL_BS_ROUNDS] >= 1).all()


def test_ring_overflow_truncates_on_real_solve():
    inst = _inst()
    phi0 = gp.init_phi(inst)
    cfg = obs.TelemetryConfig(ring=8)
    res = gp.solve(inst, phi0, telemetry=cfg, **KW)
    ref = gp.solve(inst, phi0, **KW)
    # truncation must not perturb the trajectory either
    np.testing.assert_array_equal(np.asarray(res.cost_history),
                                  np.asarray(ref.cost_history))
    rows = obs.ring_valid(res.telemetry, res.iterations)
    assert rows.shape == (8, obs.TEL_WIDTH)
    np.testing.assert_array_equal(rows[:, obs_device.COL_ITER], np.arange(8))
    assert obs.ring_overflow(res.telemetry, res.iterations) == (
        KW["max_iters"] - 8)


def test_batched_parity_and_per_member_rings():
    from repro.core import batch

    insts = [_inst(seed=s, scale=1.0 + 0.5 * s) for s in range(3)]
    binst = batch.pad_instances(insts)
    off = gp.solve_batched(binst, alpha=0.1, max_iters=25, tol=1e-4)
    on = gp.solve_batched(binst, alpha=0.1, max_iters=25, tol=1e-4,
                          telemetry=True)
    np.testing.assert_array_equal(np.asarray(on.iterations),
                                  np.asarray(off.iterations))
    np.testing.assert_array_equal(np.asarray(on.phi.e), np.asarray(off.phi.e))
    np.testing.assert_array_equal(np.asarray(on.cost_history),
                                  np.asarray(off.cost_history))
    assert off.telemetry is None
    tel = np.asarray(on.telemetry)
    assert tel.shape == (3, obs.DEFAULT_TELEMETRY.ring, obs.TEL_WIDTH)
    for b in range(3):
        n = int(np.asarray(on.iterations)[b])
        rows = obs.ring_valid(tel[b], n)
        np.testing.assert_array_equal(rows[:, obs_device.COL_ITER],
                                      np.arange(min(n, tel.shape[1])))


@multi_device
def test_sharded_parity():
    from repro.core import compat

    inst = _inst()
    phi0 = gp.init_phi(inst)
    mesh = compat.make_mesh((2,), ("stage",))
    off = distributed.solve_sharded(inst, mesh, phi0=phi0, **KW)
    on = distributed.solve_sharded(inst, mesh, phi0=phi0, telemetry=True,
                                   **KW)
    assert int(on.iterations) == int(off.iterations)
    np.testing.assert_array_equal(np.asarray(on.phi.e), np.asarray(off.phi.e))
    np.testing.assert_array_equal(np.asarray(on.cost_history),
                                  np.asarray(off.cost_history))
    rows = obs.ring_valid(on.telemetry, on.iterations)
    assert rows.shape[0] == int(on.iterations)
    np.testing.assert_array_equal(rows[:, obs_device.COL_ITER],
                                  np.arange(rows.shape[0]))
    # mesh cost column matches the mesh's own committed history
    np.testing.assert_array_equal(
        rows[:, obs_device.COL_COST],
        np.asarray(on.cost_history)[1:rows.shape[0] + 1])


# ---------------------------------------------------------------------------
# spans + metrics
# ---------------------------------------------------------------------------

def _fake_clock(times):
    it = iter(times)
    last = [0.0]

    def clock():
        try:
            last[0] = next(it)
        except StopIteration:
            pass
        return last[0]
    return clock


def test_span_nesting_and_chrome_roundtrip(tmp_path):
    tr = obs.Tracer(clock=_fake_clock([0.0, 1.0, 2.0, 3.0, 4.0]))
    with tr.span("event", tid=1, member=1):
        with tr.span("converge", tid=1):
            pass
    tr.instant("rollback", tid=1)
    tr.counter("online.iters", 42.0)
    depths = {e["name"]: e["depth"] for e in tr.events if e["ph"] == "X"}
    assert depths == {"event": 0, "converge": 1}

    path = str(tmp_path / "trace.json")
    tr.export_chrome(path, tid_names={1: "member-1"})
    evs = obs.load_chrome(path)
    phs = sorted(e["ph"] for e in evs)
    assert phs == ["C", "M", "M", "X", "X", "i"]
    x = [e for e in evs if e["ph"] == "X"]
    # child closes before parent but both carry ts/dur, child inside parent
    ev = next(e for e in x if e["name"] == "event")
    cv = next(e for e in x if e["name"] == "converge")
    assert ev["ts"] <= cv["ts"]
    assert cv["ts"] + cv["dur"] <= ev["ts"] + ev["dur"] + 1e-6
    assert all("depth" not in e for e in evs)      # internal field stripped
    # valid strict JSON end to end
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_metrics_registry(tmp_path):
    m = obs.Metrics()
    m.counter("a.b")
    m.counter("a.b", 2)
    m.gauge("g", 7.5)
    for v in range(10):
        m.observe("h", float(v))
    snap = m.snapshot()
    assert snap["counters"]["a.b"] == 3
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h"]
    assert h["count"] == 10 and h["min"] == 0.0 and h["max"] == 9.0
    assert h["p50"] == 4.0
    path = str(tmp_path / "m.json")
    m.export_json(path)
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(snap))


def test_collect_compile_caches():
    out = obs.collect_compile_caches(None)
    assert "compile.mesh_chunk.entries" in out


# ---------------------------------------------------------------------------
# profiler spans (gp.solve's host phases)
# ---------------------------------------------------------------------------

# two solves: one that runs past its first 32-iteration chunk, and one
# whose tolerance every residual meets, which stops after one chunk
PROFILED = (dict(alpha=0.1, max_iters=40, patience=10**6, tol=0.0),
            dict(alpha=0.1, max_iters=40, patience=10**6, tol=1e9))


def _solve_all():
    return [gp.solve(_inst(), accel=True, **kw) for kw in PROFILED]


def _host_spans(log_dir):
    """[name, start_ns, end_ns] of the host events a profiler trace holds."""
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [[ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The two solves with the profiler off, then on; the trace's spans."""
    off = _solve_all()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(log_dir)
    try:
        on = _solve_all()
        with obs.Tracer().span("event:probe"):
            pass
    finally:
        jax.profiler.stop_trace()
    return off, on, _host_spans(log_dir)


def test_gp_solve_spans_one_per_call_and_chunk(profiled):
    off, _, spans = profiled
    chunks = [-(-r.iterations // gp._SOLVE_CHUNK) for r in off]
    assert chunks == [2, 1]
    count = {n: sum(1 for s in spans if s[0] == n) for n in (
        "gp.solve", "gp.solve.init", "gp.solve.dispatch", "gp.solve.trim",
        "event:probe")}
    assert count == {"gp.solve": 2, "gp.solve.init": 2,
                     "gp.solve.dispatch": sum(chunks), "gp.solve.trim": 2,
                     "event:probe": 1}
    # every phase lies inside a gp.solve span, and each solve holds its
    # own chunks' dispatches
    solves = sorted(s[1:] for s in spans if s[0] == "gp.solve")
    for n, t0, t1 in spans:
        if n.startswith("gp.solve."):
            assert any(a <= t0 and t1 <= b for a, b in solves), n
    for (a, b), k in zip(solves, chunks):
        assert sum(1 for n, t0, _ in spans
                   if n == "gp.solve.dispatch" and a <= t0 <= b) == k


def test_profiler_leaves_gp_solve_bit_identical(profiled):
    off, on, _ = profiled
    for r0, r1 in zip(off, on):
        assert r0.iterations == r1.iterations
        for a, b in [(r0.phi.e, r1.phi.e), (r0.phi.c, r1.phi.c),
                     (r0.cost_history, r1.cost_history),
                     (r0.residual_history, r1.residual_history)]:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# online service drain
# ---------------------------------------------------------------------------

def _fleet(n=2):
    return [_inst(seed=s, scale=1.0 + 0.5 * s) for s in range(n)]


def test_online_parity_and_segment_drain():
    insts = _fleet()
    members = events.pad_fleet(insts, spare_apps=1)
    trace = events.random_trace(members, n_events=6, seed=0)

    kw = dict(spare_apps=1, alpha=0.1, tol=1e-4, accel=True)
    off = OnlineSolver(insts, **kw)
    reps_off = off.step(trace)

    m, tr = obs.Metrics(), obs.Tracer()
    on = OnlineSolver(insts, telemetry=True, metrics=m, tracer=tr, **kw)
    reps_on = on.step(trace)

    # parity: telemetry must not change what the service serves
    assert off.event_iters == on.event_iters
    for a, b in zip(reps_off, reps_on):
        assert a.iterations == b.iterations
        assert a.status == b.status
        np.testing.assert_array_equal(a.cost, b.cost)
    assert off.iter_trace == []

    # the drained segments reproduce the served iteration counts exactly
    per_event: dict[int, int] = {}
    for rec in on.iter_trace:
        per_event[rec["event"]] = per_event.get(rec["event"], 0) + 1
    for t, rep in enumerate(reps_on):
        assert per_event.get(t, 0) == rep.iterations, (
            f"event {t}: drained {per_event.get(t, 0)} records, "
            f"served {rep.iterations} iterations")
    assert per_event.get(-1, 0) > 0          # cold start recorded
    assert all(r.wall_s > 0 for r in reps_on)

    # metrics + spans populated
    snap = m.snapshot()
    assert snap["histograms"]["online.event.iters"]["sum"] == on.event_iters
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("online.event.")) == len(trace)
    assert any(e["name"].startswith("event:") for e in tr.events)
    assert tr.to_chrome()["traceEvents"]


# ---------------------------------------------------------------------------
# report generator
# ---------------------------------------------------------------------------

def _write_trace(tmp_path, events_rows, iters_rows, metrics=None):
    prefix = str(tmp_path / "t")
    with open(prefix + ".events.jsonl", "w") as f:
        for r in events_rows:
            f.write(json.dumps(r) + "\n")
    with open(prefix + ".iters.jsonl", "w") as f:
        for r in iters_rows:
            f.write(json.dumps(r) + "\n")
    if metrics is not None:
        with open(prefix + ".metrics.json", "w") as f:
            json.dump(metrics, f)
    return prefix


def _ev(t, member, iters, **kw):
    row = {"t": t, "event": "RateScale", "member": member,
           "iterations": iters, "cost": 1.0, "residual": 0.0,
           "status": "converged", "rungs": [], "rung_iters": [],
           "wall_s": 0.1, "solved_apps": 1, "skipped_apps": 0,
           "cold_restart": False, "rolled_back": False, "shed": []}
    row.update(kw)
    return row


def _it(member, event, segment, n):
    return [{"iter": i, "cost": 1.0, "residual": 0.1, "alpha": 0.1,
             "rung": 0, "anderson": -1.0, "bs_rounds": 1, "phi_delta": 0.0,
             "member": member, "event": event, "phase": "warm",
             "segment": segment} for i in range(n)]


def test_report_build_and_check(tmp_path):
    events_rows = [_ev(0, 0, 3), _ev(1, 1, 2,
                                     rungs=["half-alpha"], rung_iters=[2])]
    iters_rows = (_it(0, -1, 0, 4) + _it(0, 0, 1, 3) + _it(1, 1, 2, 2))
    metrics = {"counters": {"online.gate.skip": 1.0}, "gauges": {},
               "histograms": {}}
    prefix = _write_trace(tmp_path, events_rows, iters_rows, metrics)

    report = obs_report.build_report(obs_report.load_trace(prefix))
    s = report["summary"]
    assert s["n_events"] == 2 and s["event_iters"] == 5
    assert s["cold_start_iters_recorded"] == 4
    assert s["rung_iters"] == {"half-alpha": 2}
    assert s["gate_skips"] == 1.0
    m0 = next(m for m in report["members"] if m["member"] == 0)
    assert m0["total_iters"] == 3
    assert [seg["recorded"] for seg in m0["segments"]] == [4, 3]

    rows = [{"bench": "online", "scenario": "fig6-trace2", "V": 11,
             "solver": "online", "iters": 5}]
    assert obs_report.check_bench(report, rows, "fig6-trace2") == []
    rows[0]["iters"] = 6
    assert len(obs_report.check_bench(report, rows, "fig6-trace2")) == 1
    assert obs_report.check_bench(report, rows, "no-such") != []


def test_report_main_end_to_end(tmp_path):
    prefix = _write_trace(tmp_path, [_ev(0, 0, 4)], _it(0, 0, 0, 4))
    out = str(tmp_path / "report.json")
    bench = str(tmp_path / "bench.json")
    with open(bench, "w") as f:
        json.dump({"rows": [{"bench": "online", "scenario": "fig6-trace1",
                             "V": 11, "solver": "online", "iters": 4}]}, f)
    rc = obs_report.main(["--trace", prefix, "--out", out,
                          "--check-bench", bench,
                          "--scenario", "fig6-trace1"])
    assert rc == 0
    assert json.load(open(out))["summary"]["event_iters"] == 4
    # mismatch -> nonzero exit
    with open(bench, "w") as f:
        json.dump({"rows": [{"bench": "online", "scenario": "fig6-trace1",
                             "V": 11, "solver": "online", "iters": 5}]}, f)
    assert obs_report.main(["--trace", prefix, "--out", out,
                            "--check-bench", bench,
                            "--scenario", "fig6-trace1"]) == 1


# ---------------------------------------------------------------------------
# bench_record contention guard
# ---------------------------------------------------------------------------

def test_bench_record_skips_on_contended_box(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "BENCH_PATH", str(tmp_path / "bench.json"))
    monkeypatch.setattr(os, "getloadavg",
                        lambda: (1e6, 0.0, 0.0), raising=False)
    monkeypatch.delenv("BENCH_FORCE_RECORD", raising=False)
    row = common.bench_record("b", scenario="s", V=1, solver="x", seconds=1.0)
    assert row["seconds"] == 1.0                    # row still returned
    assert not os.path.exists(common.BENCH_PATH)    # but nothing written


def test_bench_record_force_override(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "BENCH_PATH", str(tmp_path / "bench.json"))
    monkeypatch.setattr(os, "getloadavg",
                        lambda: (1e6, 0.0, 0.0), raising=False)
    monkeypatch.setenv("BENCH_FORCE_RECORD", "1")
    common.bench_record("b", scenario="s", V=1, solver="x", seconds=1.0)
    assert len(common.load_rows(common.BENCH_PATH)) == 1


def test_bench_record_writes_on_idle_box(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "BENCH_PATH", str(tmp_path / "bench.json"))
    monkeypatch.setattr(os, "getloadavg",
                        lambda: (0.0, 0.0, 0.0), raising=False)
    common.bench_record("b", scenario="s", V=1, solver="x", seconds=1.0)
    assert len(common.load_rows(common.BENCH_PATH)) == 1


# ---------------------------------------------------------------------------
# overhead gate: telemetry-on <= 5% per iteration on sw-queue
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_telemetry_overhead_sw_queue():
    if common._box_is_contended() is not None:
        pytest.skip("box is contended; timing comparison would be noise")
    inst = network.table_ii_instance("sw-queue", seed=0)
    phi0 = gp.init_phi(inst)
    kw = dict(alpha=0.1, max_iters=40, patience=10**6, tol=0.0)

    def timed(**extra):
        gp.solve(inst, phi0, **kw, **extra)          # compile warm-up
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res = gp.solve(inst, phi0, **kw, **extra)
            jax.block_until_ready(res.phi.e)
            best = min(best, time.perf_counter() - t0)
        return best / int(res.iterations)

    off = timed()
    on = timed(telemetry=True)
    # 5% relative budget plus an absolute floor for dispatch jitter on
    # sub-millisecond iterations
    assert on <= off * 1.05 + 1e-4, (
        f"telemetry overhead {on / off - 1:.1%} per iteration "
        f"(on={on:.6f}s off={off:.6f}s)")
