"""The GP solve's phases read from a profiler trace: host spans, kernel
calls by call site, the stepsize ladder, and idle time under host spans."""

import json
import os

import pytest

from bench import registry
from bench.lib import phases

MS = 1e6  # ns

NEW = ("engine.ladder_ms_per_step", "engine.steps_per_iter",
       "kernels.lu_factor_ms_per_step", "kernels.chain_solve_ms_per_step",
       "solver.solves_per_cert", "solver.host_idle_ms_per_solve")


STEP = 27  # ms of one scan step in the synthetic traces


def _step(t, ladder=(10, 12)):
    """One scan step from ``t`` ms: the step's own factor and two sweeps,
    the ladder's factor and chain solve (``ladder`` ms a call, the busiest
    call sites, with a fusion overlapping the chain solve), the Anderson
    candidate's pair."""
    lu, cs = ladder
    ops = [["%lu_factor.17", t, 1], ["%chain_solve.24", t + 1, 1],
           ["%chain_solve.25", t + 2, 1], ["%lu_factor.18", t + 3, lu],
           ["%chain_solve.26", t + 13, cs], ["%fusion.3", t + 18, 2],
           ["%lu_factor.19", t + 25, 1], ["%chain_solve.27", t + 26, 1]]
    return [[n, s * MS, d * MS] for n, s, d in ops]


def _trace(ladder=(10, 12), drop=()):
    """Two solves.  The first dispatches two chunks of one step each (the
    scan loop ``%while`` spans each chunk's step); the second one chunk.
    Idle gaps: 2 ms in the first init (which holds two small ops), 5 ms in
    the second, 1 ms in each dispatch, 2 ms in each trim.  Besides: eager
    kernel calls outside the loop (a factor at 0 ms in the first init, a
    chain solve after the first solve, as the program's check makes), a
    ``%chain_solve_bsr`` and a ``%lu_solve`` (other kernels), and a
    ``%fused_chain_solve`` (an op named after its jitted wrapper).  The
    operations named in ``drop`` are left out."""
    ops = [["%lu_factor.1", 0.0, 2 * MS], ["%lu_solve.1", 150 * MS, 1 * MS],
           ["%chain_solve.1", 152 * MS, 3 * MS]]
    host = []

    def solve(t0, chunks):
        # init 5 ms; each chunk: its dispatch (1 ms, idle), then its step
        # under the scan loop's %while
        host.append(["gp.solve", t0 * MS, (8 + (STEP + 1) * chunks) * MS])
        host.append(["gp.solve.init", t0 * MS, 5 * MS])
        t = t0 + 5
        for _ in range(chunks):
            host.append(["gp.solve.dispatch", t * MS, 1 * MS])
            ops.append(["%while.46", (t + 1) * MS, STEP * MS])
            ops.extend(_step(t + 1, ladder))
            t += STEP + 1
        # trim: 2 ms idle, then the trim's own small op
        host.append(["gp.solve.trim", t * MS, 3 * MS])
        ops.append(["%slice.1", (t + 2) * MS, 1 * MS])

    ops.append(["%batched_factor", 4.0 * MS, 1 * MS])
    solve(0, 2)
    ops.append(["%chain_solve_bsr.1", 70 * MS, 30 * MS])
    ops.append(["%fused_chain_solve.7", 100 * MS, 30 * MS])
    host.append(["not.a.phase", 105 * MS, 10 * MS])
    solve(200, 1)
    ops = [op for op in ops if op[0] not in drop]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": []},
            {"name": "XLA Ops", "events": sorted(ops, key=lambda e: e[1])}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


class _Run:
    def __init__(self, trace, iterations=2, certified=1):
        self.trace, self.iterations, self.certified = (trace, iterations,
                                                       certified)
        self.chips, self.window_s = 1, 1.0


def _read(name, run):
    return registry.metric(name).read(run)


def test_kernel_calls_inside_the_scan_loop_by_call_site():
    t = _trace()
    lu = phases.kernel_sites(t, phases.LU_FACTOR)
    # the eager %lu_factor.1 and %chain_solve.1 lie outside every %while
    assert set(lu) == {"%lu_factor.17", "%lu_factor.18", "%lu_factor.19"}
    assert len(lu["%lu_factor.18"]) == 3
    cs = phases.kernel_sites(t, phases.CHAIN_SOLVE)
    assert set(cs) == {"%chain_solve.24", "%chain_solve.25",
                       "%chain_solve.26", "%chain_solve.27"}
    # every in-loop call: 3 steps x (1 + 10 + 1) and 3 x (1 + 1 + 12 + 1)
    assert phases.kernel_s(t, phases.LU_FACTOR) == pytest.approx(0.036)
    assert phases.kernel_s(t, phases.CHAIN_SOLVE) == pytest.approx(0.045)


def test_the_ladder_is_the_call_site_that_stands_out_once_per_step():
    t = _trace()
    assert {c[0] for c in phases.ladder_calls(t, phases.LU_FACTOR)} == {
        "%lu_factor.18"}
    assert phases.executed_steps(t) == 3
    # union of 10 ms + 12 ms per step; the overlapping fusion is not the
    # ladder's kernel and the enclosing %while is not counted
    assert phases.ladder_s(t) == pytest.approx(0.066)


# (trace, ladder sites named: lu_factor, chain_solve)
NO_LADDER = {
    # a ladder of 4 rungs: its calls are 4x the others', not 8x
    "few-rungs": (dict(ladder=(4, 4)), (False, False)),
    # the ladder reuses the step's factors: of the factor's sites none
    # stands out, while the chain solve's still does
    "factors-reused": (dict(drop=("%lu_factor.18",)), (False, True)),
    # one site left of each kernel: nothing to tell it from
    "one-site": (dict(drop=("%lu_factor.17", "%lu_factor.19",
                            "%chain_solve.24", "%chain_solve.25",
                            "%chain_solve.27")), (False, False)),
}


@pytest.mark.parametrize("case", NO_LADDER)
def test_no_ladder_is_named_where_no_call_site_stands_out(case):
    kw, (lu, cs) = NO_LADDER[case]
    t = _trace(**kw)
    assert bool(phases.ladder_calls(t, phases.LU_FACTOR)) == lu
    assert bool(phases.ladder_calls(t, phases.CHAIN_SOLVE)) == cs
    got = {n: _read(n, _Run(t)) for n in NEW[:4]}
    if cs:
        assert got["engine.steps_per_iter"] == pytest.approx(1.5)
        # the chain solve's 12 ms calls alone
        assert got["engine.ladder_ms_per_step"] == pytest.approx(12.0)
    else:
        assert got == dict.fromkeys(NEW[:4])


def test_host_spans_are_read_by_their_plain_names():
    t = _trace()
    assert len(phases.host_spans(t, "gp.solve")) == 2
    assert len(phases.host_spans(t, "gp.solve.dispatch")) == 3
    assert phases.host_spans(t, "gp.solve")[1] == (200 * MS, 236 * MS)
    assert phases.host_spans(t, "gp.solve.none") == []


def test_idle_under_host_spans_counts_nested_time_once():
    t = _trace()
    idle = {n: phases.idle_within_s(t, phases.host_spans(t, n))
            for n in phases.HOST_IDLE_PHASES}
    # the first init holds the eager factor 0..2 ms and %batched_factor
    # 4..5, so 2..4 is idle; the second holds no operation
    assert idle["gp.solve.init"] == pytest.approx(0.007)
    assert idle["gp.solve.dispatch"] == pytest.approx(0.003)
    assert idle["gp.solve.trim"] == pytest.approx(0.004)
    # the %while spans its step's gaps: a chunk's run is never idle
    assert phases.idle_within_s(t, [(6 * MS, (6 + STEP) * MS)]) == 0.0
    assert phases.idle_within_s(t, [(5000 * MS, 5001 * MS)]) == (
        pytest.approx(0.001))


def test_readers_on_a_synthetic_trace():
    got = {n: _read(n, _Run(_trace(), iterations=2, certified=1))
           for n in NEW}
    assert got == {
        "engine.ladder_ms_per_step": pytest.approx(22.0),
        "engine.steps_per_iter": pytest.approx(1.5),
        "kernels.lu_factor_ms_per_step": pytest.approx(12.0),
        "kernels.chain_solve_ms_per_step": pytest.approx(15.0),
        "solver.solves_per_cert": pytest.approx(2.0),
        "solver.host_idle_ms_per_solve": pytest.approx(14 / 2),
    }


def _parent_style():
    """A trace of a program without the kernel names and host spans: its
    kernel operations are named after the jitted wrappers."""
    ops = [["%while.46", 0.0, 30 * MS],
           ["%vmap_jit_batched_factor__.8", 1 * MS, 8 * MS],
           ["%vmap_jit_fused_chain_solve__.8", 10 * MS, 10 * MS],
           ["%fused_chain_solve.7", 21 * MS, 1 * MS],
           ["%batched_factor", 23 * MS, 1 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["$gp.py:374 solve", 0.0,
                                            30 * MS]]}]}]}


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_where_the_trace_lacks_their_names(name):
    assert _read(name, _Run(_parent_style())) is None
    assert _read(name, _Run(None)) is None
    empty = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": []}]}]}
    assert _read(name, _Run(empty)) is None


def _recorded():
    """153 ms of a `--trace 1` run of sw-queue.certify on one TPU v5e: the
    trim of one solve, the program's check, the next solve's init, its
    first chunk's dispatch and the first two steps of that chunk.  The
    device plane's ``XLA Modules`` and ``XLA Ops`` lines (all their events
    that start in the slice, op names cut to ``%name``) and, of the host's
    Python line, the ``gp.solve*`` spans and JAX's dispatch events.  The
    build that recorded it also had a ``gp.solve.sync`` span around the
    read of the stop latch, since taken out; no reader reads it."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_phases_excerpt.json")
    with open(path) as fh:
        return json.load(fh)


def test_recorded_v5e_trace_names_the_kernels_and_host_phases():
    t = _recorded()
    # the scan step's three factor and four chain-solve call sites; the
    # eager ones of the program's check and of the solve's init (sites .1
    # to .3) lie outside the scan loop
    assert set(phases.kernel_sites(t, phases.LU_FACTOR)) == {
        "%lu_factor.17", "%lu_factor.18", "%lu_factor.19"}
    assert set(phases.kernel_sites(t, phases.CHAIN_SOLVE)) == {
        "%chain_solve.24", "%chain_solve.25", "%chain_solve.26",
        "%chain_solve.27"}
    assert phases.executed_steps(t) == 2
    for kernel, site in ((phases.LU_FACTOR, "%lu_factor.18"),
                         (phases.CHAIN_SOLVE, "%chain_solve.26")):
        ladder = phases.ladder_calls(t, kernel)
        assert {c[0] for c in ladder} == {site}
        per_call = sum(d for _, _, d in ladder) / len(ladder)
        others = [d for name, calls in phases.kernel_sites(t, kernel).items()
                  if name != site for _, _, d in calls]
        # twelve rungs: each ladder call does about 12 times the work
        assert per_call > 11 * max(others)
    assert [len(phases.host_spans(t, n)) for n in (
        "gp.solve", "gp.solve.init", "gp.solve.dispatch",
        "gp.solve.trim")] == [1, 1, 1, 1]


def test_recorded_v5e_trace_reduces_to_phase_times():
    t = _recorded()
    ladder = phases.ladder_s(t)
    assert 0.02 < ladder / 2 < 0.08          # per step
    init = phases.host_spans(t, "gp.solve.init")
    idle = phases.idle_within_s(t, init)
    assert 0 < idle <= (init[0][1] - init[0][0]) / 1e9
    run = _Run(t, iterations=2, certified=1)
    assert _read("engine.steps_per_iter", run) == pytest.approx(1.0)
    assert _read("solver.solves_per_cert", run) == pytest.approx(1.0)
    kernels = (_read("kernels.lu_factor_ms_per_step", run)
               + _read("kernels.chain_solve_ms_per_step", run))
    assert _read("engine.ladder_ms_per_step", run) < kernels
