"""The batched driver's metrics: dead lanes from the program's lane-step
counter, device time per lane-step, and device idle under the driver's
host phases, read from a synthetic trace and from one request of a traced
run on a TPU v5e."""

import json
import os

import pytest

from bench import registry
from bench.lib import phases, xtrace

MS = 1e6  # ns
NEW = ("batch.dead_lane_share", "batch.device_ms_per_lane_step",
       "batch.host_idle_ms_per_family")


class _Run:
    def __init__(self, trace, records):
        self.trace, self.records = trace, records
        self.chips, self.window_s = 1, 1.0

    @property
    def iterations(self):
        return sum(r["iters"] for r in self.records)


def _read(name, run):
    return registry.metric(name).read(run)


def _trace():
    """Two family solves.  The first: init 4 ms (a 1 ms op in it), two
    chunks, each a 1 ms dispatch gap then 10 ms of device work under the
    dispatch span, a retire of 2 ms (idle) after each, and a compact of
    3 ms (a 1 ms gather in it) between them.  The second: init 2 ms (idle),
    one chunk (1 ms gap, 5 ms of work), a retire of 1 ms (idle).  An op
    outside every span (the program's check) and a host event that is no
    phase of the driver."""
    host, ops = [], []

    def solve(t0, chunks, work, init_op):
        t = t0
        host.append(["gp.solve_batched.init", t * MS, (4 if init_op else 2)
                     * MS])
        if init_op:
            ops.append(["%fusion.1", (t + 1) * MS, 1 * MS])
        t += 4 if init_op else 2
        for i in range(chunks):
            host.append(["gp.solve_batched.dispatch", t * MS,
                         (1 + work) * MS])
            ops.append(["%while.3", (t + 1) * MS, work * MS])
            t += 1 + work
            retire = 2 if init_op else 1
            host.append(["gp.solve_batched.retire", t * MS, retire * MS])
            t += retire
            if i < chunks - 1:
                host.append(["gp.solve_batched.compact", t * MS, 3 * MS])
                ops.append(["%gather.2", (t + 1) * MS, 1 * MS])
                t += 3
        host.append(["gp.solve_batched", t0 * MS, (t - t0) * MS])
        return t

    t = solve(0, 2, 10, True)
    ops.append(["%sufficiency.5", (t + 1) * MS, 4 * MS])
    host.append(["not.a.phase", (t + 1) * MS, 4 * MS])
    solve(t + 10, 1, 5, False)
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": sorted(ops, key=lambda e: e[1])}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


RECORDS = [dict(iters=600, lane_steps=1000), dict(iters=150, lane_steps=200)]


def test_readers_on_a_synthetic_trace():
    run = _Run(_trace(), RECORDS)
    got = {n: _read(n, run) for n in NEW}
    # busy: 1 + 10 + 1 + 10 + 4 + 5 = 31 ms over 1200 lane-steps;
    # idle under the phases: init 3 + 2, dispatch 3 x 1, retire 2 x 2 + 1,
    # compact 2, over two family solves
    assert got == {
        "batch.dead_lane_share": pytest.approx(100 * (1 - 750 / 1200)),
        "batch.device_ms_per_lane_step": pytest.approx(31 / 1200),
        "batch.host_idle_ms_per_family": pytest.approx(15 / 2),
    }


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_without_lane_steps_or_spans(name):
    """A program without the counter or the spans (the parent of the
    batched driver's host-side results) leaves every reader empty."""
    plain = [dict(iters=600), dict(iters=150)]
    trace = _trace()
    for plane in trace["planes"]:
        if plane["name"] == xtrace.HOST_PLANE:
            plane["lines"][0]["events"] = []
    assert _read(name, _Run(trace, plain)) is None
    assert _read(name, _Run(None, plain)) is None



def _recorded():
    """One request of ``abilene.ensemble32`` (seed 2449489742) from a
    traced run on a TPU v5e: from the start of its first family solve to
    the start of the next request's, 8.70 s.  Its record: 32 members, 871
    committed member-iterations, 1538 lane-steps, 4 family solves.  The
    device's operations are merged where they overlap or touch (each
    interval named after its longest operation), which leaves busy and
    idle time as they were; the host keeps the ``gp.solve_batched*``
    spans."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_family_excerpt.json")
    with open(path) as fh:
        return json.load(fh)


def test_readers_on_a_recorded_v5e_family_request():
    t = _recorded()
    run = _Run(t, t["records"])
    got = {n: _read(n, run) for n in NEW}
    busy_ms = 1e3 * xtrace.busy_s(t, 1)
    assert busy_ms == pytest.approx(8397.588, abs=1e-3)
    # the device idled 113.2 ms under the driver's phases of the 4 solves
    assert got == {
        "batch.dead_lane_share": pytest.approx(100 * (1 - 871 / 1538)),
        "batch.device_ms_per_lane_step": pytest.approx(busy_ms / 1538),
        "batch.host_idle_ms_per_family": pytest.approx(113.217 / 4,
                                                       abs=1e-3),
    }
    spans = {n for n, _, _ in t["planes"][1]["lines"][0]["events"]}
    assert spans == {"gp.solve_batched"} | {
        f"gp.solve_batched.{p}" for p in ("init", "dispatch", "retire",
                                          "compact")}
    assert len(phases.host_spans(t, "gp.solve_batched")) == 4
    # the chunk loop is the longest operation of the request
    ops = xtrace.device_ops(t)[0]
    assert max(ops, key=lambda e: e[2])[0].startswith("%while")
