"""The reduction from a profiler trace to busy time, kernel time and gaps."""

import pytest

from bench.lib import xtrace

MS = 1e6  # ns


def _trace(ops, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["step", 0.0, 100 * MS]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host)}]}]}


OPS = [["lu", 0.0, 10 * MS], ["lu", 5 * MS, 10 * MS],      # overlap 0..15
       ["chain", 20 * MS, 5 * MS],                          # 20..25
       ["lu", 60 * MS, 10 * MS]]                             # 60..70


def test_busy_is_the_union_of_operation_intervals():
    assert xtrace.union_ns(OPS) == pytest.approx(30 * MS)
    assert xtrace.busy_s(_trace(OPS), chips=1) == pytest.approx(0.030)


def test_busy_averages_over_the_chips_used():
    t = _trace(OPS)
    t["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["lu", 0.0, 10 * MS]]}]})
    assert xtrace.busy_s(t, chips=2) == pytest.approx(0.020)
    assert xtrace.busy_s(t, chips=1) == pytest.approx(0.030)


def test_kernel_time_sums_its_named_operations():
    assert xtrace.kernel_time_s(_trace(OPS), ["lu"], 1) == (
        pytest.approx(0.030), 3)
    assert xtrace.kernel_time_s(_trace(OPS), ["none"], 1) == (0.0, 0)


def test_top_ops_and_idle_gaps_name_what_took_the_time():
    host = [["solve", 0.0, 100 * MS], ["eager_init", 26 * MS, 30 * MS]]
    t = _trace(OPS, host)
    assert xtrace.top_ops(t, 1)[0] == ["lu", pytest.approx(0.030)]
    gaps = xtrace.idle_gaps(t)
    assert gaps[0] == ["eager_init", pytest.approx(0.035)]
    assert gaps[1][1] == pytest.approx(0.005)


def _recorded():
    """0.3 s of a `--trace 1` run of sw-queue.certify on one TPU v5e: the
    device plane's ``XLA Modules`` and ``XLA Ops`` lines and the host's
    Python thread, first 300 events each, op names already cut to
    ``%name`` as ``xtrace.load`` cuts them."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_trace_excerpt.json")
    with open(path) as fh:
        return json.load(fh)


def test_recorded_v5e_trace_has_the_planes_and_lines_read():
    trace = _recorded()
    ops = xtrace.device_ops(trace)
    assert len(ops) == 1 and len(ops[0]) == 300
    assert 0 < xtrace.busy_s(trace, chips=1) < 0.3
    names = {n for n, _, _ in ops[0]}
    assert all(n.startswith("%") and " " not in n for n in names)


def test_recorded_v5e_trace_reduces_to_top_ops_and_gaps():
    trace = _recorded()
    top = xtrace.top_ops(trace, chips=1)
    assert top and all(n.startswith("%") and t > 0 for n, t in top)
    assert top == sorted(top, key=lambda x: -x[1])
    busy = xtrace.busy_s(trace, chips=1)
    ops = xtrace.device_ops(trace)[0]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    gaps = xtrace.idle_gaps(trace)
    assert gaps and sum(g for _, g in gaps) <= span - busy + 1e-12
