"""From a profiler trace to device busy time, kernel times and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists, ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``; the reductions below work on that
form, so a small recorded trace can be kept as JSON and tested.

* Device planes are those named ``/device:TPU:<n>``; their operations are
  the events of the line ``XLA Ops`` (names as on a v5e trace: the HLO
  instruction, ``%name = shape op(...)``, shortened here to ``%name``).
* Busy time is the union of those operations' intervals on one chip,
  averaged over the chips the run used.
* A kernel's time is the sum of the durations of the operations that carry
  its name.
* An idle gap is a stretch between operations on the first chip; it is
  named after the innermost host event of the Python thread (the host
  line named ``python3`` on a v5e trace) that covers at least half of it (else the one that overlaps it most), which
  says what the host was doing meanwhile.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
HOST_LINE_PREFIX = "python"


def load(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` in plain form."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        host = plane.name == HOST_PLANE
        if not (host or plane.name.startswith(DEVICE_PREFIX)):
            continue
        lines = []
        for line in plane.lines:
            if host and not line.name.startswith(HOST_LINE_PREFIX):
                continue
            lines.append({"name": line.name, "events": [
                [ev.name.split(" = ", 1)[0], float(ev.start_ns),
                 float(ev.duration_ns)] for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace: dict) -> list[list]:
    """Per device plane (in plane order), its operations [name, start, dur]."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        out.append(ops)
    return out


def union_ns(intervals) -> float:
    """Total length covered by [start, start + dur) intervals."""
    total, end = 0.0, float("-inf")
    for s, d in sorted((s, d) for _, s, d in intervals):
        e = s + d
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_s(trace: dict, chips: int) -> float:
    """Seconds in which an operation ran, averaged over ``chips`` chips."""
    per_chip = [union_ns(ops) for ops in device_ops(trace)[:chips]]
    return sum(per_chip) / 1e9 / max(len(per_chip), 1) if per_chip else 0.0


def kernel_time_s(trace: dict, names, chips: int) -> tuple[float, int]:
    """(seconds, calls) of the operations named in ``names`` (exact
    names), summed over ``chips`` chips."""
    names = set(names)
    t, n = 0.0, 0
    for ops in device_ops(trace)[:chips]:
        for name, _, dur in ops:
            if name in names:
                t += dur
                n += 1
    return t / 1e9, n


def top_ops(trace: dict, chips: int, k: int = 10) -> list[list]:
    """The ``k`` operation names that took most device time, [name, s]."""
    acc = defaultdict(float)
    for ops in device_ops(trace)[:chips]:
        for name, _, dur in ops:
            acc[name] += dur / 1e9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` longest idle stretches of the first chip, [host event, s]."""
    ops = device_ops(trace)
    if not ops or not ops[0]:
        return []
    spans = sorted((s, s + d) for _, s, d in ops[0])
    gaps, end = [], spans[0][1]
    for s, e in spans[1:]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    host = [ev for plane in trace["planes"] if plane["name"] == HOST_PLANE
            for line in plane["lines"] for ev in line["events"]]
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        over = [(min(g1, s + d) - max(g0, s), d, name) for name, s, d in host]
        over = [o for o in over if o[0] > 0]
        half = [o for o in over if o[0] >= (g1 - g0) / 2]
        if half:
            best = min(half, key=lambda o: o[1])[2]
        else:
            best = max(over)[2] if over else "idle"
        out.append([best, (g1 - g0) / 1e9])
    return out
