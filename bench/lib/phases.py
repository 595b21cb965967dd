"""The GP solve's phases in a profiler trace, in the plain form of
``bench/lib/xtrace.py``.

* Host phases: ``gp.solve`` and its phases ``gp.solve.init``,
  ``gp.solve.dispatch`` and ``gp.solve.trim`` are events of the host's
  Python line under those plain names, on the same clock as the device's
  operations.
* Kernels: every call of a Pallas kernel is a device operation named after
  the kernel, ``%lu_factor`` or ``%chain_solve`` and a suffix ``.N`` that
  tells one call site of the compiled program from another.  Only the calls
  made inside the scan loop (a ``%while`` operation, which spans its body)
  are scan-step work; the eager calls of a solve's initial carry and of the
  program's check lie outside it and are not counted here.
* The stepsize ladder is read from its kernel calls, since the trace keeps
  no name of the step's phases: the ladder evaluates its candidates in one
  call of each kernel, vmapped over the rungs, a batch the size of the
  ladder times that of any other call site of the step.  So of a kernel's
  in-loop call sites the busiest is the ladder's, but only where each of
  its calls takes at least ``LADDER_RATIO`` times as long as a call at any
  other site; otherwise (a ladder of a few rungs, factors reused, a single
  site) no site is named the ladder's and the readers give nothing.  Each
  executed scan step calls it once, frozen steps after the solve's stop
  latch included.

Everything is read on the first chip.
"""

from __future__ import annotations

import bisect
import re

from bench.lib import xtrace

SOLVE = "gp.solve"
HOST_IDLE_PHASES = ("gp.solve.init", "gp.solve.dispatch", "gp.solve.trim")
LU_FACTOR = "lu_factor"
CHAIN_SOLVE = "chain_solve"
# a 12-rung ladder's calls read 12x the other sites' on a v5e (19.3 and
# 25.3 ms against 1.6 and 2.1 ms a call)
LADDER_RATIO = 8.0

_LOOP = re.compile(r"%while(\.\d+)?")


def host_spans(trace: dict, name: str) -> list[tuple[float, float]]:
    """(start, end) in ns of the host events named ``name``, in order."""
    return sorted((s, s + d) for plane in trace["planes"]
                  if plane["name"] == xtrace.HOST_PLANE
                  for line in plane["lines"]
                  for n, s, d in line["events"] if n == name)


def _ops(trace: dict) -> list[list]:
    ops = xtrace.device_ops(trace)
    return ops[0] if ops else []


def _merged(intervals) -> list[list[float]]:
    """Sorted, disjoint [start, end] covering the (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def kernel_sites(trace: dict, kernel: str) -> dict[str, list[list]]:
    """The calls of ``kernel`` inside the scan loop, [name, start, dur], by
    call site."""
    pat = re.compile(rf"%{re.escape(kernel)}(\.\d+)?")
    ops = _ops(trace)
    loops = _merged((s, s + d) for n, s, d in ops if _LOOP.fullmatch(n))
    starts = [a for a, _ in loops]
    sites: dict[str, list[list]] = {}
    for op in ops:
        if not pat.fullmatch(op[0]):
            continue
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] + op[2] <= loops[i][1]:
            sites.setdefault(op[0], []).append(op)
    return sites


def kernel_s(trace: dict, kernel: str) -> float:
    """Device seconds of ``kernel``'s calls at every in-loop call site."""
    return sum(d for calls in kernel_sites(trace, kernel).values()
               for _, _, d in calls) / 1e9


def ladder_calls(trace: dict, kernel: str) -> list[list]:
    """The calls of the ladder's call site of ``kernel``, or [] where no
    in-loop site stands out by ``LADDER_RATIO``."""
    sites = sorted(kernel_sites(trace, kernel).values(),
                   key=lambda calls: -sum(d for _, _, d in calls))
    if len(sites) < 2:
        return []
    per_call = [sum(d for _, _, d in calls) / len(calls) for calls in sites]
    if per_call[0] < LADDER_RATIO * max(per_call[1:]):
        return []
    return sites[0]


def executed_steps(trace: dict) -> int:
    """Scan steps the device ran: calls of the ladder's chain solve."""
    return len(ladder_calls(trace, CHAIN_SOLVE))


def ladder_s(trace: dict) -> float:
    """Device seconds of the ladder's kernel calls (union of intervals)."""
    return xtrace.union_ns(ladder_calls(trace, LU_FACTOR)
                           + ladder_calls(trace, CHAIN_SOLVE)) / 1e9


def idle_within_s(trace: dict, spans) -> float:
    """Seconds of the (start, end) ``spans`` in which no operation ran on
    the first chip; nested operations count once."""
    busy = _merged((s, s + d) for _, s, d in _ops(trace))
    starts = [b[0] for b in busy]
    idle = 0.0
    for a, b in spans:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(busy) and busy[i][0] < b:
            covered += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
            i += 1
        idle += (b - a) - covered
    return idle / 1e9
