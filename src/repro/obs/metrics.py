"""Fleet metrics registry: counters, gauges, histograms (DESIGN.md §19).

A deliberately small, dependency-free registry the online service (and any
driver) increments on the host side — solver-level facts that do not live
inside the device programs: skip-gate hits, escalation-rung climbs, LKG
rollbacks, quarantines, fault injections, compile-cache traffic.

Names are dot-separated (``online.gate.skip``, ``faults.injected.nan_carry``)
so exports group naturally.  The export is plain JSON; the span layer
(:mod:`repro.obs.spans`) mirrors counters into Chrome-trace ``"C"`` events
when a tracer is attached.
"""

from __future__ import annotations

import json
import math
from typing import Optional


class Metrics:
    """In-process metrics registry.

    ``counter`` accumulates, ``gauge`` overwrites, ``observe`` appends to a
    histogram (summarized at export: count/sum/min/max/mean/p50/p90/p99).
    """

    def __init__(self):
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, list[float]] = {}

    def counter(self, name: str, inc: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        self.histograms.setdefault(name, []).append(float(value))

    @staticmethod
    def _summary(vals: list[float]) -> dict:
        s = sorted(vals)
        n = len(s)

        def pct(p: float) -> float:
            return s[min(n - 1, int(math.ceil(p * n)) - 1)] if n else 0.0

        return {"count": n, "sum": sum(s),
                "min": s[0] if n else 0.0, "max": s[-1] if n else 0.0,
                "mean": (sum(s) / n) if n else 0.0,
                "p50": pct(0.50), "p90": pct(0.90), "p99": pct(0.99)}

    def snapshot(self) -> dict:
        """One JSON-serializable view of everything recorded so far."""
        return {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": {k: self._summary(v)
                           for k, v in self.histograms.items()},
        }

    def export_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1, sort_keys=True)


def collect_compile_caches(metrics: Optional[Metrics]) -> dict:
    """Gauge the solver's compile caches into ``metrics`` (and return them).

    Two cache layers matter for online-service latency (a miss is a full
    XLA compile in the event's critical path):

      * ``compile.mesh_chunk.{hits,misses,entries}`` — the
        ``functools.lru_cache`` on ``distributed._chunk_program`` (one
        entry per mesh x chunk-config combination);
      * ``compile.jit.<name>_entries`` — tracing-cache sizes of the jitted
        single-device chunk programs (one entry per static-arg combination;
        jit exposes no hit/miss counts, so entry growth is the signal).
    """
    out: dict[str, float] = {}
    try:
        from repro.core import distributed, gp
        info = distributed._chunk_program.cache_info()
        out["compile.mesh_chunk.hits"] = float(info.hits)
        out["compile.mesh_chunk.misses"] = float(info.misses)
        out["compile.mesh_chunk.entries"] = float(info.currsize)
        for name in ("_scan_chunk", "_scan_chunk_batched", "_jit_step"):
            fn = getattr(gp, name, None)
            if fn is not None and hasattr(fn, "_cache_size"):
                out[f"compile.jit.{name}_entries"] = float(fn._cache_size())
    except Exception:
        pass  # cache introspection is best-effort telemetry, never fatal
    if metrics is not None:
        for k, v in out.items():
            metrics.gauge(k, v)
    return out
