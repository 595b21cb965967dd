"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m bench.run ...   (the same)

Set-up builds the cell's inputs from ``--seed`` and warms every program the
window uses; then the window runs the cell's traffic for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` (profiler on during the window) its per-layer metrics.  After
the window the plain reference recomputes every answer the window produced
(``bench/lib/check.py``), which decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` when traced), ``breakdown`` when traced, and last ``checks``,
each compared number with its limit; the last lines of standard error
repeat the checks.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits with code 3; a traced run whose trace
holds no device operation prints no result and exits with code 4.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_DEVICE = 3
NO_DEVICE_OPS = 4


class NoDeviceOps(RuntimeError):
    """The traced window holds no device operation: the trace's device
    planes or op lines are not where ``bench/lib/xtrace.py`` reads them."""


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits as they happen."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> tuple[int, int]:
        out = (self.compiles, self.cache_hits)
        self.compiles = self.cache_hits = 0
        return out


class Run:
    """What one run measured; the metric readers take their numbers here."""

    def __init__(self, **kw):
        self.trace = None
        self.__dict__.update(kw)

    @property
    def attempted(self) -> int:
        return int(sum(r["members"] for r in self.records))

    @property
    def iterations(self) -> int:
        return int(sum(r["iters"] for r in self.records))


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def execute(name: str, seed: int, seconds: float, trace: bool, devices,
            t_start: float = T_START, out=None, err=None,
            compile_cache: bool = True, cfg: dict = None,
            mix: dict = None) -> dict:
    """Run cell ``name`` once on ``devices``; print and return the result.

    The tests, which run on the CPU, leave JAX's persistent compilation
    cache off (``compile_cache=False``) and may run the cell's traffic kind
    on another configuration or a smaller mix (``cfg``, ``mix``)."""
    import jax

    from bench import registry
    from bench.lib import check, xtrace

    out = out or sys.stdout
    err = err or sys.stderr
    cell = registry.workload(name)
    cfg = cfg or registry.config(cell["config"])
    mix = mix or registry.traffic_mix(cell["traffic"])
    if compile_cache:
        from repro.runtime import use_compile_cache

        print(f"compile cache: {use_compile_cache()}", file=err)
        # every program, however quick to compile, goes to the persistent
        # cache, so that only the first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    gen = registry.traffic_kind(mix["kind"]).Traffic(cfg, mix, seed)
    gen.setup()
    setup_s = time.perf_counter() - t_start
    compiles, hits = counter.take()
    print(f"set-up: {setup_s!r} s, {compiles} compilations, {hits} "
          "persistent-cache hits", file=err)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    try:
        stats = gen.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles, hits = counter.take()
    print(f"window: {stats['seconds']!r} s, {len(stats['records'])} "
          f"requests, {compiles} compilations, {hits} persistent-cache hits "
          "inside it", file=err)
    memory_peak = _memory_peak(devices)
    run = Run(cell_name=name, cell=cell, cfg=cfg, mix=mix, seed=seed,
              setup_s=setup_s, window_s=stats["seconds"],
              records=stats["records"], chips=len(devices),
              device_kind=devices[0].device_kind,
              window_compiles=compiles)
    if trace:
        run.trace = xtrace.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        if xtrace.busy_s(run.trace, len(devices)) <= 0:
            raise NoDeviceOps(
                "no device operation in the traced window; planes: "
                + ", ".join(p["name"] for p in run.trace["planes"]))

    gen.release()
    numbers, run.certified = check.compare(gen.answers(),
                                           float(cfg["cert_tol"]))
    claimed = int(sum(r["claimed"] for r in run.records))
    print(f"certified: {run.certified} of {run.attempted} by the reference, "
          f"{claimed} by the program's own check", file=err)
    ok, shown = check.judge(numbers, cell["limits"])

    metrics = {}
    for m in registry.metrics_for(name, trace):
        value = registry.metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok), "attempted": run.attempted,
              "failed": run.attempted - run.certified, "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = xtrace.busy_s(run.trace, len(devices))
        device["window_s"] = run.window_s
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(run.trace, len(devices)),
            "idle_gaps": xtrace.idle_gaps(run.trace)}
    result["checks"] = shown
    check.print_checks(shown, file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import registry

    chips = int(registry.workload(args.workload)["chips"])
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no device: {e}", file=sys.stderr)
        return NO_DEVICE
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: cell {args.workload!r} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return NO_DEVICE
    try:
        execute(args.workload, args.seed, args.seconds, bool(args.trace),
                devices[:chips])
    except NoDeviceOps as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_DEVICE_OPS
    return 0


if __name__ == "__main__":
    sys.exit(main())
