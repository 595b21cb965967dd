"""Benchmark harness: one module per paper table/figure.

  fig5_scenarios   — Fig. 5 normalized cost across Table II scenarios
  fig6_congestion  — Fig. 6 cost vs input rate (Abilene)
  fig7_packetsize  — Fig. 7 hop counts vs packet size
  gp_scaling       — Section IV complexity (per-iteration time scaling)
  kernel_bench     — Pallas kernels vs jnp oracles (interpret mode)
  roofline         — deliverable (g): per (arch x shape) roofline terms from
                     the dry-run artifacts (run launch/dryrun.py first)
  perf_compare     — before/after roofline terms per dry-run hillclimb pair

Prints ``name,us_per_call,derived`` CSV.  Use --only <name> for one section.
"""

from __future__ import annotations

import argparse
import sys
import traceback

SECTIONS = ["fig5_scenarios", "fig6_congestion", "fig7_packetsize",
            "gp_scaling", "kernel_bench", "roofline", "perf_compare"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    names = [args.only] if args.only else SECTIONS
    failed = []
    for name in names:
        print(f"# === {name} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
