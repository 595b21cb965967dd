"""Shared benchmark utilities (timing, CSV output, GPResult rows)."""

from __future__ import annotations

import json
import os
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(_REPO_ROOT, "results")

# Machine-readable perf trajectory: every benchmark driver appends rows here
# so future PRs can diff against the committed numbers and catch regressions.
BENCH_PATH = os.path.join(_REPO_ROOT, "BENCH_gp.json")

# bench_record refuses to overwrite committed rows from a loaded box: the
# 1.5x bench_check gate assumes rows were timed near-idle, and one contended
# rewrite poisons the committed trajectory for every later diff.  The guard
# triggers when the 1-min loadavg exceeds this multiple of the core count.
LOADAVG_CONTENTION_RATIO = 1.5


def _box_is_contended() -> float | None:
    """1-min loadavg when the box is too busy to trust timings, else None.

    ``BENCH_FORCE_RECORD=1`` disables the guard (dedicated runners whose
    steady-state load is legitimately high, or deliberate re-baselining).
    Platforms without ``os.getloadavg`` (Windows) never report contention.
    """
    if os.environ.get("BENCH_FORCE_RECORD"):
        return None
    try:
        load1 = os.getloadavg()[0]
    except (AttributeError, OSError):
        return None
    cores = os.cpu_count() or 1
    if load1 > LOADAVG_CONTENTION_RATIO * cores:
        return load1
    return None


def _default_backend() -> str:
    """The JAX backend rows are stamped with (lazy import — keep the module
    importable without initializing a device).  A backend that fails to
    start raises: a row is never stamped with a device it did not run on."""
    import jax
    return jax.default_backend()


def bench_record(bench: str, *, scenario: str, V: int, solver: str,
                 seconds: float, iters: int | None = None,
                 backend: str | None = None, **extra) -> dict:
    """Append one perf row to the top-level ``BENCH_gp.json``.

    Rows are keyed by (bench, scenario, V, solver, backend): re-running a
    driver replaces its previous rows instead of growing the file, so the
    committed trajectory stays one row per measurement point.  ``backend``
    defaults to ``jax.default_backend()`` — timings measured on different
    backends are different measurement points (the per-backend AUTO
    dispatch crossover in ``traffic._derive_auto_min_v`` depends on this),
    and rows written before the key existed count as ``"cpu"`` everywhere
    rows are consumed.

    ``seconds`` is wall clock for the measured unit; when ``iters`` (total
    committed GP iterations) is given a derived ``s_per_iter`` is stored.
    Extra keyword fields (e.g. ``speedup``, ``n``) are stored verbatim.

    On a contended box (1-min loadavg > ``LOADAVG_CONTENTION_RATIO`` x
    cores) the row is returned but NOT written — contended timings would
    replace trustworthy committed rows and trip the bench_check gate on the
    next idle run.  Set ``BENCH_FORCE_RECORD=1`` to record anyway.
    """
    row = {"bench": bench, "scenario": scenario, "V": int(V),
           "solver": solver,
           "backend": backend if backend is not None else _default_backend(),
           "seconds": round(float(seconds), 6)}
    if iters is not None:
        row["iters"] = int(iters)
        row["s_per_iter"] = round(float(seconds) / max(int(iters), 1), 8)
    row.update(extra)
    load1 = _box_is_contended()
    if load1 is not None:
        print(f"bench_record: SKIP {bench}/{scenario}/{solver} — box is "
              f"contended (loadavg {load1:.1f} > "
              f"{LOADAVG_CONTENTION_RATIO:.1f}x {os.cpu_count()} cores); "
              f"set BENCH_FORCE_RECORD=1 to record anyway")
        return row
    rows = []
    if os.path.exists(BENCH_PATH):
        try:
            with open(BENCH_PATH) as f:
                rows = json.load(f)["rows"]
        except (json.JSONDecodeError, KeyError):
            rows = []
    key = _row_key(row)
    rows = [r for r in rows if _row_key(r) != key]
    rows.append(row)
    with open(BENCH_PATH, "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    return row


# Regression-gate policy (bench_check): a fresh row fails when its metric
# exceeds MAX_SLOWDOWN x the committed baseline, but only when the pair sits
# above the noise floor — sub-floor timings on small shared CI boxes are
# dominated by dispatch jitter, not by the kernels under test.
MAX_SLOWDOWN = 1.5
NOISE_FLOOR_S = 2e-4

# Iteration-count gate: total committed GP iterations are deterministic
# (no timing noise), so the budget is much tighter than the wall-clock one.
# A pair participates only when BOTH rows carry ``iters`` — rows that
# gained or lost the field between runs are schema drift, not a regression.
MAX_ITERS_REGRESSION = 1.2
_ITERS_NOISE_FLOOR = 8    # don't flag e.g. 5 -> 7 on trivially-small solves


def _row_key(row: dict) -> tuple:
    # rows written before the backend key existed were all CPU measurements
    return (row.get("bench"), row.get("scenario"), row.get("V"),
            row.get("solver"), row.get("backend", "cpu"))


def _pair_metrics(row: dict, ref: dict):
    """The SAME metric field read from both rows of a baseline/fresh pair:
    ``s_per_iter`` when both carry it, else ``seconds`` when both carry
    that — (None, None) when the schemas disagree, so a row that gained or
    lost ``iters`` between runs is skipped rather than compared
    apples-to-oranges."""
    for field in ("s_per_iter", "seconds"):
        if field in row and field in ref:
            return row[field], ref[field]
    return None, None


def load_rows(path: str) -> list[dict]:
    """Rows of a ``BENCH_gp.json``-shaped file ([] if missing/corrupt)."""
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            return json.load(f)["rows"]
    except (json.JSONDecodeError, KeyError, TypeError):
        return []


def bench_check(baseline_rows: list[dict], fresh_rows: list[dict] | None = None,
                *, max_slowdown: float = MAX_SLOWDOWN,
                noise_floor_s: float = NOISE_FLOOR_S,
                max_iters_regression: float = MAX_ITERS_REGRESSION
                ) -> list[str]:
    """Diff freshly generated bench rows against a committed baseline.

    Rows pair up by the ``bench_record`` key (bench, scenario, V, solver);
    fresh rows with no committed counterpart (new measurements) and
    baseline rows not regenerated this run are both ignored.  Two gates run
    per pair:

      * **time** — fails when the fresh metric (``s_per_iter`` when both
        rows carry it, else ``seconds`` — always the same field on both
        sides, see :func:`_pair_metrics`) exceeds ``max_slowdown`` x
        max(baseline metric, noise floor) AND the fresh metric itself sits
        above the noise floor;
      * **iters** — when both rows carry ``iters``, fails when the fresh
        total iteration count exceeds ``max_iters_regression`` x the
        committed one (iteration counts are deterministic, so the budget is
        tight; counts at or below ``_ITERS_NOISE_FLOOR`` are exempt).

    Returns human-readable failure lines (empty = gate passes) — the CI
    ``bench-smoke`` job runs this via ``python -m benchmarks.common
    --check <committed-baseline>`` after ``kernel_bench --smoke``
    regenerates the kernel rows.
    """
    if fresh_rows is None:
        fresh_rows = load_rows(BENCH_PATH)
    base = {_row_key(r): r for r in baseline_rows}
    failures = []
    for row in fresh_rows:
        ref = base.get(_row_key(row))
        if ref is None:
            continue
        key = "/".join(str(k) for k in _row_key(row))
        m_new, m_old = _pair_metrics(row, ref)
        if m_new is not None and m_old is not None and m_new > noise_floor_s:
            limit = max_slowdown * max(float(m_old), noise_floor_s)
            if float(m_new) > limit:
                failures.append(
                    f"{key}: {float(m_new):.6f}s vs committed "
                    f"{float(m_old):.6f}s (> {max_slowdown:.2f}x)")
        if "iters" in row and "iters" in ref:
            it_new, it_old = int(row["iters"]), int(ref["iters"])
            if (it_new > _ITERS_NOISE_FLOOR
                    and it_new > max_iters_regression * max(it_old, 1)):
                failures.append(
                    f"{key}: {it_new} iters vs committed {it_old} "
                    f"(> {max_iters_regression:.2f}x)")
    return failures


def delta_table(baseline_rows: list[dict], fresh_rows: list[dict]
                ) -> list[str]:
    """One line per compared pair showing BOTH the time and iters deltas.

    Columns: row key, s_per_iter (or seconds) fresh/committed with the
    ratio, and — when both rows carry ``iters`` — the iteration counts with
    their ratio.  Purely informational (the pass/fail decision is
    :func:`bench_check`'s); ``--check`` prints it so a CI log shows where
    the time went even when the gate is green.
    """
    base = {_row_key(r): r for r in baseline_rows}
    lines = []
    for row in fresh_rows:
        ref = base.get(_row_key(row))
        if ref is None:
            continue
        key = "/".join(str(k) for k in _row_key(row))
        m_new, m_old = _pair_metrics(row, ref)
        if m_new is not None and m_old is not None:
            ratio = float(m_new) / max(float(m_old), 1e-12)
            time_col = f"{float(m_new):.6f}s/{float(m_old):.6f}s ({ratio:.2f}x)"
        else:
            time_col = "-"
        if "iters" in row and "iters" in ref:
            it_new, it_old = int(row["iters"]), int(ref["iters"])
            iters_col = f"{it_new}/{it_old} ({it_new / max(it_old, 1):.2f}x)"
        else:
            iters_col = "-"
        lines.append(f"{key}: time {time_col} | iters {iters_col}")
    return lines


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """The harness contract: ``name,us_per_call,derived`` CSV lines."""
    print(f"{name},{us_per_call:.1f},{derived}")


def save_json(rel_path: str, obj) -> str:
    path = os.path.join(RESULTS_DIR, rel_path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0

    @property
    def us(self) -> float:
        return self.seconds * 1e6


def result_row(res) -> dict:
    """JSON-serializable summary of a ``gp.GPResult``.

    Histories are dense jnp device arrays; trim to the committed prefix and
    convert via numpy so json doesn't choke on them."""
    trimmed = res.trim()
    hist = np.asarray(trimmed.cost_history, dtype=float)
    return {
        "final_cost": trimmed.final_cost,
        "iterations": int(trimmed.iterations),
        "initial_cost": float(hist[0]),
        "cost_history": hist.tolist(),
    }


def speedup_report(serial_s: float, batched_s: float, n: int) -> str:
    return (f"serial:{serial_s:.2f}s|batched:{batched_s:.2f}s|"
            f"speedup:{serial_s / max(batched_s, 1e-9):.2f}x|n:{n}")


def _check_main(argv: list[str]) -> int:
    """``python -m benchmarks.common --check <baseline.json>`` — the CI gate."""
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.common")
    ap.add_argument("--check", required=True,
                    help="committed BENCH_gp.json snapshot to diff against")
    ap.add_argument("--fresh", default=BENCH_PATH,
                    help="freshly generated rows (default: BENCH_gp.json)")
    ap.add_argument("--max-slowdown", type=float, default=MAX_SLOWDOWN)
    ap.add_argument("--max-iters-regression", type=float,
                    default=MAX_ITERS_REGRESSION)
    args = ap.parse_args(argv)
    baseline = load_rows(args.check)
    fresh = load_rows(args.fresh)
    if not baseline or not fresh:
        print(f"bench_check: nothing to compare "
              f"({len(baseline)} baseline rows, {len(fresh)} fresh rows)")
        return 0
    failures = bench_check(baseline, fresh, max_slowdown=args.max_slowdown,
                           max_iters_regression=args.max_iters_regression)
    table = delta_table(baseline, fresh)
    compared = len({_row_key(r) for r in fresh}
                   & {_row_key(r) for r in baseline})
    print(f"bench_check: {compared} compared row(s) "
          f"(fresh/committed, ratio):")
    for line in table:
        print(f"  {line}")
    if failures:
        print(f"bench_check: {len(failures)} regression(s):")
        for line in failures:
            print(f"  REGRESSION {line}")
        return 1
    print(f"bench_check: OK (time within {args.max_slowdown:.2f}x, iters "
          f"within {args.max_iters_regression:.2f}x of committed)")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_check_main(sys.argv[1:]))
