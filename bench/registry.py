"""Find the benchmark's parts by name.

Every configuration, cell, traffic mix, traffic kind and metric is a file of
its own, named after it:

* ``BENCHMARK.json`` at the root of the checkout: the declared cells and
  metrics;
* ``bench/configs/<config>.json``: a deployment;
* ``bench/workloads/<cell>.json``: a cell (configuration, traffic mix,
  chips, and the limits of its correctness check);
* ``bench/traffic/<mix>.json``: a traffic mix, whose ``kind`` names the
  generator ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: the reader of one metric.

Adding a part means adding its file and, for a cell or a metric, its entry
in ``BENCHMARK.json``; no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a valid {kind} name: {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file named {name!r} ({path})")
    return path


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    """The declaration, ``BENCHMARK.json``."""
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _json(_path("configs", name, ".json"))


def workload(name: str) -> dict:
    """A cell, checked against its entry in ``BENCHMARK.json``."""
    cell = _json(_path("workloads", name, ".json"))
    entry = {w["name"]: w for w in benchmark()["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"cell {name!r} is not declared in BENCHMARK.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise ValueError(f"cell {name!r}: {key} is {cell[key]!r} in its "
                             f"file but {entry[key]!r} in BENCHMARK.json")
    return cell


def traffic_mix(name: str) -> dict:
    return _json(_path("traffic", name, ".json"))


def _module(kind: str, name: str):
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str):
    """The generator module of a traffic kind; it defines ``Traffic``."""
    return _module("traffic", kind)


def metric(name: str):
    """The reader module of a metric; it defines ``read(run)``."""
    return _module("metrics", name)


def metrics_for(cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones without
    tracing, the per-layer ones with it (each limited to the cells its
    ``workloads`` key lists, where it has one)."""
    decl = benchmark()["per_layer" if trace else "end_to_end"]
    return [m for m in decl if cell in m.get("workloads", [cell])]
