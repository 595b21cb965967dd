"""The registry finds every declared part by name, and each part exists."""

import json
import os

import pytest

from bench import registry


def test_declaration_holds_only_the_contract_keys():
    decl = registry.benchmark()
    assert set(decl) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert decl["paths"] == ["bench"]
    assert any(m["name"] == "setup_s" for m in decl["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  registry.benchmark()["workloads"]])
def test_every_cell_finds_its_files(cell):
    w = registry.workload(cell)
    cfg = registry.config(w["config"])
    assert cfg["name"] == w["config"]
    mix = registry.traffic_mix(w["traffic"])
    assert hasattr(registry.traffic_kind(mix["kind"]), "Traffic")
    assert set(w["limits"]) == {"cost_gap", "resid_gap", "opt_gap", "feas_err"}
    # each cell reports set-up, another end-to-end metric and a per-layer one
    e2e = {m["name"] for m in registry.metrics_for(cell, trace=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.metrics_for(cell, trace=True)


@pytest.mark.parametrize("metric", [m["name"] for key in ("end_to_end",
                                                         "per_layer")
                                    for m in registry.benchmark()[key]])
def test_every_metric_has_a_reader(metric):
    assert callable(registry.metric(metric).read)


def test_config_files_are_declared_once():
    decl = registry.benchmark()
    files = [c["file"] for c in decl["configs"]]
    assert len(set(files)) == len(files)
    for c in decl["configs"]:
        path = os.path.join(registry.ROOT, c["file"])
        with open(path) as fh:
            assert json.load(fh)["name"] == c["name"]


def test_unknown_names_are_refused():
    with pytest.raises(FileNotFoundError):
        registry.config("no-such-config")
    with pytest.raises(ValueError):
        registry.workload("../BENCHMARK")
    with pytest.raises(FileNotFoundError):
        registry.workload("no-such-cell")
