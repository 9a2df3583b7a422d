"""BENCHMARK.json keeps to the contract's shape, and every file it names is there."""

import os
import re

import pytest
from benchmark_testlib import REPO, real_manifest

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_units_and_keys_use_only_what_the_contract_allows():
    m = real_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = []
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for e in m[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and len(e["why"]) <= 200 and "\n" not in e["why"]
            names.append((group, e["name"]))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in {"host_clock", "device_trace"}
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert e["source"] in SOURCES and "\n" not in e["layer"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        names.append(("metric", e["name"]))
    assert len(names) == len(set(names))
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert "setup_s" in [e["name"] for e in m["end_to_end"]]
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4) and all(w["chips"] in (1, 4) for w in m["workloads"])
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(m["workloads"])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    m = real_manifest()
    cells = {w["name"] for w in m["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]}
    for e in m["per_layer"]:
        assert e["moves"] in reports and e["moves"] != "setup_s"
        for cell in e.get("workloads", cells):
            assert cell in cells and cell in reports[e["moves"]], (e["name"], cell)
    for cell in cells:  # setup_s, one more end-to-end metric, one per-layer metric
        assert sum(cell in r for r in reports.values()) >= 2
        assert any(cell in e.get("workloads", cells) for e in m["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in real_manifest()["workloads"]])
def test_every_file_a_cell_names_exists(cell):
    m = real_manifest()
    c = harness.Cell(REPO, m, cell)
    harness.find_file(REPO, m, "drivers", c.workload["driver"], ".py")
    harness.find_file(REPO, m, "reference", c.config["reference"], ".py")
    assert set(c.workload["limits"]) and "reckoned_bytes" in c.workload
    entry = {e["name"]: e for e in m["configs"]}[c.entry["config"]]
    assert entry["file"].startswith(tuple(m["paths"])) and entry["source"] == c.config["source"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    for name in c.metric_names("per_layer"):
        spec = harness.load_json(REPO, m, "metrics", name)
        assert hasattr(harness.load_module(REPO, m, "readers", spec["reader"]), "read")
        assert spec["layer"] == {e["name"]: e for e in m["per_layer"]}[name]["layer"]
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in m["paths"])
