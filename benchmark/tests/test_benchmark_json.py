"""BENCHMARK.json against the files it names. A metric's layer, unit,
source, `moves` and cells are written twice (the contract wants them in
BENCHMARK.json, the harness's layout wants each metric to be a file of
its own): this keeps the two from drifting."""

import importlib
import json
import os

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _metric_file(name):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("entry", METRICS, ids=lambda m: m["name"])
def test_metric_entry_matches_its_file_and_reader(entry):
    spec = _metric_file(entry["name"])
    for key in ("unit", "better", "source"):
        assert spec[key] == entry[key], key
    if "moves" in entry:
        assert spec["layer"] == entry["layer"]
        assert spec["moves"] == entry["moves"]
    cells = {w["name"] for w in BENCH["workloads"]}
    listed = set(spec.get("workloads", cells)) & cells
    assert listed == set(entry.get("workloads", cells))
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in BENCH["workloads"]:
        def reported(group):
            return [m for m in group
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        assert len(reported(BENCH["end_to_end"])) >= 2, cell["name"]
        per_layer = reported(BENCH["per_layer"])
        assert per_layer, cell["name"]
        names = {m["name"] for m in reported(BENCH["end_to_end"])}
        for m in per_layer:  # a layer metric moves a metric of this cell
            assert m["moves"] in names, (cell["name"], m["name"])


def test_files_named_by_the_benchmark_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, configs[cell["config"]]["file"]))
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= 1
    assert all(0 < m["bound"] <= 0.1 for m in BENCH["end_to_end"])
