"""BENCHMARK.json against the files it names. A metric's layer, unit,
source and `moves` are written twice (the contract wants them in
BENCHMARK.json, the harness's layout wants each metric to be a file of
its own): this keeps the two from drifting. Which cells report a metric
is said once, in BENCHMARK.json, so that a new cell edits no metric file.
And the seam: a configuration's architecture resolves to an entry with
the six items, and nothing else under benchmark/ knows a model's shape."""

import importlib
import json
import os
import re

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
    assert "workloads" not in spec  # BENCHMARK.json alone names the cells
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


def test_every_per_layer_metric_lists_its_cells():
    """Without the list a metric must be reported by every cell that
    reports what it moves, those a later PR adds too."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m.get("workloads") and set(m["workloads"]) <= cells, m["name"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_resolves_to_an_architecture_entry(entry):
    from benchmark import architectures

    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    arch = architectures.load(config)  # raises where an item is missing
    assert arch.__name__ == "benchmark.architectures." + config.get(
        "architecture", "llama")
    for spec in config.get("encoders", {}).values():
        assert callable(architectures.load_encoder(spec).build)


SHAPE_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim")


def _harness_sources():
    for folder, _, files in os.walk(BENCH_DIR):
        rel = os.path.relpath(folder, BENCH_DIR).split(os.sep)[0]
        if rel in ("architectures", "tests"):
            continue
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(folder, f)


def test_only_the_entries_know_a_models_shape():
    """No file of benchmark/ outside architectures/ and tests/ imports
    the program's models or reads a published shape key."""
    seen = 0
    for path in _harness_sources():
        with open(path) as fh:
            text = fh.read()
        seen += 1
        assert not re.search(
            r"generativeaiexamples_tpu(\.|\s+import\s+)models", text), path
        for key in SHAPE_KEYS:
            assert not re.search(r"\b%s\b" % key, text), (path, key)
    assert seen >= 20  # run.py, harness/ and readers/ were walked
