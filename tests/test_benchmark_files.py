"""BENCHMARK.json against the files it names, by the harness's own lookup.

A cell whose configuration, traffic or metric file is mistyped is found
only when the chip run fails to start (`config_not_added`); this finds it
here. Nothing of the benchmark runs: `perfbench/harness.py load_cell` reads
files, and a configuration's `expect` is held to its base YAML as
`build_server` holds it before it applies `overrides`.
"""

import json
import os
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    DECLARED = json.load(_f)


@pytest.fixture(scope="module")
def harness():
    """perfbench/harness.py as run.py imports it: by plain name, with
    perfbench/ first on the path."""
    sys.path.insert(0, BENCH)
    try:
        import harness
        yield harness
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("cell", [w["name"] for w in DECLARED["workloads"]])
def test_cell_resolves_through_load_cell(harness, cell):
    loaded = harness.load_cell(cell)
    assert loaded["config_file"]["name"] == loaded["config"]
    assert loaded["traffic_file"]["kinds"]
    # what the harness reads of the control without asking
    assert set(loaded["config_file"]["control"]) >= {
        "overrides", "counter_dtype", "hll_precision"}
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded["per_layer"]
    declared = {c["name"]: c for c in DECLARED["configs"]}[loaded["config"]]
    with open(os.path.join(ROOT, declared["file"])) as f:
        assert json.load(f) == loaded["config_file"]


@pytest.mark.parametrize("config", [c["name"] for c in DECLARED["configs"]])
def test_configuration_expect_agrees_with_its_base_yaml(config):
    entry = {c["name"]: c for c in DECLARED["configs"]}[config]
    assert entry["file"] == f"perfbench/configs/{config}.json"
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfgf = json.load(f)
    assert cfgf["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfgf["reduced"] == entry["reduced"]
    with open(os.path.join(ROOT, cfgf["base"])) as f:
        raw = yaml.safe_load(f)
    differs = {k: (raw.get(k), want) for k, want in cfgf["expect"].items()
               if raw.get(k) != want}
    assert not differs, f"{cfgf['base']} against {entry['file']}: {differs}"
    assert os.path.exists(os.path.join(ROOT, cfgf["reference"]))


@pytest.mark.parametrize("metric",
                         [m["name"] for m in DECLARED["per_layer"]])
def test_per_layer_metric_has_its_file(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".json")
    assert os.path.exists(path), path
    with open(path) as f:
        spec = json.load(f)
    if spec["kind"] == "python":
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", spec["module"] + ".py"))
    entry = {m["name"]: m for m in DECLARED["per_layer"]}[metric]
    cells = {w["name"] for w in DECLARED["workloads"]}
    assert set(entry.get("workloads", [])) <= cells


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _entries():
    return [(section, e) for section in KEYS for e in DECLARED[section]]


@pytest.mark.parametrize(
    "section,entry", _entries(),
    ids=[f"{s}:{e['name']}" for s, e in _entries()])
def test_entry_keeps_the_form_the_driver_checks(section, entry):
    """The rules a BENCHMARK.json is refused by before any run: a PR was
    refused for a `why` of 207 characters that nothing here had counted."""
    import re
    optional = {"workloads"} if section in ("end_to_end", "per_layer") else set()
    assert KEYS[section] <= set(entry) <= KEYS[section] | optional
    names = [entry["name"]] + [entry[k] for k in ("config", "traffic")
                               if k in entry] + entry.get("reduced", [])
    for name in names:
        assert re.fullmatch(NAME, name), name
    for key in ("why", "layer", "source"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200, (key, len(text))
            assert text.isprintable() and text.isascii(), (key, text)
    if "unit" in entry:
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
        assert entry["better"] in ("lower", "higher")
    if "file" in entry:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", entry["file"])
        assert entry["file"].startswith(tuple(
            p + "/" for p in DECLARED["paths"]))
    assert len(entry.get("reduced", [])) <= 16


def test_benchmark_file_as_a_whole():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    for section in KEYS:
        names = [e["name"] for e in DECLARED[section]]
        assert len(names) == len(set(names)), section
    metrics = [m["name"] for m in DECLARED["end_to_end"] + DECLARED["per_layer"]]
    assert len(metrics) == len(set(metrics))
    cells = DECLARED["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in cells} == {c["name"] for c in DECLARED["configs"]}
    assert all(w["chips"] in (1, 4) for w in cells)
    end_to_end = {m["name"] for m in DECLARED["end_to_end"]}
    assert all(m["moves"] in end_to_end for m in DECLARED["per_layer"])
    # 2 + 14 runs a cell of run_seconds + 60 s, 2 x 90 s a cell, 1200 s spare
    n = len(cells)
    assert ((2 + 14 * n) * (DECLARED["run_seconds"] + 60)
            + 2 * 90 * n + 1200) <= 43200
