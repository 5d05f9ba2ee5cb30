"""BENCHMARK.json against the benchmark contract: names, units, files found
by name, and metrics that every cell can report."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = MANIFEST["workloads"]
CONFIGS = MANIFEST["configs"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_a_full_check_fits_its_time():
    n = 24                      # later PRs may grow the cells to 24
    runs = 2 + 14 * n
    assert runs * (MANIFEST["run_seconds"] + 60) + n * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", METRICS + CELLS + CONFIGS,
                         ids=lambda e: e["name"])
def test_names_use_the_allowed_characters(entry):
    assert NAME.match(entry["name"])


def test_names_are_unique():
    for group in (METRICS, CELLS, CONFIGS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        allowed |= {"layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert "\n" not in metric["layer"] and 0 < len(metric["layer"]) <= 200
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_resolve_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 0 < len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    config = {c["name"]: c for c in CONFIGS}[cell["config"]]
    assert (ROOT / config["file"]).is_file()
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    from bench import workload
    c = workload.load_cell(cell["name"], MANIFEST)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_metric_is_reported_in_each_listed_cell(metric):
    from bench import workload
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in metric["workloads"]:
        c = workload.load_cell(cell, MANIFEST)
        assert metric["moves"] in {m["name"] for m in c.end_to_end}


def test_layer_names_are_spelled_alike():
    # one layer, one spelling: no two names that differ only in case or
    # spacing
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert len({" ".join(x.lower().split()) for x in layers}) == len(layers)


@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("bench/")
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert set(config["reduced"]) == set(body["reduced"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(c["config"] == config["name"] for c in CELLS)
    assert 0 < len(config["source"]) <= 200


def test_config_files_are_distinct():
    files = [c["file"] for c in CONFIGS]
    assert len(files) == len(set(files))


def test_at_most_half_the_cells_take_four_chips():
    four = sum(1 for c in CELLS if c["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_each_config_and_traffic_pair_appears_once():
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))


def test_manifest_is_small():
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_unknown_cell_is_refused():
    from bench import workload
    with pytest.raises(KeyError):
        workload.load_cell("no-such-cell", MANIFEST)
