"""BENCHMARK.json against the contract's limits and against the files the
harness finds by name."""

import json
import re

import pytest

from bench_helpers import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = MANIFEST["workloads"]


def _all_names():
    out = [m["name"] for m in METRICS] + [c["name"] for c in CELLS]
    out += [c["name"] for c in MANIFEST["configs"]]
    out += [w["traffic"] for w in CELLS] + [w["config"] for w in CELLS]
    return sorted(set(out))


@pytest.mark.parametrize("name", _all_names())
def test_name_within_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
    assert set(metric) <= allowed


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    four = [c for c in CELLS if c["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_entry(cell, harness):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    e2e = [m["name"] for m in harness.metrics_of(MANIFEST, cell["name"],
                                                 "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(MANIFEST, cell["name"], "per_layer")


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric, harness):
    cells = metric.get("workloads", [c["name"] for c in CELLS])
    for cell in cells:
        e2e = [m["name"] for m in harness.metrics_of(MANIFEST, cell,
                                                     "end_to_end")]
        assert metric["moves"] in e2e, (metric["name"], cell)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_and_reader(metric):
    spec = json.loads((BENCH / "metrics" / f"{metric['name']}.json").read_text())
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
    if "roofline" in spec.get("args", {}):
        assert (BENCH / "roofline" / f"{spec['args']['roofline']}.py").is_file()
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_and_what_it_names(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("benchmark/configs/")
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    assert len(entry["reduced"]) <= 16
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    assert (BENCH / "data" / f"{config['data']['generator']}.py").is_file()
    assert (BENCH / "reference" / f"{config['reference']['module']}.py").is_file()
    assert (BENCH / "roofline" / f"{config['roofline']}.py").is_file()
    assert config["reference"]["limits"]
    assert any(c["config"] == entry["name"] for c in CELLS)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_traffic_file_and_driver(cell):
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    assert traffic["end_to_end"]["rate"] in [m["name"] for m in
                                             MANIFEST["end_to_end"]]


def test_peaks_name_their_source_and_refuse_the_unknown(harness):
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "Google Cloud" in peaks["source"]
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v9 imaginary")


def test_every_file_name_under_paths_is_made_of_name_characters():
    for base in MANIFEST["paths"]:
        for p in (ROOT / base).rglob("*"):
            if "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            rel = p.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
