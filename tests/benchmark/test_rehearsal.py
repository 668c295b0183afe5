"""The CPU rehearsal of each cell's command, end to end, in a process of its
own; what the command does without a chip; and the faults a train cell can
have, planted under the timed path.  (A cell of a third engine, added with
files and entries alone, its faults and its control: test_third_engine.py.)"""

import json
import shutil
import sys

import pytest

from bench_helpers import (BENCH, ROOT, assert_caught, last_line,
                           rehearsal_result, run_cell, run_faulty)

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(cell):
    code, out, err = run_cell(cell, "--trace", "0", "--rehearsal")
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True, err[-3000:]
    assert got["device"]["platform"] == "cpu"      # and labelled: no result
    assert got["attempted"] >= 1 and got["failed"] == 0
    assert set(got["metrics"]) == {"train_events_per_s", "setup_s"}
    assert got["metrics"]["train_events_per_s"]["unit"] == "events/s"
    # the rate is all the window's work over all the window's time
    n = got["notes"]
    assert got["metrics"]["train_events_per_s"]["value"] == pytest.approx(
        n["jobs"] * n["events_per_job"] / n["window_s"])
    assert n["window_s"] >= 1.0 and n["jobs"] == got["attempted"]
    assert list(got)[-1] == "checks"               # the numbers compared, last
    names = [c["name"] for c in got["checks"]]
    assert "compiles_in_window" in names and "model_not_the_last_jobs" in names
    for c in got["checks"]:
        assert f"check {c['name']}:" in err         # and on standard error


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_traced(cell):
    code, out, err = run_cell(cell, "--trace", "1", "--rehearsal")
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True
    per_layer = {m["name"] for m in MANIFEST["per_layer"]
                 if cell in m.get("workloads", [cell])}
    assert set(got["metrics"]) <= per_layer
    # no peaks on a CPU: the shares of a roofline and of a peak are left
    # out, never reported as 0
    assert "train_mfu_pct" not in got["metrics"]
    assert "llr_roofline" not in got["metrics"]
    assert {"host_lead_in_s", "host_tail_s",
            "device_idle_pct.train"} <= set(got["metrics"])
    assert 0 < got["device"]["busy_s"] <= got["device"]["window_s"]
    assert 0 < len(got["breakdown"]["device_ops"]) <= 10
    assert len(got["breakdown"]["idle_gaps"]) <= 10


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    code, out, err = run_cell(CELLS[0], "--trace", "0")
    assert code != 0
    assert out.strip() == ""
    assert "not a TPU" in err


def test_unknown_workload_fails_and_prints_no_result():
    code, out, err = run_cell("nothing.such", "--rehearsal")
    assert code != 0 and out.strip() == ""


def test_outside_the_repo_the_command_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: non-zero, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for base in MANIFEST["paths"]:
        shutil.copytree(ROOT / base, tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    code, out, err = run_cell(CELLS[0], "--rehearsal",
                              script=tmp_path / "benchmark" / "run.py",
                              cwd=tmp_path)
    assert code != 0 and out.strip() == ""
    assert "predictionio_tpu" in err


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_under_the_timed_path_reads_not_correct(cell, fault, harness):
    config = harness.find_cell(MANIFEST, cell)[1]
    assert_caught(run_faulty(fault, cell), fault, config)


def test_a_cell_is_added_with_files_and_entries_alone(tmp_path):
    """A later PR's cell: a configuration, a traffic mix and a per-layer
    metric as NEW files, and new entries in BENCHMARK.json; no file that is
    there is edited."""
    for base in MANIFEST["paths"][:1]:
        shutil.copytree(ROOT / base, tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "predictionio_tpu").symlink_to(ROOT / "predictionio_tpu")
    bench = tmp_path / "benchmark"
    config = json.loads((bench / "configs" / "als-ml1m.json").read_text())
    config["name"] = "als-throwaway"
    config["rehearsal"]["data"]["params"].update(n_users=60, n_items=50,
                                                 n_ratings=900)
    (bench / "configs" / "als-throwaway.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "train-jobs.json").read_text())
    traffic["insert_chunk"] = 100
    (bench / "traffic" / "train-jobs-small-chunks.json").write_text(
        json.dumps(traffic))
    metric = json.loads((bench / "metrics" / "als_program_ms.json").read_text())
    metric["name"] = "als_program_ms.throwaway"
    (bench / "metrics" / "als_program_ms.throwaway.json").write_text(
        json.dumps(metric))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = json.dumps(manifest)
    manifest["configs"].append({
        "name": "als-throwaway", "source": "a test",
        "file": "benchmark/configs/als-throwaway.json", "reduced": [],
        "why": "a test"})
    manifest["workloads"].append({
        "name": "als-throwaway.train", "config": "als-throwaway",
        "traffic": "train-jobs-small-chunks", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "als_program_ms.throwaway", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ALS op",
        "moves": "train_events_per_s", "workloads": ["als-throwaway.train"]})
    for m in manifest["per_layer"]:
        if m["moves"] == "train_events_per_s" and m["layer"] in (
                "device", "whole train step"):
            m["workloads"].append("als-throwaway.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert before == json.dumps(json.loads(
        (ROOT / "BENCHMARK.json").read_text()))
    code, out, err = run_cell("als-throwaway.train", "--trace", "1",
                              "--rehearsal", script=bench / "run.py",
                              cwd=tmp_path)
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True
    assert got["metrics"]["als_program_ms.throwaway"]["value"] > 0
    assert "device_idle_pct.train" in got["metrics"]
    assert last_line(out).startswith("REHEARSAL ")
