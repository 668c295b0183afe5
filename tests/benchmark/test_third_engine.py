"""A cell of a third engine, added with new files and new entries alone.

The harness's own code names no engine: what it knows of one is in the files
a configuration names.  The proof is a throw-away cell of the Complementary
Purchase template (neither UR nor the recommendation template), whose files
lie under `data/third_engine/`: a generator with `times`, a numpy reference of
pair rules with `check`, `alter` and `readings`, a roofline and a
configuration.  They are copied, as NEW files, into a copy of `benchmark/` and
`tests/benchmark/`; then the cell is rehearsed, each fault is planted, the
control is printed, and every file that was there is compared with the
repo's.  The real files of a basket configuration are a later PR's.
"""

import hashlib
import json
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_helpers import (ROOT, assert_caught, clean_env, rehearsal_result,
                           run_cell, run_control, run_faulty)

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ROOT / "tests" / "benchmark" / "data" / "third_engine"
CONFIG = json.loads((NEW / "configs" / "cp-throwaway.json").read_text())
CELL = "cp-throwaway.train"


def _files(root) -> dict:
    """relative path -> sha256, of the files under the benchmark's paths."""
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for base in MANIFEST["paths"] for p in sorted((root / base).rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The repo's benchmark, copied; the third engine's files and entries
    added.  (`predictionio_tpu` is the repo's own: the program under test.)"""
    root = tmp_path_factory.mktemp("third_engine")
    was_there = _files(ROOT)
    for base in MANIFEST["paths"]:
        shutil.copytree(ROOT / base, root / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "predictionio_tpu").symlink_to(ROOT / "predictionio_tpu")
    added = []
    for src in sorted(NEW.rglob("*")):
        if src.is_file() and "__pycache__" not in src.parts:
            dst = root / "benchmark" / src.relative_to(NEW)
            assert not dst.exists(), f"{dst} is there already: an edit"
            shutil.copy(src, dst)
            added.append(str(dst.relative_to(root)))
    # the same configuration over a generator that leaves the times out
    config = json.loads(json.dumps(CONFIG))
    config["name"] = "cp-throwaway-untimed"
    config["data"]["params"]["times"] = False
    untimed = root / "benchmark" / "configs" / "cp-throwaway-untimed.json"
    untimed.write_text(json.dumps(config))
    added.append(str(untimed.relative_to(root)))
    manifest = json.loads(json.dumps(MANIFEST))
    for name in ("cp-throwaway", "cp-throwaway-untimed"):
        manifest["configs"].append({
            "name": name, "source": "a test", "reduced": [], "why": "a test",
            "file": f"benchmark/configs/{name}.json"})
        manifest["workloads"].append({
            "name": f"{name}.train", "config": name, "traffic": "train-jobs",
            "chips": 1, "why": "a test"})
        # the whole step's share of the peak, by the new roofline: the one
        # per-layer metric an engine without spans can list (not read here)
        next(m for m in manifest["per_layer"] if m["name"] == "train_mfu_pct")[
            "workloads"].append(f"{name}.train")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return SimpleNamespace(root=root, added=sorted(added),
                           was_there=was_there)


def test_the_cell_rehearses_correct_on_the_generators_baskets(tree):
    root = tree.root
    code, out, err = run_cell(CELL, "--trace", "0", "--rehearsal",
                              script=root / "benchmark" / "run.py", cwd=root)
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True, got["checks"]
    checks = {c["name"]: c for c in got["checks"]}
    assert set(CONFIG["reference"]["limits"]) <= set(checks)
    # the lifts the program persisted imply the generator's number of
    # baskets, 60 shoppers x 4 visits: the times arrived
    assert checks["baskets_gap"]["value"] == 0
    p = CONFIG["data"]["params"]
    assert got["notes"]["events_per_job"] == (
        p["n_users"] * p["sessions"] * p["basket_size"])
    assert set(got["metrics"]) == {"train_events_per_s", "setup_s"}


def test_without_the_times_each_shopper_is_one_basket(tree):
    """The control of the case above: the same cell over events that all
    carry one time (as every event did before `times`) is not correct."""
    root = tree.root
    code, out, err = run_cell("cp-throwaway-untimed.train", "--trace", "0",
                              "--rehearsal",
                              script=root / "benchmark" / "run.py", cwd=root)
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is False
    gap = next(c for c in got["checks"] if c["name"] == "baskets_gap")
    assert gap["value"] > 0, got["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_under_the_third_engine_reads_not_correct(tree, fault):
    assert_caught(run_faulty(fault, CELL, root=tree.root), fault, CONFIG)


def test_control_prints_the_four_readings_of_the_third_engine(tree):
    lines = run_control(CELL, "2147483777,4000000007", root=tree.root)
    assert [ln["seed"] for ln in lines] == [2147483777, 4000000007]
    limits = CONFIG["reference"]["limits"]
    for ln in lines:
        assert set(ln) == {"workload", "seed", "reference", "control_bfloat16",
                           "fault_half_left_out", "fault_answer_altered"}
        assert all(abs(v) < 1e-12 for v in ln["reference"].values())
        for reading in ("control_bfloat16", "fault_half_left_out",
                        "fault_answer_altered"):
            assert any(ln[reading][k] > limits[k] for k in limits), ln


def test_the_tests_that_go_by_the_manifests_cells_take_the_new_cell(tree):
    """`test_wire_events.py` and `test_manifest.py` have a case for every cell
    of `BENCHMARK.json`.  In the copied tree, whose manifest holds the third
    engine's cells (a generator with `times`), they pass as they stand: a PR
    that adds such a cell need not edit them.  (`test_rehearsal.py` and
    `test_program_spans.py` go by the cells too, with `--trace 1`, which
    waits for spans in the program's basket rules.)"""
    env = {k: v for k, v in clean_env().items() if not k.startswith("PYTEST_")}
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark/test_wire_events.py",
         "tests/benchmark/test_manifest.py", "-v", "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=str(tree.root), env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    cases = [ln for ln in p.stdout.splitlines() if " PASSED" in ln]
    for cell in [w["name"] for w in MANIFEST["workloads"]] + [
            CELL, "cp-throwaway-untimed.train"]:
        assert any(f"one_by_one[{cell}] PASSED" in ln for ln in cases), cell
        assert any(f"test_cell_entry[{cell}] PASSED" in ln for ln in cases)
    assert str(tree.root) in p.stdout      # the copy's tests, not the repo's


def test_no_file_that_was_there_differs(tree):
    """Run last of this file: after every run above, each file of the
    repo's `paths` (hashed as it was copied) is in the copy with the repo's
    bytes, and what the copy holds besides is the list of files added."""
    now = _files(tree.root)
    assert {k: now.get(k) for k in tree.was_there} == tree.was_there
    assert sorted(set(now) - set(tree.was_there)) == tree.added
    assert len(tree.added) == 5
