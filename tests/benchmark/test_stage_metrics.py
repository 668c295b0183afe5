"""The per-stage device metrics: the two readers on hand-made traces and
stage maps, the nine metrics as files and entries, and every cell's traced
rehearsal, whose stage metrics and unstaged share have to add up to the
trace's own operation seconds."""

import functools
import json
import re
import shutil
import sys
import types

import pytest

from bench_helpers import BENCH, ROOT, rehearsal_result, run_cell

sys.path.insert(0, str(BENCH))
import trace_reduce  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
UR = ["ur-ecom-100k.train", "ur-ecom-100k-u131k.train"]
CP, ALS = "cp-ecom-100k.train", "als-ml1m.train"
DP4 = "ur-ecom-100k-u131k-dp4.train"
# the cells of each metric: the issue's table less the four-chip cell, whose
# set of metrics tests/benchmark/test_sharded_cell.py holds to sixteen (a
# file that is there: a `benchmark` PR's to edit, PERF.md section 7)
STAGE_METRICS = {
    "count_matmul_ms": UR + [CP], "densify_ms": UR + [CP], "llr_ms": UR,
    "topk_merge_ms": UR + [CP], "als_normal_eq_ms": [ALS],
    "als_solve_ms": [ALS], "als_gather_ms": [ALS], "basket_score_ms": [CP]}
ALL = {**STAGE_METRICS, "device_unstaged_pct": UR + [CP, ALS]}

MAPS = {
    "jit__cco_resident_all_tiles": {
        "stages": {"fusion.30": "cco.count_matmul", "fusion.29": "cco.densify_tile",
                   "_llr_padded.7": "cco.llr", "pad.218": "cco.llr",
                   "fusion": "cco.densify_tile"},
        "unstaged": ["while.11", "copy.20"], "instructions": 7},
    "jit__densify_global": {
        "stages": {"fusion": "cco.densify_primary", "fusion.30": "cco.count_matmul"},
        "unstaged": ["copy.20"], "instructions": 3},
    # a program the window did not run: its names say nothing here
    "jit__basket_rules_tiled": {
        "stages": {"fusion.29": "basket.densify"}, "unstaged": [],
        "instructions": 1},
}
OPS = {
    "fusion.30 (kOutput)": {"seconds": 6.0},      # the same stage in both
    "fusion.29 (kCustom)": {"seconds": 1.0},
    "_llr_padded.7 (tpu_custom_call)": {"seconds": 2.0},
    "pad.218": {"seconds": 0.5},
    "fusion (kCustom)": {"seconds": 0.25},        # two stages: never guessed
    "while.11": {"seconds": 0.125},
    "copy.20": {"seconds": 0.0625},
    "convert.3": {"seconds": 0.0625},             # of a program with no map
}
FACTS = {"jobs": 2, "reduced": {"ops": OPS, "programs": {
    "jit__cco_resident_all_tiles": {}, "jit__densify_global": {},
    "jit_convert_element_type": {}}}}


@pytest.fixture()
def readers(harness, monkeypatch):
    from predictionio_tpu.utils import device

    monkeypatch.setattr(device, "stage_maps", lambda: MAPS)
    return types.SimpleNamespace(
        ms=harness.load_module("readers", "trace_stage_ms"),
        pct=harness.load_module("readers", "trace_unstaged_pct"))


def test_stage_seconds_are_joined_by_name_with_the_kind_stripped(readers):
    read = lambda *stages: readers.ms.read({"stages": list(stages)}, FACTS)  # noqa: E731
    assert read("cco.count_matmul") == pytest.approx(3000.0)
    assert read("cco.llr") == pytest.approx(1250.0)
    assert read("cco.densify_tile", "cco.densify_primary") == pytest.approx(500.0)
    # a program outside the window lends its names to nothing
    assert read("basket.densify") is None


def test_a_name_two_programs_stage_differently_is_unstaged(readers):
    by_stage = readers.ms.seconds_by_stage(FACTS)
    assert by_stage[None] == pytest.approx(0.25 + 0.125 + 0.0625 + 0.0625)
    assert sum(by_stage.values()) == pytest.approx(
        sum(v["seconds"] for v in OPS.values()))
    assert readers.pct.read({}, FACTS) == pytest.approx(100 * 0.5 / 10.0)


def test_nothing_to_read_is_none_never_zero(readers, monkeypatch):
    from predictionio_tpu.utils import device

    args = {"stages": ["als.solve"]}
    assert readers.ms.read(args, FACTS) is None             # no such stage ran
    assert readers.ms.read({"stages": ["cco.llr"]}, {**FACTS, "jobs": 0}) is None
    # the window ran no program that keeps a map
    other = {"jobs": 1, "reduced": {"ops": OPS, "programs": {"jit_f": {}}}}
    assert readers.ms.read({"stages": ["cco.llr"]}, other) is None
    assert readers.pct.read({}, other) is None
    # the parent commit's program: it keeps no stage map at all
    monkeypatch.delattr(device, "stage_maps")
    assert readers.ms.read({"stages": ["cco.llr"]}, FACTS) is None
    assert readers.pct.read({}, FACTS) is None


@pytest.mark.parametrize("name", sorted(ALL))
def test_a_metric_is_a_file_a_reader_and_an_entry(name):
    spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    layers = {m["layer"] for m in MANIFEST["per_layer"] if m["name"] not in ALL}
    assert spec["layer"] == entry["layer"] and entry["layer"] in layers
    assert sorted(entry["workloads"]) == sorted(ALL[name])
    assert entry["source"] == spec["source"] == "device_trace"
    assert entry["moves"] == spec["moves"] == "train_events_per_s"
    for key in ("unit", "better"):
        assert entry[key] == spec[key]
    if name in STAGE_METRICS:
        assert spec["args"]["stages"] and entry["unit"] == "ms"


def test_every_declared_stage_is_some_metrics_or_the_exchanges():
    """A stage the ops declare and no metric reads would leave the stage
    metrics and the unstaged share short of the trace's seconds."""
    declared = set()
    for path in ("ops/cco.py", "ops/als.py"):
        declared |= set(re.findall(
            r'\bstage\("([\w.]+)"\)',
            (ROOT / "predictionio_tpu" / path).read_text()))
    read = {s for name in STAGE_METRICS for s in json.loads(
        (BENCH / "metrics" / f"{name}.json").read_text())["args"]["stages"]}
    assert declared - read == {"cco.exchange"}     # cco_exchange_ms, by opcode
    assert read <= declared


@functools.lru_cache(maxsize=None)
def _traced(cell: str):
    """(result line, sum of the trace's operation seconds) of one traced
    rehearsal of the cell, its trace reduced here again."""
    code, out, err = run_cell(cell, "--trace", "1", "--rehearsal", "--keep")
    assert code == 0, err[-3000:]
    work = re.search(r"kept (\S+)", err).group(1)
    try:
        reduced = trace_reduce.reduce(work + "/trace", "bench:train_job")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return rehearsal_result(out), sum(
        v["seconds"] for v in reduced["ops"].values())


@pytest.mark.parametrize("cell", ALL["device_unstaged_pct"])
def test_traced_rehearsal_reports_its_stage_metrics_and_they_add_up(cell):
    got, total = _traced(cell)
    assert got["correct"] is True
    m = {k: v["value"] for k, v in got["metrics"].items()}
    mine = {name for name, cells in ALL.items() if cell in cells}
    assert mine <= set(m), sorted(mine - set(m))
    assert not (set(ALL) - mine) & set(m)
    assert 0 <= m["device_unstaged_pct"] < 100
    jobs = got["notes"]["jobs"]
    staged = sum(m[name] for name in mine & set(STAGE_METRICS)) * jobs / 1e3
    assert staged + m["device_unstaged_pct"] / 100 * total == pytest.approx(
        total, rel=0.01)


def test_the_four_chip_cell_keeps_the_metrics_it_had():
    """Its sharded programs keep stage maps like the others
    (tests/test_stage_map.py); the entries leave the cell out."""
    assert DP4 in [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        if m["name"] in ALL:
            assert DP4 not in m["workloads"]
