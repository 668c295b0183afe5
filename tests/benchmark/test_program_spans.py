"""The per-layer metrics that read the program's own spans: the reader on
hand-made runs, the nine metrics' files, and a traced rehearsal of each
cell that reports them all."""

import json
import sys

import pytest

from bench_helpers import BENCH, ROOT, rehearsal_result, run_cell

if str(BENCH) not in sys.path:       # as run.py does before it calls a reader
    sys.path.insert(0, str(BENCH))

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [
    m["name"] for m in MANIFEST["per_layer"]
    if json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())
    ["reader"] == "journal_span"]
NINE = {"store_read_s", "host_layout_s", "h2d_s", "h2d_mb_per_job",
        "dispatch_s", "device_wait_s", "model_build_s", "persist_s",
        "train_unattributed_s"}


def _span(id_, parent, name, start, dur, **attrs):
    s = {"id": id_, "parent": parent, "name": name, "start": start,
         "duration_s": dur, "end": start + dur}
    if attrs:
        s["attrs"] = attrs
    return s


def _run(t0=100.0, scale=1.0, error=False, root="train"):
    """train 10 s: engine_train 8 s (read 1, algo 6 holding h2d 0.5 + 0.25
    and a wait of 4), save 1.5; 0.5 s of the root and 1 s of engine_train
    are no span's."""
    k = scale
    run = [
        _span(1, None, root, t0, 10 * k),
        _span(2, 1, "engine_train", t0, 8 * k),
        _span(3, 2, "read_training", t0, 1 * k, events=1000),
        _span(4, 2, "algo_train", t0 + 2 * k, 6 * k),
        _span(5, 4, "h2d", t0 + 2 * k, 0.5 * k, bytes=4_000_000),
        _span(6, 4, "h2d", t0 + 2.5 * k, 0.25 * k, bytes=2_000_000),
        _span(7, 4, "device_wait", t0 + 3 * k, 4 * k, bytes=100),
        # a compile reported after the fact, overlapping the first h2d:
        # the part both cover is taken off the parent once
        _span(9, 4, "compile", t0 + 2.25 * k, 0.5 * k),
        _span(8, 1, "save_models", t0 + 8 * k, 1.5 * k),
    ]
    if error:
        run[0]["error"] = True
    return run


@pytest.fixture()
def reader(harness, monkeypatch):
    from predictionio_tpu.obs import spans

    runs = []
    monkeypatch.setattr(spans, "recent_runs", lambda: list(runs))
    mod = harness.load_module("readers", "journal_span")

    def read(args, jobs, *these):
        runs[:] = these
        return mod.read(args, {"jobs": jobs})

    return read


def test_duration_is_summed_over_a_runs_spans_and_averaged_over_jobs(reader):
    args = {"spans": ["h2d"], "take": "duration"}
    assert reader(args, 1, _run()) == pytest.approx(0.75)
    assert reader(args, 2, _run(), _run(scale=2)) == pytest.approx(1.125)
    both = {"spans": ["read_training", "save_models"], "take": "duration"}
    assert reader(both, 1, _run()) == pytest.approx(2.5)


def test_self_time_is_duration_less_what_the_children_cover(reader):
    # algo_train 6 s; h2d 2.0..2.5 and 2.5..2.75, compile 2.25..2.75
    # (overlapping both), wait 3..7: covered 0.75 + 4
    assert reader({"spans": ["algo_train"], "take": "self"}, 1,
                  _run()) == pytest.approx(6 - 4.75)
    assert reader({"spans": ["train", "engine_train"], "take": "self"}, 1,
                  _run()) == pytest.approx(0.5 + 1.0)
    # a leaf's self time is its duration
    assert reader({"spans": ["save_models"], "take": "self"}, 1,
                  _run()) == pytest.approx(1.5)


def test_attr_is_summed_and_scaled(reader):
    args = {"spans": ["h2d"], "take": "attr:bytes", "scale": 1e-6}
    assert reader(args, 1, _run()) == pytest.approx(6.0)
    # a span without the attribute adds nothing; none with it: nothing read
    assert reader({"spans": ["save_models"], "take": "attr:bytes"}, 1,
                  _run()) is None


def test_only_the_last_jobs_sound_train_runs_are_taken(reader):
    args = {"spans": ["read_training"], "take": "duration"}
    warm_up, first, second = _run(scale=5), _run(), _run(scale=3)
    assert reader(args, 2, warm_up, first, second) == pytest.approx(2.0)
    # a failed job's journal and an eval's are no job of the window
    assert reader(args, 2, first, _run(scale=7, error=True),
                  _run(scale=9, root="eval"), second) == pytest.approx(2.0)


def test_nothing_to_read_is_none_never_zero(reader, harness, monkeypatch):
    args = {"spans": ["read_training"], "take": "duration"}
    assert reader(args, 1) is None                          # no run
    assert reader(args, 0, _run()) is None                  # no job
    assert reader(args, 1, _run(error=True)) is None        # none sound
    assert reader({"spans": ["no_such_span"], "take": "duration"}, 1,
                  _run()) is None
    # a program from before it kept its runs (this PR's parent)
    from predictionio_tpu.obs import spans

    monkeypatch.delattr(spans, "recent_runs")
    mod = harness.load_module("readers", "journal_span")
    assert mod.read(args, {"jobs": 1}) is None


def test_the_nine_metrics_are_entries_and_files():
    assert set(SPAN_METRICS) == NINE
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        if m["name"] in NINE:
            assert m["workloads"] == cells and m["better"] == "lower"
            assert m["moves"] == "train_events_per_s"
            assert m["source"] == ("program_counter" if m["unit"] == "MB"
                                   else "program_span")


@pytest.mark.parametrize("name", sorted(NINE))
def test_metric_file_names_spans_the_program_opens(name):
    """Each span a metric reads is opened somewhere in the program, by that
    name, through the one span function."""
    spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    assert spec["args"]["take"].split(":")[0] in ("duration", "self", "attr")
    sources = "".join(p.read_text() for p in
                      (ROOT / "predictionio_tpu").rglob("*.py"))
    for span in spec["args"]["spans"]:
        assert f'span("{span}"' in sources, span


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_traced_rehearsal_reports_all_nine(cell):
    code, out, err = run_cell(cell, "--trace", "1", "--rehearsal",
                              seed=2147484001)
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True
    m = {k: v["value"] for k, v in got["metrics"].items()}
    assert NINE <= set(m)
    assert all(m[k] > 0 for k in NINE - {"train_unattributed_s"})
    # the same journal the driver's notes read
    spans = got["notes"]["journal_spans_mean_s"]
    assert m["persist_s"] == pytest.approx(spans["save_models"], abs=2e-4)
    # every second of a job belongs to one of the spans' metrics
    parts = NINE - {"h2d_mb_per_job"}
    assert sum(m[k] for k in parts) == pytest.approx(spans["train"],
                                                     rel=0.02, abs=2e-4)
    assert m["train_unattributed_s"] < 0.1 * spans["train"]
