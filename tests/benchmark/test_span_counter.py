"""The reader of a count the program writes on its spans, on hand-made
runs; and the two metrics of the user-blocked CCO program as entries and
files."""

import json

import pytest

from bench_helpers import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ur-ecom-100k-u131k.train"
ARGS = json.loads((BENCH / "metrics" / "cco_block_steps_per_job.json"
                   ).read_text())["args"]


def _span(id_, parent, name, **attrs):
    s = {"id": id_, "parent": parent, "name": name, "start": 0.0,
         "duration_s": 1.0, "end": 1.0}
    if attrs:
        s["attrs"] = attrs
    return s


def _run(steps=3200, root="train", error=False, program="_cco_chunked_all_tiles"):
    run = [_span(1, None, root),
           _span(2, 1, "dispatch", program=program, tiles=25, block_steps=steps),
           _span(3, 1, "dispatch", program=program, tiles=25, block_steps=steps),
           _span(4, 1, "dispatch", program="_densify_global"),
           _span(5, 1, "layout", user_blocks=128)]
    if error:
        run[0]["error"] = True
    return run


@pytest.fixture()
def reader(harness, monkeypatch):
    from predictionio_tpu.obs import spans

    runs = []
    monkeypatch.setattr(spans, "recent_runs", lambda: list(runs))
    mod = harness.load_module("readers", "span_counter")

    def read(args, jobs, *these):
        runs[:] = these
        return mod.read(args, {"jobs": jobs})

    return read


def test_the_count_is_summed_over_a_jobs_matching_spans(reader):
    assert reader(ARGS, 1, _run()) == 6400.0
    assert reader(ARGS, 2, _run(), _run(steps=1600)) == 4800.0
    # the warm-up's journal, a failed job's and an eval's are no job of the window
    assert reader(ARGS, 1, _run(steps=1), _run(steps=2, error=True),
                  _run(steps=3, root="eval"), _run()) == 6400.0
    assert reader({"span": "layout", "attr": "user_blocks"}, 1, _run()) == 128.0


def test_nothing_to_read_is_none_never_zero(reader, harness, monkeypatch):
    assert reader(ARGS, 1) is None and reader(ARGS, 0, _run()) is None
    # the resident program's dispatch spans carry no block_steps
    assert reader(ARGS, 1, _run(program="_cco_resident_all_tiles")) is None
    parent = [[{k: v for k, v in s.items() if k != "attrs"}
               for s in _run()]]                    # spans with no attributes
    assert reader(ARGS, 1, *parent) is None
    from predictionio_tpu.obs import spans

    monkeypatch.delattr(spans, "recent_runs")
    mod = harness.load_module("readers", "span_counter")
    assert mod.read(ARGS, {"jobs": 1}) is None


def test_the_chunked_programs_metrics_are_entries_files_and_spans():
    for name, reader_name in (("cco_chunked_program_ms", "trace_program_ms"),
                              ("cco_block_steps_per_job", "span_counter")):
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == "CCO op"
        assert entry["moves"] == "train_events_per_s"
        spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert spec["reader"] == reader_name
    program = (ROOT / "predictionio_tpu" / "ops" / "cco.py").read_text()
    assert f'span("{ARGS["span"]}", program="{ARGS["where"]["program"]}"' in program
    assert f'{ARGS["attr"]}=' in program
    chunked = json.loads((BENCH / "metrics" / "cco_chunked_program_ms.json"
                          ).read_text())["args"]["programs"]
    assert all(f"def {p}(" in program for p in chunked)
