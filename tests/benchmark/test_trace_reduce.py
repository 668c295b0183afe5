"""The reduction from a profiler trace to numbers: on a small trace recorded
on the CPU and checked in beside this file (two annotated jobs, each a 10 ms
sleep, two runs of a small program, a 5 ms sleep), and on a trace of a TPU's
shape written here by hand (a device plane with `XLA Modules` and `XLA Ops`,
a `while` with its body nested under it)."""

import sys
from pathlib import Path

import pytest

from bench_helpers import BENCH

sys.path.insert(0, str(BENCH))
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_recorded_cpu_trace():
    r = trace_reduce.reduce(DATA, "bench:train_job")
    assert len(r["jobs"]) == 2 and r["chips"] == 1
    assert r["programs"]["jit_work"]["count"] == 4
    assert 0 < r["busy_s"] < r["window_s"] < 0.1
    for job in r["jobs"]:
        lead_in = job["first_op_s"] - job["start_s"]
        tail = job["end_s"] - job["last_op_s"]
        assert 0.010 <= lead_in < 0.02          # the 10 ms sleep
        assert 0.005 <= tail < 0.012            # the 5 ms sleep
    top = r["breakdown"]["device_ops"][0]
    assert top[0].startswith("dot_general")
    kinds = dict(r["breakdown"]["idle_gaps"])
    assert kinds["bench:train_job:lead_in"] == pytest.approx(
        sum(j["first_op_s"] - j["start_s"] for j in r["jobs"]), rel=1e-6)
    assert "between:bench:train_job" in kinds
    assert sum(v["seconds"] for v in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=0.05)


# -- a trace of a TPU's shape, encoded by hand --------------------------------


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _plane(name: str, lines: dict) -> bytes:
    """lines: {line name: [(event name, start_ns, duration_ns)]}"""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: k + 1 for k, n in enumerate(names)}
    out = _field(2, name)
    for k, (line, evs) in enumerate(lines.items()):
        body = _field(1, k + 1) + _field(2, line) + _field(3, 1_000)
        for ev, start, dur in evs:
            body += _field(4, _field(1, meta[ev]) + _field(2, start * 1000)
                           + _field(3, dur * 1000))
        out += _field(3, body)
    for n, k in meta.items():
        out += _field(4, _field(1, k) + _field(2, _field(1, k) + _field(2, n)))
    return out


MS = 1_000_000


@pytest.fixture()
def tpu_trace(tmp_path):
    device = _plane("/device:TPU:0", {
        "XLA Modules": [("jit__densify_global(7)", 10 * MS, 5 * MS),
                        ("jit__cco_resident_all_tiles(8)", 20 * MS, 60 * MS),
                        ("jit__cco_resident_all_tiles(8)", 120 * MS, 60 * MS)],
        "XLA Ops": [("scatter.1", 10 * MS, 5 * MS),
                    ("while.2", 20 * MS, 60 * MS),
                    ("convolution.3", 20 * MS, 30 * MS),
                    ("llr_masked_scores", 50 * MS, 10 * MS),
                    ("sort.4", 60 * MS, 20 * MS),
                    ("while.2", 120 * MS, 60 * MS),
                    ("convolution.3", 120 * MS, 60 * MS)]})
    other_core = _plane("/device:TPU:0 SparseCore", {"Steps": [("s", 0, MS)]})
    host = _plane("/host:CPU", {"python": [
        ("bench:train_job", 0, 90 * MS), ("bench:train_job", 100 * MS, 100 * MS),
        ("something else", 0, 500 * MS)]})
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        _field(1, device) + _field(1, other_core) + _field(1, host))
    return tmp_path


def test_tpu_shaped_trace(tpu_trace):
    r = trace_reduce.reduce(tpu_trace, "bench:train_job")
    assert r["chips"] == 1                     # the plane without XLA Ops is no chip
    assert r["window_s"] == pytest.approx(0.200)
    assert r["busy_s"] == pytest.approx(0.125)     # 5 + 60 + 60 ms
    assert r["programs"]["jit__cco_resident_all_tiles"] == {
        "count": 2, "seconds": pytest.approx(0.120)}
    assert r["programs"]["jit__densify_global"]["seconds"] == pytest.approx(0.005)
    own = {k: v["seconds"] for k, v in r["ops"].items()}
    assert own["while.2"] == pytest.approx(0.0)        # all of it is its body's
    assert own["convolution.3"] == pytest.approx(0.090)
    assert own["llr_masked_scores"] == pytest.approx(0.010)
    assert r["jobs"][0]["first_op_s"] == pytest.approx(0.010)
    assert r["jobs"][0]["last_op_s"] == pytest.approx(0.080)
    assert r["jobs"][1]["first_op_s"] == pytest.approx(0.120)
    kinds = dict(r["breakdown"]["idle_gaps"])
    assert kinds["bench:train_job:lead_in"] == pytest.approx(0.030)
    assert kinds["bench:train_job:between_programs"] == pytest.approx(0.005)
    assert kinds["bench:train_job:tail"] == pytest.approx(0.030)
    assert kinds["between:bench:train_job"] == pytest.approx(0.010)
    assert r["breakdown"]["device_ops"][0] == ["convolution.3",
                                               pytest.approx(0.090)]


def test_readers_on_the_tpu_shaped_trace(tpu_trace, harness):
    r = trace_reduce.reduce(tpu_trace, "bench:train_job")
    facts = {"reduced": r, "jobs": 2, "least_job_s": None}
    read = lambda name, args: harness.load_module(   # noqa: E731
        "readers", name).read(args, facts)
    assert read("trace_program_ms", {"programs": [
        "_cco_resident_all_tiles", "_densify_global"]}) == pytest.approx(62.5)
    assert read("trace_program_ms", {"programs": ["_als_run_single"]}) is None
    assert read("trace_job_edge_s", {"edge": "lead_in"}) == pytest.approx(0.015)
    assert read("trace_job_edge_s", {"edge": "tail"}) == pytest.approx(0.015)
    assert read("trace_idle_pct", {}) == pytest.approx(37.5)
    assert read("kernel_roofline", {"roofline": "llr_tile",
                                    "ops": ["llr_masked_scores"]}) is None


def test_a_trace_with_no_annotation_or_no_device_work_is_refused(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    host = _plane("/host:CPU", {"python": [("bench:train_job", 0, MS)]})
    (d / "host.xplane.pb").write_bytes(_field(1, host))
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(tmp_path, "bench:train_job")
    with pytest.raises(ValueError, match="annotation"):
        trace_reduce.reduce(tmp_path, "bench:other")
