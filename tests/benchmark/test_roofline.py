"""Each roofline function against a shape worked by hand."""

import pytest

from bench_helpers import load_harness

H = load_harness()


def _ur(users, items, tile):
    return {"data": {"params": {"n_users": users, "n_items": items}},
            "engine": {"datasource": {"params": {"eventNames": ["buy", "view"]}},
                       "algorithms": [{"params": {"itemTile": tile}}]}}


def test_cco_train_by_hand():
    w = H.load_module("roofline", "cco_train").work(_ur(1000, 512, 128))
    users = 1024                                   # padded to 128
    assert w["calls"] == 2 * 4
    assert w["flops"] == 2 * 2.0 * users * 512 * 512
    per_tile = users * 512 * 2 + 2 * users * 128 * 2 + 2 * 512 * 128 * 4
    assert w["bytes"] == 8 * per_tile


def test_llr_tile_by_hand():
    w = H.load_module("roofline", "llr_tile").work(_ur(1000, 100000, 4096))
    assert w["calls"] == 2 * 25
    assert w["bytes"] == 50 * 100000 * 4096 * 8.0
    # one [100000, 4096] float32 tile, read and written, at 819 GB/s: 4.0 ms
    assert w["bytes"] / w["calls"] / 819e9 == pytest.approx(4.0e-3, rel=0.01)
    assert w["flops"] / 197e12 < w["bytes"] / 819e9     # bound by bytes


def test_als_train_by_hand():
    config = {"data": {"params": {"n_users": 10, "n_items": 5,
                                  "n_ratings": 30}},
              "engine": {"algorithms": [{"params": {
                  "rank": 2, "numIterations": 3}}]}}
    w = H.load_module("roofline", "als_train").work(config)
    sweep_flops = 2 * 30 * (2 * 4 + 4) + 15 * (8 / 3 + 8)
    sweep_bytes = 2 * 30 * (12 + 8) + 15 * 8
    assert w["flops"] == pytest.approx(3 * sweep_flops)
    assert w["bytes"] == 3 * sweep_bytes and w["calls"] == 6


def test_roofline_readers_return_nothing_where_nothing_matched():
    peaks = H.peaks_for("TPU v5 lite")
    llr, train = _ur(1000, 100000, 4096), _ur(32768, 100000, 4096)
    reader = H.load_module("readers", "kernel_roofline")
    facts = {"jobs": 2, "reduced": {"ops": {"fusion.1": {"seconds": 1.0}}},
             "least_job_s": lambda name: H.least_seconds(name, llr, peaks)}
    args = {"roofline": "llr_tile", "ops": ["_llr_padded"]}
    assert reader.read(args, facts) is None            # never 0
    facts["reduced"]["ops"]["_llr_padded.8 (tpu_custom_call)"] = {
        "seconds": 2.0}
    # 2 jobs x 50 tiles x 4.0 ms of bytes = 0.40 s over 2.0 s measured
    assert reader.read(args, facts) == pytest.approx(20.0, rel=0.01)
    mfu = H.load_module("readers", "step_mfu")
    facts.update(window_s=70.0, roofline="cco_train",
                 least_job_s=lambda name: H.least_seconds(name, train, peaks))
    # 1.31e15 operations a job / 197e12 = 6.65 s; two jobs in 70 s = 19%
    assert mfu.read({}, facts) == pytest.approx(19.0, rel=0.01)
    facts["least_job_s"] = None                        # a rehearsal: no peaks
    assert mfu.read({}, facts) is None and reader.read(args, facts) is None
