"""The roofline functions at `ur-ecom-100k-u131k`'s shapes: they count the
work from the configuration, so they read the same whatever program does
it."""

import json

import pytest

from bench_helpers import BENCH, load_harness

H = load_harness()
CONFIG = json.loads((BENCH / "configs" / "ur-ecom-100k-u131k.json").read_text())


def test_cco_train_and_llr_tile_at_131072_users():
    train = H.load_module("roofline", "cco_train").work(CONFIG)
    # 2 event types x 2 x 131,072 users x 100,000^2 items
    assert train["flops"] == 2 * 2.0 * 131072 * 100000 * 100000
    assert train["flops"] == pytest.approx(5.24e15, rel=0.001)
    assert train["calls"] == 2 * 25
    llr = H.load_module("roofline", "llr_tile").work(CONFIG)
    assert llr["calls"] == 50
    assert llr["bytes"] == 50 * 100000 * 4096 * 8.0
    # bound by the MXU: 26.6 s a job at 197 TFLOP/s, 1.9 s of bytes
    peaks = H.peaks_for("TPU v5 lite")
    assert H.least_seconds("cco_train", CONFIG, peaks) == pytest.approx(
        26.6, rel=0.005)
    assert train["bytes"] / peaks["bytes_per_s"] < 2.0


def test_the_users_are_four_times_the_resident_cells_and_nothing_else_moves():
    small = json.loads((BENCH / "configs" / "ur-ecom-100k.json").read_text())
    a, b = CONFIG["data"]["params"], small["data"]["params"]
    for key in ("n_users", "n_buy", "n_view"):
        assert a[key] == 4 * b[key]
    for key in ("n_items", "zipf_buy", "zipf_view"):
        assert a[key] == b[key]
    assert CONFIG["engine"] == small["engine"]
    assert CONFIG["reduced"] == [] and CONFIG["roofline"] == small["roofline"]
