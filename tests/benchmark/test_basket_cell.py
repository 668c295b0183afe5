"""The files of `cp-ecom-100k`: the generator's visits at the configuration's
own sizes, the plain reference of pair rules on a table worked by hand, its
control and faults against the cell's limits, and the roofline's arithmetic."""

import json

import numpy as np
import pytest

from bench_helpers import BENCH, load_harness, run_control

H = load_harness()
GEN = H.load_module("data", "shop_visits")
REF = H.load_module("reference", "basket_pair_rules")
CONFIG = json.loads((BENCH / "configs" / "cp-ecom-100k.json").read_text())
LIMITS = CONFIG["reference"]["limits"]
CELL = "cp-ecom-100k.train"
SMALL = dict(CONFIG["data"]["params"], n_users=64, n_items=400, n_kept=300,
             n_single=100, max_items=10, floors={})


def test_the_generator_makes_the_shop_the_configuration_names():
    """100,000 items, 32,768 shoppers, 65,536 kept and 16,384 dropped
    visits, 410,000 events within 5%; and the reference forms exactly the
    kept baskets from the times under the engine's own parameters."""
    data = GEN.generate(CONFIG["data"]["params"], 3310000901)
    (block,) = data["blocks"]
    assert (data["n_users"], data["n_items"], data["n_baskets"],
            data["n_single"]) == (32768, 100000, 65536, 16384)
    assert abs(len(block["users"]) - 410_000) <= 0.05 * 410_000
    assert len(np.unique(block["items"])) == 100_000
    assert len(np.unique(block["users"])) == 32_768
    sizes = np.bincount(block["baskets"])
    assert (sizes[:65536] >= 2).all() and sizes.max() == 32
    assert (sizes[65536:] == 1).all() and len(sizes) == 81_920
    assert abs(sizes[:65536].mean() - 6.0) < 0.1
    pairs = block["baskets"] * 100_000 + block["items"]
    assert len(np.unique(pairs)) == len(pairs)       # a visit's items differ
    p = REF.params_of(CONFIG["engine"])
    assert (p["window_us"], p["min_size"], p["k"]) == (120_000_000, 2, 5)
    B = REF.baskets(block, 100_000, p["window_us"], p["min_size"])
    assert B.shape == (65_536, 100_000) and B.nnz == len(pairs) - 16_384
    ref = REF.cells(B, p["cuts"])
    floors = CONFIG["data"]["params"]["floors"]
    assert ref["kept"].sum() >= floors["rules"] == 20_000
    assert len(np.unique(ref["rows"][ref["kept"]])) >= floors[
        "condition_items"] == 5_000


@pytest.mark.parametrize("seed", [3, 2147483659, 4000000007])
def test_visits_lie_inside_the_window_and_apart_by_more(seed):
    data = GEN.generate(SMALL, seed)
    (b,) = data["blocks"]
    order = np.lexsort((b["times"], b["users"]))
    u, t, v = b["users"][order], b["times"][order], b["baskets"][order]
    same_u, same_v = u[1:] == u[:-1], v[1:] == v[:-1]
    gap = np.diff(t) / 1e6
    assert not (same_v & ~same_u).any()           # a visit has one shopper
    assert (gap[same_u & same_v] >= 1).all()
    assert (gap[same_u & same_v] <= SMALL["within_s"]).all()
    assert (gap[same_u & ~same_v] >= SMALL["between_s"]).all()
    window = float(CONFIG["engine"]["algorithms"][0]["params"]["basketWindow"])
    assert SMALL["within_s"] < window < SMALL["between_s"]
    again = GEN.generate(SMALL, seed)["blocks"][0]
    assert all(np.array_equal(b[k], again[k]) for k in
               ("users", "items", "times", "baskets"))
    other = GEN.generate(SMALL, seed + 1)["blocks"][0]
    assert not np.array_equal(b["items"][:200], other["items"][:200])


def test_a_catalogue_the_visits_cannot_cover_is_refused():
    with pytest.raises(ValueError, match="left to cover"):
        GEN.generate(dict(SMALL, n_items=4000), 5)
    with pytest.raises(ValueError, match="cannot cover"):
        GEN.generate(dict(SMALL, n_users=1000), 5)


def _block(baskets_of_items, gap_s=10, apart_s=86_400):
    users, items, times = [], [], []
    for n, its in enumerate(baskets_of_items):
        for k, it in enumerate(its):
            users.append(0), items.append(it)
            times.append((n * apart_s + k * gap_s) * 10**6)
    return {"users": np.array(users), "items": np.array(items),
            "times": np.array(times, np.int64)}


def test_reference_on_a_table_worked_by_hand():
    """5 baskets: {0,1} four times and {2}: with baskets of one item kept,
    N = 5, support(0,1) = 4/5, confidence 1, lift 1 / (4/5) = 1.25; with
    minBasketSize 2, N = 4 and the lift is 1."""
    block = _block([[0, 1]] * 4 + [[2]])
    p = {"window_us": 120 * 10**6, "min_size": 1, "cuts": (0.0, 0.0, 0.0),
         "k": 2}
    idx, lift, ids = REF.table(block, 3, p)
    assert idx.tolist() == [[1, -1], [0, -1], [-1, -1]]
    assert lift[0, 0] == lift[1, 0] == 1.25 and ids.tolist() == [0, 1, 2]
    idx, lift, _ = REF.table(block, 3, dict(p, min_size=2))
    assert lift[0, 0] == 1.0
    # each cut, where it bites: support 4/5, confidence 1, lift 1.25
    for cuts, kept in (((0.8, 0.0, 0.0), True), ((0.81, 0.0, 0.0), False),
                       ((0.0, 1.0, 0.0), True), ((0.0, 0.0, 1.25), True),
                       ((0.0, 0.0, 1.26), False)):
        idx, _, _ = REF.table(block, 3, dict(p, cuts=cuts))
        assert (idx[0, 0] == 1) == kept, cuts
    # a gap over the window splits the basket, one of exactly the window
    # does not
    assert REF.baskets(_block([[0, 1]], gap_s=120), 3, 120 * 10**6, 1
                       ).shape[0] == 1
    assert REF.baskets(_block([[0, 1]], gap_s=121), 3, 120 * 10**6, 1
                       ).shape[0] == 2


def test_compare_reads_zero_on_itself_and_names_what_differs():
    data = GEN.generate(SMALL, 11)
    p = REF.params_of(CONFIG["rehearsal"]["engine"])
    idx, lift, ids = REF.table(data["blocks"][0], data["n_items"], p)
    assert (idx >= 0).sum() > 200
    got = REF.compare(idx, lift, ids, data, p)
    assert got == {"rules_short": 0, "condition_items_short": 0,
                   "lift_gap_max": 0.0, "topk_gap_max": 0.0,
                   "baskets_gap": 0.0}
    # rows in another order, under their own ids: the same table
    perm = np.random.default_rng(0).permutation(data["n_items"])
    inv = np.argsort(perm)
    again = REF.compare(np.where(idx[perm] >= 0, inv[idx[perm]], -1),
                        lift[perm], perm, data, p)
    assert again == got
    # the floors are the generator's to pass on
    short = REF.compare(idx, lift, ids, dict(data, floors={
        "rules": 10**6, "condition_items": 10**5}), p)
    assert short["rules_short"] > 0 and short["condition_items_short"] > 0
    # another number of baskets than the generator's
    assert REF.compare(idx, lift, ids, dict(data, n_baskets=301), p)[
        "baskets_gap"] == 1.0


def test_control_and_faults_fail_the_cells_limits_at_rehearsal_size():
    lines = run_control(CELL, "2147483777,4000000007")
    for ln in lines:
        assert set(ln) == {"workload", "seed", "reference", "control_bfloat16",
                           "fault_half_left_out", "fault_answer_altered"}
        assert all(ln["reference"][k] <= LIMITS[k] for k in LIMITS)
        assert ln["reference"]["lift_gap_max"] == 0.0
        for reading in ("control_bfloat16", "fault_half_left_out",
                        "fault_answer_altered"):
            assert any(ln[reading][k] > LIMITS[k] for k in LIMITS), ln


def test_roofline_counts_the_pair_counts_of_the_kept_baskets():
    work = H.load_module("roofline", "basket_train").work(CONFIG)
    assert work["flops"] == 2.0 * 65_536 * 100_000 ** 2
    assert work["calls"] == 25
    assert work["bytes"] == 25 * (65_536 * 100_000 * 2 + 2 * 65_536 * 4096 * 2
                                  + 2 * 100_000 * 4096 * 4)
    peaks = H.peaks_for("TPU v5 lite")
    least = H.least_seconds("basket_train", CONFIG, peaks)
    assert least == pytest.approx(6.653, rel=1e-3)          # compute-bound
    assert work["bytes"] / peaks["bytes_per_s"] < least
