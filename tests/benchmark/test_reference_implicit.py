"""The implicit ALS reference (the E-Commerce Recommendation cell's) against
the program at a tiny size on the CPU; its control, its faults and `alter`
failing the cell's own limits; its generator and its roofline."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench_helpers import BENCH, load_harness, rehearsal_config

H = load_harness()
REF = H.load_module("reference", "als_implicit")
CONFIG = json.loads((BENCH / "configs" / "ecom-retailrocket.json").read_text())
LIMITS = CONFIG["reference"]["limits"]
PARAMS = {**CONFIG["data"]["params"], **CONFIG["rehearsal"]["data"]["params"]}
SEEDS = [3, 2147483659, 4000000007]
_half = load_harness("control.py")._half     # what `control.py` hands in


def _log(seed, **sizes):
    return H.load_module("data", "view_log").generate({**PARAMS, **sizes},
                                                      seed)


def _algorithm(sweeps=6, seed=3):
    return dict(CONFIG["engine"]["algorithms"][0]["params"],
                numIterations=sweeps, seed=seed)


def _program(data, params):
    """The program's factors, trained from the cells the engine folds."""
    from predictionio_tpu.ops import als

    users, items, r = REF.cells(data, REF.WEIGHTS)
    prepared = als.prepare_als_data(users, items, r, data["n_users"],
                                    data["n_items"], dp=1)
    return als.als_train(prepared, k=int(params["rank"]),
                         reg=float(params["lambda"]),
                         iterations=int(params["numIterations"]),
                         seed=int(params["seed"]), implicit=True,
                         alpha=REF.ALPHA)


@pytest.mark.parametrize("seed", SEEDS)
def test_implicit_reference_agrees_with_the_program(seed):
    """Over the observed cells, and one item half-step from the program's
    own user factors: both read float32's rounding."""
    data = _log(seed)
    params = _algorithm(seed=seed % (2 ** 31 - 1))
    x, y = _program(data, params)
    ids = (np.arange(data["n_users"]), np.arange(data["n_items"]))
    got = REF.compare(x, y, *ids, data, params)
    assert got["pred_gap_rms"] <= LIMITS["pred_gap_rms"], got
    assert got["half_step_gap"] < 1e-5, got
    # user rows in another order, as a model's dictionary may hold them
    # (the item rows' order is the start's, and part of the program)
    order = np.random.default_rng(seed % 97).permutation(data["n_users"])
    assert REF.compare(x[order], y, order, ids[1], data, params) == got
    assert REF.compare(x, y, ids[0][:-1], ids[1], data, params)[
        "pred_gap_rms"] == REF.BIG


def test_the_half_step_gap_sees_the_grams_precision():
    """A Gram of one bfloat16 pass in place of the program's float32 one:
    the item half-step moves past the cell's limit."""
    import ml_dtypes

    data = _log(5)
    params = _algorithm(seed=5)
    x, y = _program(data, params)
    prob = REF.Problem(data, params)
    xr, yr = prob.factorize(REF.start(5, prob.ni, prob.k))
    assert prob.compare(x, y, xr, yr)["half_step_gap"] < 1e-5
    low = x.astype(ml_dtypes.bfloat16).astype(np.float64)
    k = x.shape[1]
    gram = low.T @ low
    p, q = np.triu_indices(k)
    sums = prob.by_item[0][0] @ (x[:, p] * x[:, q])
    rhs = prob.by_item[0][1] @ x
    y_low = REF._cholesky_solve(np.ascontiguousarray(sums.T),
                                np.ascontiguousarray(rhs.T), gram,
                                prob.ridge_i, k)
    assert prob.compare(x, y_low, xr, yr)["half_step_gap"] > LIMITS[
        "half_step_gap"]


def test_cholesky_over_rows_solves_as_numpy_does():
    rng = np.random.default_rng(0)
    k, n = 10, 37
    f = rng.normal(size=(50, k))
    v = rng.normal(size=(n, k))
    p, q = np.triu_indices(k)
    s = (v[:, p] * v[:, q]).T
    b = rng.normal(size=(k, n))
    lam = rng.random(n) + 0.01
    got = REF._cholesky_solve(s, b, f.T @ f, lam, k)
    A = f.T @ f + v[:, :, None] * v[:, None, :] + lam[:, None, None] * np.eye(k)
    want = np.linalg.solve(A, b.T[..., None])[..., 0]
    assert np.abs(got - want).max() < 1e-12 * np.abs(want).max()


@pytest.mark.parametrize("seed", [7, 4000000007])
def test_implicit_alter_zeroes_the_largest_items_factors(seed):
    data = _log(7)
    params = _algorithm(seed=7)
    prob = REF.Problem(data, params)
    x, y = prob.factorize(REF.start(7, prob.ni, prob.k))
    model = SimpleNamespace(user_factors=x, item_factors=y)
    REF.alter(model, seed)
    assert model.item_factors is not y                  # a copy zeroed
    changed = (model.item_factors != y).any(1).nonzero()[0].tolist()
    assert changed == [int(np.argmax((y ** 2).sum(1)))]
    got = prob.compare(x, model.item_factors, x, y)
    assert all(got[k] > LIMITS[k] for k in LIMITS), got


def test_readings_are_the_four_and_only_the_reference_reads_nought():
    config = rehearsal_config(H, "ecom-retailrocket.train")
    data = H.load_module("data", config["data"]["generator"]).generate(
        config["data"]["params"], 4000000007)
    got = REF.readings(config, data, 4000000007, _half)
    assert list(got) == ["reference", "control_bfloat16",
                         "fault_half_left_out", "fault_answer_altered"]
    assert all(abs(v) < 1e-9 for v in got["reference"].values())
    for reading in list(got)[1:]:
        assert set(LIMITS) <= set(got[reading])
        assert any(got[reading][k] > LIMITS[k] for k in LIMITS), got


def test_view_log_is_seeded_and_keeps_its_counts():
    a, b, c = _log(2 ** 31 + 9), _log(2 ** 31 + 9), _log(2 ** 31 + 10)
    n_items = PARAMS["n_items"]
    for x, y, z in zip(a["blocks"], b["blocks"], c["blocks"]):
        assert np.array_equal(x["users"], y["users"])
        assert np.array_equal(x["items"], y["items"])
        assert not np.array_equal(x["items"], z["items"])
        assert len(x["users"]) == len(z["users"])      # same sizes every seed
    views, buys = a["blocks"]
    assert [views["event"], buys["event"]] == ["view", "buy"]
    assert (len(views["users"]), len(buys["users"])) == (
        PARAMS["n_views"], PARAMS["n_buys"])
    assert set(views["users"].tolist()) == set(range(PARAMS["n_visitors"]))
    assert set(views["items"].tolist()) == set(range(n_items))
    viewed = np.unique(views["users"] * n_items + views["items"])
    assert len(viewed) == PARAMS["n_cells"]             # fixed for every seed
    bought = buys["users"] * n_items + buys["items"]
    assert np.isin(bought, viewed).all() and len(np.unique(bought)) == len(
        bought)


def test_implicit_roofline_by_hand():
    config = {"data": {"params": {"n_visitors": 10, "n_items": 5,
                                  "n_cells": 30}},
              "engine": {"algorithms": [{"params": {
                  "rank": 2, "numIterations": 3}}]}}
    w = H.load_module("roofline", "als_implicit_train").work(config)
    sweep_flops = 2 * 30 * (2 * 4 + 4) + 2 * 15 * 4 + 15 * (8 / 3 + 8)
    sweep_bytes = 2 * 30 * (16 + 8) + 2 * 15 * 8
    assert w["flops"] == pytest.approx(3 * sweep_flops)
    assert w["bytes"] == 3 * sweep_bytes and w["calls"] == 6
