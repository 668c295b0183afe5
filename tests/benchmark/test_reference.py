"""Each plain reference against the program at a tiny size on the CPU, and
its control (one precision lower) failing the cell's own limits."""

import json

import numpy as np
import pytest

from bench_helpers import (BENCH, load_harness, rehearsal_config,
                           run_control)

H = load_harness()
CCO = H.load_module("reference", "cco")
ALS = H.load_module("reference", "als")
UR_LIMITS = json.loads((BENCH / "configs" / "ur-ecom-100k.json").read_text()
                       )["reference"]["limits"]
ALS_LIMITS = json.loads((BENCH / "configs" / "als-ml1m.json").read_text()
                        )["reference"]["limits"]
SEEDS = [3, 2147483659, 4000000007]


def test_g2_against_scipy_on_a_hand_table():
    from scipy.stats import chi2_contingency

    table = np.array([[10.0, 20.0], [30.0, 940.0]])
    want = chi2_contingency(table, correction=False,
                            lambda_="log-likelihood")[0]
    got = CCO.g2(table[0, 0], table[0, 1], table[1, 0], table[1, 1])
    assert float(got) == pytest.approx(want, rel=1e-12)
    assert float(CCO.g2(5.0, 0.0, 0.0, 95.0)) > 0       # empty cells are fine


def _commerce(seed):
    gen = H.load_module("data", "commerce")
    return gen.generate(dict(n_users=300, n_items=500, n_buy=3000,
                             n_view=6000, zipf_buy=1.3, zipf_view=1.2), seed)


def _program_tables(data, top_k=10):
    from predictionio_tpu.ops import cco

    buy, view = data["blocks"]
    u, i = buy["users"].astype(np.int32), buy["items"].astype(np.int32)
    out = cco.cco_train_indicators(
        u, i, [("buy", u, i, data["n_items"]),
               ("view", view["users"].astype(np.int32),
                view["items"].astype(np.int32), data["n_items"])],
        data["n_users"], data["n_items"], top_k=top_k, item_tile=256,
        exclude_self_for="buy")
    return {n: (idx, np.where(np.isfinite(s), s, 0.0))
            for n, (s, idx) in out.items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_cco_reference_agrees_with_the_program(seed):
    data = _commerce(seed)
    ids = np.arange(data["n_items"])
    got = CCO.compare(_program_tables(data), ids, {"buy": ids, "view": ids},
                      data, 10, 0.0, "buy")
    assert got["score_gap_max"] <= UR_LIMITS["score_gap_max"], got
    assert got["topk_gap_max"] <= UR_LIMITS["topk_gap_max"], got


@pytest.mark.parametrize("seed", SEEDS)
def test_cco_control_and_faults_come_out_not_correct(seed):
    import ml_dtypes

    data = _commerce(seed)
    held = lambda t: CCO.compare(*t, data, 10, 0.0, "buy")   # noqa: E731
    assert held(CCO.control_tables(data, 10, 0.0, "buy", np.float64)) == {
        "score_gap_max": 0.0, "topk_gap_max": 0.0}
    low = held(CCO.control_tables(data, 10, 0.0, "buy", ml_dtypes.bfloat16))
    assert low["score_gap_max"] > UR_LIMITS["score_gap_max"]
    half = {**data, "blocks": [{k: (v[: len(v) // 2] if k != "event" else v)
                                for k, v in b.items()}
                               for b in data["blocks"]]}
    left_out = held(CCO.control_tables(half, 10, 0.0, "buy", np.float64))
    assert left_out["score_gap_max"] > 10 * UR_LIMITS["score_gap_max"]
    tables, rows, cols = CCO.control_tables(data, 10, 0.0, "buy", np.float64)
    idx, llr = tables["buy"]
    idx = idx.copy()
    row = int(np.flatnonzero((idx >= 0).sum(1) >= 2)[0])
    idx[row, 0], idx[row, 1] = idx[row, 1], idx[row, 0]   # a swap inside a row
    tables["buy"] = (idx, llr)
    swapped = held((tables, rows, cols))
    assert swapped["topk_gap_max"] == 0.0      # the same cells were kept ...
    if llr[row, 0] != llr[row, 1]:             # ... under the wrong scores
        assert swapped["score_gap_max"] > 0
    idx[row, 0] = (idx[row, 0] + 7) % data["n_items"]
    assert max(held((tables, rows, cols)).values()) > 1.0


def test_cco_compare_refuses_a_table_of_other_rows():
    data = _commerce(5)
    tables, rows, cols = CCO.control_tables(data, 10, 0.0, "buy", np.float64)
    got = CCO.compare(tables, rows[:-1], cols, data, 10, 0.0, "buy")
    assert got["score_gap_max"] == CCO.BIG


def _ratings(seed):
    gen = H.load_module("data", "ratings")
    return gen.generate(dict(n_users=120, n_items=90, n_ratings=3000,
                             min_ratings_per_user=5, user_sigma=1.0,
                             item_zipf=0.9, taste_rank=8), seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_als_reference_agrees_with_the_program_and_control_fails(seed):
    import ml_dtypes

    from predictionio_tpu.ops import als

    data = _ratings(seed)
    b = data["blocks"][0]
    start = seed % (2 ** 31 - 1)
    prepared = als.prepare_als_data(b["users"], b["items"], b["ratings"],
                                    data["n_users"], data["n_items"], dp=1)
    x, y = als.als_train(prepared, k=10, reg=0.01, iterations=6, seed=start)
    ids = (np.arange(data["n_users"]), np.arange(data["n_items"]))
    got = ALS.compare(x, y, *ids, data, 10, 0.01, 6, start)
    assert got["pred_gap_rms"] <= ALS_LIMITS["pred_gap_rms"], got
    low = ALS.compare(None, None, *ids, data, 10, 0.01, 6, start,
                      round_to=ml_dtypes.bfloat16, control=True)
    assert low["pred_gap_rms"] > 3 * got["pred_gap_rms"]
    assert low["pred_gap_rms"] > ALS_LIMITS["pred_gap_rms"], low


def test_als_start_is_the_configurations_own():
    import jax

    want = np.asarray(jax.random.normal(jax.random.PRNGKey(11), (1, 7, 3),
                                        "float32"))[0] * 0.1
    assert np.array_equal(ALS.start(11, 7, 3), want)


@pytest.mark.parametrize("name,params", [
    ("commerce", dict(n_users=64, n_items=50, n_buy=400, n_view=900,
                      zipf_buy=1.3, zipf_view=1.2)),
    ("ratings", dict(n_users=40, n_items=30, n_ratings=400,
                     min_ratings_per_user=4, user_sigma=1.0, item_zipf=0.9,
                     taste_rank=4)),
])
def test_generators_are_seeded_and_cover_every_id(name, params):
    gen = H.load_module("data", name)
    a, b = gen.generate(params, 2 ** 31 + 9), gen.generate(params, 2 ** 31 + 9)
    c = gen.generate(params, 2 ** 31 + 10)
    for x, y, z in zip(a["blocks"], b["blocks"], c["blocks"]):
        assert np.array_equal(x["users"], y["users"])
        assert np.array_equal(x["items"], y["items"])
        assert not np.array_equal(x["items"], z["items"])
        assert len(x["users"]) == len(z["users"])      # same sizes every seed
        assert set(x["users"].tolist()) == set(range(params["n_users"]))
        assert set(x["items"].tolist()) == set(range(params["n_items"]))
    if name == "ratings":
        r = a["blocks"][0]
        pairs = r["users"] * params["n_items"] + r["items"]
        assert len(np.unique(pairs)) == params["n_ratings"]
        assert set(np.unique(r["ratings"]).tolist()) <= {1, 2, 3, 4, 5}


# -- what a reference module says of its own engine: `alter`, `readings` ------


_half = load_harness("control.py")._half     # what `control.py` hands in


def test_cco_alter_moves_the_kept_cell_that_readings_moves():
    from types import SimpleNamespace

    data = _commerce(7)
    tables, rows, cols = CCO.control_tables(data, 10, 0.0, "buy", np.float64)
    model = SimpleNamespace(
        primary_event="buy", item_dict=list(range(data["n_items"])),
        indicator_idx={n: idx for n, (idx, _) in tables.items()})
    before = {n: idx.copy() for n, idx in model.indicator_idx.items()}
    CCO.alter(model, 7)
    assert np.array_equal(model.indicator_idx["view"], before["view"])
    assert (model.indicator_idx["buy"] != before["buy"]).sum() == 1
    assert np.array_equal(tables["buy"][0], before["buy"])    # a copy moved
    assert np.array_equal(model.indicator_idx["buy"],
                          CCO._moved(before["buy"], 7, data["n_items"]))
    got = CCO.compare({n: (model.indicator_idx[n], llr)
                       for n, (_, llr) in tables.items()},
                      rows, cols, data, 10, 0.0, "buy")
    assert max(got.values()) > max(UR_LIMITS.values())


def test_cco_moved_draws_its_row_by_the_seed_from_however_few():
    idx = np.array([[4, -1, -1], [2, 5, -1], [1, 3, 0], [-1, -1, -1]])
    was = idx.copy()
    for seed, row in ((0, 1), (1, 2), (2147483777, 2), (4000000006, 1)):
        got = CCO._moved(idx, seed, 6)
        assert (got != was).nonzero() == ([row], [0]), (seed, got)
        assert 0 <= got[row, 0] < 6 and np.array_equal(idx, was)


@pytest.mark.parametrize("seed", [7, 4000000007])
def test_als_alter_zeroes_one_items_factors(seed):
    from types import SimpleNamespace

    data = _ratings(7)
    b = data["blocks"][0]
    nu, ni = data["n_users"], data["n_items"]
    x, y = ALS.factorize(b["users"], b["items"], b["ratings"], nu, ni,
                         ALS.start(7, ni, 10), 0.01, 6)
    model = SimpleNamespace(user_factors=x, item_factors=y)
    ALS.alter(model, seed)
    assert model.item_factors is not y and y.any(1).all()     # a copy zeroed
    assert (model.item_factors != y).any(1).nonzero()[0].tolist() == [
        seed % ni]
    got = ALS.compare(x, model.item_factors, np.arange(nu), np.arange(ni),
                      data, 10, 0.01, 6, 7)
    assert got["pred_gap_rms"] > ALS_LIMITS["pred_gap_rms"]


@pytest.mark.parametrize("cell", ["ur-ecom-100k.train", "als-ml1m.train",
                                  "ur-ecom-100k-u131k.train"])
def test_readings_are_the_four_and_only_the_reference_reads_nought(cell):
    """`readings(config, data, seed, half)` of the configuration's reference
    module, at rehearsal size: what `control.py` prints."""
    config = rehearsal_config(H, cell)
    module = H.load_module("reference", config["reference"]["module"])
    data = H.load_module("data", config["data"]["generator"]).generate(
        config["data"]["params"], 4000000007)
    got = module.readings(config, data, 4000000007, _half)
    assert list(got) == ["reference", "control_bfloat16",
                         "fault_half_left_out", "fault_answer_altered"]
    limits = config["reference"]["limits"]
    assert all(abs(v) < 1e-9 for v in got["reference"].values())
    for reading in list(got)[1:]:
        assert set(limits) <= set(got[reading])
    # bfloat16 keeps 8 bits; whether that passes a limit is for the cell's
    # own size to say (PERF.md section 2), a fault fails one at any size
    assert max(got["control_bfloat16"].values()) > 1e-3, got
    for fault in ("fault_half_left_out", "fault_answer_altered"):
        assert any(got[fault][k] > limits[k] for k in limits), got


@pytest.mark.parametrize("line", [json.loads(ln) for ln in (
    BENCH.parent / "tests" / "benchmark" / "data" /
    "control_rehearsal_pr29.jsonl").read_text().splitlines()],
    ids=lambda ln: ln["workload"])
def test_control_prints_what_it_printed_before_the_readings_moved(line):
    """`control.py --rehearsal` on seed 2147483777, as PR 29's tree printed
    it when the readings were functions of `control.py` itself."""
    got, = run_control(line["workload"], str(line["seed"]))
    assert list(got) == list(line)
    for reading, numbers in line.items():
        if isinstance(numbers, dict):
            assert got[reading] == pytest.approx(numbers, rel=1e-9, abs=1e-15)
        else:
            assert got[reading] == numbers
