"""Complementary Purchase template tests: basket sessionization, rule
mining (support/confidence/lift), cart-aggregated serving."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.events.event import Event
from predictionio_tpu.models.complementary_purchase import (
    ComplementaryPurchaseEngine,
    CPQuery,
)
from predictionio_tpu.models.complementary_purchase.engine import (
    CPAlgorithmParams,
    CPDataSourceParams,
)
from predictionio_tpu.ops import cco as cco_ops
from predictionio_tpu.ops.cco import basket_rules, session_baskets
from predictionio_tpu.storage import App

APP = "cpapp"
T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


@pytest.fixture()
def cp_app(mem_storage):
    app_id = mem_storage.apps.insert(App(0, APP))
    rng = np.random.default_rng(6)
    events = []
    # coffee+filter bought together; tea+kettle together; bread alone.
    # One basket per (user, day): events inside a basket are seconds apart,
    # different days are far beyond the 1-hour window.
    for u in range(60):
        for day in range(3):
            base = T0 + dt.timedelta(days=day, hours=u % 12)
            basket = (["coffee", "filter"] if (u + day) % 2 == 0
                      else ["tea", "kettle"])
            if rng.random() < 0.3:
                basket = basket + ["bread"]
            for k, item in enumerate(basket):
                events.append(Event(
                    event="buy", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=item,
                    event_time=base + dt.timedelta(seconds=k)))
    mem_storage.l_events.insert_batch(events, app_id)
    return mem_storage, app_id


def make_ep(**algo):
    return EngineParams(
        data_source_params=CPDataSourceParams(app_name=APP),
        algorithm_params_list=[("rules", CPAlgorithmParams(**algo))],
    )


def test_basket_sessionization(cp_app):
    engine = ComplementaryPurchaseEngine.apply()
    ds = engine.make_components(make_ep())[0]
    td = ds.read_training()
    # the baskets are the algorithm's to form, by its window (an hour here)
    basket_idx, _, n_baskets = session_baskets(
        td.user_idx, td.item_idx, td.times_us, 3600 * 10**6)
    # 60 users x 3 days = 180 baskets
    assert n_baskets == 180
    # every basket holds 2 or 3 items
    sizes = np.bincount(basket_idx)
    assert set(sizes.tolist()) <= {2, 3}


def test_complements_found_and_ranked(cp_app):
    engine = ComplementaryPurchaseEngine.apply()
    ep = make_ep(min_support=0.01, min_confidence=0.2)
    models = engine.train(ep)
    predict = engine.predictor(ep, models)
    res = predict(CPQuery(items=["coffee"], num=2))
    items = [s.item for s in res.item_scores]
    assert items and items[0] == "filter", items
    assert "coffee" not in items
    res = predict(CPQuery(items=["tea"], num=2))
    assert [s.item for s in res.item_scores][0] == "kettle"
    # cart aggregation: two antecedents still exclude the cart itself
    res = predict(CPQuery(items=["coffee", "tea"], num=4))
    items = [s.item for s in res.item_scores]
    assert not {"coffee", "tea"} & set(items)
    assert {"filter", "kettle"} <= set(items)


def test_min_confidence_prunes_weak_rules(cp_app):
    engine = ComplementaryPurchaseEngine.apply()
    # bread co-occurs randomly (30%) with everything: a high confidence
    # cut keeps the deterministic pairs and drops bread rules
    ep = make_ep(min_support=0.01, min_confidence=0.9)
    models = engine.train(ep)
    predict = engine.predictor(ep, models)
    res = predict(CPQuery(items=["bread"], num=5))
    assert res.item_scores == []
    res = predict(CPQuery(items=["coffee"], num=5))
    assert [s.item for s in res.item_scores] == ["filter"]


def test_basket_rules_op_exact_metrics():
    # 5 baskets: {0,1} x4, {2} x1 -> conf(0->1)=1, lift=1/(4/5)=1.25
    b = np.array([0, 0, 1, 1, 2, 2, 3, 3, 4], np.int32)
    i = np.array([0, 1, 0, 1, 0, 1, 0, 1, 2], np.int32)
    lift, idx, conf = basket_rules(b, i, 5, 3, top_k=2)
    assert idx[0][0] == 1 and conf[0][0] == 1.0
    assert abs(lift[0][0] - 1.25) < 1e-6
    assert idx[2][0] == -1
    # duplicate items in one basket do not inflate counts (scatter-max)
    b2 = np.concatenate([b, [0, 0]]).astype(np.int32)
    i2 = np.concatenate([i, [0, 1]]).astype(np.int32)
    lift2, idx2, conf2 = basket_rules(b2, i2, 5, 3, top_k=2)
    assert np.allclose(lift[np.isfinite(lift)], lift2[np.isfinite(lift2)])


def test_model_roundtrip(cp_app):
    import pickle

    engine = ComplementaryPurchaseEngine.apply()
    ep = make_ep(min_support=0.01, min_confidence=0.2)
    models = engine.train(ep)
    restored = [pickle.loads(pickle.dumps(m)) for m in models]
    q = CPQuery(items=["coffee"], num=3)
    assert (engine.predictor(ep, models)(q).to_json()
            == engine.predictor(ep, restored)(q).to_json())


def _basket_shape(n_baskets: int, n_items: int, item_tile: int):
    """``(tile, tiles, chunk, chunks, group)`` as `basket_rules` derives
    them: UR's tiling, and `_block_plan` for a step with no slab of its
    own and one float32 tile."""
    tile, n_tiles = cco_ops._tiling(n_items, item_tile)
    chunk, group, _ = cco_ops._block_plan(
        n_baskets, n_tiles * tile, tile, n_tiles, own_slab=False, f32_tiles=1)
    return tile, n_tiles, chunk, max(-(-n_baskets // chunk), 1), group


def _step_bytes(chunk: int, group: int, width: int, tile: int) -> int:
    """What `_block_plan` reckons for a basket step in bf16: the carried
    group, one float32 tile, the densified chunk three times."""
    return (group + 1) * width * tile * 4 + chunk * 3 * width * 2


def _small_plan(monkeypatch, chunk: int, group: int = 1):
    """Basket chunks of ``chunk`` rows and ``group`` tiles a step: the block
    the matmul needs shrunk to the chunk, and the plan's budget to exactly
    what such a step takes (see _block_plan)."""
    real = cco_ops._block_plan

    def plan(n_rows, width, tile, n_tiles, **flags):
        monkeypatch.setattr(cco_ops, "_TILED_P_BYTES",
                            _step_bytes(chunk, group, width, tile))
        return real(n_rows, width, tile, n_tiles, **flags)

    monkeypatch.setattr(cco_ops, "_BLOCK_ROWS", chunk)
    monkeypatch.setattr(cco_ops, "_block_plan", plan)


def test_the_plan_of_the_basket_program_at_the_cells_size(monkeypatch):
    """65,536 baskets x 100,000 items: 25 tiles of 4,096 counted five to a
    group against 32 chunks of 2,048, planned at 11.32 GB of the 12 GB the
    rule has; a shop of few baskets is one chunk, a chip half the size
    counts one tile a step against a chunk of 4,096, or two in int8."""
    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    assert _basket_shape(65_536, 100_000, 4096) == (4096, 25, 2048, 32, 5)
    assert _step_bytes(2048, 5, 25 * 4096, 4096) == pytest.approx(
        11.32e9, rel=1e-3)
    assert _basket_shape(900, 100_000, 4096) == (4096, 25, 1024, 1, 5)
    assert _basket_shape(5, 3, 4096) == (3, 1, 128, 1, 1)
    monkeypatch.setattr(cco_ops, "_TILED_P_BYTES", cco_ops._TILED_P_BYTES // 2)
    assert _basket_shape(65_536, 100_000, 4096) == (4096, 25, 4096, 16, 1)
    monkeypatch.setenv("PIO_CCO_MM_DTYPE", "int8")
    assert _basket_shape(65_536, 100_000, 4096) == (4096, 25, 2048, 32, 2)


@pytest.mark.parametrize("n_baskets,n_items,tile,chunk,group,min_size", [
    (300, 150, 32, 128, 2, 1),   # 5 tiles: two groups of 2 and a last one
                                 # of 1; a last tile of 22; a last chunk of 44
    (300, 150, 32, 128, 3, 1),   # one whole group and a last one of 2
    (300, 150, 32, 128, 5, 1),   # every tile against one densify of a chunk
    (300, 128, 32, 256, 2, 1),   # whole tiles, two whole groups, 2 chunks
    (100, 150, 32, 128, 4, 1),   # one chunk only
    (400, 90, 32, 128, 2, 2),    # baskets of one item dropped: N is fewer
])
def test_grouped_basket_program_equals_one_tile_a_step_to_the_bit(
        monkeypatch, n_baskets, n_items, tile, chunk, group, min_size):
    """Several item tiles counted against one densified chunk give the
    counts one tile a step gives, and the tiles merge in the same order:
    lifts, ids and confidences are equal to the bit, the budget alone
    deciding the group."""
    from predictionio_tpu.obs.spans import SpanCollector

    rng = np.random.default_rng(360 + group)
    gb = rng.integers(0, n_baskets, 6 * n_baskets).astype(np.int32)
    gi = (rng.zipf(1.3, 6 * n_baskets) % n_items).astype(np.int32)
    if min_size > 1:            # a fifth of the baskets hold one item
        lone = gb % 5 == 0
        gi[lone] = gb[lone] % n_items

    def run(g):
        monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
        _small_plan(monkeypatch, chunk, g)
        with SpanCollector().activate() as collector:
            out = basket_rules(gb, gi, n_baskets, n_items, top_k=6,
                               min_support=0.004, min_confidence=0.05,
                               min_basket_size=min_size, item_tile=tile)
        monkeypatch.undo()
        attrs = {s["name"]: s["attrs"] for s in collector.spans()}
        return out, attrs["dispatch"], attrs["layout"]

    one, at_one, _ = run(1)
    grouped, at_group, laid = run(group)
    n_tiles = -(-n_items // tile)
    n_chunks = -(-laid["baskets"] // chunk)
    assert (laid["baskets_dropped"] > n_baskets // 10) == (min_size > 1)
    assert (at_one["chunk"], at_one["tile_group"]) == (chunk, 1)
    assert at_one["steps"] == n_tiles * n_chunks
    assert (at_group["chunk"], at_group["tile_group"]) == (chunk, group)
    assert at_group["plan_bytes"] == _step_bytes(chunk, group, n_tiles * tile,
                                                 tile)
    assert (at_group["tiles"], at_group["chunks"]) == (n_tiles, n_chunks)
    assert at_group["steps"] == -(-n_tiles // group) * n_chunks
    for a, b in zip(one, grouped):
        assert np.array_equal(a, b)
    assert (one[1] >= 0).sum() > n_items    # and there is something to compare


def test_basket_rules_chunked_exact(monkeypatch):
    """Counts stay exact when baskets span many scan chunks."""
    _small_plan(monkeypatch, 256)
    rng = np.random.default_rng(1)
    n_baskets, n_items = 1000, 8
    b = np.concatenate([np.arange(n_baskets),      # none empty: N is 1000
                        rng.integers(0, n_baskets, 7000)]).astype(np.int32)
    i = rng.integers(0, n_items, 8000).astype(np.int32)
    assert _basket_shape(n_baskets, n_items, 4096)[2:] == (256, 4, 1)
    lift, idx, conf = basket_rules(b, i, n_baskets, n_items, top_k=n_items)
    # dense numpy reference
    B = np.zeros((n_baskets, n_items))
    B[b, i] = 1.0
    C = B.T @ B
    ci = np.diag(C)
    for row in range(n_items):
        for k_, j in enumerate(idx[row]):
            if j < 0:
                continue
            conf_ref = C[row, j] / max(ci[row], 1)
            lift_ref = conf_ref / (ci[j] / n_baskets)
            assert abs(conf[row, k_] - conf_ref) < 1e-5
            assert abs(lift[row, k_] - lift_ref) < 1e-4


def _host_reference_rules(gb, gi, n_baskets, n_items, top_k,
                          min_support=0.0, min_confidence=0.0):
    """Exact numpy reference from sparse pairs (no dense matrix)."""
    pairs = sorted(set(zip(gb.tolist(), gi.tolist())))
    by_basket = {}
    ci = np.zeros(n_items, np.int64)
    for b, i in pairs:
        by_basket.setdefault(b, []).append(i)
        ci[i] += 1
    counts = {}
    for items in by_basket.values():
        for i in items:
            for j in items:
                if i != j:
                    counts[(i, j)] = counts.get((i, j), 0) + 1
    n = max(float(n_baskets), 1.0)
    rules = {}
    for (i, j), c in counts.items():
        support, conf = c / n, c / ci[i]
        lift = conf / (ci[j] / n)
        if support >= min_support and conf >= min_confidence:
            rules.setdefault(i, []).append((lift, j, conf))
    out = {}
    for i, rs in rules.items():
        rs.sort(key=lambda t: (-t[0], t[1]))
        out[i] = rs[:top_k]
    return out


def test_basket_rules_tiled_matches_dense(monkeypatch):
    """Several ragged tiles and basket chunks against one tile and one
    chunk (the whole count matrix at once): identical lift/ids/confidence
    (modulo tie order)."""
    rng = np.random.default_rng(8)
    n_baskets, n_items = 300, 90
    gb = rng.integers(0, n_baskets, 2_000).astype(np.int32)
    gi = rng.integers(0, n_items, 2_000).astype(np.int32)
    dense = basket_rules(gb, gi, n_baskets, n_items, top_k=6,
                         min_support=0.004, min_confidence=0.1)
    _small_plan(monkeypatch, 256)
    assert _basket_shape(n_baskets, n_items, 32) == (32, 3, 256, 2, 1)
    tiled = basket_rules(gb, gi, n_baskets, n_items, top_k=6,
                         min_support=0.004, min_confidence=0.1,
                         item_tile=32)
    np.testing.assert_allclose(dense[0], tiled[0], rtol=1e-5)
    for r in range(n_items):
        fin = np.isfinite(dense[0][r])
        assert set(dense[1][r][fin]) == set(tiled[1][r][fin])
    np.testing.assert_allclose(np.sort(dense[2], axis=1),
                               np.sort(tiled[2], axis=1), rtol=1e-5)


def test_basket_rules_past_old_cap():
    """The 40k-item cliff is gone: a 41k-item catalog trains on the tiled
    strategy and matches an exact sparse host reference row for row."""
    rng = np.random.default_rng(9)
    n_baskets, n_items = 200, 41_000
    # clustered baskets so real rules exist among high ids too
    gb = np.repeat(np.arange(n_baskets, dtype=np.int32), 6)
    base = rng.integers(0, n_items - 8, n_baskets)
    gi = (base[:, None] + rng.integers(0, 8, (n_baskets, 6))).astype(np.int32).ravel()
    st, si, conf = basket_rules(gb, gi, n_baskets, n_items, top_k=5,
                                item_tile=8192)
    assert st.shape == (n_items, 5)
    ref = _host_reference_rules(gb, gi, n_baskets, n_items, top_k=5)
    checked = 0
    for i, rs in list(ref.items())[:300]:
        got_lift = st[i][np.isfinite(st[i])]
        want_lift = np.array([t[0] for t in rs], np.float64)
        np.testing.assert_allclose(
            got_lift, want_lift[: len(got_lift)], rtol=1e-4)
        want_conf = {j: c for (_, j, c) in rs}
        for lift_v, j, cv in zip(st[i], si[i], conf[i]):
            if j >= 0 and j in want_conf:
                np.testing.assert_allclose(cv, want_conf[j], rtol=1e-4)
                checked += 1
    assert checked > 100


def test_cp_serve_batch_matches_serial(cp_app):
    """serve_batch_predict ≡ predict across carts, multi-item carts, and
    unresolvable carts in one batch."""
    engine = ComplementaryPurchaseEngine.apply()
    ep = make_ep()
    models = engine.train(ep)
    model = models[0]
    name, params = ep.algorithm_params_list[0]
    algo = engine.algorithm_classes[name](params)
    queries = [
        CPQuery(items=["coffee"], num=3),
        CPQuery(items=["tea"], num=2),
        CPQuery(items=["coffee", "tea"], num=4),
        CPQuery(items=["nothing-known"], num=3),
        CPQuery(items=[], num=3),
    ]
    serial = [algo.predict(model, q) for q in queries]
    batched = algo.serve_batch_predict(model, queries)
    for q, s, b in zip(queries, serial, batched):
        s_i = [(r.item, round(r.score, 4)) for r in s.item_scores]
        b_i = [(r.item, round(r.score, 4)) for r in b.item_scores]
        assert s_i == b_i, (q, s_i, b_i)


# ---------------------------------------------------------------------------
# the template's own engine.json, and the engine against the plain reference
# (benchmark/reference/basket_pair_rules.py, numpy/scipy float64)
# ---------------------------------------------------------------------------

import importlib.util   # noqa: E402
import json   # noqa: E402
from pathlib import Path   # noqa: E402

from predictionio_tpu.models.complementary_purchase.engine import (  # noqa: E402
    CPAlgorithm, CPTrainingData)
from predictionio_tpu.store.columnar import IdDict   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
CP_CONFIG = json.loads((BENCH / "configs" / "cp-ecom-100k.json").read_text())
LIMITS = CP_CONFIG["reference"]["limits"]
# apache/predictionio-template-complementary-purchase, engine.json, key for key
PUBLISHED = {
    "id": "default",
    "description": "Default settings",
    "engineFactory": "predictionio_tpu.models.complementary_purchase."
                     "ComplementaryPurchaseEngine",
    "datasource": {"params": {"appName": APP}},
    "algorithms": [{"name": "algo", "params": {
        "basketWindow": 120, "maxRuleLength": 2, "minSupport": 0.1,
        "minConfidence": 0.6, "minLift": 1.0, "minBasketSize": 2,
        "maxNumRulesPerCond": 5}}],
}


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "basket_pair_rules")


def _published(**algo):
    variant = json.loads(json.dumps(PUBLISHED))
    variant["algorithms"][0]["name"] = "rules"
    variant["algorithms"][0]["params"].update(algo)
    return variant


def test_published_engine_json_loads_and_trains(cp_app):
    """The template's file as published: every key binds, under its own
    name, and the engine trains and answers from it (coffee -> filter in
    half of the baskets: support 0.5, confidence 1, lift 2)."""
    from predictionio_tpu.workflow import create_workflow

    _, engine, ep = create_workflow.engine_from_variant(_published())
    algo = ep.algorithm_params_list[0][1]
    assert (algo.basket_window, algo.max_rule_length, algo.min_support,
            algo.min_confidence, algo.min_lift, algo.min_basket_size,
            algo.max_num_rules_per_cond) == (120, 2, 0.1, 0.6, 1.0, 2, 5)
    assert ep.data_source_params.app_name == APP
    models = engine.train(ep)
    res = engine.predictor(ep, models)(CPQuery(items=["coffee"], num=5))
    assert [(s.item, round(s.score, 4)) for s in res.item_scores] == [
        ("filter", 2.0)]
    # bread (in 30% of the baskets, at random) passes no 0.6 confidence
    assert engine.predictor(ep, models)(
        CPQuery(items=["bread"], num=5)).item_scores == []


@pytest.mark.parametrize("length", [1, 3, 4])
def test_a_rule_length_other_than_two_is_refused(length):
    from predictionio_tpu.workflow import create_workflow

    with pytest.raises(ValueError, match="maxRuleLength.*only pair rules"):
        CPAlgorithmParams.from_json({"maxRuleLength": length})
    with pytest.raises(ValueError, match="cannot run"):
        create_workflow.engine_from_variant(_published(maxRuleLength=length))


@pytest.mark.parametrize("older,now", [
    ({"maxRulesPerItem": 7}, {"maxNumRulesPerCond": 7}),
    ({"max_rules_per_item": 7}, {"max_num_rules_per_cond": 7}),
])
def test_the_older_spelling_of_the_rule_count_still_loads(older, now):
    assert CPAlgorithmParams.from_json(older) == CPAlgorithmParams.from_json(now)
    assert CPAlgorithmParams.from_json(older).max_num_rules_per_cond == 7


def test_one_place_decides_the_basket_window():
    from predictionio_tpu.models.complementary_purchase.engine import (
        basket_window_seconds)

    def td(window):
        return CPTrainingData(np.empty(0, np.int32), np.empty(0, np.int32),
                              np.empty(0, np.int64), IdDict([]), window)

    assert basket_window_seconds(CPAlgorithmParams(), td(None)) == 3600.0
    assert basket_window_seconds(CPAlgorithmParams(), td("10 minutes")) == 600.0
    assert basket_window_seconds(
        CPAlgorithmParams(basket_window=120), td(None)) == 120.0
    assert basket_window_seconds(
        CPAlgorithmParams(basket_window=600), td("10 minutes")) == 600.0
    with pytest.raises(ValueError, match="set one"):
        basket_window_seconds(CPAlgorithmParams(basket_window=120),
                              td("10 minutes"))


SHOP = dict(n_users=150, n_items=700, n_kept=900, n_single=220, mean_items=6,
            max_items=12, zipf=1.3, complements=4, within_s=60, between_s=3600,
            floors={"rules": 300, "condition_items": 100})


@pytest.mark.parametrize("seed", [3, 4000000007])
def test_engine_train_on_the_blocked_program_against_the_reference(
        mem_storage, monkeypatch, seed):
    """cp-ecom-100k's engine.json at a small size: 900 kept baskets in
    chunks of 256 (the last holds 132), 700 items in tiles of 256 (the
    last holds 188) counted two to a group (the last group holds one),
    220 one-item visits dropped.  `Engine.train` from the
    engine variant, the Pallas tournament interpreted, every row of the
    persisted table held against the plain reference by the
    configuration's limits."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.workflow import create_workflow

    monkeypatch.setenv("PIO_PALLAS", "interpret")
    _small_plan(monkeypatch, 256, group=2)
    data = _bench_module("data", "shop_visits").generate(SHOP, seed)
    assert _basket_shape(data["n_baskets"], data["n_items"], 256) == (
        256, 3, 256, 4, 2)
    app_id = mem_storage.apps.insert(App(0, "blocked"))
    wire = _bench_module("drivers", "train_jobs").wire_events
    for r in mem_storage.l_events.insert_json_batch(
            list(wire(data["blocks"][0])), app_id):
        assert r["status"] == 201
    variant = json.loads(json.dumps(CP_CONFIG["engine"]).replace(
        "$app", "blocked"))
    variant["algorithms"][0]["params"].update(minSupport=0.002, itemTile=256)
    _, engine, params = create_workflow.engine_from_variant(variant)
    with SpanCollector().activate() as collector:
        models = engine.train(params)

    spans = collector.spans()
    n_events = len(data["blocks"][0]["users"])
    dispatched = [s["attrs"] for s in spans if s["name"] == "dispatch"]
    # densify + count steps: groups x chunks, a whole group and one of 1
    assert dispatched == [{"program": "_basket_rules_tiled", "topk": "pallas",
                           "tiles": 3, "chunks": 4, "steps": 2 * 4,
                           "chunk": 256, "tile_group": 2,
                           "plan_bytes": cco_ops._TILED_P_BYTES,
                           "topk_block": 8, "topk_slab_stages": 10.0,
                           "topk_lane_stages": 0.0}]
    formed, laid = [s["attrs"] for s in spans if s["name"] == "layout"]
    assert formed == {"events": n_events, "baskets_formed": 900 + 220}
    assert laid == {"events": n_events, "baskets": 900,
                    "baskets_dropped": 220}
    (h2d,) = [s["attrs"]["bytes"] for s in spans if s["name"] == "h2d"]
    # the chunk-grouped log (row, item: four chunks padded to the fullest,
    # by eights), a count a chunk, and the per-item counts of three tiles
    slots, odd = divmod(h2d // 4 - 4 - 3 * 256, 2)
    assert odd == 0 and slots % (4 * 8) == 0
    assert n_events - 220 <= slots < 2 * n_events
    (wait,) = [s["attrs"]["bytes"] for s in spans if s["name"] == "device_wait"]
    assert wait == 2 * 4 * 768 * 8       # the carry: block_width(5) wide
    names = [s["name"] for s in spans]
    assert names.index("layout") < names.index("h2d") < names.index(
        "dispatch") < names.index("device_wait")

    checks = REF.check(models[0], data, variant, LIMITS, seed)
    assert {c["name"] for c in checks} == set(LIMITS)
    for c in checks:
        assert c["ok"], checks
    assert (models[0].comp_idx >= 0).sum() > 300


def _hand_log():
    """Three shoppers; buys 50 s apart inside a visit, but for u0's second
    visit, whose two buys lie exactly 100 s apart; visits a day apart."""
    day = 86_400
    visits = [  # (user, start s, gap s, items)
        (0, 0, 50, [0, 1, 2]), (0, day, 100, [0, 1]), (0, 2 * day, 50, [3]),
        (1, 0, 50, [0, 1]), (1, day, 50, [1, 2]), (1, 2 * day, 50, [0, 3]),
        (2, 0, 50, [2, 3]), (2, day, 50, [0, 1, 3]), (2, 2 * day, 50, [4]),
    ]
    users, items, times = [], [], []
    for u, start, gap, its in visits:
        for k, it in enumerate(its):
            users.append(u), items.append(it)
            times.append((start + k * gap) * 10**6)
    return (np.array(users, np.int32), np.array(items, np.int32),
            np.array(times, np.int64))


BASE = dict(basketWindow=120, maxRuleLength=2, minSupport=0.0,
            minConfidence=0.0, minLift=0.0, minBasketSize=1,
            maxNumRulesPerCond=4)


def _train_and_hold(algo: dict):
    """CPAlgorithm.train on the hand log, held to the reference under the
    same parameters; the model's (idx, lift) and the baskets it counted."""
    users, items, times = _hand_log()
    td = CPTrainingData(users, items, times, IdDict([f"i{k}" for k in range(5)]))
    model = CPAlgorithm(CPAlgorithmParams.from_json(algo)).train(td)
    p = REF.params_of({"algorithms": [{"params": algo}]})
    block = {"users": users, "items": items, "times": times}
    n = REF.baskets(block, 5, p["window_us"], p["min_size"]).shape[0]
    data = {"n_items": 5, "n_baskets": n, "blocks": [block]}
    got = REF.compare(model.comp_idx, model.comp_lift, np.arange(5), data, p)
    assert got["lift_gap_max"] <= LIMITS["lift_gap_max"], got
    assert got["topk_gap_max"] <= LIMITS["topk_gap_max"], got
    assert got["baskets_gap"] == 0, got
    return model.comp_idx, model.comp_lift, n


@pytest.mark.parametrize("change,baskets", [
    ({"minLift": 1.0}, 9),              # lifts under 1 go
    ({"minBasketSize": 2}, 7),          # the two one-item visits go: N 9 -> 7
    ({"basketWindow": 99}, 10),         # just under the 100 s gap: it splits
    ({"basketWindow": 100}, 9),         # exactly the gap: it stays
    ({"basketWindow": 101}, 9),         # just over
    ({"maxNumRulesPerCond": 1}, 9),     # one rule an item
    ({"minSupport": 0.3}, 9),
    ({"minConfidence": 0.7}, 9),
], ids=lambda v: "-".join(f"{k}{x}" for k, x in v.items())
   if isinstance(v, dict) else None)
def test_each_parameter_acts_as_the_reference_says(change, baskets):
    base_idx, base_lift, n = _train_and_hold(BASE)
    assert n == 9
    idx, lift, n = _train_and_hold({**BASE, **change})
    assert n == baskets
    same = (idx.shape == base_idx.shape and (idx == base_idx).all()
            and np.array_equal(lift, base_lift))
    assert same == (change in ({"basketWindow": 100}, {"basketWindow": 101}))
    kept = np.isfinite(lift)
    if "minLift" in change:
        assert (lift[kept] >= 1.0).all() and (base_lift[np.isfinite(
            base_lift)] < 1.0).any()
    if "maxNumRulesPerCond" in change:
        assert idx.shape[1] == 1
        np.testing.assert_array_equal(lift[:, 0], base_lift[:, 0])


def test_a_rule_on_a_cut_does_not_decide_correct():
    """64 baskets; item 0 in 24, item 1 in 8, both in 3: lift(0 -> 1) =
    3 * 64 / (24 * 8) is exactly 1.0 = minLift.  The program keeps it, as
    float64 does; the reference reads no gap whether the program's table
    holds the rule or not, and reads a missing rule off the cut as
    missing."""
    rows = ([[0, 1]] * 3 + [[0, 2]] * 20 + [[0, 3]] + [[1, 3]] * 5
            + [[2, 3]] * 30 + [[4, 5]] * 5)
    b = np.repeat(np.arange(64), 2).astype(np.int32)
    i = np.array(rows, np.int32).ravel()
    lift, idx, _ = basket_rules(b, i, 64, 6, top_k=3, min_lift=1.0,
                                min_basket_size=2)
    (at,) = np.flatnonzero(idx[0] == 1)
    assert lift[0, at] == 1.0
    times = (b.astype(np.int64) * 86_400 + np.tile([0, 1], 64)) * 10**6
    data = {"n_items": 6, "n_baskets": 64, "blocks": [
        {"users": np.zeros(128, np.int64), "items": i, "times": times}]}
    p = {"window_us": 120 * 10**6, "min_size": 2, "cuts": (0.0, 0.0, 1.0),
         "k": 3}
    ref = REF.cells(REF.baskets(data["blocks"][0], 6, p["window_us"], 2),
                    p["cuts"])
    assert sorted(zip(ref["rows"][ref["edge"]], ref["cols"][ref["edge"]])
                  ) == [(0, 1), (1, 0)]

    def held(idx, lift):
        got = REF.compare(idx, lift, np.arange(6), data, p)
        return got["lift_gap_max"], got["topk_gap_max"], got["baskets_gap"]

    def without(row, col):
        idx_, lift_ = idx.copy(), lift.copy()
        (k,) = np.flatnonzero(idx[row] == col)
        idx_[row, k:] = np.append(idx[row, k + 1:], -1)
        lift_[row, k:] = np.append(lift[row, k + 1:], -np.inf)
        return idx_, lift_

    sound = held(idx, lift)
    assert sound[0] < 1e-6 and sound[1:] == (0.0, 0.0)
    assert held(*without(0, 1)) == sound          # on the cut: free
    # a rule well above its cuts (4 -> 5: lift 5 * 64 / (5 * 5)) is not
    assert idx[4].tolist() == [5, -1, -1] and abs(lift[4, 0] - 12.8) < 1e-5
    assert held(*without(4, 5))[1] == REF.BIG
    # nor is one the reference cuts (1 -> 3: lift 5 * 64 / (8 * 36) = 1.11
    # is kept; 0 -> 3, lift 64 / (24 * 36), is not): an extra rule
    extra = idx.copy(), lift.copy()
    assert extra[0][0, 2] == -1
    extra[0][0, 2], extra[1][0, 2] = 3, 0.074
    assert held(*extra)[0] == REF.BIG
