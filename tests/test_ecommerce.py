"""E-commerce template tests: implicit-ALS recommendations, three predict
tiers, live seen/unavailable constraints, category/white/black-list rules."""

import numpy as np
import pytest

from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.events.event import DataMap, Event
from predictionio_tpu.models.ecommerce import ECommerceEngine, ECommQuery
from predictionio_tpu.models.ecommerce.engine import (
    ECommAlgorithmParams,
    ECommDataSourceParams,
)
from predictionio_tpu.storage import App

APP = "ecommapp"


@pytest.fixture()
def ecomm_app(mem_storage):
    app_id = mem_storage.apps.insert(App(0, APP))
    rng = np.random.default_rng(11)
    events = []
    # two taste clusters: even users view/buy a-items, odd users z-items
    for u in range(40):
        items = [f"a{i}" for i in range(6)] if u % 2 == 0 else [f"z{i}" for i in range(6)]
        for it in items:
            if rng.random() < 0.8:
                events.append(Event(event="view", entity_type="user",
                                    entity_id=f"u{u}", target_entity_type="item",
                                    target_entity_id=it))
            if rng.random() < 0.3:
                events.append(Event(event="buy", entity_type="user",
                                    entity_id=f"u{u}", target_entity_type="item",
                                    target_entity_id=it))
    for i in range(6):
        events.append(Event(event="$set", entity_type="item", entity_id=f"a{i}",
                            properties=DataMap({"categories": ["alpha"]})))
        events.append(Event(event="$set", entity_type="item", entity_id=f"z{i}",
                            properties=DataMap({"categories": ["zeta"]})))
    mem_storage.l_events.insert_batch(events, app_id)
    return mem_storage, app_id


def make_ep(**algo_overrides):
    params = dict(app_name=APP, rank=8, num_iterations=10, alpha=2.0, mesh_dp=1)
    params.update(algo_overrides)
    return EngineParams(
        data_source_params=ECommDataSourceParams(app_name=APP),
        algorithm_params_list=[("ecomm", ECommAlgorithmParams(**params))],
    )


def trained(ep):
    engine = ECommerceEngine.apply()
    models = engine.train(ep)
    return engine.predictor(ep, models)


def items_of(res):
    return [s.item for s in res.item_scores]


def test_known_user_stays_in_cluster(ecomm_app):
    predict = trained(make_ep())
    res = predict(ECommQuery(user="u0", num=4))
    assert res.item_scores
    assert all(i.startswith("a") for i in items_of(res)), items_of(res)
    res = predict(ECommQuery(user="u1", num=4))
    assert all(i.startswith("z") for i in items_of(res)), items_of(res)


def test_category_white_black_rules(ecomm_app):
    predict = trained(make_ep())
    res = predict(ECommQuery(user="u0", num=6, categories=["zeta"]))
    assert res.item_scores and all(i.startswith("z") for i in items_of(res))
    res = predict(ECommQuery(user="u0", num=6, white_list=["a1", "a2"]))
    assert set(items_of(res)) <= {"a1", "a2"}
    res = predict(ECommQuery(user="u0", num=6, black_list=["a0", "a1"]))
    assert not {"a0", "a1"} & set(items_of(res))
    # unknown category name: nothing qualifies
    res = predict(ECommQuery(user="u0", num=6, categories=["nope"]))
    assert res.item_scores == []


def test_unavailable_items_update_live(ecomm_app):
    storage, app_id = ecomm_app
    predict = trained(make_ep())
    base = items_of(predict(ECommQuery(user="u0", num=3)))
    assert base
    # mark the top item unavailable — takes effect with NO retrain
    storage.l_events.insert(
        Event(event="$set", entity_type="constraint",
              entity_id="unavailableItems",
              properties=DataMap({"items": [base[0]]})), app_id)
    after = items_of(predict(ECommQuery(user="u0", num=3)))
    assert base[0] not in after and after
    # a newer constraint replaces (not extends) the previous list
    storage.l_events.insert(
        Event(event="$set", entity_type="constraint",
              entity_id="unavailableItems",
              properties=DataMap({"items": []})), app_id)
    assert base[0] in items_of(predict(ECommQuery(user="u0", num=3)))


def test_unseen_only_excludes_live_seen(ecomm_app):
    storage, app_id = ecomm_app
    predict = trained(make_ep(unseen_only=True))
    res = items_of(predict(ECommQuery(user="u0", num=6)))
    seen = {e.target_entity_id for e in storage.l_events.find(
        app_id, entity_type="user", entity_id="u0")}
    assert res and not (set(res) & seen)
    # a view recorded AFTER training is excluded too (live read)
    if res:
        storage.l_events.insert(
            Event(event="view", entity_type="user", entity_id="u0",
                  target_entity_type="item", target_entity_id=res[0]), app_id)
        assert res[0] not in items_of(predict(ECommQuery(user="u0", num=6)))


def test_unknown_user_with_recent_views_gets_similar(ecomm_app):
    storage, app_id = ecomm_app
    predict = trained(make_ep())
    # brand-new user (absent from training) views two z-items post-train
    for it in ["z0", "z1"]:
        storage.l_events.insert(
            Event(event="view", entity_type="user", entity_id="unew",
                  target_entity_type="item", target_entity_id=it), app_id)
    res = items_of(predict(ECommQuery(user="unew", num=3)))
    assert res, "similar-items fallback should fire"
    assert all(i.startswith("z") for i in res), res
    assert not {"z0", "z1"} & set(res), "recently viewed items are excluded"


def test_cold_user_popular_fallback_respects_rules(ecomm_app):
    predict = trained(make_ep())
    res = items_of(predict(ECommQuery(user="nobody", num=4)))
    assert res, "popular fallback should return items"
    res = items_of(predict(ECommQuery(user="nobody", num=4, categories=["alpha"])))
    assert res and all(i.startswith("a") for i in res)


def test_model_roundtrip_serves_identically(ecomm_app):
    import pickle

    engine = ECommerceEngine.apply()
    ep = make_ep()
    models = engine.train(ep)
    restored = [pickle.loads(pickle.dumps(m)) for m in models]
    q = ECommQuery(user="u0", num=4)
    a = engine.predictor(ep, models)(q).to_json()
    b = engine.predictor(ep, restored)(q).to_json()
    assert a == b


def test_explicitly_empty_whitelist_returns_nothing(ecomm_app):
    predict = trained(make_ep())
    assert items_of(predict(ECommQuery(user="u0", num=4, white_list=[]))) == []
    # and via the wire format: present-but-empty != absent
    q = ECommQuery.from_json({"user": "u0", "num": 4, "whiteList": []})
    assert q.white_list == []
    assert ECommQuery.from_json({"user": "u0"}).white_list is None


def test_first_revision_pickle_format_migrates(ecomm_app):
    """Models persisted by the first ECommModel revision (dense cat_masks +
    cat-name dict in state) still load and serve identically."""
    import pickle

    engine = ECommerceEngine.apply()
    ep = make_ep()
    models = engine.train(ep)
    m = models[0]
    old_state = {
        "X": m.user_factors, "Y": m.item_factors,
        "users": m.user_dict.to_state(), "items": m.item_dict.to_state(),
        "cats": m.cat_dict.to_state(), "cat_masks": m.cat_masks,
        "popular": m.popular,
    }
    restored = type(m).__new__(type(m))
    restored.__setstate__(old_state)
    assert sorted(restored.item_categories) == sorted(m.item_categories)
    assert (restored.cat_masks == m.cat_masks).all()
    q = ECommQuery(user="u0", num=4, categories=["alpha"])
    a = engine.predictor(ep, models)(q).to_json()
    b = engine.predictor(ep, [restored])(q).to_json()
    assert a == b


def test_ecomm_serve_batch_matches_serial(ecomm_app):
    """serve_batch_predict ≡ predict across tier-1 (known user), tier-2
    (recent-similar), tier-3 (popularity), rules, and infeasible queries
    in one batch."""
    from predictionio_tpu.models.ecommerce import ECommerceEngine

    engine = ECommerceEngine.apply()
    ep = make_ep()
    models = engine.train(ep)
    model = models[0]
    name, params = ep.algorithm_params_list[0]
    algo = engine.algorithm_classes[name](params)
    queries = [
        ECommQuery(user="u0", num=4),
        ECommQuery(user="u1", num=4),
        ECommQuery(user="totally-new", num=4),           # popularity tier
        ECommQuery(user="u0", num=6, categories=["zeta"]),
        ECommQuery(user="u0", num=6, white_list=["a1", "a2"]),
        ECommQuery(user="u0", num=6, black_list=["a0", "a1"]),
        ECommQuery(user="u0", num=6, categories=["nope"]),  # infeasible
    ]
    serial = [algo.predict(model, q) for q in queries]
    batched = algo.serve_batch_predict(model, queries)
    for q, s, b in zip(queries, serial, batched):
        s_i = [(r.item, round(r.score, 4)) for r in s.item_scores]
        b_i = [(r.item, round(r.score, 4)) for r in b.item_scores]
        assert s_i == b_i, (q, s_i, b_i)


def test_cell_fold_sums_weighted_events_under_a_layout_span(ecomm_app, tmp_path):
    """The engine's cell fold: each (user, item) cell's strength is the sum
    of its events' weights, the popularity of an item the sum over its
    cells, as a float32 `np.add.at` over the events gives them; the fold
    runs under a `layout` span that counts events and cells."""
    from predictionio_tpu.models.ecommerce.engine import (
        ECommAlgorithm, ECommDataSource)
    from predictionio_tpu.obs import spans as obs_spans

    ep = make_ep(event_weights={"buy": 4.5})
    td = ECommDataSource(ep.data_source_params).read_training()
    with obs_spans.SpanJournal(tmp_path / "fold.jsonl").activate():
        model = ECommAlgorithm(ep.algorithm_params_list[0][1]).train(td)
    w = np.where(np.asarray(td.event_names)[td.event_codes] == "buy", 4.5,
                 1.0).astype(np.float32)
    popular = np.zeros(len(td.item_dict), np.float32)
    np.add.at(popular, td.item_idx, w)
    assert np.array_equal(model.popular, popular)
    cells = np.unique(td.user_idx.astype(np.int64) * len(td.item_dict)
                      + td.item_idx)
    (fold,) = [s for s in obs_spans.recent_runs()[-1]
               if s["name"] == "layout" and "cells" in s.get("attrs", {})]
    assert fold["attrs"] == {"events": len(td.user_idx), "cells": len(cells)}
