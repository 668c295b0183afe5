"""Shared-memory model plane: arena roundtrip exactness, read-only
mapped views, torn-arena quarantine, GC safety, watcher convergence, and
the prefork e2e (one fold per delta, one /reload converges every
worker).

The plane's contract is that a worker serving mapped views is
bit-indistinguishable from one serving the publisher's private model —
every test here diffs responses/arrays exactly, never approximately.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


# -- fixtures ----------------------------------------------------------------


def _buy(u, i, event="purchase"):
    from predictionio_tpu.events.event import Event

    return Event(event=event, entity_type="user", entity_id=u,
                 target_entity_type="item", target_entity_id=i)


def _set_item(i, props):
    from predictionio_tpu.events.event import DataMap, Event

    return Event(event="$set", entity_type="item", entity_id=i,
                 properties=DataMap(props))


def _seed(storage, app_name="mpapp", n_users=14, n_items=9, seed=5):
    from predictionio_tpu.storage.base import App

    app_id = storage.apps.insert(App(0, app_name))
    rng = np.random.default_rng(seed)
    evs = [_buy(f"u{u}", f"i{it}")
           for u in range(n_users) for it in range(n_items)
           if rng.random() < 0.5]
    evs += [_set_item(f"i{it}", {"category": f"c{it % 3}"})
            for it in range(n_items)]
    storage.l_events.insert_batch(evs, app_id)
    return app_id


def _ur(app_name="mpapp"):
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.universal_recommender import (
        UniversalRecommenderEngine,
    )
    from predictionio_tpu.models.universal_recommender.engine import (
        URAlgorithm, URAlgorithmParams, URDataSourceParams,
    )

    engine = UniversalRecommenderEngine.apply()
    ap = URAlgorithmParams(app_name=app_name, mesh_dp=1,
                           max_correlators_per_item=5)
    ep = EngineParams(
        data_source_params=URDataSourceParams(
            app_name=app_name, event_names=["purchase"]),
        algorithm_params_list=[("ur", ap)])
    return engine, ep, URAlgorithm(ap)


def _canon(res):
    return [(s.item, float(s.score)) for s in res.item_scores]


@pytest.fixture()
def host_serving(monkeypatch):
    monkeypatch.setenv("PIO_UR_SERVE_SCORER", "host")
    monkeypatch.setenv("PIO_UR_SERVE_TAIL", "host")


@pytest.fixture()
def plane_dir(tmp_path, monkeypatch):
    d = tmp_path / "plane"
    monkeypatch.setenv("PIO_MODEL_PLANE_POLL_S", "0.05")
    return str(d)


def _corpus():
    from predictionio_tpu.models.universal_recommender import URQuery

    return [URQuery.from_json(b) for b in (
        {"user": "u2", "num": 5},
        {"user": "nobody", "num": 4},
        {"user": "u3", "num": 5,
         "fields": [{"name": "category", "values": ["c1"], "bias": -1}]},
        {"user": "u4", "num": 5,
         "fields": [{"name": "category", "values": ["c0"], "bias": 2.0}]},
        {"user": "u5", "num": 5, "blacklistItems": ["i1", "i2"]},
        {"item": "i1", "num": 4},
    )]


# -- arena roundtrip ---------------------------------------------------------


def test_plane_roundtrip_bit_exact_and_readonly(mem_storage, host_serving,
                                                plane_dir):
    """A mapped generation is array-identical to the published model,
    answers every query identically, carries derived serving state
    pre-built, and rejects in-place mutation of the shared views."""
    from predictionio_tpu.streaming.plane import ModelPlane

    _seed(mem_storage)
    engine, ep, algo = _ur()
    model = engine.train(ep)[0]
    pub = ModelPlane(plane_dir)
    gen = pub.publish([model], {"mode": "test"})
    assert gen == 1
    sub = ModelPlane(plane_dir)
    mapped, info = sub.load(sub.current())
    assert info["planeGeneration"] == 1
    for name in model.indicator_idx:
        assert np.array_equal(mapped.indicator_idx[name],
                              model.indicator_idx[name])
        assert np.array_equal(mapped.indicator_llr[name],
                              model.indicator_llr[name])
        assert (mapped.event_item_dicts[name].strings()
                == model.event_item_dicts[name].strings())
        # derived CSR inversion rode the arena — no rebuild on the worker
        for a, b in zip(mapped.__dict__["_host_inv"][name],
                        model.host_inverted(name)):
            assert np.array_equal(a, b)
    assert np.array_equal(mapped.popularity, model.popularity)
    assert np.array_equal(mapped.__dict__["_host_pop_order"],
                          model.host_pop_order())
    assert np.array_equal(mapped.user_seen.indptr, model.user_seen.indptr)
    assert np.array_equal(mapped.user_seen.values, model.user_seen.values)
    assert dict(mapped.item_properties) == dict(model.item_properties)
    # responses identical (the live-store history path)
    for q in _corpus():
        assert _canon(algo.predict(mapped, q)) == _canon(
            algo.predict(model, q))
    # no worker can corrupt the shared mapping
    for arr in (mapped.indicator_idx["purchase"],
                mapped.indicator_llr["purchase"],
                mapped.popularity, mapped.user_seen.values,
                mapped.__dict__["_host_pop_order"],
                mapped.__dict__["_host_inv"]["purchase"][2]):
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[..., 0] = 1


def test_plane_dict_carry_and_extension(mem_storage, host_serving,
                                        plane_dir):
    """Unchanged dictionaries carry BY OBJECT across mapped generations;
    an end-grown item dictionary (publisher proves the byte-prefix)
    extends the worker's previous dictionary instead of rebuilding."""
    from predictionio_tpu.store.columnar import IdDict
    from predictionio_tpu.streaming.plane import ModelPlane

    _seed(mem_storage)
    engine, ep, _ = _ur()
    model = engine.train(ep)[0]
    pub, sub = ModelPlane(plane_dir), ModelPlane(plane_dir)
    pub.publish([model])
    m1, _ = sub.load(sub.current())
    rebuilt0 = sub.dicts_rebuilt
    # same model again: every dict carried by content crc
    pub.publish([model])
    m2, _ = sub.load(sub.current())
    assert m2.item_dict is m1.item_dict
    assert m2.user_dict is m1.user_dict
    assert sub.dicts_rebuilt == rebuilt0
    # end-grown item dict: clone + append (the fold engine's new-item
    # case) — worker extends, never re-decodes the covered prefix
    grown = model.item_dict.clone()
    grown.add("brand-new-item")
    import dataclasses as _dc  # noqa: F401  (document intent)
    model.item_dict = grown
    model.event_item_dicts = {"purchase": grown}
    model.indicator_idx = {
        "purchase": np.vstack([model.indicator_idx["purchase"],
                               -np.ones((1, model.indicator_idx[
                                   "purchase"].shape[1]), np.int32)])}
    model.indicator_llr = {
        "purchase": np.vstack([model.indicator_llr["purchase"],
                               np.zeros((1, model.indicator_llr[
                                   "purchase"].shape[1]), np.float32)])}
    model.popularity = np.concatenate(
        [np.asarray(model.popularity, np.float32), [0.0]])
    for k in ("_host_inv", "_host_pop_order", "_host_pop", "_pop_norm"):
        model.__dict__.pop(k, None)
    pub.publish([model])
    ext0 = sub.dicts_extended
    m3, _ = sub.load(sub.current())
    assert sub.dicts_extended == ext0 + 1
    assert m3.item_dict.strings() == grown.strings()
    assert isinstance(m3.item_dict, IdDict)


def test_torn_arena_quarantined_old_generation_serves(
        mem_storage, host_serving, plane_dir):
    """A publisher SIGKILL'd mid-emit leaves either an unreferenced tmp
    file (invisible) or a manifest pointing at a torn arena: the watcher
    quarantines the torn file, keeps the served generation, and heals on
    the next good publish."""
    from predictionio_tpu.streaming.plane import ModelPlane, PlaneWatcher

    _seed(mem_storage)
    engine, ep, algo = _ur()
    model = engine.train(ep)[0]
    pub = ModelPlane(plane_dir)
    pub.publish([model])
    sub = ModelPlane(plane_dir)
    installed = []
    watcher = PlaneWatcher(sub, lambda models, info: (
        installed.append((models[0], info)), True)[1], poll_s=0.05)
    assert watcher.check_now()
    assert watcher.generation == 1
    # a crash between arena write and manifest flip: tmp file only
    (Path(plane_dir) / ".gen-0000000002.arena.tmp-999").write_bytes(
        b"PIOARR01garbage")
    assert not watcher.check_now()          # manifest still at gen 1
    # a torn arena REFERENCED by the manifest (worst case: manifest
    # written, arena bytes truncated by the crash/disk)
    torn = Path(plane_dir) / "gen-0000000002.arena"
    torn.write_bytes(b"PIOARR01" + b"\x00" * 8)
    cur = pub.current()
    pub._write_manifest({**cur, "generation": 2,
                         "file": "gen-0000000002.arena"})
    assert not watcher.check_now()
    assert watcher.generation == 1          # old generation still serves
    assert (Path(plane_dir)
            / "gen-0000000002.arena.quarantine").exists()
    q = _corpus()[0]
    assert _canon(algo.predict(installed[-1][0], q)) == _canon(
        algo.predict(model, q))
    # the next good publish supersedes the quarantined generation
    gen = pub.publish([model])
    assert gen == 3
    assert watcher.check_now()
    assert watcher.generation == 3


def test_gc_keeps_window_and_never_breaks_a_mapped_arena(
        mem_storage, host_serving, plane_dir, monkeypatch):
    """GC unlinks generations past PIO_MODEL_PLANE_KEEP (counted in
    pio_model_plane_gc_total); a model still mapping an unlinked arena
    keeps serving identical responses — POSIX keeps the pages until the
    mapping drops.  Runs with delta arenas OFF — every generation is a
    full arena, so the keep window alone decides reclamation (the
    delta-chain refcount cases live in test_gc_refcount_*)."""
    from predictionio_tpu.obs import metrics as obs_metrics
    from predictionio_tpu.streaming.plane import ModelPlane

    monkeypatch.setenv("PIO_MODEL_PLANE_KEEP", "2")
    monkeypatch.setenv("PIO_MODEL_PLANE_DELTA", "off")
    _seed(mem_storage)
    engine, ep, algo = _ur()
    model = engine.train(ep)[0]
    pub, sub = ModelPlane(plane_dir), ModelPlane(plane_dir)
    pub.publish([model])
    mapped, _ = sub.load(sub.current())     # worker pins generation 1
    ref = [_canon(algo.predict(mapped, q)) for q in _corpus()]
    gc0 = obs_metrics.get_registry().counter(
        "pio_model_plane_gc_total", "x").value()
    for _ in range(4):
        pub.publish([model])                # gens 2..5; GC as it goes
    arenas = sorted(p.name for p in Path(plane_dir).glob("gen-*.arena"))
    assert arenas == ["gen-0000000004.arena", "gen-0000000005.arena"]
    assert obs_metrics.get_registry().counter(
        "pio_model_plane_gc_total", "x").value() > gc0
    # generation 1's file is unlinked, its mapping is not: the stale
    # worker serves bit-identical answers until it converges
    assert [_canon(algo.predict(mapped, q)) for q in _corpus()] == ref


# -- server topology ---------------------------------------------------------


def test_watcher_converges_two_states_and_single_reload(
        mem_storage, host_serving, plane_dir):
    """Two in-process query servers sharing one plane (the prefork
    topology minus process isolation): the initial publish converges
    both, ONE plane_reload on either converges both, and both serve
    identical bytes."""
    from predictionio_tpu.models.universal_recommender import URQuery
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_server import QueryServerState

    _seed(mem_storage)
    engine, ep, _ = _ur()
    core_workflow.run_train(engine, ep, engine_id="mp-engine",
                            storage=mem_storage)
    a = QueryServerState(engine, ep, URQuery, "mp-engine", "1", "default",
                         storage=mem_storage, plane_dir=plane_dir)
    b = QueryServerState(engine, ep, URQuery, "mp-engine", "1", "default",
                         storage=mem_storage, plane_dir=plane_dir)
    try:
        a.plane_publish_initial()
        deadline = time.time() + 10
        while time.time() < deadline and (
                a.plane_generation < 1 or b.plane_generation < 1):
            time.sleep(0.02)
        assert a.plane_generation == b.plane_generation == 1
        body = {"user": "u2", "num": 5}
        assert a.predict(body).to_json() == b.predict(body).to_json()
        gen, iid = b.plane_reload()
        assert gen == 2 and iid
        assert b.plane_generation == 2      # synchronous on the reloader
        deadline = time.time() + 10
        while time.time() < deadline and a.plane_generation < 2:
            time.sleep(0.02)
        assert a.plane_generation == 2      # sibling converged, no poll
        assert a.predict(body).to_json() == b.predict(body).to_json()
        assert a.info()["planeGeneration"] == 2
        assert a.freshness()["planeGeneration"] == 2
    finally:
        a.stop_auto_reload()
        b.stop_auto_reload()


def test_embedded_follower_publishes_through_plane(
        mem_storage, host_serving, plane_dir):
    """--workers 1 with PIO_MODEL_PLANE=on: the embedded follower IS the
    publisher — folds land in the arena, a sibling state converges, and
    post-drain responses equal a from-scratch retrain EXACTLY."""
    from predictionio_tpu.models.universal_recommender import URQuery
    from predictionio_tpu.store.event_store import invalidate_staging_cache
    from predictionio_tpu.streaming.follow import FollowTrainer
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_server import QueryServerState

    app_id = _seed(mem_storage)
    engine, ep, algo = _ur()
    core_workflow.run_train(engine, ep, engine_id="mp-engine",
                            storage=mem_storage)
    a = QueryServerState(engine, ep, URQuery, "mp-engine", "1", "default",
                         storage=mem_storage, plane_dir=plane_dir)
    b = QueryServerState(engine, ep, URQuery, "mp-engine", "1", "default",
                         storage=mem_storage, plane_dir=plane_dir)
    follower = None
    try:
        a.plane_publish_initial()
        follower = a.follower = FollowTrainer(
            engine, ep, "mp-engine", storage=mem_storage, interval=0.05,
            on_publish=a.plane_publish, persist=False)
        follower.start()
        g0_deadline = time.time() + 10
        while time.time() < g0_deadline and b.plane_generation < 1:
            time.sleep(0.02)
        gref = b.plane_generation
        mem_storage.l_events.insert_batch(
            [_buy("newbie", f"i{j}") for j in (0, 1, 2)], app_id)
        deadline = time.time() + 20
        while time.time() < deadline and not (
                a.plane_generation > gref
                and b.plane_generation == a.plane_generation
                and follower.last_outcome == "idle"):
            time.sleep(0.05)
        assert a.plane_generation > gref
        assert b.plane_generation == a.plane_generation
        invalidate_staging_cache()
        ref = engine.train(ep)[0]
        # post-drain parity on BOTH states (the publisher's own mapped
        # copy and the pure-consumer sibling) vs a from-scratch retrain
        bodies = [{"user": "u2", "num": 5}, {"user": "newbie", "num": 5},
                  {"user": "u3", "num": 5,
                   "fields": [{"name": "category", "values": ["c1"],
                               "bias": -1}]}]
        for st in (a, b):
            for body in bodies:
                got = st.predict(body).to_json()
                want = algo.predict(
                    ref, URQuery.from_json(body)).to_json()
                assert got == want, (body, got, want)
    finally:
        if follower is not None:
            follower.stop()
        a.stop_auto_reload()
        b.stop_auto_reload()


# -- delta arenas ------------------------------------------------------------


def _fold_state(n_items=1200, hist=4, k=5):
    """A resident fold state over a synthetic catalog (one buy per item,
    hist-item user histories — the freshness-sweep shape)."""
    from predictionio_tpu.events.event import Event
    from predictionio_tpu.models.universal_recommender.engine import (
        URAlgorithmParams, URDataSourceParams,
    )
    from predictionio_tpu.store.columnar import EventBatch
    from predictionio_tpu.streaming.fold import URFoldState

    ap = URAlgorithmParams(app_name="delta", mesh_dp=1,
                           max_correlators_per_item=k)
    dp = URDataSourceParams(app_name="delta", event_names=["buy"])
    evs = [Event(event="buy", entity_type="user",
                 entity_id=f"u{j // hist}", target_entity_type="item",
                 target_entity_id=f"i{j}") for j in range(n_items)]
    batch = EventBatch.from_events(evs)
    batch.prop_columns = {}
    return URFoldState.bootstrap(ap, dp, batch)


def _fold_delta(state, events):
    """Fold a delta batch sharing the state's dictionaries (the
    scan_tail contract) and return the emitted model, serving-state
    warm included."""
    from predictionio_tpu.store.columnar import EventBatch

    d = EventBatch.from_events(
        events, entity_dict=state.batch.entity_dict,
        target_dict=state.batch.target_dict,
        event_dict=state.batch.event_dict)
    d.prop_columns = {}
    model = state.fold(d)
    model.ensure_host_serving_state()
    return model


def _freshness_delta(state, r, n_items):
    """The PR-13 freshness-sweep round shape: new correlated users + a
    brand-new item — marginals move, so every finite LLR score changes
    and pure-ref publishing alone cannot stay small."""
    from predictionio_tpu.events.event import Event

    seed = f"i{(r * 97) % n_items}"
    evs = [Event(event="buy", entity_type="user", entity_id=f"probe{r}",
                 target_entity_type="item", target_entity_id=seed)]
    for j in range(4):
        for tgt in (seed, f"fresh_item_{r}"):
            evs.append(Event(event="buy", entity_type="user",
                             entity_id=f"cob{r}_{j}",
                             target_entity_type="item",
                             target_entity_id=tgt))
    return evs


def _assert_models_identical(a, b):
    """Every serialized array, derived structure, and dictionary —
    bit-exact, dtypes included."""
    for n in b.indicator_idx:
        pairs = [(a.indicator_idx[n], b.indicator_idx[n]),
                 (a.indicator_llr[n], b.indicator_llr[n])]
        pairs += list(zip(a.__dict__["_host_inv"][n], b.host_inverted(n)))
        for x, y in pairs:
            assert x.dtype == y.dtype
            assert np.array_equal(x, y)
        assert (a.event_item_dicts[n].strings()
                == b.event_item_dicts[n].strings())
    assert np.array_equal(a.popularity, b.popularity)
    po_a = a.__dict__["_host_pop_order"]
    po_b = b.host_pop_order()
    assert po_a.dtype == po_b.dtype and np.array_equal(po_a, po_b)
    assert np.array_equal(a.user_seen.indptr, b.user_seen.indptr)
    assert np.array_equal(a.user_seen.values, b.user_seen.values)
    for n, csr in b.user_seen_by_event.items():
        assert np.array_equal(a.user_seen_by_event[n].indptr, csr.indptr)
        assert np.array_equal(a.user_seen_by_event[n].values, csr.values)
    assert a.item_dict.strings() == b.item_dict.strings()
    assert a.user_dict.strings() == b.user_dict.strings()
    assert dict(a.item_properties) == dict(b.item_properties)


def test_delta_composed_bit_exact_vs_full_arena_oracle(
        plane_dir, tmp_path, monkeypatch):
    """The acceptance proof at test scale: freshness-shaped folds
    published as delta generations compose — on an incremental worker
    AND a cold mid-chain joiner — into models bit-identical to the
    PIO_MODEL_PLANE_DELTA=off full-arena oracle, every array, derived
    CSR, and dictionary included, while each delta writes ≤ 10% (and a
    duplicate-only fold ≤ 5%) of the full-arena bytes."""
    from predictionio_tpu.events.event import Event
    from predictionio_tpu.streaming.plane import ModelPlane

    # k=8 is the freshness-sweep shape (maxCorrelatorsPerItem) the
    # acceptance criterion is calibrated to: the delta floor is the
    # finite-LLR values, ≈ (nnz / (I_p·K)) of one table
    n_items = 2000
    state = _fold_state(n_items=n_items, k=8)
    pub = ModelPlane(plane_dir)
    worker = ModelPlane(plane_dir)
    oracle_pub = ModelPlane(str(tmp_path / "oracle"))
    oracle_sub = ModelPlane(str(tmp_path / "oracle"))

    def oracle_load(model):
        monkeypatch.setenv("PIO_MODEL_PLANE_DELTA", "off")
        try:
            oracle_pub.publish([model])
            return oracle_sub.load(oracle_sub.current())[0]
        finally:
            monkeypatch.delenv("PIO_MODEL_PLANE_DELTA")

    m0 = state.model
    m0.ensure_host_serving_state()
    pub.publish([m0], {"mode": "fold"})
    full_bytes = pub.last_publish_stats["written"]
    w0, _ = worker.load(worker.current())
    _assert_models_identical(w0, oracle_load(m0))
    cold = None
    for r in range(3):
        m = _fold_delta(state, _freshness_delta(state, r, n_items))
        pub.publish([m], {"mode": "fold"})
        st = pub.last_publish_stats
        assert os.path.exists(
            os.path.join(plane_dir, f"gen-{r + 2:010d}.delta"))
        assert st["written"] <= 0.10 * full_bytes, st
        wa, info = worker.load(worker.current())
        assert info["planeGeneration"] == r + 2
        ref = oracle_load(m)
        _assert_models_identical(wa, ref)
        if r == 1:
            cold = ModelPlane(plane_dir)    # joins mid-chain
        if cold is not None:
            wc, _ = cold.load(cold.current())
            _assert_models_identical(wc, ref)
        # composed arrays are read-only, like mapped views
        for arr in (wa.indicator_llr["buy"], wa.popularity,
                    wa.__dict__["_host_inv"]["buy"][2]):
            assert not arr.flags.writeable
    # duplicate-only fold: ~zero new bytes, asserted via the counter
    from predictionio_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.get_registry()

    def written_counter():
        c = reg.counter("pio_model_plane_publish_bytes_total", "x")
        return (c.value(path="full") or 0) + (c.value(path="delta") or 0)

    before = written_counter()
    m = _fold_delta(state, [Event(
        event="buy", entity_type="user", entity_id="u0",
        target_entity_type="item", target_entity_id="i0")])
    pub.publish([m], {"mode": "fold"})
    assert pub.last_publish_stats["written"] <= 0.05 * full_bytes
    assert written_counter() - before <= 0.05 * full_bytes
    wa, _ = worker.load(worker.current())
    _assert_models_identical(wa, oracle_load(m))


def test_publisher_sigkill_mid_blob_and_mid_manifest(plane_dir):
    """Delta-chain torture: a publisher killed mid-blob leaves an
    unreferenced tmp file (invisible — the manifest still names the
    previous generation); killed mid-manifest leaves a tmp CURRENT
    (ignored — the flip is an atomic rename).  A REFERENCED torn delta
    (manifest written, bytes truncated by the crash/disk) quarantines
    the torn file, the old generation keeps serving, and a restarted
    publisher — which cannot prove the chain — heals with a keyframe."""
    from predictionio_tpu.streaming.plane import ModelPlane, PlaneWatcher

    n_items = 600
    state = _fold_state(n_items=n_items)
    pub = ModelPlane(plane_dir)
    m0 = state.model
    m0.ensure_host_serving_state()
    pub.publish([m0], {"mode": "fold"})
    m1 = _fold_delta(state, _freshness_delta(state, 0, n_items))
    pub.publish([m1], {"mode": "fold"})
    sub = ModelPlane(plane_dir)
    installed = []
    watcher = PlaneWatcher(sub, lambda models, info: (
        installed.append(models[0]), True)[1], poll_s=0.05)
    assert watcher.check_now() and watcher.generation == 2
    # SIGKILL mid-blob: partial tmp container only
    (Path(plane_dir) / ".gen-0000000003.delta.tmp-999").write_bytes(
        b"PIOARR01" + b"\x00" * 4)
    # SIGKILL mid-manifest: partial CURRENT tmp only
    (Path(plane_dir) / "CURRENT.json.tmp-999").write_bytes(b'{"gen')
    assert not watcher.check_now()
    assert watcher.generation == 2
    # torn REFERENCED delta: manifest flipped, delta bytes truncated
    m2 = _fold_delta(state, _freshness_delta(state, 1, n_items))
    pub.publish([m2], {"mode": "fold"})
    torn = Path(plane_dir) / "gen-0000000003.delta"
    good = torn.read_bytes()
    torn.write_bytes(good[:len(good) // 2])
    assert not watcher.check_now()
    assert watcher.generation == 2          # old generation serves
    assert (Path(plane_dir)
            / "gen-0000000003.delta.quarantine").exists()
    # publisher restart: no in-memory prev state -> full keyframe heals
    pub2 = ModelPlane(plane_dir)
    gen = pub2.publish([m2], {"mode": "fold"})
    assert gen == 4
    assert (Path(plane_dir) / "gen-0000000004.arena").exists()
    assert watcher.check_now() and watcher.generation == 4
    _assert_models_identical(installed[-1], m2)


def test_torn_mid_chain_file_quarantines_the_failing_file(plane_dir):
    """A cold worker composing a chain whose MIDDLE file is torn must
    quarantine that file — not the newest generation, whose bytes are
    fine — and the live publisher's next publish heals the chain with a
    keyframe (chain-intact probe)."""
    from predictionio_tpu.streaming.plane import ModelPlane, PlaneWatcher

    n_items = 600
    state = _fold_state(n_items=n_items)
    pub = ModelPlane(plane_dir)
    m = state.model
    m.ensure_host_serving_state()
    pub.publish([m], {"mode": "fold"})
    for r in range(2):
        m = _fold_delta(state, _freshness_delta(state, r, n_items))
        pub.publish([m], {"mode": "fold"})
    mid = Path(plane_dir) / "gen-0000000002.delta"
    mid.write_bytes(mid.read_bytes()[:64])
    cold = ModelPlane(plane_dir)
    watcher = PlaneWatcher(cold, lambda models, info: True,
                           poll_s=0.05)
    assert not watcher.check_now()
    assert (Path(plane_dir)
            / "gen-0000000002.delta.quarantine").exists()
    assert not (Path(plane_dir)
                / "gen-0000000003.delta.quarantine").exists()
    # the LIVE publisher (prev state intact) notices the missing chain
    # file and publishes a keyframe instead of a delta
    m2 = _fold_delta(state, _freshness_delta(state, 2, n_items))
    gen = pub.publish([m2], {"mode": "fold"})
    assert gen == 4
    assert (Path(plane_dir) / "gen-0000000004.arena").exists()
    assert watcher.check_now() and watcher.generation == 4


def test_keyframe_interval_and_restart_replay(plane_dir, monkeypatch):
    """PIO_MODEL_PLANE_FULL_EVERY bounds the chain: every Nth
    generation is a full arena, and a fresh worker joining at the tip
    composes from the latest keyframe only — files older than it are
    not needed (restart cost is the keyframe + the tail deltas)."""
    from predictionio_tpu.streaming.plane import ModelPlane

    monkeypatch.setenv("PIO_MODEL_PLANE_FULL_EVERY", "3")
    monkeypatch.setenv("PIO_MODEL_PLANE_KEEP", "10")   # no GC here
    n_items = 600
    state = _fold_state(n_items=n_items)
    pub = ModelPlane(plane_dir)
    m = state.model
    m.ensure_host_serving_state()
    pub.publish([m], {"mode": "fold"})          # gen 1: keyframe
    for r in range(5):                          # gens 2..6
        m = _fold_delta(state, _freshness_delta(state, r, n_items))
        pub.publish([m], {"mode": "fold"})
    names = sorted(p.name for p in Path(plane_dir).glob("gen-*"))
    # keyframes at 1 and 4 (gen-1 + 3 = interval), deltas between
    assert "gen-0000000001.arena" in names
    assert "gen-0000000004.arena" in names
    assert "gen-0000000005.delta" in names
    assert "gen-0000000006.delta" in names
    # a fresh worker needs only keyframe 4 + deltas 5..6: delete older
    for p in Path(plane_dir).glob("gen-000000000[123].*"):
        p.unlink()
    fresh = ModelPlane(plane_dir)
    mapped, info = fresh.load(fresh.current())
    assert info["planeGeneration"] == 6
    _assert_models_identical(mapped, m)


def test_gc_refcount_keeps_chain_incl_quarantine_heal(
        plane_dir, monkeypatch):
    """The GC-refcount satellite: with delta chains, GC must never
    unlink a blob a kept generation's manifest still composes from —
    the keyframe survives while any kept delta references it, even
    past the PIO_MODEL_PLANE_KEEP count; after a quarantined-then-
    healed chain, the superseded files (quarantine included) are
    reclaimed once no kept generation needs them, and a fresh worker
    can still compose every kept generation."""
    from predictionio_tpu.obs import metrics as obs_metrics
    from predictionio_tpu.streaming.plane import ModelPlane

    monkeypatch.setenv("PIO_MODEL_PLANE_KEEP", "2")
    monkeypatch.setenv("PIO_MODEL_PLANE_FULL_EVERY", "100")
    n_items = 600
    state = _fold_state(n_items=n_items)
    pub = ModelPlane(plane_dir)
    m = state.model
    m.ensure_host_serving_state()
    pub.publish([m], {"mode": "fold"})          # gen 1: keyframe
    for r in range(4):                          # gens 2..5: deltas
        m = _fold_delta(state, _freshness_delta(state, r, n_items))
        pub.publish([m], {"mode": "fold"})
    names = {p.name for p in Path(plane_dir).glob("gen-*")}
    # count-only GC would have kept {4, 5}; the refcount keeps the
    # whole chain back to the keyframe both compose from
    assert names == {"gen-0000000001.arena", "gen-0000000002.delta",
                     "gen-0000000003.delta", "gen-0000000004.delta",
                     "gen-0000000005.delta"}
    fresh = ModelPlane(plane_dir)
    _assert_models_identical(fresh.load(fresh.current())[0], m)
    # quarantine a chain file -> the next publish heals with a keyframe
    q = Path(plane_dir) / "gen-0000000003.delta"
    q.replace(str(q) + ".quarantine")
    m = _fold_delta(state, _freshness_delta(state, 4, n_items))
    gen = pub.publish([m], {"mode": "fold"})    # gen 6: healing keyframe
    assert (Path(plane_dir) / "gen-0000000006.arena").exists()
    gc0 = obs_metrics.get_registry().counter(
        "pio_model_plane_gc_total", "x").value()
    for r in range(5, 7):                       # gens 7..8: new chain
        m = _fold_delta(state, _freshness_delta(state, r, n_items))
        gen = pub.publish([m], {"mode": "fold"})
    assert gen == 8
    names = {p.name for p in Path(plane_dir).glob("gen-*")}
    # kept gens {7, 8} chain to keyframe 6; everything older —
    # including the quarantined file — was reclaimed
    assert names == {"gen-0000000006.arena", "gen-0000000007.delta",
                     "gen-0000000008.delta"}
    assert obs_metrics.get_registry().counter(
        "pio_model_plane_gc_total", "x").value() > gc0
    fresh2 = ModelPlane(plane_dir)
    _assert_models_identical(fresh2.load(fresh2.current())[0], m)


def test_watcher_inotify_wake_beats_the_poll_period(
        mem_storage, host_serving, plane_dir):
    """The propagation-latency satellite: with a deliberately huge poll
    period, a publish must still install within ~a second — the inotify
    wake on the manifest rename, not the poll, drives the swap.  (Where
    inotify is unavailable the watcher falls back to stat-polling and
    this test is skipped.)"""
    from predictionio_tpu.streaming.plane import (
        ModelPlane, PlaneWatcher, _DirNotify,
    )

    os.makedirs(plane_dir, exist_ok=True)
    try:
        probe = _DirNotify(plane_dir)
        probe.close()
    except OSError:
        pytest.skip("inotify unavailable on this platform")
    _seed(mem_storage)
    engine, ep, _ = _ur()
    model = engine.train(ep)[0]
    pub, sub = ModelPlane(plane_dir), ModelPlane(plane_dir)
    installed = []
    watcher = PlaneWatcher(sub, lambda models, info: (
        installed.append(info["planeGeneration"]), True)[1],
        poll_s=30.0)
    watcher.start()
    try:
        time.sleep(0.3)                  # let the loop enter its wait
        t0 = time.time()
        pub.publish([model])
        deadline = time.time() + 5
        while time.time() < deadline and not installed:
            time.sleep(0.02)
        assert installed == [1]
        assert time.time() - t0 < 5.0    # not the 30 s poll
    finally:
        watcher.stop()


def test_watcher_stat_poll_fallback_converges(
        mem_storage, host_serving, plane_dir, monkeypatch):
    """PIO_MODEL_PLANE_NOTIFY=off: the stat-poll fallback still
    converges within the poll period, and an unchanged manifest costs a
    stat — not an open/parse — per period."""
    from predictionio_tpu.streaming.plane import ModelPlane, PlaneWatcher

    monkeypatch.setenv("PIO_MODEL_PLANE_NOTIFY", "off")
    _seed(mem_storage)
    engine, ep, _ = _ur()
    model = engine.train(ep)[0]
    pub, sub = ModelPlane(plane_dir), ModelPlane(plane_dir)
    installed = []
    watcher = PlaneWatcher(sub, lambda models, info: (
        installed.append(info["planeGeneration"]), True)[1], poll_s=0.05)
    watcher.start()
    try:
        pub.publish([model])
        deadline = time.time() + 5
        while time.time() < deadline and not installed:
            time.sleep(0.02)
        assert installed == [1]
    finally:
        watcher.stop()


# -- prefork e2e (real processes) --------------------------------------------


def _wait_group(base, n_workers, min_gen, deadline_s, proc=None):
    """Poll fresh GET / connections until n_workers distinct pids all
    report planeGeneration >= min_gen; returns {pid: gen}."""
    deadline = time.time() + deadline_s
    seen = {}
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(base + "/", timeout=2) as r:
                d = json.loads(r.read())
            seen[d["pid"]] = d.get("planeGeneration") or 0
        except Exception:
            pass
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(f"deploy died rc {proc.returncode}")
        if len(seen) >= n_workers and all(
                g >= min_gen for g in seen.values()):
            return seen
        time.sleep(0.1)
    raise AssertionError(
        f"group did not converge to gen>={min_gen}: {seen}")


def test_prefork_plane_one_fold_one_reload(tmp_path):
    """The acceptance drill on a REAL ``deploy --workers 2 --follow``
    prefork group: all workers converge on plane generations, appending
    a delta folds exactly ONCE across the group (fold counters from the
    cross-worker /metrics merge), the fold is reflected on every worker,
    and ONE /reload converges every worker onto a new generation."""
    import re

    from predictionio_tpu.storage.locator import (
        Storage, StorageConfig, set_storage,
    )
    from predictionio_tpu.workflow import core_workflow

    store_path = str(tmp_path / "store")
    storage = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": store_path}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                        "MODELDATA")}))
    set_storage(storage)
    try:
        app_id = _seed(storage, app_name="mpe2e")
        engine, ep, _ = _ur(app_name="mpe2e")
        variant = {
            "id": "mpe2e-engine",
            "engineFactory": "predictionio_tpu.models."
                             "universal_recommender."
                             "UniversalRecommenderEngine",
            "datasource": {"params": {"appName": "mpe2e",
                                      "eventNames": ["purchase"]}},
            "algorithms": [{"name": "ur", "params": {
                "appName": "mpe2e", "eventNames": [], "meshDp": 1,
                "maxCorrelatorsPerItem": 5}}]}
        ur_json = str(tmp_path / "engine.json")
        with open(ur_json, "w") as f:
            json.dump(variant, f)
        core_workflow.run_train(engine, ep, engine_id="mpe2e-engine",
                                storage=storage)
    finally:
        set_storage(None)
    env = {**os.environ,
           "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
           "PIO_STORAGE_SOURCES_FS_PATH": store_path,
           "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
           "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
           "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
           "JAX_PLATFORMS": "cpu",
           "PIO_METRICS_FLUSH_S": "0.25",
           "PIO_MODEL_PLANE_POLL_S": "0.1",
           "PIO_FOLLOW_INTERVAL_S": "0.3"}
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
         "--engine-json", ur_json, "--ip", "127.0.0.1",
         "--port", str(port), "--workers", "2", "--follow", "0.3"],
        env=env, cwd=str(REPO))
    base = f"http://127.0.0.1:{port}"
    try:
        # generation 1 = the parent's initial publish; generation 2 =
        # the publisher process's bootstrap restage.  Wait for BOTH so
        # the delta below is folded incrementally (not swallowed by a
        # bootstrap that started after the append)
        _wait_group(base, 2, 2, 120, proc)
        # ONE reload converges BOTH workers (the kernel routes the
        # request to one listener; the plane carries it to the rest)
        with urllib.request.urlopen(base + "/reload", timeout=30) as r:
            rel = json.loads(r.read())
        assert rel["reloaded"] is True and rel["generation"] >= 2
        _wait_group(base, 2, rel["generation"], 30)
        # append a delta: the publisher folds it ONCE; every worker
        # converges and reflects it
        storage2 = Storage(StorageConfig(
            sources={"FS": {"type": "localfs", "path": store_path}},
            repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                            "MODELDATA")}))
        storage2.l_events.insert_batch(
            [_buy("newbie", f"i{j}") for j in (0, 1, 2)], app_id)
        seen = _wait_group(base, 2, rel["generation"] + 1, 60)
        pids = set(seen)
        reflected = set()
        deadline = time.time() + 30
        while time.time() < deadline and reflected != pids:
            req = urllib.request.Request(
                base + "/queries.json",
                json.dumps({"user": "newbie", "num": 5}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                assert json.loads(r.read())["itemScores"]
            with urllib.request.urlopen(base + "/", timeout=2) as r:
                reflected.add(json.loads(r.read())["pid"])
        # fold counters across the WHOLE group (any worker's /metrics
        # merges every sibling + the publisher): the delta folded ONCE —
        # with per-worker followers this reads >= 2.  Poll: the
        # publisher's snapshot flush lags the fold by up to
        # PIO_METRICS_FLUSH_S.
        deadline = time.time() + 15
        folds, text = 0.0, ""
        while time.time() < deadline and folds < 1.0:
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            folds = sum(float(m.group(1)) for m in re.finditer(
                r'pio_follow_folds_total\{outcome="fold"\} ([0-9.e+]+)',
                text))
            if folds < 1.0:
                time.sleep(0.3)
        assert folds == 1.0, f"expected exactly one fold, saw {folds}"
        assert len(re.findall(
            r'pio_worker_up\{worker="[^"]+"\} 1', text)) == 3
        gens = {m.group(1): float(m.group(2)) for m in re.finditer(
            r'pio_model_plane_generation\{worker="([^"]+)"\}'
            r' ([0-9.e+]+)', text)}
        assert len(gens) == 3               # 2 workers + the publisher
        assert len(re.findall(r"pio_process_rss_bytes\{", text)) >= 3
    finally:
        for _ in range(16):
            try:
                with urllib.request.urlopen(base + "/stop",
                                            timeout=5) as r:
                    r.read()
                time.sleep(0.3)
            except Exception:
                break
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
