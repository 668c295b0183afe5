"""REST server tests: event ingestion + query serving over real HTTP
(reference analogues: EventServiceSpec and the integration harness's
deploy/query loop — SURVEY.md §4)."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.api.event_server import run_event_server
from predictionio_tpu.events.event import DataMap, Event
from predictionio_tpu.storage import AccessKey, App


def http(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


@pytest.fixture()
def event_server(mem_storage):
    app_id = mem_storage.apps.insert(App(0, "esapp"))
    key = mem_storage.access_keys.insert(AccessKey("", app_id, []))
    restricted = mem_storage.access_keys.insert(AccessKey("", app_id, ["view"]))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=mem_storage,
                             background=True)
    port = httpd.server_address[1]
    yield {"base": f"http://127.0.0.1:{port}", "key": key,
           "restricted": restricted, "app_id": app_id, "storage": mem_storage}
    httpd.shutdown()
    httpd.server_close()


def test_event_server_alive(event_server):
    import os

    status, body = http("GET", event_server["base"] + "/")
    assert status == 200 and body["status"] == "alive"
    assert body["pid"] == os.getpid()   # identifies the serving worker


def test_post_and_get_event(event_server):
    base, key = event_server["base"], event_server["key"]
    status, body = http("POST", f"{base}/events.json?accessKey={key}", {
        "event": "buy", "entityType": "user", "entityId": "u1",
        "targetEntityType": "item", "targetEntityId": "i1",
        "properties": {"price": 9.99},
    })
    assert status == 201 and "eventId" in body
    eid = body["eventId"]
    status, got = http("GET", f"{base}/events/{eid}.json?accessKey={key}")
    assert status == 200 and got["event"] == "buy" and got["properties"]["price"] == 9.99
    # find with filters
    status, found = http("GET", f"{base}/events.json?accessKey={key}&event=buy")
    assert status == 200 and len(found) == 1
    status, none = http("GET", f"{base}/events.json?accessKey={key}&event=view")
    assert status == 200 and none == []
    # delete
    status, _ = http("DELETE", f"{base}/events/{eid}.json?accessKey={key}")
    assert status == 200
    status, _ = http("GET", f"{base}/events/{eid}.json?accessKey={key}")
    assert status == 404


def test_auth_rejections(event_server):
    base = event_server["base"]
    status, body = http("POST", f"{base}/events.json", {"event": "x"})
    assert status == 401
    status, body = http("POST", f"{base}/events.json?accessKey=WRONG", {"event": "x"})
    assert status == 401
    # restricted key may only write "view"
    rk = event_server["restricted"]
    status, _ = http("POST", f"{base}/events.json?accessKey={rk}", {
        "event": "buy", "entityType": "user", "entityId": "u1"})
    assert status == 403
    status, _ = http("POST", f"{base}/events.json?accessKey={rk}", {
        "event": "view", "entityType": "user", "entityId": "u1"})
    assert status == 201


def test_malformed_event_rejected(event_server):
    base, key = event_server["base"], event_server["key"]
    status, body = http("POST", f"{base}/events.json?accessKey={key}", {
        "event": "$set", "entityType": "user", "entityId": "u1",
        "targetEntityType": "item", "targetEntityId": "i1"})
    assert status == 400
    status, body = http("POST", f"{base}/events.json?accessKey={key}", {
        "entityType": "user", "entityId": "u1"})
    assert status == 400


def test_batch_events(event_server):
    base, key = event_server["base"], event_server["key"]
    batch = [
        {"event": "view", "entityType": "user", "entityId": f"u{i}",
         "targetEntityType": "item", "targetEntityId": "i1"}
        for i in range(3)
    ]
    batch.append({"entityType": "user", "entityId": "broken"})  # missing event
    status, results = http("POST", f"{base}/batch/events.json?accessKey={key}", batch)
    assert status == 200
    assert [r["status"] for r in results] == [201, 201, 201, 400]
    # over-limit batch rejected
    status, _ = http("POST", f"{base}/batch/events.json?accessKey={key}",
                     [batch[0]] * 51)
    assert status == 400


def test_stats(event_server):
    base, key = event_server["base"], event_server["key"]
    for _ in range(2):
        http("POST", f"{base}/events.json?accessKey={key}", {
            "event": "rate", "entityType": "user", "entityId": "u1",
            "targetEntityType": "item", "targetEntityId": "i1",
            "properties": {"rating": 5}})
    status, body = http("GET", f"{base}/stats.json?accessKey={key}")
    assert status == 200 and body["counts"].get("rate") == 2


@pytest.fixture()
def deployed_engine(tmp_path, mem_storage):
    """Full loop: ingest ratings → pio-style train → deploy → HTTP query."""
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_server import deploy
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithmParams, DataSourceParams,
    )
    from predictionio_tpu.controller.engine import EngineParams

    app_id = mem_storage.apps.insert(App(0, "qsapp"))
    events = []
    rng = np.random.default_rng(2)
    for u in range(12):
        for i in range(8):
            liked = (u < 6) == (i < 4)
            if rng.random() < 0.9:
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5.0 if liked else 1.0})))
    mem_storage.l_events.insert_batch(events, app_id)

    variant = {
        "id": "qs-engine",
        "engineFactory": "predictionio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "qsapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 6, "lambda": 0.05,
                                   "meshDp": 1}}],
    }
    engine_json = tmp_path / "engine.json"
    engine_json.write_text(json.dumps(variant))

    engine = RecommendationEngine.apply()
    ep = engine.engine_params_from_variant(variant)
    core_workflow.run_train(engine, ep, engine_id="qs-engine", storage=mem_storage)

    httpd = deploy(engine_json=str(engine_json), host="127.0.0.1", port=0,
                   storage=mem_storage, background=True)
    port = httpd.server_address[1]
    yield {"base": f"http://127.0.0.1:{port}", "storage": mem_storage,
           "engine_json": engine_json}
    httpd.shutdown()
    httpd.server_close()


def test_query_server_predicts(deployed_engine):
    base = deployed_engine["base"]
    status, info = http("GET", base + "/")
    assert status == 200 and info["engineId"] == "qs-engine"
    # the server says what it runs on and what it resolved to (ALS has no
    # scorer/tail choice; the batcher's auto is off on the CPU backend)
    assert info["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert (info["scorer"], info["tail"], info["batcher"]) == (None, None, False)
    assert info["compile"]["programs"] >= 0 and "cacheDir" in info["compile"]
    status, res = http("POST", base + "/queries.json", {"user": "u1", "num": 3})
    assert status == 200
    items = [s["item"] for s in res["itemScores"]]
    assert len(items) == 3
    assert all(int(i[1:]) < 4 for i in items), items


def test_deploy_fails_when_backend_fails(deployed_engine, monkeypatch):
    """A JAX backend that cannot initialise fails the deploy — no server
    comes up quietly on something else than what it was deployed for."""
    from predictionio_tpu.utils import device
    from predictionio_tpu.workflow.create_server import deploy

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(device, "device_info", broken)
    for batch in ("auto", "off"):
        monkeypatch.setenv("PIO_SERVE_BATCH", batch)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            deploy(engine_json=str(deployed_engine["engine_json"]),
                   host="127.0.0.1", port=0, background=True,
                   storage=deployed_engine["storage"])


def test_query_server_bad_requests(deployed_engine):
    base = deployed_engine["base"]
    status, _ = http("POST", base + "/queries.json", {"num": 3})  # missing user
    assert status == 400
    status, _ = http("POST", base + "/nope.json", {"user": "u1"})
    assert status == 404


def test_query_server_reload(deployed_engine):
    base = deployed_engine["base"]
    status, body = http("GET", base + "/reload")
    assert status == 200 and body["reloaded"]


def test_query_server_web_ui(deployed_engine):
    """GET / with Accept: text/html renders the deploy web UI
    (reference: CreateServer engine-instance info page)."""
    import urllib.request

    req = urllib.request.Request(deployed_engine["base"] + "/",
                                 headers={"Accept": "text/html"})
    body = urllib.request.urlopen(req).read().decode()
    assert "Engine server: qs-engine" in body
    assert "queries.json" in body


def test_cli_undeploy_stops_server(deployed_engine):
    """`pio undeploy` contacts the deployed server's /stop (reference
    Console.undeploy semantics) and reports failure when nothing listens."""
    import urllib.error
    import urllib.request

    from predictionio_tpu.cli.main import main as pio_main

    base = deployed_engine["base"]
    port = int(base.rsplit(":", 1)[1])
    assert pio_main(["undeploy", "--ip", "127.0.0.1",
                     "--port", str(port)]) == 0
    # server is gone: queries now fail at the connection level
    import time

    for _ in range(50):
        try:
            urllib.request.urlopen(base + "/", timeout=2)
            time.sleep(0.1)
        except (urllib.error.URLError, ConnectionError):
            break
    else:
        raise AssertionError("server still reachable after undeploy")
    assert pio_main(["undeploy", "--ip", "127.0.0.1",
                     "--port", str(port), "--timeout", "2"]) == 1


def test_keepalive_unread_body_drained(event_server):
    """An early-error response (401 auth) must not leave the POST body in
    the stream — the next request on the same keep-alive connection is
    parsed from the request line, not body bytes."""
    import http.client
    import json as _json
    from urllib.parse import urlsplit

    base, key = event_server["base"], event_server["key"]
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port)
    body = _json.dumps({"event": "buy", "entityType": "user",
                        "entityId": "u1"})
    conn.request("POST", "/events.json?accessKey=WRONG", body,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 401
    r.read()
    # same connection, now a valid request: must succeed, not 400
    conn.request("POST", f"/events.json?accessKey={key}", body,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 201, r.read()
    r.read()
    conn.close()


def test_header_count_cap(event_server):
    """More than 100 headers on one request is rejected, not accumulated."""
    import socket
    from urllib.parse import urlsplit

    u = urlsplit(event_server["base"])
    s = socket.create_connection((u.hostname, u.port))
    req = b"GET / HTTP/1.1\r\nHost: x\r\n"
    req += b"".join(b"X-Flood-%d: y\r\n" % i for i in range(150))
    req += b"\r\n"
    s.sendall(req)
    data = s.recv(65536)
    assert b"400" in data.split(b"\r\n", 1)[0], data[:100]
    s.close()


def test_auto_reload_hot_swaps_on_retrain(tmp_path, mem_storage):
    """MasterActor parity: train -> deploy --auto-reload -> retrain on new
    data -> queries reflect the NEW model with no manual /reload."""
    import time as _time

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_server import deploy

    app_id = mem_storage.apps.insert(App(0, "arapp"))
    rng = np.random.default_rng(4)

    def rate_cluster(flip):
        evs = []
        for u in range(12):
            for i in range(8):
                liked = ((u < 6) == (i < 4)) != flip
                evs.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5.0 if liked else 1.0})))
        return evs

    mem_storage.l_events.insert_batch(rate_cluster(False), app_id)
    variant = {
        "id": "ar-engine",
        "engineFactory": "predictionio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "arapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 6,
                                   "lambda": 0.05, "meshDp": 1}}],
    }
    engine_json = tmp_path / "engine.json"
    engine_json.write_text(json.dumps(variant))
    engine = RecommendationEngine.apply()
    ep = engine.engine_params_from_variant(variant)
    core_workflow.run_train(engine, ep, engine_id="ar-engine",
                            storage=mem_storage)
    httpd = deploy(engine_json=str(engine_json), host="127.0.0.1", port=0,
                   storage=mem_storage, background=True, auto_reload=0.05)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        first_instance = httpd.pio_state.instance.id
        status, r1 = http("POST", base + "/queries.json",
                          {"user": "u1", "num": 3})
        assert status == 200 and r1["itemScores"]

        # retrain against flipped preferences: a NEW engine instance
        mem_storage.l_events.insert_batch(rate_cluster(True) * 3, app_id)
        core_workflow.run_train(engine, ep, engine_id="ar-engine",
                                storage=mem_storage)
        deadline = _time.time() + 10
        while (httpd.pio_state.instance.id == first_instance
               and _time.time() < deadline):
            _time.sleep(0.05)
        assert httpd.pio_state.instance.id != first_instance, \
            "watcher never hot-swapped to the retrained instance"
        status, r2 = http("POST", base + "/queries.json",
                          {"user": "u1", "num": 3})
        assert status == 200 and r2["itemScores"]
    finally:
        httpd.pio_state.stop_auto_reload()
        httpd.shutdown()
        httpd.server_close()


def test_java_sdk_wire_format(event_server):
    """Replays the exact requests sdk/java/PredictionIO.java constructs
    (method, path, query, headers, JSON body shape) against a live event
    server — the wire-format contract the Java client compiles against."""
    import http.client
    import json as _json
    from urllib.parse import urlsplit

    base, key = event_server["base"], event_server["key"]
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port)

    # EventClient.createEvent: POST /events.json?accessKey=K
    body = ('{"event":"buy","entityType":"user","entityId":"u1",'
            '"targetEntityType":"item","targetEntityId":"i3",'
            '"properties":{"price":9.5}}')
    conn.request("POST", f"/events.json?accessKey={key}", body,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    out = _json.loads(r.read())
    assert r.status == 201 and out["eventId"]
    eid = out["eventId"]

    # EventClient.createEvents: POST /batch/events.json
    batch = _json.dumps([
        {"event": "view", "entityType": "user", "entityId": "u1",
         "targetEntityType": "item", "targetEntityId": "i9"}])
    conn.request("POST", f"/batch/events.json?accessKey={key}", batch,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    out = _json.loads(r.read())
    assert r.status == 200 and out[0]["status"] == 201

    # EventClient.getEvent: GET /events/{id}.json
    conn.request("GET", f"/events/{eid}.json?accessKey={key}", None,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    got = _json.loads(r.read())
    assert r.status == 200 and got["properties"]["price"] == 9.5

    # EventClient.findEvents: GET /events.json with filters
    conn.request("GET",
                 f"/events.json?accessKey={key}&entityType=user&entityId=u1",
                 None, {"Content-Type": "application/json"})
    r = conn.getresponse()
    found = _json.loads(r.read())
    assert r.status == 200 and len(found) == 2

    # EventClient.deleteEvent: DELETE /events/{id}.json
    conn.request("DELETE", f"/events/{eid}.json?accessKey={key}", None,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200
    r.read()
    conn.close()


def test_serve_micro_batching_matches_serial(tmp_path, mem_storage, monkeypatch):
    """PIO_SERVE_BATCH=on: concurrent queries coalesce through the
    group-commit micro-batcher with results identical to serial predict
    (the ALS batch path is the serving-batchable case)."""
    import http.client
    import threading as _threading

    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_server import deploy

    app_id = mem_storage.apps.insert(App(0, "mbapp"))
    rng = np.random.default_rng(6)
    events = []
    for u in range(30):
        for i in rng.integers(0, 40, 10):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(rng.integers(1, 6))})))
    mem_storage.l_events.insert_batch(events, app_id)
    variant = {
        "id": "mb-engine",
        "engineFactory": "predictionio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "mbapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 8, "numIterations": 4,
                                   "lambda": 0.05, "meshDp": 1}}],
    }
    engine_json = tmp_path / "engine.json"
    engine_json.write_text(json.dumps(variant))
    engine = RecommendationEngine.apply()
    ep = engine.engine_params_from_variant(variant)
    core_workflow.run_train(engine, ep, engine_id="mb-engine",
                            storage=mem_storage)

    def run_queries(batch_mode):
        monkeypatch.setenv("PIO_SERVE_BATCH", batch_mode)
        httpd = deploy(engine_json=str(engine_json), host="127.0.0.1",
                       port=0, storage=mem_storage, background=True)
        try:
            assert (httpd.pio_state.batcher is not None) == (batch_mode == "on")
            port = httpd.server_address[1]
            results = {}

            def worker(w):
                conn = http.client.HTTPConnection("127.0.0.1", port)
                for u in range(w, 30, 6):
                    conn.request("POST", "/queries.json",
                                 json.dumps({"user": f"u{u}", "num": 5}),
                                 {"Content-Type": "application/json"})
                    r = conn.getresponse()
                    assert r.status == 200
                    results[u] = json.loads(r.read())
                conn.close()

            ts = [_threading.Thread(target=worker, args=(w,)) for w in range(6)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            return results
        finally:
            httpd.shutdown()
            httpd.server_close()

    serial = run_queries("off")
    batched = run_queries("on")
    assert serial.keys() == batched.keys() and len(serial) == 30
    for u in serial:
        s = serial[u]["itemScores"]
        b = batched[u]["itemScores"]
        # matvec vs batched-matmul accumulate in different orders: items
        # must match, scores to f32 tolerance
        assert [r["item"] for r in s] == [r["item"] for r in b], (u, s, b)
        np.testing.assert_allclose([r["score"] for r in s],
                                   [r["score"] for r in b], rtol=2e-5)


def test_prefork_workers_share_port_and_die_with_server(tmp_path, monkeypatch):
    """deploy --workers: N processes bind one port via SO_REUSEPORT, all
    answer queries, and children terminate when the parent closes.
    (This VM has one core, so only lifecycle — not scaling — is
    assertable here.)"""
    import http.client
    import time as _time

    store = tmp_path / "store"
    env_vars = {
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(store),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "JAX_PLATFORMS": "cpu",
    }
    for k, v in env_vars.items():
        monkeypatch.setenv(k, v)
    from predictionio_tpu.storage.locator import Storage, StorageConfig, set_storage
    st = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(store)}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA", "MODELDATA")}))
    set_storage(st)
    try:
        app_id = st.apps.insert(App(0, "pfapp"))
        rng = np.random.default_rng(5)
        evs = []
        for u in range(20):
            for i in rng.integers(0, 30, 6):
                evs.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": float(rng.integers(1, 6))})))
        st.l_events.insert_batch(evs, app_id)
        variant = {
            "id": "pf-engine",
            "engineFactory": "predictionio_tpu.models.recommendation.RecommendationEngine",
            "datasource": {"params": {"appName": "pfapp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 2, "lambda": 0.05, "meshDp": 1}}],
        }
        ej = tmp_path / "engine.json"
        ej.write_text(json.dumps(variant))
        from predictionio_tpu.models.recommendation import RecommendationEngine
        from predictionio_tpu.workflow import core_workflow
        from predictionio_tpu.workflow.create_server import deploy

        engine = RecommendationEngine.apply()
        ep = engine.engine_params_from_variant(variant)
        core_workflow.run_train(engine, ep, engine_id="pf-engine", storage=st)
        httpd = deploy(engine_json=str(ej), host="127.0.0.1", port=0,
                       background=True, workers=2)
        try:
            assert len(httpd.pio_workers) == 1
            port = httpd.server_address[1]
            deadline = _time.time() + 60
            while (httpd.pio_workers[0].poll() is None
                   and _time.time() < deadline):
                # parent serves regardless; just confirm it answers while
                # the child boots
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.request("POST", "/queries.json",
                             json.dumps({"user": "u1", "num": 3}),
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                assert r.status == 200
                r.read()
                conn.close()
                _time.sleep(1.0)
                # child came up and stayed: good enough
                if _time.time() > deadline - 50:
                    break
            assert httpd.pio_workers[0].poll() is None, "child worker died"
        finally:
            httpd.shutdown()
            httpd.server_close()
        httpd.pio_workers[0].wait(timeout=10)
        assert httpd.pio_workers[0].poll() is not None
    finally:
        set_storage(None)


def test_http_pipelined_requests(event_server):
    """Two requests written in ONE TCP segment (HTTP/1.1 pipelining) are
    served in order — the lean request loop must consume exact body
    boundaries from the buffered stream."""
    import socket
    from urllib.parse import urlsplit

    u = urlsplit(event_server["base"])
    key = event_server["key"]
    body = json.dumps({"event": "buy", "entityType": "user",
                       "entityId": "u1", "targetEntityType": "item",
                       "targetEntityId": "i1"}).encode()
    one = (b"POST /events.json?accessKey=" + key.encode() +
           b" HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
           b"Content-Length: %d\r\n\r\n" % len(body) + body)
    s = socket.create_connection((u.hostname, u.port))
    s.sendall(one + one)          # pipelined: both before any read
    data = b""
    while data.count(b"HTTP/1.1 201") < 2:
        chunk = s.recv(65536)
        assert chunk, data
        data += chunk
    assert data.count(b'"eventId"') == 2
    s.close()


def test_micro_batcher_isolates_poisoned_query():
    """One failing query must not 500 its batchmates: the leader re-runs
    the batch serially so only the offender errors.  Also covers
    leadership handoff under sustained concurrent load."""
    import threading as _threading

    from predictionio_tpu.workflow.create_server import _MicroBatcher

    def run_one(q):
        if q == "poison":
            raise ValueError("bad query")
        return f"ok:{q}"

    def run_batch(queries):
        return [run_one(q) for q in queries]

    batcher = _MicroBatcher(run_batch, run_one, max_batch=4)
    results = {}
    errors = {}
    gate = _threading.Barrier(8)

    def worker(q):
        gate.wait()
        try:
            results[q] = batcher.predict(q)
        except ValueError as e:
            errors[q] = str(e)

    qs = [f"q{i}" for i in range(7)] + ["poison"]
    ts = [_threading.Thread(target=worker, args=(q,)) for q in qs]
    [t.start() for t in ts]
    [t.join(timeout=30) for t in ts]
    assert errors == {"poison": "bad query"}
    assert results == {f"q{i}": f"ok:q{i}" for i in range(7)}
    # batcher fully drained and leadership released
    assert batcher._queue == [] and not batcher._leader_active


def test_micro_batcher_soak():
    """Stress the leadership-rotation machinery: many threads, many
    queries each, random poisoned queries and randomly slow batches.
    Every query must get exactly its own result (no lost, duplicated, or
    mis-routed responses) and the batcher must fully drain."""
    import random
    import threading as _threading
    import time as _time

    from predictionio_tpu.workflow.create_server import _MicroBatcher

    rng = random.Random(42)  # only the (single) leader calls run_batch

    def run_one(q):
        if q.endswith(":poison"):
            raise ValueError(q)
        return "ok:" + q

    def run_batch(queries):
        if rng.random() < 0.2:          # a slow batch: mid-flight queries
            _time.sleep(0.002)          # must coalesce into the next one
        return [run_one(q) for q in queries]

    batcher = _MicroBatcher(run_batch, run_one, max_batch=6)
    n_threads, n_queries = 12, 30
    results: dict = {}
    errors: dict = {}
    gate = _threading.Barrier(n_threads)

    def worker(tid):
        trng = random.Random(tid)
        gate.wait()
        for seq in range(n_queries):
            q = f"{tid}:{seq}"
            if trng.random() < 0.1:
                q += ":poison"
            try:
                results[q] = batcher.predict(q)
            except ValueError as e:
                errors[q] = str(e)

    ts = [_threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    start = _time.monotonic()
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    elapsed = _time.monotonic() - start
    assert not any(t.is_alive() for t in ts), "soak deadlocked"
    assert elapsed < 30, f"soak took {elapsed:.1f}s — unbounded waits?"
    assert len(results) + len(errors) == n_threads * n_queries
    for q, r in results.items():
        assert r == "ok:" + q, f"mis-routed response: {q} -> {r}"
    for q, e in errors.items():
        assert q.endswith(":poison") and e == q
    assert batcher._queue == [] and not batcher._leader_active


def test_micro_batcher_recovers_when_nudged_waiter_departed(monkeypatch):
    """Regression for the leadership-handoff wedge: a slow batch makes a
    queued waiter hit its wait timeout and depart; the finishing leader
    must RELEASE leadership (not transfer it to the departed thread), so
    the next query can claim it and be served.  Under the old
    transfer-to-queue[0] scheme this left ``_leader_active`` stuck True
    and every later query timed out until restart."""
    import threading as _threading
    import time as _time

    from predictionio_tpu.workflow import create_server as cs

    monkeypatch.setattr(cs, "_WAIT_TIMEOUT_S", 0.2)
    slow_gate = _threading.Event()

    def run_batch(queries):
        if "slow" in queries:
            slow_gate.wait(timeout=10)
        return ["ok:" + q for q in queries]

    batcher = cs._MicroBatcher(run_batch, lambda q: "ok:" + q, max_batch=1)
    res: dict = {}
    errs: list = []

    def leader():
        res["slow"] = batcher.predict("slow")

    def waiter():
        try:
            res["w"] = batcher.predict("w")
        except TimeoutError as e:
            errs.append(e)

    t1 = _threading.Thread(target=leader)
    t1.start()
    _time.sleep(0.05)        # leader claims the lead, blocks in run_batch
    t2 = _threading.Thread(target=waiter)
    t2.start()
    t2.join(timeout=5)       # waiter times out at 0.2 s and departs
    assert not t2.is_alive() and errs, "waiter should have timed out"
    slow_gate.set()
    t1.join(timeout=5)
    assert res["slow"] == "ok:slow"
    # the actual regression check: the batcher must not be wedged
    assert batcher.predict("after") == "ok:after"
    assert batcher._queue == [] and not batcher._leader_active


def test_http_rejects_transfer_encoding(event_server):
    """We never decode chunked bodies — ignoring the header would leave
    chunk bytes in the stream to be parsed as the next pipelined request
    (request smuggling behind a chunked-forwarding proxy).  RFC 9112
    §6.1: 501 + connection close."""
    import socket
    from urllib.parse import urlsplit

    u = urlsplit(event_server["base"])
    key = event_server["key"]
    req = (b"POST /events.json?accessKey=" + key.encode() +
           b" HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
           b"Transfer-Encoding: chunked\r\n\r\n"
           b"5\r\nhello\r\n0\r\n\r\n")
    s = socket.create_connection((u.hostname, u.port))
    s.sendall(req)
    data = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        data += chunk
    s.close()
    assert data.startswith(b"HTTP/1.1 501"), data[:80]
    assert b"connection: close" in data.lower()
    # the connection was closed (recv returned b"") — no smuggled parse


def test_micro_batcher_short_batch_result_falls_back_serial():
    """A batch predictor returning the wrong result count must not strand
    any item: the strict zip raises and the serial fallback serves every
    query individually."""
    from predictionio_tpu.workflow.create_server import _MicroBatcher

    def run_batch(queries):
        return ["ok:" + q for q in queries][:-1]   # one short

    batcher = _MicroBatcher(run_batch, lambda q: "one:" + q, max_batch=4)
    assert batcher.predict("a") == "one:a"
    assert batcher._queue == [] and not batcher._leader_active


def test_sdk_event_pipeline(event_server):
    """Pipelined single-event ingestion: many requests in flight on one
    keep-alive socket, responses drained in order; errors are isolated to
    their own handle."""
    from predictionio_tpu.sdk import EventClient

    c = EventClient(event_server["key"], event_server["base"])
    with c.pipeline(depth=16) as p:
        handles = [p.record_user_action_on_item("buy", f"pu{i}", f"pi{i}")
                   for i in range(50)]
        bad = p.create_event("", "", "")          # server rejects: 400
        more = [p.record_user_action_on_item("view", f"pu{i}", f"pi{i}")
                for i in range(10)]
    ids = [h.result()["eventId"] for h in handles]
    assert len(set(ids)) == 50
    import pytest as _pytest

    from predictionio_tpu.sdk import PIOError
    with _pytest.raises(PIOError):
        bad.result()
    assert all(m.result()["eventId"] for m in more)
    # the events actually landed
    got = c.find_events(entityType="user", entityId="pu3")
    assert {e["event"] for e in got} == {"buy", "view"}


def test_sdk_event_pipeline_abort_fails_pending(event_server):
    """Leaving the pipeline context via an exception must fail the
    outstanding handles cleanly (PIOError), not let a later result()
    drain into the closed socket."""
    import pytest as _pytest

    from predictionio_tpu.sdk import EventClient, PIOError

    c = EventClient(event_server["key"], event_server["base"])
    with _pytest.raises(RuntimeError, match="boom"):
        with c.pipeline(depth=64) as p:
            handles = [p.record_user_action_on_item("buy", "au", f"ai{i}")
                       for i in range(5)]
            raise RuntimeError("boom")
    for h in handles:
        assert h.done
        with _pytest.raises(PIOError, match="aborted"):
            h.result()


def test_sdk_event_pipeline_partial_drain_and_close(event_server):
    """result() on an early handle drains only up to it; close() finishes
    the rest; a closed pipeline refuses new sends."""
    import pytest as _pytest

    from predictionio_tpu.sdk import EventClient, PIOError

    c = EventClient(event_server["key"], event_server["base"])
    p = c.pipeline(depth=64)
    handles = [p.record_user_action_on_item("buy", f"du{i}", f"di{i}")
               for i in range(9)]
    # draining handle 2 completes 0..2 but leaves 3.. pending
    assert handles[2].result()["eventId"]
    assert all(h.done for h in handles[:3])
    assert not any(h.done for h in handles[3:])
    p.close()
    assert all(h.done for h in handles)
    assert all(h.result()["eventId"] for h in handles)
    with _pytest.raises(PIOError, match="closed"):
        p.create_event("buy", "user", "x")


def test_sdk_event_pipeline_honors_connection_close():
    """ADVICE r5: a server 'Connection: close' mid-pipeline must fail the
    outstanding handles with the committed-but-unacknowledged message and
    refuse NEW sends — not surface an opaque 'server closed' for
    everything later."""
    import socket as _socket
    import threading as _threading

    import pytest as _pytest

    from predictionio_tpu.sdk import EventClient, PIOError

    srv = _socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def serve():
        c, _ = srv.accept()
        buf = b""
        # read until the FIRST request's body is in, then answer it with
        # Connection: close and drop the socket (http_util does exactly
        # this after e.g. an oversized unread body)
        while b"\r\n\r\n" not in buf:
            buf += c.recv(65536)
        head, _, rest = buf.partition(b"\r\n\r\n")
        clen = 0
        for h in head.split(b"\r\n"):
            if h.lower().startswith(b"content-length:"):
                clen = int(h.split(b":")[1])
        while len(rest) < clen:
            rest += c.recv(65536)
        body = b'{"eventId": "first"}'
        c.sendall(b"HTTP/1.1 201 Created\r\nContent-Type: application/json"
                  b"\r\nContent-Length: %d\r\nConnection: close\r\n\r\n"
                  % len(body) + body)
        c.close()
        srv.close()

    t = _threading.Thread(target=serve, daemon=True)
    t.start()
    c = EventClient("k", f"http://127.0.0.1:{port}")
    p = c.pipeline(depth=64)
    first = p.record_user_action_on_item("buy", "u1", "i1")
    rest = [p.record_user_action_on_item("buy", "u1", f"i{i}")
            for i in range(2, 5)]
    # draining the first handle reads its response AND sees the close
    assert first.result()["eventId"] == "first"
    for h in rest:
        assert h.done
        with _pytest.raises(PIOError, match="Connection: close"):
            h.result()
    # fail fast on new sends after the server signaled close
    with _pytest.raises(PIOError, match="closed"):
        p.record_user_action_on_item("buy", "u1", "i9")
    t.join(timeout=10)


def _rst_close(c):
    import socket as _socket
    import struct

    c.setsockopt(_socket.SOL_SOCKET, _socket.SO_LINGER,
                 struct.pack("ii", 1, 0))   # linger 0 => RST on close
    c.close()


def test_undeploy_mid_response_death_counts_as_stop():
    """A query server that dies while answering its own /stop (partial
    response then reset, port then dead) must still be reported as
    undeployed — the reset WAS the stop."""
    import socket
    import threading

    from predictionio_tpu.cli.main import main as pio_main

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]

    def one_shot():
        c, _ = srv.accept()
        c.recv(65536)
        c.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: 50\r\n\r\n{")
        _rst_close(c)          # truncated body + RST
        srv.close()            # port goes dead: the server is gone

    threading.Thread(target=one_shot, daemon=True).start()
    rc = pio_main(["undeploy", "--ip", "127.0.0.1", "--port", str(port),
                   "--timeout", "2"])
    assert rc == 0


def test_undeploy_persistent_resetter_reports_failure():
    """A listener that keeps dropping /stop mid-response while STAYING
    on the port (not a query server) must not be reported as undeployed."""
    import socket
    import threading

    from predictionio_tpu.cli.main import main as pio_main

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    port = srv.getsockname()[1]
    alive = True

    def reset_loop():
        preamble = True
        while alive:
            try:
                c, _ = srv.accept()
                c.recv(65536)
                if preamble:   # alternate: with and without any response
                    c.sendall(
                        b"HTTP/1.1 200 OK\r\nContent-Length: 50\r\n\r\n{")
                preamble = not preamble
                _rst_close(c)
            except OSError:
                return

    t = threading.Thread(target=reset_loop, daemon=True)
    t.start()
    try:
        rc = pio_main(["undeploy", "--ip", "127.0.0.1", "--port", str(port),
                       "--timeout", "2"])
        assert rc == 1
    finally:
        alive = False
        srv.close()
