"""Flight-recorder coverage: tail-sampling triggers, ring eviction under
concurrent requests, cross-worker /traces.json merge through real
prefork workers, metric exemplars, incremental span-journal persistence
(crash-safe), SDK request-id joinability, quantile interpolation, and
the trace round-trip script."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.obs import tracing as obs_tracing
from predictionio_tpu.obs.tracing import FlightRecorder
from predictionio_tpu.storage import AccessKey, App

REPO = Path(__file__).resolve().parent.parent


def http(method, url, body=None, headers=None):
    import urllib.error

    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


@pytest.fixture()
def fresh_recorder():
    """Install a fresh default-config recorder for the test and restore
    the lazy default afterwards (the recorder is process-global)."""
    def install(**kw):
        rec = FlightRecorder(**kw)
        obs_tracing.set_recorder(rec)
        return rec

    yield install
    obs_tracing.set_recorder(None)


@pytest.fixture()
def event_server(mem_storage, fresh_recorder):
    from predictionio_tpu.api.event_server import run_event_server

    app_id = mem_storage.apps.insert(App(0, "traceapp"))
    key = mem_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=mem_storage,
                             background=True)
    yield {"base": f"http://127.0.0.1:{httpd.server_address[1]}",
           "key": key, "app_id": app_id, "install": fresh_recorder}
    httpd.shutdown()
    httpd.server_close()


# -- tail-sampling policy (unit) ----------------------------------------------

def test_tail_sampling_reasons():
    rec = FlightRecorder(slow_ms=10_000, sample_n=0, enabled=True)
    assert rec.finish(rec.begin("r1", "GET"), 200, "/x") is None  # boring
    assert rec.finish(rec.begin("r2", "GET"), 500, "/x") == "error"
    assert rec.finish(rec.begin("r3", "GET"), 0, "/x") == "error"
    t = rec.begin("r4", "GET", debug=True)
    assert rec.finish(t, 200, "/x") == "debug"
    slow = FlightRecorder(slow_ms=0.0, sample_n=0, enabled=True)
    assert slow.finish(slow.begin("r5", "GET"), 200, "/x") == "slow"
    always = FlightRecorder(slow_ms=10_000, sample_n=1, enabled=True)
    assert always.finish(always.begin("r6", "GET"), 200, "/x") == "sampled"
    off = FlightRecorder(enabled=False)
    assert off.begin("r7", "GET") is None
    assert off.finish(None, 200, "/x") is None


def test_trace_spans_and_waterfall_text():
    rec = FlightRecorder(slow_ms=0, sample_n=0, enabled=True)
    t = rec.begin("wf1", "POST")
    with t.activate():
        assert obs_tracing.current_trace() is t
        with obs_tracing.trace_span("group_commit_append"):
            pass
        with t.span("ur_predict") as r:
            pass
        t.add_span("history", r["start"], 0.002, parent=r["id"])
    assert obs_tracing.current_trace() is None
    rec.finish(t, 201, "/events.json")
    doc = rec.get("wf1")
    by_name = {s["name"]: s for s in doc["spans"]}
    assert by_name["history"]["parent"] == by_name["ur_predict"]["id"]
    assert by_name["group_commit_append"]["parent"] is None
    text = obs_tracing.render_waterfall_text(doc)
    assert "wf1" in text and "ur_predict" in text and "history" in text


def test_timed_lands_in_active_trace():
    from predictionio_tpu.utils.tracing import timed

    rec = FlightRecorder(slow_ms=0, sample_n=0, enabled=True)
    t = rec.begin("tm1", "GET")
    with t.activate():
        with timed("outer_op"):
            with timed("inner_op"):
                pass
    by_name = {s["name"]: s for s in t.spans()}
    assert by_name["inner_op"]["parent"] == by_name["outer_op"]["id"]


# -- e2e through the event server ---------------------------------------------

def test_debug_header_forces_retention(event_server):
    event_server["install"](slow_ms=10_000, sample_n=0)
    base, key = event_server["base"], event_server["key"]
    s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                {"event": "buy", "entityType": "user", "entityId": "u1"})
    assert s == 201   # boring request: dropped
    s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                {"event": "buy", "entityType": "user", "entityId": "u1"},
                headers={"X-Request-ID": "dbg-1", "X-PIO-Debug": "1"})
    assert s == 201
    s, idx = http("GET", f"{base}/traces.json")
    assert s == 200
    assert {t["rid"] for t in idx["traces"]} == {"dbg-1"}
    assert idx["traces"][0]["reason"] == "debug"
    s, doc = http("GET", f"{base}/traces/dbg-1.json")
    assert s == 200
    assert doc["route"] == "/events.json" and doc["status"] == 201
    # the group-commit span from the storage layer is in the waterfall
    # (memory backend has no group commit; accept either, but the
    # envelope itself must be present)
    assert doc["rid"] == "dbg-1" and doc["durationMs"] > 0
    s, _ = http("GET", f"{base}/traces/unknown.json")
    assert s == 404


def test_slow_threshold_retains_with_spans(event_server):
    event_server["install"](slow_ms=0.0, sample_n=0)
    base, key = event_server["base"], event_server["key"]
    s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                {"event": "buy", "entityType": "user", "entityId": "u2"},
                headers={"X-Request-ID": "slow-1"})
    assert s == 201
    s, doc = http("GET", f"{base}/traces/slow-1.json")
    assert s == 200 and doc["reason"] == "slow"


def test_sample_one_in_one_retains_everything(event_server):
    event_server["install"](slow_ms=10_000, sample_n=1)
    base, key = event_server["base"], event_server["key"]
    for k in range(3):
        s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                    {"event": "buy", "entityType": "user", "entityId": "u3"},
                    headers={"X-Request-ID": f"samp-{k}"})
        assert s == 201
    s, idx = http("GET", f"{base}/traces.json")
    rids = {t["rid"] for t in idx["traces"]}
    assert {"samp-0", "samp-1", "samp-2"} <= rids
    assert all(t["reason"] == "sampled" for t in idx["traces"]
               if t["rid"].startswith("samp-"))


def test_tracing_kill_switch_503(event_server):
    event_server["install"](enabled=False)
    base = event_server["base"]
    s, body = http("GET", f"{base}/traces.json")
    assert s == 503 and "disabled" in body["message"]
    s, _ = http("GET", f"{base}/traces/whatever.json")
    assert s == 503


def test_ring_eviction_under_concurrent_requests(event_server):
    rec = event_server["install"](slow_ms=0.0, sample_n=0, ring=8)
    base, key = event_server["base"], event_server["key"]
    n_threads, per_thread = 8, 6
    errors = []

    def worker(w):
        try:
            for k in range(per_thread):
                s, _ = http(
                    "POST", f"{base}/events.json?accessKey={key}",
                    {"event": "buy", "entityType": "user",
                     "entityId": f"u{w}"},
                    headers={"X-Request-ID": f"ev-{w}-{k}"})
                assert s == 201
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    with rec._lock:
        ring = list(rec._ring)
    assert len(ring) == 8          # bounded, newest survive
    s, idx = http("GET", f"{base}/traces.json")
    assert s == 200
    ev_rids = [t for t in idx["traces"] if t["rid"].startswith("ev-")]
    assert len(ev_rids) <= 8 + 1   # ring + the /traces.json request itself


def test_exemplar_links_metrics_to_trace(event_server, monkeypatch):
    from predictionio_tpu.obs.exposition import parse_exemplars

    # a short window so earlier tests' slower observations (the process
    # registry is shared) age out and this request's id wins the slot
    monkeypatch.setenv("PIO_EXEMPLAR_WINDOW_S", "0.1")
    time.sleep(0.15)
    event_server["install"](slow_ms=0.0, sample_n=0)
    base, key = event_server["base"], event_server["key"]
    s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                {"event": "buy", "entityType": "user", "entityId": "u9"},
                headers={"X-Request-ID": "exemplar-rid-1"})
    assert s == 201
    with urllib.request.urlopen(base + "/metrics") as r:
        text = r.read().decode()
    ex = parse_exemplars(text)
    linked = {(lb.get("route"), rid) for lb, rid, _v in
              ex.get("pio_http_request_duration_seconds_bucket", ())}
    assert any(rid == "exemplar-rid-1" and route == "/events.json"
               for route, rid in linked), ex
    # the exemplar-carrying text still parses cleanly
    from predictionio_tpu.obs.exposition import parse_prometheus_text

    fams, _ = parse_prometheus_text(text)
    assert fams["pio_http_request_duration_seconds_bucket"]


def test_trace_persists_for_dashboard_merge(fs_storage, fresh_recorder,
                                            tmp_path):
    """A single fs-backed server persists retained traces under
    <store>/traces; a dashboard on the same storage merges them."""
    from predictionio_tpu.api.dashboard import run_dashboard
    from predictionio_tpu.api.event_server import run_event_server

    fresh_recorder(slow_ms=10_000, sample_n=0)
    app_id = fs_storage.apps.insert(App(0, "fsapp"))
    key = fs_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=fs_storage,
                             background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                    {"event": "buy", "entityType": "user", "entityId": "u1"},
                    headers={"X-Request-ID": "persist-1",
                             "X-PIO-Debug": "1"})
        assert s == 201
    finally:
        httpd.shutdown()
        httpd.server_close()
    store = Path(fs_storage.config.sources["FS"]["path"])
    files = list((store / "traces").glob("*.json"))
    assert files, "retained trace was not persisted under <store>/traces"
    dash = run_dashboard(host="127.0.0.1", port=0, storage=fs_storage,
                         background=True)
    dbase = f"http://127.0.0.1:{dash.server_address[1]}"
    try:
        s, doc = http("GET", f"{dbase}/traces/persist-1.json")
        assert s == 200 and doc["reason"] == "debug"
        with urllib.request.urlopen(f"{dbase}/traces/persist-1.html") as r:
            page = r.read().decode()
        assert "waterfall" in page and "persist-1" in page
        with urllib.request.urlopen(dbase + "/") as r:
            front = r.read().decode()
        assert "persist-1" in front   # recent-traces table
    finally:
        dash.shutdown()
        dash.server_close()


# -- cross-worker merge through real prefork workers --------------------------

def test_cross_worker_traces_merge(tmp_path, monkeypatch, fresh_recorder):
    """`eventserver --workers 2`: debug-marked requests served by BOTH
    workers must appear in ONE /traces.json (whoever answers), and a
    trace retained by one worker must be fetchable via a request that
    may land on the other."""
    from predictionio_tpu.api.event_server import run_event_server
    from predictionio_tpu.storage.locator import (
        Storage,
        StorageConfig,
        set_storage,
    )

    store = tmp_path / "store"
    for k, v in {
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(store),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "JAX_PLATFORMS": "cpu",
        "PIO_METRICS_FLUSH_S": "0.2",
    }.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("PIO_WRITER_TAG", raising=False)
    fresh_recorder()   # default policy; debug header forces the keeps
    meta = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(store)}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                        "MODELDATA")}))
    app_id = meta.apps.insert(App(0, "tracexw"))
    key = meta.access_keys.insert(AccessKey("", app_id, []))
    set_storage(None)
    httpd = run_event_server(host="127.0.0.1", port=0, background=True,
                             workers=2)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        pids, deadline = set(), time.time() + 90
        while len(pids) < 2 and time.time() < deadline:
            try:
                with urllib.request.urlopen(base + "/", timeout=2) as r:
                    pids.add(json.loads(r.read())["pid"])
            except Exception:
                time.sleep(0.2)
        assert len(pids) == 2, f"second worker never came up: {pids}"
        # debug-marked posts: fresh connections are kernel-balanced, so
        # enough of them land on both workers
        n = 24
        for k2 in range(n):
            for _ in range(5):
                try:
                    s, _b = http(
                        "POST", f"{base}/events.json?accessKey={key}",
                        {"event": "buy", "entityType": "user",
                         "entityId": "u1", "eventId": f"txw-{k2}"},
                        headers={"X-Request-ID": f"xw-{k2}",
                                 "X-PIO-Debug": "1"})
                    assert s == 201
                    break
                except Exception:
                    time.sleep(0.2)
            else:
                raise AssertionError(f"event txw-{k2} could not be posted")
        want = {f"xw-{k2}" for k2 in range(n)}
        deadline = time.time() + 30
        workers_seen: set = set()
        got: set = set()
        while time.time() < deadline:
            s, idx = http("GET", f"{base}/traces.json")
            assert s == 200
            entries = [t for t in idx["traces"]
                       if t["rid"].startswith("xw-")]
            got = {t["rid"] for t in entries}
            workers_seen = {t["worker"] for t in entries}
            if got == want and len(workers_seen) == 2:
                break
            time.sleep(0.3)
        assert got == want, f"merged index missing {sorted(want - got)}"
        assert len(workers_seen) == 2, (
            f"all retained traces claim one worker: {workers_seen} "
            "(kernel did not balance, or the merge is broken)")
        # a full waterfall resolves no matter which worker answers
        s, doc = http("GET", f"{base}/traces/xw-0.json")
        assert s == 200 and doc["reason"] == "debug"
        assert doc["route"] == "/events.json"
    finally:
        httpd.shutdown()
        httpd.server_close()
        set_storage(None)


# -- span journal: incremental append + crash safety --------------------------

def test_span_journal_incremental_append(tmp_path):
    from predictionio_tpu.obs.spans import SpanJournal, read_journal

    path = tmp_path / "j.jsonl"
    j = SpanJournal(path)
    with j.span("phase_one"):
        with j.span("child_a"):
            pass
    # flushed at root completion, BEFORE write()
    spans = read_journal(path)
    assert {s["name"] for s in spans} == {"phase_one", "child_a"}
    with j.span("phase_two"):
        pass
    j.write()
    spans = read_journal(path)
    assert {s["name"] for s in spans} == {"phase_one", "child_a",
                                          "phase_two"}
    by_name = {s["name"]: s for s in spans}
    assert by_name["child_a"]["parent"] == by_name["phase_one"]["id"]


def test_span_journal_survives_sigkill(tmp_path):
    """A crashed run keeps every completed root span (the old buffer-
    everything journal lost the whole file)."""
    path = tmp_path / "crash.jsonl"
    code = f"""
import os, signal
from predictionio_tpu.obs.spans import SpanJournal
j = SpanJournal({str(path)!r})
with j.activate():
    with j.span("completed_phase"):
        with j.span("completed_child"):
            pass
    with j.span("doomed_phase"):
        os.kill(os.getpid(), signal.SIGKILL)
"""
    r = subprocess.run([sys.executable, "-c", code], timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == -9
    from predictionio_tpu.obs.spans import read_journal

    spans = read_journal(path)
    names = {s["name"] for s in spans}
    assert "completed_phase" in names and "completed_child" in names
    assert "doomed_phase" not in names   # never completed, never flushed


# -- SDK request-id joinability -----------------------------------------------

def test_sdk_error_includes_request_id(event_server):
    from predictionio_tpu.sdk.client import EventClient, PIOError

    base = event_server["base"]
    bad = EventClient("wrong-key", base)
    with pytest.raises(PIOError) as ei:
        bad.create_event("buy", "user", "u1")
    assert ei.value.request_id
    assert f"request-id {ei.value.request_id}" in str(ei.value)
    # the echoed server-side id IS the client's (joinable): a good
    # client's event post must round-trip the minted id
    good = EventClient(event_server["key"], base)
    assert good.create_event("buy", "user", "u1")


def test_sdk_pipeline_error_includes_request_id(event_server):
    from predictionio_tpu.sdk.client import EventClient, PIOError

    bad = EventClient("wrong-key", event_server["base"])
    with bad.pipeline(depth=4) as p:
        h = p.create_event("buy", "user", "u1")
    with pytest.raises(PIOError) as ei:
        h.result()
    assert ei.value.request_id == h.request_id
    assert h.request_id in str(ei.value)


# -- quantile interpolation ---------------------------------------------------

def test_quantile_single_observation_not_upper_bound():
    from predictionio_tpu.obs.exposition import _quantile_from_buckets

    inf = float("inf")
    # one observation, landing in the (0.1, 0.25] bucket
    buckets = [(0.1, 0.0), (0.25, 1.0), (inf, 1.0)]
    p50 = _quantile_from_buckets(buckets, 1.0, 0.50)
    p95 = _quantile_from_buckets(buckets, 1.0, 0.95)
    p99 = _quantile_from_buckets(buckets, 1.0, 0.99)
    for q in (p50, p95, p99):
        assert 0.1 <= q < 0.25, "quantile must stay inside the bucket"
    assert p50 <= p95 <= p99
    assert p99 < 0.25 - 1e-9, "single observation must not report the " \
                              "bucket's upper bound"


def test_summarize_prometheus_quantiles_clamped():
    from predictionio_tpu.obs.exposition import summarize_prometheus
    from predictionio_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("pio_q_seconds", "t", buckets=(0.1, 0.25, 1.0))
    h.observe(0.2)   # crafted: a single observation
    from predictionio_tpu.obs.exposition import render_prometheus

    digest = summarize_prometheus(render_prometheus(reg.snapshot()))
    line = next(ln for ln in digest.splitlines() if "p50" in ln)
    import re

    p50, p95, p99 = (float(x) for x in re.findall(
        r"p\d+≈([0-9.e+-]+)", line))
    assert p50 <= p95 <= p99 < 0.25


# -- route labels + lint + round trip ----------------------------------------

def test_trace_route_labels_bounded():
    from predictionio_tpu.api.http_util import route_label

    assert route_label("/traces.json") == "/traces.json"
    assert route_label("/traces/abc-123.json") == "/traces/{rid}.json"
    assert route_label("/traces/abc-123.html") == "/traces/{rid}.html"


def test_check_trace_roundtrip_script():
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_trace_roundtrip.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok:" in r.stdout


def test_pio_trace_cli(event_server, capsys):
    from predictionio_tpu.cli.main import main as cli_main

    event_server["install"](slow_ms=10_000, sample_n=0)
    base, key = event_server["base"], event_server["key"]
    s, _ = http("POST", f"{base}/events.json?accessKey={key}",
                {"event": "buy", "entityType": "user", "entityId": "u1"},
                headers={"X-Request-ID": "cli-rid-1", "X-PIO-Debug": "1"})
    assert s == 201
    assert cli_main(["trace", base]) == 0
    out = capsys.readouterr().out
    assert "cli-rid-1" in out and "kept=debug" in out
    assert cli_main(["trace", base, "--rid", "cli-rid-1"]) == 0
    out = capsys.readouterr().out
    assert "trace cli-rid-1" in out and "/events.json" in out
    assert cli_main(["trace", base, "--slow"]) == 0
    out = capsys.readouterr().out
    assert "trace " in out


# -- spans inside run_train, on the journal and on the profiler's clock -------

TRAIN_SPANS = {"train", "engine_train", "staging_summary", "save_models",
               "read_training", "prepare", "algo_train", "layout", "h2d",
               "dispatch", "device_wait", "serialize_models", "models_insert"}

# how each CCO strategy a TPU or the rehearsal can reach is forced on the CPU
# (the dense count matrix allowed or not; the resident budget taken away)
UR_STRATEGIES = {
    "dense": {"PIO_CCO_SPARSE": "0", "PIO_CCO_DENSE": "1"},
    "resident": {"PIO_CCO_SPARSE": "0", "PIO_CCO_DENSE": "0"},
    "chunked": {"PIO_CCO_SPARSE": "0", "PIO_CCO_DENSE": "0",
                "_TILED_P_BYTES": 0},
}


def _interaction_app(storage, name, events_of_pair):
    app_id = storage.apps.insert(App(0, name))
    rng = np.random.default_rng(11)
    events = []
    for u in range(40):
        for i in range(12):
            if rng.random() < (0.7 if (i < 6) == (u < 20) else 0.1):
                events.extend(events_of_pair(f"u{u}", f"i{i}", rng))
    storage.l_events.insert_batch(events, app_id)
    return storage


def _ur_job(storage, monkeypatch, strategy="resident"):
    from predictionio_tpu.events.event import Event
    from predictionio_tpu.models.universal_recommender import (
        UniversalRecommenderEngine)
    from predictionio_tpu.ops import cco as cco_ops

    for key, value in UR_STRATEGIES.get(strategy, {}).items():
        if key.startswith("PIO_"):
            monkeypatch.setenv(key, value)
        else:
            monkeypatch.setattr(cco_ops, key, value)

    def pair(u, i, rng):
        out = [Event(event="view", entity_type="user", entity_id=u,
                     target_entity_type="item", target_entity_id=i)]
        if rng.random() < 0.6:
            out.append(Event(event="buy", entity_type="user", entity_id=u,
                             target_entity_type="item", target_entity_id=i))
        return out

    _interaction_app(storage, "spanapp", pair)
    engine = UniversalRecommenderEngine.apply()
    ep = engine.engine_params_from_variant({
        "datasource": {"params": {"appName": "spanapp",
                                  "eventNames": ["buy", "view"]}},
        "algorithms": [{"name": "ur", "params": {
            "appName": "spanapp", "meshDp": 1, "maxCorrelatorsPerItem": 4,
            "itemTile": 8}}]})
    return engine, ep


def _als_job(storage, monkeypatch, strategy=None):
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.events.event import DataMap, Event
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithmParams, DataSourceParams)

    def pair(u, i, rng):
        return [Event(event="rate", entity_type="user", entity_id=u,
                      target_entity_type="item", target_entity_id=i,
                      properties=DataMap({"rating": float(rng.integers(1, 6))}))]

    _interaction_app(storage, "spanapp", pair)
    ep = EngineParams(
        data_source_params=DataSourceParams(app_name="spanapp"),
        algorithm_params_list=[("als", ALSAlgorithmParams(
            rank=4, num_iterations=3, lambda_=0.05, mesh_dp=1))])
    return RecommendationEngine.apply(), ep


EXPECTED_PROGRAM = {"dense": "_cco_counts_dense",
                    "resident": "_cco_resident_all_tiles",
                    "chunked": "_cco_chunked_all_tiles",
                    None: "_als_run_single"}
TRAIN_JOBS = [(_ur_job, s) for s in UR_STRATEGIES] + [(_als_job, None)]
TRAIN_JOB_IDS = [f"ur-{s}" for s in UR_STRATEGIES] + ["als"]


def _run_train(make, strategy, storage, monkeypatch):
    from predictionio_tpu.obs import spans as obs_spans
    from predictionio_tpu.workflow import core_workflow

    engine, ep = make(storage, monkeypatch, strategy)
    instance = core_workflow.run_train(engine, ep, engine_id="span-engine",
                                       storage=storage)
    spans = obs_spans.read_journal(
        obs_spans.journal_path(storage, instance.id))
    # the same run, kept in memory for whoever is in the process
    assert obs_spans.recent_runs()[-1] == spans
    return spans


@pytest.mark.parametrize("make,strategy", TRAIN_JOBS, ids=TRAIN_JOB_IDS)
def test_train_journal_holds_a_span_at_every_host_boundary(
        make, strategy, fs_storage, monkeypatch):
    spans = _run_train(make, strategy, fs_storage, monkeypatch)
    by_id = {s["id"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert TRAIN_SPANS <= names, TRAIN_SPANS - names
    assert "host_compute" not in names      # no strategy of a TPU counts on the host

    def parents(name):
        return {by_id[s["parent"]]["name"] if s["parent"] else None
                for s in spans if s["name"] == name}

    assert parents("train") == {None}
    for name in ("engine_train", "staging_summary", "save_models"):
        assert parents(name) == {"train"}
    for name in ("read_training", "prepare", "algo_train"):
        assert parents(name) == {"engine_train"}
    for name in ("layout", "h2d", "dispatch", "device_wait"):
        assert parents(name) == {"algo_train"}
    for name in ("serialize_models", "models_insert"):
        assert parents(name) == {"save_models"}

    # a span's self time is its duration less its children's; no child
    # outlasts its parent, and the self times add up to the whole run
    self_s = {s["id"]: s["duration_s"] for s in spans}
    for s in spans:
        if s["parent"] is not None:
            self_s[s["parent"]] -= s["duration_s"]
    assert min(self_s.values()) > -1e-3
    root = next(s for s in spans if s["parent"] is None)
    assert sum(self_s.values()) == pytest.approx(root["duration_s"])

    attrs = lambda name: [s.get("attrs", {}) for s in spans   # noqa: E731
                          if s["name"] == name]
    assert all(a["bytes"] > 0 for a in attrs("h2d") + attrs("device_wait")
               + attrs("serialize_models") + attrs("models_insert"))
    programs = {a["program"] for a in attrs("dispatch")}
    assert EXPECTED_PROGRAM[strategy] in programs, programs
    assert attrs("algo_train")[0]["algorithm"] in ("URAlgorithm",
                                                   "ALSAlgorithm")
    assert attrs("read_training")[0]["events"] > 100


def test_host_sparse_strategy_is_one_host_compute_span(fs_storage,
                                                       monkeypatch):
    """What `auto` takes on the CPU backend: the counting is the host's."""
    monkeypatch.setenv("PIO_CCO_SPARSE", "1")
    monkeypatch.delenv("PIO_CCO_DENSE", raising=False)
    spans = _run_train(_ur_job, "host-sparse", fs_storage, monkeypatch)
    by_id = {s["id"]: s for s in spans}
    host = [s for s in spans if s["name"] == "host_compute"]
    assert len(host) == 2                                  # buy, view
    assert {by_id[s["parent"]]["name"] for s in host} == {"algo_train"}


def _bench_job(config_name):
    """A job of one benchmark configuration at its rehearsal size: the
    benchmark's own generator, through the ingest path its driver takes."""
    import importlib.util

    def load(kind, name):
        spec = importlib.util.spec_from_file_location(
            f"span_test_{kind}_{name}",
            REPO / "benchmark" / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def make(storage, monkeypatch, strategy=None):
        from predictionio_tpu.workflow import create_workflow

        config = json.loads(
            (REPO / "benchmark" / "configs" / f"{config_name}.json")
            .read_text())
        small = config["rehearsal"]
        for key, value in small.get("env", {}).items():
            monkeypatch.setenv(key, value)
        data = load("data", config["data"]["generator"]).generate(
            {**config["data"]["params"], **small["data"]["params"]}, 2 ** 31 + 5)
        wire_events = load("drivers", "train_jobs").wire_events
        make.app_id = app_id = storage.apps.insert(App(0, "bench"))
        make.n_events = 0
        for block in data["blocks"]:
            results = storage.l_events.insert_json_batch(
                list(wire_events(block)), app_id)
            assert all(r["status"] == 201 for r in results)
            make.n_events += len(results)
        variant = json.loads(json.dumps(
            {**config["engine"], **small.get("engine", {})})
            .replace("$app", "bench").replace('"$seed"', "5"))
        for algo in variant["algorithms"]:
            algo["params"]["meshDp"] = 1    # the suite's CPU shows 8 devices
        _, engine, ep = create_workflow.engine_from_variant(variant)
        return engine, ep

    return make


@pytest.mark.parametrize("config_name", ["als-ml1m", "ur-ecom-100k"])
def test_native_scan_span_on_a_train_journal(config_name, fs_storage,
                                             monkeypatch):
    """`scan_segments` opens `native_scan` inside `read_training`, with what
    the scan did: every event of the store a row, and on the lines the
    benchmark's generators write no value off the fast paths."""
    from predictionio_tpu.native import native_available

    if not native_available():
        pytest.skip("g++ unavailable; native scanner not built")
    monkeypatch.setenv("PIO_DELTA_STAGING", "off")    # as the train-jobs mix
    make = _bench_job(config_name)
    spans = _run_train(make, None, fs_storage, monkeypatch)
    by_id = {s["id"]: s for s in spans}
    scans = [s for s in spans if s["name"] == "native_scan"]
    assert len(scans) == 1
    assert by_id[scans[0]["parent"]]["name"] == "read_training"
    attrs = scans[0]["attrs"]
    assert set(attrs) == {"files", "bytes", "ranges", "threads", "rows",
                          "parse_s", "merge_s", "export_s", "slow_strings",
                          "slow_times"}
    assert attrs["rows"] == make.n_events > 1000
    assert attrs["slow_strings"] == 0 and attrs["slow_times"] == 0
    segments = fs_storage.p_events.segment_paths(make.app_id, None)
    assert attrs["files"] == len(segments) >= 1
    assert attrs["bytes"] == sum(p.stat().st_size for p in segments)
    assert 1 <= attrs["threads"] <= attrs["ranges"]
    parts = attrs["parse_s"] + attrs["merge_s"] + attrs["export_s"]
    assert 0 < parts <= scans[0]["duration_s"] + 1e-3


@pytest.mark.parametrize("make,strategy", [TRAIN_JOBS[1], TRAIN_JOBS[3]],
                         ids=["ur", "als"])
def test_spans_lie_on_the_profilers_clock(make, strategy, fs_storage,
                                          monkeypatch, tmp_path):
    """Under a profiler session every span is a `pio:<name>` event on the
    thread's host line, nested inside `pio:train`."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 2
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        spans = _run_train(make, strategy, fs_storage, monkeypatch)
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)
    lines = [ln for plane in ProfileData.from_file(pb).planes
             if not plane.name.startswith("/device:") for ln in plane.lines]
    on_line = {}
    for k, ln in enumerate(lines):
        for e in ln.events:
            if e.name.startswith("pio:"):
                on_line.setdefault(k, []).append(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns))
    [events] = on_line.values()           # one thread ran the job: one line
    [(_, lo, hi)] = [e for e in events if e[0] == "pio:train"]
    # (the app's ingest before the job had no collector: annotation alone)
    inside = {n for n, s, e in events if lo <= s and e <= hi}
    assert inside == {"pio:" + s["name"] for s in spans
                      if s["name"] != "compile"}
    assert {"pio:" + n for n in TRAIN_SPANS} <= inside
    assert {n for n, _, _ in events} - inside <= {"pio:group_commit_append"}


def test_spans_without_jax_import_no_jax():
    """The event server's process: spans open, JAX stays out."""
    code = """
import sys
from predictionio_tpu.obs import spans
import predictionio_tpu.obs
j = spans.SpanJournal(sys.argv[1])
with spans.span("no_collector") as rec:
    rec["attrs"]["seen"] = 1
with j.activate():
    with spans.span("outer", k=1):
        with spans.span("inner"):
            pass
assert [s["name"] for s in spans.recent_runs()[-1]] == ["outer", "inner"]
assert "jax" not in sys.modules, "obs.spans imported jax"
print("ok")
"""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        r = subprocess.run([sys.executable, "-c", code, f"{d}/j.jsonl"],
                           capture_output=True, text=True, timeout=120,
                           cwd=str(REPO))
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


def test_a_compile_inside_a_span_is_its_compile_child(tmp_path):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.obs import spans as obs_spans
    from predictionio_tpu.utils import device as device_

    device_.watch_compiles()
    before = device_.compile_stats()["programs"]
    journal = obs_spans.SpanJournal(tmp_path / "c.jsonl")
    with journal.activate():
        with obs_spans.span("dispatch", program="fresh") as rec:
            # a function object of its own: no jit cache can hold it
            jax.jit(lambda x: jnp.tanh(x) * 3.25 + 1)(jnp.arange(7.0))
    compiles = [s for s in journal.spans() if s["name"] == "compile"]
    assert len(compiles) == device_.compile_stats()["programs"] - before >= 1
    for c in compiles:
        assert c["parent"] == rec["id"]
        assert c["attrs"]["seconds"] > 0
        assert c["attrs"]["cache_hit"] in (True, False)
        assert rec["start"] <= c["start"] + 1e-3
    # outside any collector a compile is only counted
    jax.jit(lambda x: jnp.tanh(x) * 4.5 - 2)(jnp.arange(5.0))
    assert len(journal.spans()) == 1 + len(compiles)
