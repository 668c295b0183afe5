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
