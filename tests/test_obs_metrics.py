"""Observability subsystem: registry thread-safety, Prometheus golden
text, cross-worker aggregation through real prefork workers, stats.json
window semantics, span-journal round trip through the train workflow,
and the metric-name lint."""

import json
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from predictionio_tpu.obs.exposition import (
    StatsCollector,
    family_total,
    parse_prometheus_text,
    render_prometheus,
    summarize_prometheus,
)
from predictionio_tpu.obs.metrics import (
    MetricsRegistry,
    merge_snapshots,
)
from predictionio_tpu.storage import AccessKey, App

REPO = Path(__file__).resolve().parent.parent


def http(method, url, body=None):
    import urllib.error

    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


# -- registry -----------------------------------------------------------------

def test_registry_thread_safety_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("pio_tst_total", "t")
    g = reg.gauge("pio_tst_gauge", "t")
    h = reg.histogram("pio_tst_seconds", "t")
    n_threads, per_thread = 8, 5_000

    def work():
        for k in range(per_thread):
            c.inc(1, route="/x")
            g.inc(1)
            h.observe(0.001 * (k % 7))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert c.value(route="/x") == total
    assert g.value() == total
    snap = reg.snapshot()
    hs = snap["pio_tst_seconds"]["series"][""]
    assert hs["count"] == total
    assert sum(hs["counts"]) == total


def test_registry_name_and_help_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("http_requests_total", "missing pio_ prefix")
    with pytest.raises(ValueError):
        reg.counter("pio_Bad_Case", "uppercase")
    with pytest.raises(ValueError):
        reg.counter("pio_ok_total", "")
    c = reg.counter("pio_ok_total", "help")
    assert reg.counter("pio_ok_total", "help") is c   # idempotent
    with pytest.raises(ValueError):
        reg.gauge("pio_ok_total", "kind mismatch")


def test_registry_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("pio_off_total", "t")
    c.inc(5)
    assert c.value() == 0.0


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    c = reg.counter("pio_g_requests_total", "Requests served")
    c.inc(3, route="/a", status="200")
    c.inc(1, route="/b", status="404")
    g = reg.gauge("pio_g_in_flight", "In-flight requests")
    g.set(2)
    h = reg.histogram("pio_g_latency_seconds", "Latency", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(5.0)
    assert render_prometheus(reg.snapshot()) == (
        "# HELP pio_g_in_flight In-flight requests\n"
        "# TYPE pio_g_in_flight gauge\n"
        "pio_g_in_flight 2\n"
        "# HELP pio_g_latency_seconds Latency\n"
        "# TYPE pio_g_latency_seconds histogram\n"
        'pio_g_latency_seconds_bucket{le="0.01"} 1\n'
        'pio_g_latency_seconds_bucket{le="0.1"} 2\n'
        'pio_g_latency_seconds_bucket{le="+Inf"} 3\n'
        "pio_g_latency_seconds_sum 5.055\n"
        "pio_g_latency_seconds_count 3\n"
        "# HELP pio_g_requests_total Requests served\n"
        "# TYPE pio_g_requests_total counter\n"
        'pio_g_requests_total{route="/a",status="200"} 3\n'
        'pio_g_requests_total{route="/b",status="404"} 1\n'
    )


def test_prometheus_parse_and_summary_roundtrip():
    reg = MetricsRegistry()
    reg.counter("pio_r_total", "t").inc(7, route="/x,y", status="201")
    reg.histogram("pio_r_seconds", "t").observe(0.3)
    text = render_prometheus(reg.snapshot())
    fams, types = parse_prometheus_text(text)
    assert types == {"pio_r_total": "counter", "pio_r_seconds": "histogram"}
    # label values containing a comma survive the round trip
    assert fams["pio_r_total"] == [({"route": "/x,y", "status": "201"}, 7.0)]
    assert family_total(fams, "pio_r_seconds_count") == 1.0
    digest = summarize_prometheus(text)
    assert "pio_r_total" in digest and "count=1" in digest


def test_label_escape_roundtrip_hostile_values():
    reg = MetricsRegistry()
    c = reg.counter("pio_esc_total", "t")
    nasty = ['a\\nb', 'a\nb', 'say "hi"', "back\\slash", "plain"]
    for v in nasty:
        c.inc(1, event=v)
    fams, _ = parse_prometheus_text(render_prometheus(reg.snapshot()))
    parsed = {lb["event"] for lb, _v in fams["pio_esc_total"]}
    assert parsed == set(nasty)


@pytest.mark.parametrize("age_s,evicted", [(60.0, False), (660.0, True)])
def test_stale_worker_snapshot_zeroes_gauges_keeps_counters(
        tmp_path, monkeypatch, age_s, evicted):
    """A sibling file past the stale rule (ten flush intervals, at least
    15 s) and short of PIO_OBS_SIBLING_STALE_S is a dead worker: its
    counters still aggregate, its gauges read 0.  Past
    PIO_OBS_SIBLING_STALE_S the file is evicted and adds nothing."""
    import os

    from predictionio_tpu.obs import metrics as obs_metrics

    monkeypatch.setenv("PIO_METRICS_FLUSH_S", "1.0")
    monkeypatch.setenv("PIO_OBS_SIBLING_STALE_S", "600")
    counter, gauge = ("pio_storage_events_appended_total",
                      "pio_http_requests_in_flight")
    reg = obs_metrics.get_registry()

    def total(snap, name):
        return sum(snap.get(name, {"series": {}})["series"].values())

    try:
        obs_metrics.start_worker_flusher(str(tmp_path), tag="live-w")
        # fake a dead sibling: old mtime, nonzero gauge + counter
        dead = MetricsRegistry()
        dead.gauge(gauge, "x").set(3)
        dead.counter(counter, "x").inc(7)
        p = tmp_path / "dead-w.json"
        p.write_text(json.dumps(dead.snapshot()))
        then = time.time() - age_s
        os.utime(p, (then, then))
        own = reg.snapshot()
        evictions = obs_metrics.STALE_SIBLINGS.value(kind="metrics")
        snap = obs_metrics.aggregate_snapshot(reg)
        assert total(snap, counter) == total(own, counter) + (0 if evicted else 7)
        assert total(snap, gauge) == total(own, gauge)
        assert p.exists() is not evicted
        assert obs_metrics.STALE_SIBLINGS.value(kind="metrics") == (
            evictions + evicted)
    finally:
        obs_metrics.stop_worker_flusher()


def test_merge_snapshots_across_workers():
    def make(n):
        reg = MetricsRegistry()
        reg.counter("pio_m_total", "t").inc(n)
        reg.histogram("pio_m_seconds", "t", buckets=(0.1, 1.0)).observe(n)
        return reg.snapshot()

    merged = merge_snapshots([make(0.05), make(0.5)])
    assert merged["pio_m_total"]["series"][""] == 0.55
    hs = merged["pio_m_seconds"]["series"][""]
    assert hs["count"] == 2 and hs["counts"] == [1, 1, 0]
    text = render_prometheus(merged)
    fams, _ = parse_prometheus_text(text)
    assert family_total(fams, "pio_m_seconds_count") == 2.0


# -- stats.json windows -------------------------------------------------------

def test_stats_collector_window_semantics():
    s = StatsCollector(window_s=10.0)
    s.record(1, 201, "buy", "user", now=0.0)
    s.record(1, 201, "buy", "user", now=3.0)
    s.record(2, 400, None, None, now=4.0)
    doc = s.to_json(now=5.0)
    assert doc["statsSinceStart"] == doc["statsCurrent"]
    assert doc["statsLastWindow"] == []
    buy = next(e for e in doc["statsCurrent"] if e.get("event") == "buy")
    assert buy == {"status": 201, "count": 2, "appId": 1, "event": "buy",
                   "entityType": "user"}
    # crossing the window boundary publishes current as last-window
    s.record(1, 201, "view", "user", now=12.0)
    doc = s.to_json(now=12.5)
    assert [e["count"] for e in doc["statsLastWindow"]] == [2, 1]
    assert len(doc["statsCurrent"]) == 1
    assert doc["statsCurrent"][0]["event"] == "view"
    assert len(doc["statsSinceStart"]) == 3   # since-start never resets
    # app filter keeps only that app's entries
    doc1 = s.to_json(app_id=2, now=13.0)
    assert all(e["appId"] == 2 for e in doc1["statsSinceStart"])
    # an idle gap spanning multiple windows: the just-completed window
    # was empty — old counts must not resurface as "last window"
    doc2 = s.to_json(now=300.0)
    assert doc2["statsLastWindow"] == []
    assert doc2["statsCurrent"] == []
    assert len(doc2["statsSinceStart"]) == 3


def test_event_server_state_bounds_event_label_cardinality(mem_storage):
    from predictionio_tpu.api.event_server import EventServerState

    state = EventServerState(mem_storage)
    state.MAX_EVENT_LABELS = 10
    for k in range(50):
        state.record(1, f"evt-{k}", 201, entity_type="user")
    recorded = set(state.counts[1])
    # names and entity types share the budget: at most MAX distinct
    # labels total, overflow folded into "(other)"
    assert "(other)" in recorded
    assert len(recorded) <= state.MAX_EVENT_LABELS + 1
    assert sum(state.counts[1].values()) == 50  # nothing dropped, only folded
    assert len(state._event_labels) == state.MAX_EVENT_LABELS


# -- event server endpoints ---------------------------------------------------

@pytest.fixture()
def event_server(mem_storage):
    from predictionio_tpu.api.event_server import run_event_server

    app_id = mem_storage.apps.insert(App(0, "obsapp"))
    key = mem_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=mem_storage,
                             background=True)
    yield {"base": f"http://127.0.0.1:{httpd.server_address[1]}",
           "key": key, "app_id": app_id}
    httpd.shutdown()
    httpd.server_close()


def test_readiness_probe_reports_version_and_tag(event_server):
    from predictionio_tpu import __version__

    status, body = http("GET", event_server["base"] + "/")
    assert status == 200
    assert body["version"] == __version__
    assert body["workerTag"]   # pid-based when not prefork-spawned


def test_event_server_stats_json_windows_and_compat(event_server):
    base, key = event_server["base"], event_server["key"]
    for _ in range(2):
        s, _b = http("POST", f"{base}/events.json?accessKey={key}", {
            "event": "rate", "entityType": "user", "entityId": "u1"})
        assert s == 201
    status, doc = http("GET", f"{base}/stats.json?accessKey={key}")
    assert status == 200
    # back-compat keys survive
    assert doc["appId"] == event_server["app_id"]
    assert doc["counts"]["rate"] == 2
    # reference-parity windows
    entry = next(e for e in doc["statsSinceStart"] if e.get("event") == "rate")
    assert entry["status"] == 201 and entry["count"] == 2
    assert entry["entityType"] == "user"
    assert doc["statsCurrent"] and "startTime" in doc and "window" in doc


def test_event_server_metrics_endpoint(event_server):
    base, key = event_server["base"], event_server["key"]
    s, _ = http("POST", f"{base}/events.json?accessKey={key}", {
        "event": "buy", "entityType": "user", "entityId": "u9"})
    assert s == 201
    with urllib.request.urlopen(base + "/metrics") as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    fams, types = parse_prometheus_text(text)
    assert types["pio_http_requests_total"] == "counter"
    assert types["pio_http_request_duration_seconds"] == "histogram"
    assert family_total(fams, "pio_events_ingested_total",
                        app=str(event_server["app_id"]), event="buy") >= 1
    # route label is normalized, not per-path cardinality
    assert any(lb.get("route") == "/events.json"
               for lb, _v in fams["pio_http_requests_total"])


def test_request_id_echoed_and_propagated(event_server):
    req = urllib.request.Request(event_server["base"] + "/",
                                 headers={"X-Request-ID": "abc-123"})
    with urllib.request.urlopen(req) as r:
        assert r.headers["X-Request-ID"] == "abc-123"
    with urllib.request.urlopen(event_server["base"] + "/") as r:
        assert r.headers["X-Request-ID"]   # server-minted when absent


def test_route_label_bounds_cardinality():
    from predictionio_tpu.api.http_util import route_label

    assert route_label("/events.json?accessKey=k") == "/events.json"
    assert route_label("/events/abc123.json") == "/events/{id}.json"
    assert route_label("/webhooks/segmentio.json") == "/webhooks/{name}.json"
    assert route_label("/cmd/app/My App/accesskeys") == "/cmd/app/{name}/accesskeys"
    assert route_label("/totally/unknown/path") == "(other)"


# -- dashboard + query server endpoints ---------------------------------------

def test_dashboard_serves_metrics_stats_and_durations(fs_storage):
    import datetime as dt

    from predictionio_tpu.api.dashboard import run_dashboard
    from predictionio_tpu.storage.base import EngineInstance

    t0 = dt.datetime(2026, 8, 1, 12, 0, 0, tzinfo=dt.timezone.utc)
    fs_storage.engine_instances.insert(EngineInstance(
        id="dashinst1", status="COMPLETED", start_time=t0,
        end_time=t0 + dt.timedelta(seconds=12.5),
        engine_id="e", engine_version="1", engine_variant="default",
        engine_factory="f"))
    httpd = run_dashboard(host="127.0.0.1", port=0, storage=fs_storage,
                          background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        with urllib.request.urlopen(base + "/") as r:
            page = r.read().decode()
        assert "12.50 s" in page          # rendered end−start duration
        with urllib.request.urlopen(base + "/metrics") as r:
            assert b"pio_http_requests_total" in r.read()
        status, doc = http("GET", base + "/stats.json")
        assert status == 200 and "statsSinceStart" in doc
        status, _ = http("GET", base + "/spans/nonexistent.json")
        assert status == 404
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- span journal through the train workflow ----------------------------------

class _TracedEngine:
    """Minimal duck-typed Engine: train() runs timed() blocks that must
    land in the active span journal as children of the run's root."""

    def train(self, engine_params):
        from predictionio_tpu.utils.tracing import timed

        with timed("read_training"):
            with timed("parse"):
                pass
        with timed("fit"):
            pass
        return [{"weights": [1, 2, 3]}]


def test_span_journal_roundtrip_through_train(fs_storage):
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.obs import spans as obs_spans
    from predictionio_tpu.workflow import core_workflow

    instance = core_workflow.run_train(
        _TracedEngine(), EngineParams(), engine_id="traced",
        storage=fs_storage)
    assert instance.status == "COMPLETED"
    path = obs_spans.journal_path(fs_storage, instance.id)
    # persisted next to the engine instances (under the storage root)
    assert str(path).startswith(
        fs_storage.config.sources["FS"]["path"])
    spans = obs_spans.read_journal(path)
    by_name = {s["name"]: s for s in spans}
    assert {"train", "engine_train", "read_training", "parse", "fit",
            "save_models"} <= set(by_name)
    root = by_name["train"]
    assert root["parent"] is None
    assert by_name["engine_train"]["parent"] == root["id"]
    # timed() inside engine.train nests under the engine_train span
    assert by_name["read_training"]["parent"] == by_name["engine_train"]["id"]
    assert by_name["parse"]["parent"] == by_name["read_training"]["id"]
    assert all(s["duration_s"] >= 0 and s["end"] >= s["start"]
               for s in spans)
    assert root["attrs"]["instance_id"] == instance.id

    # the dashboard serves and renders the journal
    from predictionio_tpu.api.dashboard import run_dashboard

    httpd = run_dashboard(host="127.0.0.1", port=0, storage=fs_storage,
                          background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        status, doc = http("GET", f"{base}/spans/{instance.id}.json")
        assert status == 200 and len(doc["spans"]) == len(spans)
        with urllib.request.urlopen(base + "/") as r:
            assert b"engine_train" in r.read()
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_timed_sink_accumulates_seconds_and_count():
    from predictionio_tpu.utils.tracing import timed

    sink = {}
    for _ in range(3):
        with timed("op", sink):
            pass
    assert sink["op"] >= 0 and sink["op.count"] == 3


# -- cross-worker aggregation through real prefork workers --------------------

def test_cross_worker_scrape_sees_both_prefork_workers(tmp_path, monkeypatch):
    """`eventserver --workers 2`: ingest through BOTH workers, then one
    scrape of whichever worker answers must report the group aggregate —
    exactly the number of events acked — and two pio_worker_up series."""
    from predictionio_tpu.api.event_server import run_event_server
    from predictionio_tpu.storage.locator import (
        Storage,
        StorageConfig,
        set_storage,
    )

    store = tmp_path / "store"
    env_vars = {
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(store),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "JAX_PLATFORMS": "cpu",
        "PIO_METRICS_FLUSH_S": "0.2",
    }
    for k, v in env_vars.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("PIO_WRITER_TAG", raising=False)
    meta = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(store)}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                        "MODELDATA")}))
    app_id = meta.apps.insert(App(0, "obsxw"))
    key = meta.access_keys.insert(AccessKey("", app_id, []))
    set_storage(None)   # workers>1 resolves storage from env

    def scrape(base):
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            return parse_prometheus_text(r.read().decode())[0]

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
        # baseline: the in-process parent registry may carry counts from
        # earlier tests in this pytest process — assert on the DELTA
        base_fams = scrape(base)
        base_appended = family_total(
            base_fams, "pio_storage_events_appended_total")
        n = 40
        for k2 in range(n):
            body = {"event": "buy", "entityType": "user",
                    "entityId": "u1", "eventId": f"xw-{k2}"}
            for _ in range(5):
                try:
                    s, _b = http("POST",
                                 f"{base}/events.json?accessKey={key}",
                                 body)
                    assert s == 201
                    break
                except Exception:
                    time.sleep(0.2)
            else:
                raise AssertionError(f"event xw-{k2} could not be posted")
        # fresh connections are kernel-balanced; poll until the aggregate
        # converges (sibling snapshots flush on an interval)
        deadline = time.time() + 30
        while time.time() < deadline:
            fams = scrape(base)
            appended = family_total(
                fams, "pio_storage_events_appended_total") - base_appended
            if appended == n and len(fams.get("pio_worker_up", ())) >= 2:
                break
            time.sleep(0.3)
        assert appended == n, f"aggregate scrape saw {appended}/{n}"
        workers_up = {lb["worker"] for lb, v in fams["pio_worker_up"]
                      if v >= 1}
        assert len(workers_up) == 2, workers_up
    finally:
        httpd.shutdown()
        httpd.server_close()
        set_storage(None)


# -- lint ---------------------------------------------------------------------

def test_check_metrics_names_lint_passes():
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_metrics_names.py")],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok:" in r.stdout
