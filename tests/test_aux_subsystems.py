"""Aux-subsystem tests: webhooks, plugins, SDK clients, pio-env loader,
tracing helpers."""

import json
import logging
import urllib.request

import pytest

from predictionio_tpu.api.event_server import run_event_server
from predictionio_tpu.storage import AccessKey, App


@pytest.fixture()
def server(mem_storage):
    app_id = mem_storage.apps.insert(App(0, "auxapp"))
    key = mem_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=mem_storage,
                             background=True)
    yield {"base": f"http://127.0.0.1:{httpd.server_address[1]}", "key": key,
           "app_id": app_id, "storage": mem_storage}
    httpd.shutdown()
    httpd.server_close()


def post(url, body):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_segmentio_webhook(server):
    base, key = server["base"], server["key"]
    status, body = post(f"{base}/webhooks/segmentio.json?accessKey={key}", {
        "type": "track", "userId": "u99", "event": "Item Purchased",
        "properties": {"revenue": 39.95},
        "timestamp": "2026-02-01T12:00:00Z",
    })
    assert status == 201, body
    ev = next(iter(server["storage"].l_events.find(server["app_id"])))
    assert ev.event == "Item Purchased" and ev.entity_id == "u99"
    assert ev.properties["revenue"] == 39.95


def test_webhook_unknown_connector_and_bad_payload(server):
    base, key = server["base"], server["key"]
    status, _ = post(f"{base}/webhooks/nope.json?accessKey={key}", {"a": 1})
    assert status == 404
    status, _ = post(f"{base}/webhooks/segmentio.json?accessKey={key}", {"type": "track"})
    assert status == 400


def test_form_webhook(server):
    base, key = server["base"], server["key"]
    status, _ = post(f"{base}/webhooks/form.json?accessKey={key}", {
        "event": "buy", "entityType": "user", "entityId": "u5",
        "targetEntityType": "item", "targetEntityId": "i5", "price": 3})
    assert status == 201
    evs = list(server["storage"].l_events.find(server["app_id"], event_names=["buy"]))
    assert evs and evs[0].properties["price"] == 3


def test_mailchimp_webhook(server):
    base, key = server["base"], server["key"]
    # nested data form (JSON re-post)
    status, _ = post(f"{base}/webhooks/mailchimp.json?accessKey={key}", {
        "type": "subscribe", "fired_at": "2026-02-01 12:00:00",
        "data": {"email": "a@example.com", "list_id": "L1"}})
    assert status == 201
    # flattened data[...] form fields (MailChimp's native shape)
    status, _ = post(f"{base}/webhooks/mailchimp.json?accessKey={key}", {
        "type": "unsubscribe", "data[email]": "a@example.com",
        "data[reason]": "manual"})
    assert status == 201
    evs = {e.event: e for e in server["storage"].l_events.find(server["app_id"])}
    sub = evs["subscribe"]
    assert sub.entity_id == "a@example.com"
    assert sub.properties["list_id"] == "L1"
    assert sub.event_time.isoformat().startswith("2026-02-01T12:00:00")
    assert evs["unsubscribe"].properties["reason"] == "manual"
    # unsupported type and missing member key are 400s
    status, _ = post(f"{base}/webhooks/mailchimp.json?accessKey={key}",
                     {"type": "bogus"})
    assert status == 400
    status, _ = post(f"{base}/webhooks/mailchimp.json?accessKey={key}",
                     {"type": "cleaned", "data": {}})
    assert status == 400


def test_register_custom_connector(server):
    """The documented extension point: one function, one register call."""
    from predictionio_tpu.api.webhooks import register_connector
    from predictionio_tpu.events.event import Event

    def my_connector(payload):
        return Event(event=payload["action"], entity_type="user",
                     entity_id=str(payload["uid"]))

    register_connector("mysystem", my_connector)
    base, key = server["base"], server["key"]
    status, _ = post(f"{base}/webhooks/mysystem.json?accessKey={key}",
                     {"action": "signup", "uid": 7})
    assert status == 201
    evs = list(server["storage"].l_events.find(
        server["app_id"], event_names=["signup"]))
    assert evs and evs[0].entity_id == "7"


def test_plugins_blocker_and_sniffer():
    from predictionio_tpu.api.plugins import (
        OutputBlocker, OutputSniffer, PluginRegistry,
    )

    seen = []

    class Cap(OutputBlocker):
        name = "cap"

        def process(self, query, prediction):
            return min(prediction, 10)

    class Sniff(OutputSniffer):
        name = "sniff"

        def process(self, query, prediction):
            seen.append((query, prediction))

    class Broken(OutputSniffer):
        name = "broken"

        def process(self, query, prediction):
            raise RuntimeError("boom")

    reg = PluginRegistry()
    reg.register(Cap())
    reg.register(Sniff())
    reg.register(Broken())
    out = reg.apply("q", 42)
    assert out == 10          # blocker transformed
    assert seen == [("q", 10)]  # sniffer saw transformed value; broken one ignored


def test_sdk_event_client(server):
    from predictionio_tpu.sdk import EventClient

    c = EventClient(server["key"], server["base"])
    eid = c.record_user_action_on_item("rate", "u1", "i1", {"rating": 4})
    got = c.get_event(eid)
    assert got["event"] == "rate" and got["properties"]["rating"] == 4
    c.set_user("u1", {"plan": "pro"})
    results = c.create_events([
        {"event": "view", "entityType": "user", "entityId": "u1",
         "targetEntityType": "item", "targetEntityId": "i2"},
    ])
    assert results[0]["status"] == 201
    found = c.find_events(event="view")
    assert len(found) == 1
    c.delete_event(eid)
    from predictionio_tpu.sdk.client import PIOError

    with pytest.raises(PIOError) as ei:
        c.get_event(eid)
    assert ei.value.status == 404


def test_load_pio_env(tmp_path, monkeypatch):
    from predictionio_tpu.utils.config import load_pio_env

    f = tmp_path / "pio-env.sh"
    f.write_text(
        "# storage config\n"
        "export PIO_STORAGE_SOURCES_FS_TYPE=localfs\n"
        'PIO_STORAGE_SOURCES_FS_PATH="$BASE/store"\n'
        "export PIO_STORAGE_REPOSITORIES_METADATA_SOURCE=FS\n"
        "ignored line without assignment\n"
    )
    out = load_pio_env(str(f), apply=False, base={"BASE": "/data"})
    assert out["PIO_STORAGE_SOURCES_FS_TYPE"] == "localfs"
    assert out["PIO_STORAGE_SOURCES_FS_PATH"] == "/data/store"
    assert len(out) == 3
    assert load_pio_env("/nonexistent/pio-env.sh", apply=False) == {}


def test_timed_tracer():
    from predictionio_tpu.utils.tracing import timed

    sink = {}
    with timed("span", sink):
        pass
    assert "span" in sink and sink["span"] >= 0


def test_compile_cache_default_is_fixed_path_in_checkout(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR unset: the cache lives at the ONE fixed
    path inside the checkout (gitignored), and compiles land there."""
    import os
    from pathlib import Path

    import jax
    from jax._src import compilation_cache as _cc

    from predictionio_tpu.utils import config as cfg

    repo = Path(__file__).resolve().parents[1]
    assert cfg.COMPILE_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()

    loc = tmp_path / "xla_cache"   # keep the test's compile out of the repo
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cfg, "COMPILE_CACHE_DIR", loc)
    cfg.enable_compilation_cache()
    assert loc.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(loc)
    # a fresh-process compile lands in the cache (threshold forced to 0
    # for the test; production keeps JAX's >=1s default)
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        import numpy as np

        import jax.numpy as jnp

        # jax pins the persistent-cache singleton to the dir in effect at
        # the FIRST in-process compile; reset it so this test's dir takes
        # (otherwise the test is order-sensitive: any earlier compile —
        # e.g. a deploy test — pins the default dir and nothing lands
        # here)
        _cc.reset_cache()
        # and a never-before-compiled program, so the in-memory executable
        # cache can't satisfy it without touching disk
        c = float(np.random.default_rng().uniform(2.0, 3.0))

        @jax.jit
        def f(x):
            return (x @ x * c).sum()

        np.asarray(f(jnp.ones((63, 63))))
        assert len(os.listdir(loc)) >= 1
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved_min)
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()   # unpin our tmp dir for later tests


def test_compile_cache_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program sets NO directory in
    code — JAX reads the variable itself, in this and every child
    process."""
    import subprocess
    import sys
    from pathlib import Path

    import jax

    from predictionio_tpu.utils import config as cfg

    outside = tmp_path / "outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    monkeypatch.setattr(cfg, "COMPILE_CACHE_DIR", tmp_path / "never")
    before = jax.config.jax_compilation_cache_dir
    cfg.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "never").exists()
    r = subprocess.run(
        [sys.executable, "-c",
         "from predictionio_tpu.utils.config import enable_compilation_cache;"
         "enable_compilation_cache(); import jax;"
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120,
        cwd=str(Path(__file__).resolve().parents[1]))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(outside)
