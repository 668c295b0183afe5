"""Multi-worker event ingestion: per-writer segments, group commit,
prefork event-server workers, crash safety.

The PR-1 tentpole's correctness contract: N concurrent writer processes
appending to one (app, channel) — each via its own ``seg-<tag>-NNNNN``
series — lose nothing and duplicate nothing across segment rotation, and
a SIGKILLed writer leaves a log every acknowledged event survives in
(PIO_FSYNC=always) that readers scan without crashing."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.events.event import Event
from predictionio_tpu.storage import AccessKey, App
from predictionio_tpu.storage.localfs import FSEvents


def _writer_script(root, tag, n, rotate_bytes=4096, fsync="rotate",
                   ack_each=False):
    """A real OS-process writer: inserts ``n`` events with client-supplied
    ids ``<tag>-<k>``, tiny segments so rotation happens constantly."""
    return textwrap.dedent(f"""
        import os, sys
        os.environ["PIO_FSYNC"] = {fsync!r}
        from predictionio_tpu.storage import localfs
        localfs.SEGMENT_MAX_BYTES = {rotate_bytes}
        ev = localfs.FSEvents({root!r}, writer_tag={tag!r})
        for k in range({n}):
            ev.insert_json_batch(
                [{{"event": "buy", "entityType": "user",
                   "entityId": "u%d" % k,
                   "eventId": "{tag}-%d" % k}}], 1)
            if {ack_each!r}:
                print("{tag}-%d" % k, flush=True)
        # close writers so the tail is flushed (rotate policy)
        for w in ev._writers.values():
            w.close()
        print("DONE", flush=True)
    """)


def test_concurrent_writer_processes_no_loss_no_dup(tmp_path):
    """Two real writer processes, one (app, channel), constant rotation:
    the union of their per-writer segments holds every event exactly
    once."""
    n = 300
    procs = [
        subprocess.Popen([sys.executable, "-c",
                          _writer_script(str(tmp_path), tag, n)],
                         stdout=subprocess.PIPE, text=True)
        for tag in ("wA", "wB")
    ]
    for p in procs:
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0 and "DONE" in out
    reader = FSEvents(tmp_path)
    ids = [e.event_id for e in reader._iter_raw(1, None)]
    expect = {f"{t}-{k}" for t in ("wA", "wB") for k in range(n)}
    assert len(ids) == len(expect), (len(ids), len(expect))
    assert set(ids) == expect
    # rotation actually happened, per writer, with per-writer naming
    chan = tmp_path / "events" / "app_1" / "_default"
    for tag in ("wA", "wB"):
        own = list(chan.glob(f"seg-{tag}-*.jsonl"))
        assert len(own) > 1, f"writer {tag} never rotated"


def test_kill_writer_mid_stream_acked_events_survive(tmp_path):
    """SIGKILL a writer mid-append (PIO_FSYNC=always): every event acked
    BEFORE the kill is recovered; the torn tail neither crashes the scan
    nor corrupts later appends by a restarted writer."""
    p = subprocess.Popen(
        [sys.executable, "-c",
         _writer_script(str(tmp_path), "wK", 100_000, fsync="always",
                        ack_each=True)],
        stdout=subprocess.PIPE, text=True)
    acked = []
    for line in p.stdout:
        acked.append(line.strip())
        if len(acked) >= 50:
            break
    os.kill(p.pid, signal.SIGKILL)
    p.wait(timeout=30)
    # under fsync=always every acked insert is durable
    reader = FSEvents(tmp_path)
    got = {e.event_id for e in reader._iter_raw(1, None)}   # must not raise
    missing = set(acked) - got
    assert not missing, f"acked events lost after SIGKILL: {missing}"
    # a restarted writer with the same tag heals any torn tail and
    # continues; the union stays readable and gains the new event
    w2 = FSEvents(tmp_path, writer_tag="wK")
    w2.insert(Event(event="buy", entity_type="user", entity_id="after",
                    event_id="after-kill"), 1)
    got2 = {e.event_id for e in FSEvents(tmp_path)._iter_raw(1, None)}
    assert "after-kill" in got2
    assert set(acked) <= got2


def test_group_commit_many_threads_exactly_once(tmp_path, monkeypatch):
    """In-process group commit: concurrent request threads' appends all
    land exactly once across rotation, under the strictest fsync policy
    (where group commit matters most)."""
    from predictionio_tpu.storage import localfs

    monkeypatch.setenv("PIO_FSYNC", "always")
    monkeypatch.setattr(localfs, "SEGMENT_MAX_BYTES", 8192)
    ev = FSEvents(tmp_path)
    n_threads, per_thread = 8, 40
    errs = []

    def work(t):
        try:
            for k in range(per_thread):
                r = ev.insert_json_batch(
                    [{"event": "buy", "entityType": "user",
                      "entityId": f"u{t}",
                      "eventId": f"t{t}-{k}"}], 1)
                assert r[0]["status"] == 201
        except Exception as e:   # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    ids = [e.event_id for e in ev._iter_raw(1, None)]
    assert len(ids) == n_threads * per_thread
    assert len(set(ids)) == n_threads * per_thread


def test_append_error_nacks_whole_group(tmp_path, monkeypatch):
    """A failed write (ENOSPC analogue) must raise for EVERY member of
    the commit group — no event may be acked without landing on disk —
    and the group must recover for subsequent appends."""
    from predictionio_tpu.storage import localfs

    ev = FSEvents(tmp_path)
    boom = {"on": True}
    orig_append = localfs._SegmentWriter.append

    def flaky_append(self, text):
        if boom["on"]:
            raise OSError(28, "No space left on device")
        return orig_append(self, text)

    monkeypatch.setattr(localfs._SegmentWriter, "append", flaky_append)
    errs = []

    def work(k):
        try:
            ev.insert(Event(event="buy", entity_type="user",
                            entity_id=f"u{k}"), 1)
        except OSError as e:
            errs.append(e)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(errs) == 4   # every member NACKed
    boom["on"] = False
    ev.insert(Event(event="buy", entity_type="user", entity_id="ok",
                    event_id="recovered"), 1)
    assert {e.event_id for e in ev._iter_raw(1, None)} == {"recovered"}


def test_torn_tail_skipped_and_healed(tmp_path):
    """An unterminated final line (writer killed mid-append) is skipped by
    scans and truncated away when the owning writer reopens the segment."""
    ev = FSEvents(tmp_path)
    ev.insert(Event(event="buy", entity_type="user", entity_id="u1",
                    event_id="whole"), 1)
    for w in ev._writers.values():
        w.close()
    ev._writers.clear()
    chan = tmp_path / "events" / "app_1" / "_default"
    seg = sorted(chan.glob("seg-*.jsonl"))[-1]
    with open(seg, "a") as f:
        f.write('{"eventId": "torn", "event": "bu')   # no newline
    got = [e.event_id for e in FSEvents(tmp_path)._iter_raw(1, None)]
    assert got == ["whole"]
    # the writer truncates the torn tail before appending
    ev2 = FSEvents(tmp_path)
    ev2.insert(Event(event="buy", entity_type="user", entity_id="u2",
                     event_id="next"), 1)
    got = sorted(e.event_id for e in FSEvents(tmp_path)._iter_raw(1, None))
    assert got == ["next", "whole"]
    raw = seg.read_text()
    assert "torn" not in raw and raw.endswith("\n")


def test_kill_replicated_shard_primary_mid_group_commit(tmp_path):
    """The replicated-path extension of the SIGKILL crash test: a real
    writer process ingests through the sharded store's semi-sync
    replication barrier (every printed ack means BOTH nodes hold the
    event), is SIGKILLed mid-group-commit, and the primary node dirs are
    yanked away.  The promoted follower must serve every acked event
    exactly once, and the un-acked tail is either absent or present at
    most once (at-least-once ingest contract); a restarted writer
    continues on the promoted topology."""
    from pathlib import Path

    from predictionio_tpu.storage.sharded import ShardedEvents

    scripts_dir = str(Path(__file__).resolve().parent.parent / "scripts")
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    from check_store_failover import writer_script

    script = writer_script(str(tmp_path / "store"), "rk", 100_000)
    p = subprocess.Popen([sys.executable, "-c", script],
                         stdout=subprocess.PIPE, text=True)
    acked = []
    for line in p.stdout:
        acked.append(line.strip())
        if len(acked) >= 60:
            break
    os.kill(p.pid, signal.SIGKILL)
    p.wait(timeout=30)
    # the "node died" injection: both shard primaries vanish outright
    import shutil

    for k in (0, 1):
        pdir = tmp_path / "store" / f"shard_{k:02d}" / "a"
        shutil.move(str(pdir), str(pdir) + ".lost")
    os.environ["PIO_FSYNC"] = "always"
    ev = ShardedEvents(tmp_path / "store", shards=2, replicas=2)
    try:
        got = [e.event_id for e in ev.scan(1)]
        missing = set(acked) - set(got)
        assert not missing, f"acked events lost after promotion: {missing}"
        assert len(got) == len(set(got)), "duplicated events after promotion"
        # un-acked tail: absent or healed (each id at most once) — already
        # covered by the uniqueness assert; promotion happened on both
        topo = ev.topology_status()
        assert all(s["primary"] == "b" and s["epoch"] == 1
                   for s in topo["perShard"]), topo
        # a restarted writer keeps ingesting on the promoted topology
        res = ev.insert_json_batch(
            [{"event": "buy", "entityType": "user", "entityId": "uZ",
              "eventId": "after-kill"}], 1)
        assert res[0]["status"] == 201
        assert "after-kill" in {e.event_id for e in ev.scan(1)}
    finally:
        ev.close()
        os.environ.pop("PIO_FSYNC", None)


# -- HTTP layer ------------------------------------------------------------


@pytest.fixture()
def fs_event_server(fs_storage):
    from predictionio_tpu.api.event_server import run_event_server

    app_id = fs_storage.apps.insert(App(0, "mwapp"))
    key = fs_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=fs_storage,
                             background=True)
    yield {"base": f"http://127.0.0.1:{httpd.server_address[1]}",
           "key": key, "app_id": app_id, "storage": fs_storage}
    httpd.shutdown()
    httpd.server_close()


def _post(url, body):
    data = json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method="POST",
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return resp.status, json.loads(resp.read() or b"null")


def test_batch_path_matches_n_single_posts(fs_event_server):
    """Group-commit batch parity: one N-event batch stores the same events
    (modulo server-assigned ids/times) as N single posts, with identical
    per-item statuses."""
    base, key = fs_event_server["base"], fs_event_server["key"]
    events = [
        {"event": "buy", "entityType": "user", "entityId": f"u{i}",
         "targetEntityType": "item", "targetEntityId": f"i{i}",
         "properties": {"price": float(i)}}
        for i in range(5)
    ]
    bad = {"entityType": "user", "entityId": "broken"}   # missing event
    status, results = _post(
        f"{base}/batch/events.json?accessKey={key}", events + [bad])
    assert status == 200
    assert [r["status"] for r in results] == [201] * 5 + [400]
    single_statuses = []
    for e in events:
        s, _ = _post(f"{base}/events.json?accessKey={key}", e)
        single_statuses.append(s)
    try:
        _post(f"{base}/events.json?accessKey={key}", bad)
        single_statuses.append(200)
    except urllib.error.HTTPError as e:
        single_statuses.append(e.code)
    assert single_statuses == [201] * 5 + [400]

    def strip(e):
        d = e.to_json()
        for k in ("eventId", "eventTime", "creationTime"):
            d.pop(k, None)
        return json.dumps(d, sort_keys=True)

    st = fs_event_server["storage"]
    got = sorted(strip(e) for e in st.l_events.scan(fs_event_server["app_id"]))
    # every event stored twice (once per path), identically
    assert got == sorted(
        2 * [strip(Event.from_json(e)) for e in events])


def test_pio_max_batch_env(fs_storage, monkeypatch):
    """PIO_MAX_BATCH raises the batch cap (default 50 stays for reference
    parity)."""
    from predictionio_tpu.api.event_server import run_event_server

    monkeypatch.setenv("PIO_MAX_BATCH", "10")
    app_id = fs_storage.apps.insert(App(0, "capapp"))
    key = fs_storage.access_keys.insert(AccessKey("", app_id, []))
    httpd = run_event_server(host="127.0.0.1", port=0, storage=fs_storage,
                             background=True)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        ok = [{"event": "buy", "entityType": "user", "entityId": f"u{i}"}
              for i in range(10)]
        status, results = _post(f"{base}/batch/events.json?accessKey={key}", ok)
        assert status == 200 and all(r["status"] == 201 for r in results)
        try:
            status, _ = _post(f"{base}/batch/events.json?accessKey={key}",
                              ok + ok[:1])
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 400
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_eventserver_prefork_workers_end_to_end(tmp_path, monkeypatch):
    """`pio eventserver --workers 2` semantics, driven programmatically:
    both workers answer on one port (distinct pids), events ingested
    through the group land exactly once in the per-writer segment union,
    SIGKILLing one worker loses nothing acked (fsync=always), and the
    survivors keep ingesting."""
    from predictionio_tpu.api.event_server import run_event_server
    from predictionio_tpu.storage.locator import set_storage

    store = tmp_path / "store"
    env_vars = {
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(store),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "PIO_FSYNC": "always",
        "JAX_PLATFORMS": "cpu",
    }
    for k, v in env_vars.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("PIO_WRITER_TAG", raising=False)
    from predictionio_tpu.storage.locator import Storage, StorageConfig
    meta = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(store)}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                        "MODELDATA")}))
    app_id = meta.apps.insert(App(0, "pfesapp"))
    key = meta.access_keys.insert(AccessKey("", app_id, []))
    set_storage(None)   # workers>1 resolves storage from env
    httpd = run_event_server(host="127.0.0.1", port=0, background=True,
                             workers=2)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        assert len(httpd.pio_workers) == 1
        # wait until BOTH workers answer (child needs interpreter startup)
        pids, deadline = set(), time.time() + 90
        while len(pids) < 2 and time.time() < deadline:
            try:
                with urllib.request.urlopen(base + "/", timeout=2) as r:
                    pids.add(json.loads(r.read())["pid"])
            except Exception:
                time.sleep(0.2)
        assert len(pids) == 2, f"second worker never came up: {pids}"

        def post_event(eid):
            # fresh connection each time so the kernel balances across
            # workers; retry on the error surfaced when a connection lands
            # on the killed worker (client-supplied id keeps it idempotent)
            body = {"event": "buy", "entityType": "user",
                    "entityId": "u1", "eventId": eid}
            for _ in range(5):
                try:
                    s, r = _post(f"{base}/events.json?accessKey={key}", body)
                    assert s == 201
                    return
                except Exception:
                    time.sleep(0.2)
            raise AssertionError(f"event {eid} could not be posted")

        acked = []
        for k2 in range(30):
            post_event(f"pre-{k2}")
            acked.append(f"pre-{k2}")
        # kill the CHILD worker outright; the parent keeps serving
        child = httpd.pio_workers[0]
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
        for k2 in range(30):
            post_event(f"post-{k2}")
            acked.append(f"post-{k2}")
        reader = FSEvents(store)
        got = [e.event_id for e in reader._iter_raw(app_id, None)]
        assert set(acked) <= set(got), f"lost: {set(acked) - set(got)}"
        # idempotent retries may legitimately duplicate an id; anything
        # never retried must appear exactly once — and the union must come
        # from BOTH writers' segment series
        chan = store / "events" / f"app_{app_id}" / "_default"
        tags = {p.name.split("-")[1] for p in chan.glob("seg-*.jsonl")}
        assert "w0" in tags and len(tags) >= 2, tags
    finally:
        httpd.shutdown()
        httpd.server_close()
        set_storage(None)


def test_native_scan_skips_torn_tail(tmp_path):
    """The native scanner and the Python scan must agree on torn tails:
    an unterminated final line is unacknowledged and skipped by both."""
    from predictionio_tpu.native.scanner import native_available, scan_segments

    if not native_available():
        pytest.skip("native scanner unavailable")
    seg = tmp_path / "seg-00000.jsonl"
    good = {"event": "view", "entityType": "user", "entityId": "u1",
            "eventTime": "2026-01-01T00:00:00+00:00"}
    seg.write_text(json.dumps(good) + "\n" + json.dumps(good))  # torn tail
    assert len(scan_segments([seg])) == 1
