#!/usr/bin/env python
"""End-to-end generation-lineage roundtrip check.

Builds a localfs store, trains a small UR model, then deploys it as a
REAL ``pio deploy --workers 2 --follow`` prefork group in model-plane
mode — so the process that OPENS each lineage record (the dedicated
plane publisher, tag ``pub-*``) is never one of the processes that
serve ``/lineage.json`` (tags ``w0-*``/``w1-*``).  Appends a delta,
waits for the fold to converge every worker, makes sure BOTH workers
answered a query on the new generation, then asserts over plain HTTP:

- ``/lineage.json`` indexes the folded generation and the serving
  worker's tag differs from the record's origin (the cross-process
  proof: a worker that did not produce the generation can explain it);
- ``/lineage/<gen>.json`` returns the merged record with outcome
  ``complete``: the publisher-side stages (append_observed, fold.*,
  publish, plane.write), the watcher hops (watcher_wake, compose), an
  ``install`` from BOTH serving workers, the ``cache_invalidation``
  child parented under install, and at least one ``first_serve``;
- stage start times are monotone along the freshness waterfall
  (append_observed → publish → plane.write → watcher_wake → compose →
  install → first_serve);
- ``/lineage/<lid>.json`` (id-keyed fetch) returns the same record;
- ``/healthz`` answers HTTP 200 with a non-``burning`` verdict and
  ``/metrics/history.json`` serves at least one TSDB sample.

Exit 0 = roundtrip complete; 1 = any assertion failed (printed).  Run
standalone (``python scripts/check_lineage_roundtrip.py``) or via the
tier-1 suite (tests/test_lineage.py wraps it), like
check_trace_roundtrip.py.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

WORKERS = 2
READY_S = 180.0
CONVERGE_S = 120.0
# the publisher-side stages every record must carry, in waterfall order
# (fold.* phases vary with the fold's shape and are asserted separately)
ORDERED = ("append_observed", "publish", "plane.write", "watcher_wake",
           "compose", "install", "first_serve")


def buy(u: str, i: str):
    from predictionio_tpu.events.event import Event

    return Event(event="purchase", entity_type="user", entity_id=u,
                 target_entity_type="item", target_entity_id=i)


def build_store(path: str):
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.storage.locator import (
        Storage, StorageConfig, set_storage,
    )

    storage = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": path}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA",
                                        "MODELDATA")}))
    set_storage(storage)
    app_id = storage.apps.insert(App(0, "lineageapp"))
    events = [buy(f"u{u}", f"i{it}")
              for u in range(12) for it in range(8) if (u * it + u) % 3]
    storage.l_events.insert_batch(events, app_id)
    return storage, app_id


def get_json(base: str, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def post_query(base: str, body: dict, timeout: float = 30.0):
    req = urllib.request.Request(
        base + "/queries.json", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def main() -> int:
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.create_workflow import engine_from_variant

    problems = []
    tmp = tempfile.mkdtemp(prefix="pio-lineage-rt-")
    store_path = os.path.join(tmp, "store")
    proc = None
    base = None
    try:
        storage, app_id = build_store(store_path)
        variant = {
            "id": "lineage-rt",
            "engineFactory": "predictionio_tpu.models."
                             "universal_recommender."
                             "UniversalRecommenderEngine",
            "datasource": {"params": {
                "appName": "lineageapp", "eventNames": ["purchase"]}},
            "algorithms": [{"name": "ur", "params": {
                "appName": "lineageapp", "eventNames": [], "meshDp": 1,
                "maxCorrelatorsPerItem": 8}}],
        }
        engine_json = os.path.join(tmp, "engine.json")
        with open(engine_json, "w") as f:
            json.dump(variant, f)
        _factory, engine, ep = engine_from_variant(variant)
        core_workflow.run_train(engine, ep, engine_id="lineage-rt",
                                storage=storage)

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = {
            **os.environ,
            "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_FS_PATH": store_path,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "FS",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "FS",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
            "JAX_PLATFORMS": "cpu",
            "PIO_MODEL_PLANE": "on",
            "PIO_MODEL_PLANE_POLL_S": "0.1",
            "PIO_METRICS_FLUSH_S": "0.25",
            "PIO_TSDB_INTERVAL_S": "0.5",
        }
        proc = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.cli.main",
             "deploy", "--engine-json", engine_json,
             "--ip", "127.0.0.1", "--port", str(port),
             "--workers", str(WORKERS), "--follow", "0.2"],
            env=env)
        base = f"http://127.0.0.1:{port}"

        # ready = every worker pid visible AND on the publisher's
        # bootstrap generation (>= 2: 1 is the parent's initial publish)
        pids: dict = {}
        deadline = time.time() + READY_S
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"deploy died during startup (rc {proc.returncode})")
            if time.time() > deadline:
                raise RuntimeError(f"group not ready in {READY_S}s ({pids})")
            try:
                _, d = get_json(base, "/", timeout=2)
                pids[d["pid"]] = int(d.get("planeGeneration") or 0)
            except Exception:
                time.sleep(0.1)
                continue
            if len(pids) >= WORKERS and all(g >= 2 for g in pids.values()):
                break
            time.sleep(0.05)
        gref = max(pids.values())

        # the delta: co-buyers couple a brand-new item to i1
        storage.l_events.insert_batch(
            [buy("probe0", "i1")]
            + [buy(f"cob{j}", "i1") for j in range(6)]
            + [buy(f"cob{j}", "fresh_item") for j in range(6)], app_id)

        conv: dict = {}
        deadline = time.time() + CONVERGE_S
        while time.time() < deadline:
            try:
                _, d = get_json(base, "/", timeout=2)
                conv[d["pid"]] = int(d.get("planeGeneration") or 0)
            except Exception:
                pass
            if len(conv) >= WORKERS and all(g > gref for g in conv.values()):
                break
            time.sleep(0.05)
        if len(conv) < WORKERS or not all(g > gref for g in conv.values()):
            raise RuntimeError(
                f"fold never converged the group in {CONVERGE_S}s "
                f"(gref={gref}, seen={conv})")
        gen = max(conv.values())

        # make BOTH workers answer on the new generation, so each one
        # records its first_serve hop (SO_REUSEPORT balances fresh
        # connections across the group eventually)
        served = set()
        deadline = time.time() + 60
        while len(served) < WORKERS and time.time() < deadline:
            try:
                _, d = get_json(base, "/", timeout=2)
                st, _doc = post_query(base, {"user": "probe0", "num": 5})
                if st == 200:
                    served.add(d["pid"])
            except Exception:
                pass
            time.sleep(0.02)
        if len(served) < WORKERS:
            problems.append(
                f"only {len(served)}/{WORKERS} workers answered queries "
                "(cannot assert both first_serve hops)")

        # the record needs a persist cycle (0.5 s throttle) to cross
        # processes; poll for completeness instead of sleeping blind
        doc = None
        deadline = time.time() + 30
        while time.time() < deadline:
            st, d = get_json(base, f"/lineage/{gen}.json")
            if st == 200:
                doc = d
                installs = {s.get("worker") for s in d.get("stages", ())
                            if s.get("stage") == "install"}
                if (d.get("outcome") == "complete"
                        and len(installs) >= WORKERS):
                    break
            time.sleep(0.25)
        if doc is None:
            raise RuntimeError(f"/lineage/{gen}.json never answered 200")

        stages = doc.get("stages", ())
        names = {s.get("stage") for s in stages}
        if doc.get("outcome") != "complete":
            problems.append(f"generation {gen} record outcome="
                            f"{doc.get('outcome')!r}, expected 'complete'")
        for need in ORDERED:
            if need not in names:
                problems.append(f"record is missing stage {need!r}")
        if not any(n.startswith("fold.") for n in names):
            problems.append("record carries no fold.* phase stage")
        cache_kids = [s for s in stages
                      if s.get("stage") == "cache_invalidation"]
        if not cache_kids:
            problems.append("no cache_invalidation stage (serve cache is "
                            "on by default — the install hook is broken)")
        elif any(s.get("parent") != "install" for s in cache_kids):
            problems.append("cache_invalidation not parented under install")
        installs = {s.get("worker") for s in stages
                    if s.get("stage") == "install"}
        if len(installs) < WORKERS:
            problems.append(
                f"install recorded by {sorted(installs)} — expected all "
                f"{WORKERS} serving workers")
        serves = {s.get("worker") for s in stages
                  if s.get("stage") == "first_serve"}
        if not serves:
            problems.append("no first_serve stage recorded")
        origin = doc.get("origin") or ""
        if not origin.startswith("pub-"):
            problems.append(
                f"record origin {origin!r} is not the plane publisher — "
                "the fold stages came from the wrong process")
        if origin in installs | serves:
            problems.append(
                f"origin {origin!r} also recorded install/first_serve — "
                "the publisher must not serve")
        # waterfall monotonicity on earliest start per ordered stage
        starts = {}
        for s in stages:
            n = s.get("stage")
            if n in ORDERED:
                t = float(s.get("start") or 0)
                starts[n] = min(starts.get(n, t), t)
        seq = [(n, starts[n]) for n in ORDERED if n in starts]
        for (a, ta), (b, tb) in zip(seq, seq[1:]):
            if tb < ta - 1e-3:
                problems.append(
                    f"stage {b} starts before {a} ({tb:.6f} < {ta:.6f})")
        for s in stages:
            if not (0 <= float(s.get("duration_s") or 0) <= 300):
                problems.append(f"stage {s.get('stage')!r} has a bogus "
                                f"duration {s.get('duration_s')!r}")

        # index + id-keyed fetch + cross-process serving proof
        _, index = get_json(base, "/lineage.json")
        entry = next((e for e in index.get("records", ())
                      if e.get("generation") == gen), None)
        if entry is None:
            problems.append(f"/lineage.json does not index generation {gen}")
        elif entry.get("lid") != doc.get("lid"):
            problems.append("/lineage.json indexes a different lid than "
                            "the generation fetch returned")
        server_tag = index.get("worker") or ""
        if not server_tag or server_tag == origin:
            problems.append(
                f"/lineage.json served by {server_tag!r} — must be a "
                "worker that did NOT produce the record")
        st, by_lid = get_json(base, f"/lineage/{doc.get('lid')}.json")
        if st != 200 or by_lid.get("lid") != doc.get("lid"):
            problems.append("id-keyed /lineage/<lid>.json fetch failed")

        # the two lineage consumers answer on the same sockets
        st, hz = get_json(base, "/healthz")
        if st != 200:
            problems.append(f"/healthz answered HTTP {st}")
        if hz.get("status") == "burning":
            problems.append(f"/healthz reports burning on an idle "
                            f"deploy: {hz}")
        st, hist = get_json(base, "/metrics/history.json")
        if st != 200 or not hist.get("samples"):
            problems.append("/metrics/history.json has no TSDB samples")
    except Exception as e:  # noqa: BLE001 - the harness wants one rc
        problems.append(f"roundtrip aborted: {e!r}")
    finally:
        if proc is not None and base is not None:
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
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        from predictionio_tpu.storage.locator import set_storage

        set_storage(None)
        shutil.rmtree(tmp, ignore_errors=True)
    for p in problems:
        print(f"FAIL {p}", file=sys.stderr)
    if not problems:
        print(f"ok: generation {gen} lineage complete across "
              f"{WORKERS} serving workers + publisher "
              f"(origin {origin}, installs {sorted(installs)}), "
              "waterfall monotone, /healthz + /metrics/history.json live")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
