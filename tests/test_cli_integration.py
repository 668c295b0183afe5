"""Full CLI loop via subprocess — the reference's `pio_tests` integration
harness analogue (SURVEY.md §4): app new → import → train → deploy → HTTP
query → eval → export, all through the `pio` entry point."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pio_env(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PIO_FS_BASEDIR"] = str(tmp_path / "pio_store")
    # keep subprocess JAX on CPU regardless of ambient TPU state
    env["JAX_PLATFORMS"] = "cpu"
    return env


def pio(args, tmp_path, **kw):
    return subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.cli.main", *args],
        env=pio_env(tmp_path), capture_output=True, text=True, timeout=180, **kw,
    )


@pytest.mark.slow
def test_full_cli_loop(tmp_path):
    # 1. app new
    r = pio(["app", "new", "MyApp"], tmp_path)
    assert r.returncode == 0, r.stderr
    assert "Created app" in r.stdout

    # duplicate rejected
    r = pio(["app", "new", "MyApp"], tmp_path)
    assert r.returncode == 1

    # 2. import events (ML-100K-like tiny ratings file)
    rng = np.random.default_rng(0)
    events_file = tmp_path / "events.jsonl"
    with open(events_file, "w") as f:
        for u in range(15):
            for i in range(10):
                liked = (u < 8) == (i < 5)
                if rng.random() < 0.85:
                    f.write(json.dumps({
                        "event": "rate", "entityType": "user", "entityId": f"u{u}",
                        "targetEntityType": "item", "targetEntityId": f"i{i}",
                        "properties": {"rating": 5.0 if liked else 1.0},
                        "eventTime": "2026-01-01T00:00:00Z",
                    }) + "\n")
    r = pio(["import", "--app-name", "MyApp", "--input", str(events_file)], tmp_path)
    assert r.returncode == 0, r.stderr
    assert "Imported" in r.stdout

    # 3. train
    engine_json = os.path.join(REPO, "examples", "recommendation", "engine.json")
    r = pio(["train", "--engine-json", engine_json], tmp_path)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "Training completed" in r.stdout

    # 4. deploy (background process) + query over HTTP
    port = 18321
    proc = subprocess.Popen(
        [sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
         "--engine-json", engine_json, "--ip", "127.0.0.1", "--port", str(port)],
        env=pio_env(tmp_path), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        deadline = time.time() + 60
        last_err = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json",
                    data=json.dumps({"user": "u1", "num": 3}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=5) as resp:
                    body = json.loads(resp.read())
                break
            except Exception as e:  # server not up yet
                last_err = e
                assert proc.poll() is None, proc.stderr.read().decode()
                time.sleep(0.5)
        else:
            raise AssertionError(f"query server never came up: {last_err}")
        items = [s["item"] for s in body["itemScores"]]
        assert len(items) == 3
        assert all(int(i[1:]) < 5 for i in items), items  # u1 is in group 0
    finally:
        proc.send_signal(signal.SIGINT)
        proc.wait(timeout=10)

    # 5. eval (uses the example Evaluation over the same store)
    r = pio(["eval", "examples.recommendation.evaluation.RecommendationEvaluation"],
            tmp_path)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "Evaluation completed" in r.stdout

    # 6. export round-trips the events
    out = tmp_path / "export.jsonl"
    r = pio(["export", "--app-name", "MyApp", "--output", str(out)], tmp_path)
    assert r.returncode == 0, r.stderr
    exported = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(exported) > 100 and all("eventId" in e for e in exported)

    # 7. status reports the trained instance's storage
    r = pio(["status"], tmp_path)
    assert r.returncode == 0 and "apps: 1" in r.stdout


def sharedfs_env(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PIO_FS_BASEDIR", None)
    env["PIO_STORAGE_SOURCES_SH_TYPE"] = "sharedfs"
    env["PIO_STORAGE_SOURCES_SH_PATH"] = str(tmp_path / "shared_store")
    for r in ("METADATA", "EVENTDATA", "MODELDATA"):
        env[f"PIO_STORAGE_REPOSITORIES_{r}_SOURCE"] = "SH"
    return env


def pio_sh(args, tmp_path, **kw):
    return subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.cli.main", *args],
        env=sharedfs_env(tmp_path), capture_output=True, text=True,
        timeout=180, **kw)


@pytest.mark.slow
def test_cli_loop_on_sharedfs_with_concurrent_importers(tmp_path):
    """The full product path on the multi-host backend: app new → TWO
    concurrent importer PROCESSES (per-writer segments in one shared log)
    → UR train → deploy → HTTP query."""
    r = pio_sh(["app", "new", "ShopApp"], tmp_path)
    assert r.returncode == 0, r.stderr

    rng = np.random.default_rng(23)
    files = []
    for w in range(2):
        lines = []
        for k in range(400):
            u, it = int(rng.integers(0, 40)), int(rng.integers(0, 15))
            lines.append(json.dumps({
                "event": "buy", "entityType": "user", "entityId": f"u{u}",
                "targetEntityType": "item", "targetEntityId": f"i{it}"}))
        f = tmp_path / f"events{w}.jsonl"
        f.write_text("\n".join(lines) + "\n")
        files.append(f)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "predictionio_tpu.cli.main", "import",
         "--app-name", "ShopApp", "--input", str(f)],
        env=sharedfs_env(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for f in files]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err
    # two writer processes → per-writer segments, one log
    segs = list((tmp_path / "shared_store" / "events").rglob("seg-*.jsonl"))
    assert len(segs) >= 2
    assert len({s.name.rsplit("-", 1)[0] for s in segs}) >= 2

    variant = {
        "id": "sh-ur",
        "engineFactory":
            "predictionio_tpu.models.universal_recommender.UniversalRecommenderEngine",
        "datasource": {"params": {"appName": "ShopApp", "eventNames": ["buy"]}},
        "algorithms": [{"name": "ur", "params": {
            "appName": "ShopApp", "meshDp": 1, "maxCorrelatorsPerItem": 5}}],
    }
    ej = tmp_path / "engine.json"
    ej.write_text(json.dumps(variant))
    r = pio_sh(["train", "--engine-json", str(ej)], tmp_path)
    assert r.returncode == 0, r.stderr

    server = subprocess.Popen(
        [sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
         "--engine-json", str(ej), "--ip", "127.0.0.1", "--port", "18731"],
        env=sharedfs_env(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 90
        body = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    "http://127.0.0.1:18731/queries.json",
                    data=json.dumps({"user": "u1", "num": 3}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=5) as resp:
                    body = json.loads(resp.read())
                break
            except Exception:
                time.sleep(1.5)
        assert body is not None and "itemScores" in body, body
        assert len(body["itemScores"]) > 0
    finally:
        server.send_signal(signal.SIGINT)
        try:
            server.wait(timeout=20)
        except subprocess.TimeoutExpired:
            server.kill()


def test_train_stop_after_read_and_prepare(tmp_path):
    """--stop-after-read/--stop-after-prepare sanity-check the pipeline
    without training or persisting an instance (reference WorkflowParams)."""
    r = pio(["app", "new", "DbgApp"], tmp_path)
    assert r.returncode == 0, r.stderr
    events = tmp_path / "ev.jsonl"
    events.write_text("\n".join(
        json.dumps({"event": "rate", "entityType": "user", "entityId": f"u{k}",
                    "targetEntityType": "item", "targetEntityId": f"i{k % 4}",
                    "properties": {"rating": 4.0}})
        for k in range(12)) + "\n")
    r = pio(["import", "--app-name", "DbgApp", "--input", str(events)], tmp_path)
    assert r.returncode == 0, r.stderr
    variant = {
        "id": "dbg", "engineFactory":
            "predictionio_tpu.models.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "DbgApp"}},
        "algorithms": [{"name": "als", "params": {"rank": 2,
                                                  "numIterations": 2,
                                                  "meshDp": 1}}],
    }
    ej = tmp_path / "engine.json"
    ej.write_text(json.dumps(variant))
    r = pio(["train", "--engine-json", str(ej), "--stop-after-read"], tmp_path)
    assert r.returncode == 0, r.stderr
    assert "read_training ->" in r.stdout and "Stopped before training" in r.stdout
    r = pio(["train", "--engine-json", str(ej), "--stop-after-prepare"], tmp_path)
    assert r.returncode == 0, r.stderr
    assert "prepare ->" in r.stdout
    # no engine instance was persisted by the debug runs
    r = pio(["deploy", "--engine-json", str(ej), "--port", "0"], tmp_path)
    assert r.returncode != 0  # nothing trained yet
