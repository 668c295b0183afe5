"""chip_smoke.py off the chip: the explicit rehearsal mode runs the whole
train -> deploy -> query path green on CPU, and without the flag a
machine with no accelerator gets a non-zero exit and no result line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(tmp_path, *argv, devices=1):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a cache placed from outside, and empty: the first train of the
    # rehearsal is really cold and the second must hit what it wrote
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla_cache")
    env["TMPDIR"] = str(tmp_path)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, capture_output=True, text=True, timeout=600)


def _report(r):
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[-1] and '"ok"' not in lines[-1]
    report = json.loads("\n".join(lines[lines.index("{"):-1]))
    assert report["rehearsal"] is True and report["claim"] is None
    return report


def test_rehearsal_runs_green(tmp_path):
    report = _report(_smoke(tmp_path, "--rehearsal"))
    assert report["device"]["platform"] == "cpu"
    assert report["train_first"]["pallas"] == "interpret"
    cache = report["compile_cache"]
    assert cache["dir"] == str(tmp_path / "xla_cache")
    assert cache["first_process"]["cacheWrites"] >= 1
    assert cache["second_process"]["cacheHits"] >= 1
    assert cache["second_process"]["cacheWrites"] == 0
    dev = report["serve_device"]
    assert (dev["scorer"], dev["tail"], dev["batcher"]) == (
        "device", "device", True)
    assert report["serve_host"]["scorer"] == "host"
    assert report["parity"]["users"] == 30
    assert report["model"]["n_items"] == 700
    assert not list(tmp_path.glob("chip_smoke_*"))   # work dir removed


def test_mesh_rehearsal_compares_sharded_and_one_device_trains(tmp_path):
    """`--mesh` (the builder's four-chip run): the default train shards
    over every device, a second one is pinned to one, and the persisted
    indicator tables agree."""
    report = _report(_smoke(tmp_path, "--rehearsal", "--mesh", devices=4))
    assert report["device"]["count"] == 4
    assert report["model"]["compared_with"] == "smoke-ur-dp1"
    assert report["model"]["mismatched_rows"] == 0


def test_without_a_chip_it_fails_and_prints_no_result(tmp_path):
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stdout.strip().splitlines()[-1]
