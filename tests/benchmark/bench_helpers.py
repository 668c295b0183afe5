"""Helpers of the benchmark's CPU tests: where the harness lives, and how a
test starts it as a process of its own (a rehearsal sets PIO_PALLAS and
friends in its environment, which must not leak into this test worker)."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
if str(ROOT) not in sys.path:        # the program, wherever pytest started
    sys.path.insert(0, str(ROOT))


def load_harness(script: str = "run.py"):
    """`benchmark/run.py` (or `control.py`) as a module: they are scripts,
    not a package."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_" + script[:-3], BENCH / script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def clean_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PIO_", "JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def run_cell(workload: str, *flags: str, script=None, cwd=ROOT, env=None,
             seconds: float = 1.0, seed: int = 2147483777):
    """One run of the benchmark's command as a child; (code, stdout, stderr)."""
    argv = [sys.executable, str(script or BENCH / "run.py"),
            "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), *flags]
    p = subprocess.run(argv, cwd=str(cwd), env=env or clean_env(),
                       capture_output=True, text=True, timeout=600)
    return p.returncode, p.stdout, p.stderr


def last_line(stdout: str) -> str:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return lines[-1] if lines else ""


def rehearsal_result(stdout: str) -> dict:
    line = last_line(stdout)
    assert line.startswith("REHEARSAL "), line[:200]
    return json.loads(line[len("REHEARSAL "):])


def rehearsal_config(harness, cell: str) -> dict:
    """A cell's configuration at its `rehearsal` sizes, as a rehearsal
    merges it."""
    config = harness.find_cell(harness.load_manifest(), cell)[1]
    return harness.merged(config, {k: v for k, v in
                                   config.get("rehearsal", {}).items()
                                   if k != "env"})


def run_control(cell: str, seeds: str, root=ROOT) -> list:
    """`control.py --rehearsal` of the tree at `root` as a child; its lines."""
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "control.py"), "--workload",
         cell, "--seeds", seeds, "--rehearsal"],
        cwd=str(root), env=clean_env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]


def run_faulty(fault: str, cell: str, root=ROOT) -> dict:
    """`faulty_run.py` of the tree at `root` as a child: a rehearsal with
    the timed path broken underneath; its result line."""
    p = subprocess.run(
        [sys.executable, str(root / "tests" / "benchmark" / "faulty_run.py"),
         fault, "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        cwd=str(root), env=clean_env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return rehearsal_result(p.stdout)


def assert_caught(got: dict, fault: str, config: dict) -> None:
    """Not `correct`, and by a check that is this fault's to catch: a state
    left unchanged by the driver's own, half of the events left out and an
    answer altered by one the configuration's limits name."""
    assert got["correct"] is False
    failed = {c["name"] for c in got["checks"]
              if not (c["value"] <= c["limit"])}
    caught_by = ({"model_not_the_last_jobs"} if fault == "unchanged"
                 else set(config["reference"]["limits"]))
    assert failed & caught_by, got["checks"]
