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


def load_harness():
    """`benchmark/run.py` as a module (it is a script, not a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
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
