#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of `BENCHMARK.json`, in this process: find the chip or
fail, make the data from the seed, warm the cell's shapes (set-up), drive
the timed window, read the device's memory, free the program's state, hold
what the window produced against the plain reference, and print one JSON
object as the last line of standard output.  `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a profiler trace
of the same window.

Everything that belongs to one configuration, one traffic mix or one metric
is a file found by the name `BENCHMARK.json` gives (see README.md here); this
file knows none of them.

`--rehearsal` is the explicit CPU debugging mode (JAX on the CPU, Pallas
kernels interpreted, the configuration's `rehearsal` sizes): the same code
path, and a last line that starts with the word REHEARSAL, so that it can
never be read as a result.  It is a flag, not a fallback: without it a run
that finds no TPU, or fewer chips than the cell asks for, exits 3 and prints
no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from the first line we own

import argparse   # noqa: E402
import importlib.util   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import shutil   # noqa: E402
import sys   # noqa: E402
from pathlib import Path   # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WORK = ROOT / ".bench_work"          # stores, traces (listed in .gitignore)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero, print no result."""


# -- files found by name -----------------------------------------------------


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, imported under a name of its own."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest: dict, workload: str) -> tuple:
    cell = next((w for w in manifest["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"BENCHMARK.json has no workload {workload!r}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = load_json("traffic", cell["traffic"])
    return cell, config, traffic


def merged(base, over):
    """`over` laid on `base`, dict by dict; anything else replaces."""
    if isinstance(base, dict) and isinstance(over, dict):
        return {k: merged(base[k], over[k]) if k in base and k in over
                else over.get(k, base.get(k)) for k in {**base, **over}}
    return over


def metrics_of(manifest: dict, cell_name: str, group: str) -> list:
    """The metrics of `group` that this cell reports: those with no
    `workloads` key, and those whose key lists the cell."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


def peaks_for(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise Refused(f"peaks.json has no device kind {device_kind!r}: add "
                      "its published peaks with their source, never a default")
    return table[device_kind]


# -- the run -----------------------------------------------------------------


def prepare_env(config: dict, traffic: dict, rehearsal: bool) -> None:
    """Everything the program reads from the environment, set before it or
    JAX is imported."""
    env = os.environ
    for k in [k for k in env if k.startswith("PIO_STORAGE_")]:
        del env[k]
    env.update({k: str(v) for k, v in config.get("env", {}).items()})
    env.update({k: str(v) for k, v in traffic.get("env", {}).items()})
    if rehearsal:
        env.update({"JAX_PLATFORMS": "cpu", "PIO_PALLAS": "interpret",
                    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
        env.update({k: str(v) for k, v in
                    config.get("rehearsal", {}).get("env", {}).items()})
    # the compile cache: where JAX_COMPILATION_CACHE_DIR is set JAX reads it
    # itself; otherwise the program's own fixed <checkout>/.jax_cache
    # (utils/config.enable_compilation_cache), never a temporary name


def find_chip(chips: int, rehearsal: bool) -> dict:
    from predictionio_tpu.utils.config import enable_compilation_cache
    from predictionio_tpu.utils.device import device_info

    enable_compilation_cache()
    try:
        dev = device_info()
    except RuntimeError as e:
        raise Refused(f"JAX found no backend: {e}")
    if rehearsal:
        if dev["platform"] != "cpu":
            raise Refused("--rehearsal is the CPU mode; JAX found "
                          f"{dev['platform']}")
        return dev
    if dev["platform"] != "tpu":
        raise Refused(f"JAX found {dev['platform']!r}, not a TPU: this is a "
                      "chip benchmark (--rehearsal is the CPU debugging mode)")
    if dev["count"] < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{dev['count']}")
    return dev


def least_seconds(roofline: str, config: dict, peaks: dict) -> float:
    """The least time the chip could take for one job's work as
    `roofline/<roofline>.py` counts it from the configuration's shapes: the
    larger of operations over peak and bytes over bandwidth."""
    w = load_module("roofline", roofline).work(config)
    return max(w["flops"] / peaks["flops_per_s"],
               w["bytes"] / peaks["bytes_per_s"])


def memory_peak_bytes() -> tuple:
    """(peak on the fullest chip, every statistic of that chip).  The TPU
    runtime keeps two books: `peak_bytes_in_use` counts live buffers
    (arguments and results) and `peak_bytes_reserved` the scratch it set
    aside for the temporaries of the programs it ran; a program's plan
    (`memory_analysis()`: arguments + temporaries) is their sum (PERF.md).
    A backend that keeps neither gives 0."""
    import jax

    def peak(s: dict) -> int:
        return int(s.get("peak_bytes_in_use", 0)) + int(
            s.get("peak_bytes_reserved", 0))

    stats = [d.memory_stats() or {} for d in jax.devices()]
    fullest = max(stats, key=peak)
    return peak(fullest), fullest


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def run(args) -> int:
    manifest = load_manifest()
    cell, config, traffic = find_cell(manifest, args.workload)
    if args.rehearsal:     # the configuration's tiny sizes, merged over it
        small = config.get("rehearsal", {})
        config = merged(config, {k: v for k, v in small.items() if k != "env"})
    prepare_env(config, traffic, args.rehearsal)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(HERE))
    dev = find_chip(int(cell["chips"]), args.rehearsal)
    peaks = None if args.rehearsal else peaks_for(dev["kind"])
    say(f"{cell['name']} seed {args.seed} on {dev}"
        + (" REHEARSAL" if args.rehearsal else ""))

    # the store and the trace of this run; only the compile cache has to
    # sit at a fixed path, so two runs at once never share a store
    work = WORK / f"{cell['name']}-s{args.seed}-t{args.trace}-p{os.getpid()}"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    driver = load_module("drivers", traffic["driver"])
    ctx = {"config": config, "traffic": traffic, "seed": int(args.seed),
           "seconds": float(args.seconds), "trace": bool(args.trace),
           "work": work, "say": say, "load_module": load_module}
    try:
        session = driver.Session(ctx)
        session.set_up()                      # data, store, warm-up
        setup_s = time.perf_counter() - _T0
        say(f"set-up done in {setup_s:.1f}s; window of {args.seconds}s")
        window = session.window()             # timed; traced if asked
        peak, mem_stats = memory_peak_bytes()
        say(f"memory: peak {peak} = in use + reserved of {mem_stats}")
        checks = session.check()              # frees the program's state first
        reduced = None
        if args.trace:
            import trace_reduce

            reduced = trace_reduce.reduce(window["trace_dir"],
                                          window["annotation"])
    finally:
        if args.keep:
            say(f"kept {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": window["attempted"], "failed": window["failed"]}
    if args.trace:
        wanted = metrics_of(manifest, cell["name"], "per_layer")
        facts = {**window["facts"], "reduced": reduced,
                 "window_s": window["window_s"],
                 # no peaks (a rehearsal): no share of a roofline or a peak
                 "least_job_s": peaks and (
                     lambda roofline: least_seconds(roofline, config, peaks))}
        values = {}
        for m in wanted:
            spec = load_json("metrics", m["name"])
            got = load_module("readers", spec["reader"]).read(
                spec.get("args", {}), facts)
            if got is not None:     # nothing to read: the metric is left out
                values[m["name"]] = got
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    else:
        wanted = metrics_of(manifest, cell["name"], "end_to_end")
        values = dict(window["end_to_end"], setup_s=setup_s)
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in wanted if m["name"] in values}
    result["device"] = device
    result["notes"] = {**window.get("notes", {}), "memory": {
        k: mem_stats.get(k) for k in ("peak_bytes_in_use",
                                      "peak_bytes_reserved", "bytes_limit")}}
    result["checks"] = [{"name": c["name"], "value": c["value"],
                         "limit": c["limit"]} for c in checks]
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    line = json.dumps(result)
    print(("REHEARSAL " if args.rehearsal else "") + line, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU debugging mode; its output is no result")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (store, trace)")
    args = ap.parse_args(argv)
    if not (ROOT / "predictionio_tpu" / "workflow" / "core_workflow.py"
            ).is_file():
        print(f"benchmark/run.py: {ROOT} holds no predictionio_tpu package: "
              "the benchmark measures the repo it is part of",
              file=sys.stderr)
        return 2
    try:
        return run(args)
    except Refused as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
