#!/usr/bin/env python3
"""The control and the planted faults of a cell's comparison, at the cell's
own size: the readings the limits in the configuration's file were set from.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed the data is made as a run makes it, and the comparison that
decides `correct` is given, in the program's place: the reference itself
(must read ~0), the control (the reference one precision below the one the
configuration states), and each fault a train cell can have (half of the
events left out; one answer altered where it is produced).  What those are
for an engine is its reference module's to say: `readings(config, data,
seed, half)` of `reference/<module>.py`, where `half(data)` is the data with
the second half of every block left out.  One JSON line a seed.  A benchmark
run never calls this; PERF.md holds what it printed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as harness   # noqa: E402


def _half(data: dict) -> dict:
    """The data with the second half of every block's events left out."""
    blocks = [{k: (v[: len(v) // 2] if isinstance(v, np.ndarray) else v)
               for k, v in b.items()} for b in data["blocks"]]
    return {**data, "blocks": blocks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    cell, config, _ = harness.find_cell(harness.load_manifest(), args.workload)
    if args.rehearsal:
        config = harness.merged(config, {
            k: v for k, v in config.get("rehearsal", {}).items() if k != "env"})
    gen = harness.load_module("data", config["data"]["generator"])
    reference = harness.load_module("reference", config["reference"]["module"])
    for seed in (int(s) for s in args.seeds.split(",")):
        data = gen.generate(config["data"]["params"], seed)
        got = reference.readings(config, data, seed, _half)
        print(json.dumps({"workload": cell["name"], "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
