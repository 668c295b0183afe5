#!/usr/bin/env python3
"""The control and the planted faults of a cell's comparison, at the cell's
own size: the readings the limits in the configuration's file were set from.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed the data is made as a run makes it, and the comparison that
decides `correct` is given, in the program's place: the reference itself
(must read ~0), the control (the reference one precision below the one the
configuration states), and each fault a train cell can have (half of the
events left out; one answer altered where it is produced).  One JSON line a
seed.  A benchmark run never calls this; PERF.md holds what it printed.
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
    blocks = [{k: (v[: len(v) // 2] if isinstance(v, np.ndarray) else v)
               for k, v in b.items()} for b in data["blocks"]]
    return {**data, "blocks": blocks}


def ur_readings(config: dict, data: dict, seed: int) -> dict:
    import ml_dtypes

    cco = harness.load_module("reference", "cco")
    algo = config["engine"]["algorithms"][0]["params"]
    k, thr = int(algo["maxCorrelatorsPerItem"]), float(algo.get("minLlr", 0))
    primary = config["engine"]["datasource"]["params"]["eventNames"][0]

    def held(tables, rows, cols):
        return cco.compare(tables, rows, cols, data, k, thr, primary)

    out = {"reference": held(*cco.control_tables(
        data, k, thr, primary, np.float64))}
    out["control_bfloat16"] = held(*cco.control_tables(
        data, k, thr, primary, ml_dtypes.bfloat16))
    out["fault_half_left_out"] = held(*cco.control_tables(
        _half(data), k, thr, primary, np.float64))
    tables, rows, cols = cco.control_tables(data, k, thr, primary, np.float64)
    idx, llr = tables[primary]
    row = int(np.flatnonzero((idx >= 0).sum(1) >= 2)[seed % 97])
    idx = idx.copy()
    idx[row, 0] = (idx[row, 0] + 1 + row) % data["n_items"]   # one cell moved
    tables[primary] = (idx, llr)
    out["fault_answer_altered"] = held(tables, rows, cols)
    return out


def als_readings(config: dict, data: dict, seed: int) -> dict:
    import ml_dtypes

    als = harness.load_module("reference", "als")
    a = config["engine"]["algorithms"][0]["params"]
    rank, reg, sweeps = int(a["rank"]), float(a["lambda"]), int(
        a["numIterations"])
    start = seed % (2 ** 31 - 1)
    nu, ni = data["n_users"], data["n_items"]
    ids = (np.arange(nu), np.arange(ni))
    b = data["blocks"][0]
    y0 = als.start(start, ni, rank)

    def held(x, y):
        got = als.compare(x, y, *ids, data, rank, reg, sweeps, start)
        return {k: got[k] for k in ("pred_gap_rms", "rmse_gap")}

    x, y = als.factorize(b["users"], b["items"], b["ratings"], nu, ni, y0,
                         reg, sweeps)
    out = {"reference": held(x, y)}
    out["control_bfloat16"] = held(*als.factorize(
        b["users"], b["items"], b["ratings"], nu, ni, y0, reg, sweeps,
        ml_dtypes.bfloat16))
    h = _half(data)["blocks"][0]
    out["fault_half_left_out"] = held(*als.factorize(
        h["users"], h["items"], h["ratings"], nu, ni, y0, reg, sweeps))
    y_bad = y.copy()
    y_bad[int(np.argmax(np.bincount(b["items"])))] = 0.0    # one answer altered
    out["fault_answer_altered"] = held(x, y_bad)
    return out


READINGS = {"cco": ur_readings, "als": als_readings}


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
    for seed in (int(s) for s in args.seeds.split(",")):
        data = gen.generate(config["data"]["params"], seed)
        got = READINGS[config["reference"]["module"]](config, data, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
