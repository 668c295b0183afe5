"""One chip's share of a Universal Recommender train job whose users are
sharded over `meshDp` chips: `cco_train.work(config)` (what the work is:
the count matrices, the densified slabs and the count tiles of the whole
job) divided by the `meshDp` the configuration's engine states.

`train_mfu_pct` and `run.least_seconds` hold this against ONE chip's peaks,
so the share they print is of what all `meshDp` chips could do: with
`cco_train` itself a four-chip cell would read four times too high.  What
crosses the interconnect is not counted: `peaks.json` has no interconnect
peak to hold it against (PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path


def _cco_train():
    spec = importlib.util.spec_from_file_location(
        "benchmark_roofline_cco_train", Path(__file__).with_name("cco_train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work(config: dict) -> dict:
    dp = int(config["engine"]["algorithms"][0]["params"]["meshDp"])
    if dp < 1:
        raise ValueError(f"meshDp must be stated and positive, got {dp}")
    whole = _cco_train().work(config)
    return {"flops": whole["flops"] / dp, "bytes": whole["bytes"] / dp,
            "calls": whole["calls"], "chips": dp}
