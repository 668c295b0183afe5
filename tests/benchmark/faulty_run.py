#!/usr/bin/env python3
"""A run of the benchmark's command with the timed path broken underneath:

    python3 tests/benchmark/faulty_run.py <fault> --workload ... --rehearsal

The harness's look for a chip is the rehearsal's; everything after it is a
run's own code.  The fault is planted in the program AFTER the warm-up job,
so the window drives the broken path and `correct` has to come out false:

  unchanged   a step that returns its state unchanged: run_train persists
              nothing and hands back the instance it already had
  half        half of the batch left out: the data source reads every
              second event only
  altered     an answer altered where it is produced: one cell of the model
              changed as it is persisted
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as harness   # noqa: E402


def plant(fault: str, session) -> None:
    import numpy as np

    from predictionio_tpu.workflow import core_workflow, persistence

    if fault == "unchanged":
        warm = session.storage.engine_instances.get(session.warm_instance)
        core_workflow.run_train = lambda *a, **k: warm
    elif fault == "half":
        ds_cls = type(session.engine.make_components(session.params)[0])
        read = ds_cls.read_training

        def half(self):
            td = read(self)
            if hasattr(td, "interactions"):          # UR: per-type COO
                td.interactions = {
                    n: (u[::2], i[::2], d, t[::2])
                    for n, (u, i, d, t) in td.interactions.items()}
                return td
            import dataclasses                       # ALS: an EventBatch

            keep = np.arange(0, len(td.entity_ids), 2)
            return dataclasses.replace(td, **{
                f.name: getattr(td, f.name)[keep]
                for f in dataclasses.fields(td)
                if isinstance(getattr(td, f.name), np.ndarray)
                and len(getattr(td, f.name)) == len(td.entity_ids)})

        ds_cls.read_training = half
    elif fault == "altered":
        save = persistence.save_models

        def altered(storage, instance_id, models):
            m = models[0]
            if hasattr(m, "indicator_idx"):
                idx = m.indicator_idx[m.primary_event].copy()
                row = int(np.flatnonzero((idx >= 0).sum(1) >= 1)[0])
                idx[row, 0] = (idx[row, 0] + 1) % len(m.item_dict)
                m.indicator_idx[m.primary_event] = idx
            else:
                m.item_factors = np.array(m.item_factors)
                m.item_factors[0] = 0.0
            return save(storage, instance_id, models)

        persistence.save_models = altered
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    driver = harness.load_module
    real = {}

    def load_module(kind, name):
        mod = driver(kind, name)
        if kind == "drivers" and name not in real:
            real[name] = mod.Session.window

            def window(self):
                plant(fault, self)
                return real[name](self)

            mod.Session.window = window
        return mod

    harness.load_module = load_module
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
