#!/usr/bin/env python3
"""A run of the benchmark's command with the timed path broken underneath:

    python3 tests/benchmark/faulty_run.py <fault> --workload ... --rehearsal

The harness's look for a chip is the rehearsal's; everything after it is a
run's own code.  The fault is planted in the program AFTER the warm-up job,
so the window drives the broken path and `correct` has to come out false:

  unchanged   a step that returns its state unchanged: run_train persists
              nothing and hands back the instance it already had
  half        half of the batch left out: the store's columnar reads
              (`PEventStore.batch`, `PEventStore.native_batch`), beneath the
              DataSource of every template that trains from events of a
              user on an item, give every second event only
  altered     an answer altered where it is produced: `alter(model, seed)`
              of the configuration's reference module changes the model
              as it is persisted

This file names no field of a model or of an engine's training data: what a
model holds is its reference module's to know.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as harness   # noqa: E402


def plant(fault: str, session) -> None:
    from predictionio_tpu.store.event_store import PEventStore
    from predictionio_tpu.workflow import core_workflow, persistence

    if fault == "unchanged":
        warm = session.storage.engine_instances.get(session.warm_instance)
        core_workflow.run_train = lambda *a, **k: warm
    elif fault == "half":
        import numpy as np

        def halved(read):
            def every_second(*a, **k):
                batch = read(*a, **k)
                if batch is None:          # no columnar read: passes through
                    return None
                return batch.subset(np.arange(len(batch)) % 2 == 0)
            return staticmethod(every_second)

        for name in ("batch", "native_batch"):
            setattr(PEventStore, name, halved(getattr(PEventStore, name)))
    elif fault == "altered":
        reference = session.ctx["load_module"](
            "reference", session.config["reference"]["module"])
        save = persistence.save_models

        def altered(storage, instance_id, models):
            reference.alter(models[0], session.ctx["seed"])
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
