"""Traffic driver `train_jobs`: training jobs back to back on a populated
event store, for the length of the window.

A job is `core_workflow.run_train` from the engine variant of the
configuration's file: store scan, staging, the device programs, top-k and
the persisted model: what `pio train` runs, less the start of a process.
Set-up populates the store from the seed through the ingest fast path
(`insert_json_batch`, what `pio import` and the event server's batch route
call) and runs one job, which compiles or loads every program the window
uses.  The window ends when the last job that started inside it has
persisted its model; what is compared is the model that job persisted,
read back with `load_latest_models`.

Traffic parameters (`benchmark/traffic/<mix>.json`): `driver`, `env`, and
`insert_chunk`, the events to one `insert_json_batch` call.
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

ANNOTATION = "bench:train_job"
EVENT_TIME = "2026-01-01T00:00:00+00:00"


def _fill(obj, values: dict):
    """The engine variant with `$app` and `$seed` filled in."""
    if isinstance(obj, dict):
        return {k: _fill(v, values) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_fill(v, values) for v in obj]
    return values.get(obj, obj) if isinstance(obj, str) else obj


def wire_events(block: dict):
    """One generator block as the wire dicts the event server takes.

    A block may hold `times` (int64 microseconds after `EVENT_TIME`, one an
    event); without the key every event carries `EVENT_TIME`."""
    name = block["event"]
    ratings = block.get("ratings")
    users, items = block["users"].tolist(), block["items"].tolist()
    times = block.get("times")
    if times is not None:
        at = np.datetime64(EVENT_TIME[:19], "us") + np.asarray(
            times, np.int64).astype("timedelta64[us]")
        times = [t + EVENT_TIME[19:]
                 for t in np.datetime_as_string(at, unit="us").tolist()]
    for k in range(len(users)):
        d = {"event": name, "entityType": "user", "entityId": f"u{users[k]}",
             "targetEntityType": "item", "targetEntityId": f"i{items[k]}",
             "eventTime": EVENT_TIME if times is None else times[k]}
        if ratings is not None:
            d["properties"] = {"rating": float(ratings[k])}
        yield d


class Session:
    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.say = ctx["say"]
        self.config = ctx["config"]
        self.app = "bench"
        self.jobs = []            # one dict per timed job
        self.failed = 0

    # -- set-up ---------------------------------------------------------------

    def set_up(self) -> None:
        from predictionio_tpu.storage import App
        from predictionio_tpu.storage.locator import (
            Storage, StorageConfig, set_storage)
        from predictionio_tpu.utils import device as device_
        from predictionio_tpu.workflow import create_workflow

        t = time.perf_counter()
        gen = self.ctx["load_module"]("data", self.config["data"]["generator"])
        self.data = gen.generate(self.config["data"]["params"],
                                 self.ctx["seed"])
        self.n_events = sum(len(b["users"]) for b in self.data["blocks"])
        self.say(f"generated {self.n_events} events in "
                 f"{time.perf_counter() - t:.1f}s")

        t = time.perf_counter()
        self.storage = Storage(StorageConfig(
            sources={"FS": {"type": "localfs",
                            "path": str(self.ctx["work"] / "store")}},
            repositories={r: "FS" for r in
                          ("METADATA", "EVENTDATA", "MODELDATA")}))
        set_storage(self.storage)
        app_id = self.storage.apps.insert(App(0, self.app))
        chunk = int(self.ctx["traffic"].get("insert_chunk", 50_000))
        for block in self.data["blocks"]:
            batch = []
            for d in wire_events(block):
                batch.append(d)
                if len(batch) >= chunk:
                    self._insert(batch, app_id)
                    batch = []
            if batch:
                self._insert(batch, app_id)
        self.say(f"store populated in {time.perf_counter() - t:.1f}s")

        variant = _fill(self.config["engine"],
                        {"$app": self.app,
                         "$seed": self.ctx["seed"] % (2 ** 31 - 1)})
        _, self.engine, self.params = create_workflow.engine_from_variant(
            variant)
        self.variant = variant
        device_.watch_compiles()
        t = time.perf_counter()
        self.warm_instance = self._job().id   # every program the window uses
        self.say(f"warm-up job {time.perf_counter() - t:.1f}s, compile "
                 f"{device_.compile_stats()}")

    def _insert(self, batch: list, app_id: int) -> None:
        for r in self.storage.l_events.insert_json_batch(batch, app_id):
            if r.get("status") != 201:
                raise RuntimeError(f"event refused at ingest: {r}")

    def _job(self):
        from predictionio_tpu.workflow import core_workflow

        return core_workflow.run_train(
            self.engine, self.params, engine_id=self.variant["id"],
            engine_factory=self.variant["engineFactory"],
            storage=self.storage)

    # -- the window -----------------------------------------------------------

    def window(self) -> dict:
        import jax

        from predictionio_tpu.utils import device as device_

        seconds, trace = self.ctx["seconds"], self.ctx["trace"]
        trace_dir = self.ctx["work"] / "trace"
        compiles_before = device_.compile_stats()["programs"]
        if trace:
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 2
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        t_start = time.perf_counter()
        try:
            while time.perf_counter() - t_start < seconds:
                t = time.perf_counter()
                job = {"start_s": t - t_start}
                try:
                    with jax.profiler.TraceAnnotation(ANNOTATION):
                        job["instance"] = self._job().id
                except Exception as e:       # counted, and the run goes on
                    self.failed += 1
                    job["error"] = repr(e)
                    self.say(f"job failed: {e!r}")
                job["end_s"] = time.perf_counter() - t_start
                self.jobs.append(job)
        finally:
            t_end = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
        done = [j for j in self.jobs if "instance" in j]
        self.compiles_in_window = (
            device_.compile_stats()["programs"] - compiles_before)
        for k, j in enumerate(done):
            j["spans"] = self._journal(j["instance"])
            if k < 8:
                self.say(f"job {j['start_s']:.2f}..{j['end_s']:.2f}s "
                         f"journal spans {j['spans']}")
        mean_spans = {n: sum(j["spans"].get(n, 0.0) for j in done) / len(done)
                      for n in (done[0]["spans"] if done else {})}
        window_s = t_end - t_start
        name = self.ctx["traffic"]["end_to_end"]["rate"]
        return {
            "attempted": len(self.jobs), "failed": self.failed,
            "window_s": window_s,
            "end_to_end": {name: self.n_events * len(done) / window_s},
            "facts": {"jobs": len(done), "roofline": self.config["roofline"]},
            "notes": {"jobs": len(done), "window_s": window_s,
                      "events_per_job": self.n_events,
                      "compiles_in_window": self.compiles_in_window,
                      "journal_spans_mean_s": mean_spans},
            "trace_dir": str(trace_dir), "annotation": ANNOTATION,
        }

    def _journal(self, instance_id: str) -> dict:
        """The program's own span journal of one job (host clock)."""
        from predictionio_tpu.obs import spans

        out = {}
        try:
            path = spans.journal_path(self.storage, instance_id)
            for line in path.read_text().splitlines():
                s = json.loads(line)
                if s.get("name") in ("train", "engine_train", "save_models"):
                    out[s["name"]] = round(s["duration_s"], 4)
        except (OSError, ValueError):
            pass
        return out

    # -- what the window produced ---------------------------------------------

    def check(self) -> list:
        from predictionio_tpu.workflow import core_workflow

        checks = [{"name": "compiles_in_window",
                   "value": self.compiles_in_window, "limit": 0,
                   "ok": self.compiles_in_window == 0},
                  {"name": "jobs_failed", "value": self.failed, "limit": 0,
                   "ok": self.failed == 0 and bool(self.jobs)}]
        # the model compared is the one the LAST timed job persisted: an
        # instance of its own, newer than the warm-up's and every other job's
        ids = [self.warm_instance] + [j.get("instance") for j in self.jobs]
        last = ids[-1]
        try:
            instance, models = core_workflow.load_latest_models(
                self.variant["id"], storage=self.storage)
            stale = int(last is None or instance.id != last
                        or last in ids[:-1])
        except LookupError:
            models, stale = None, 1
        checks.append({"name": "model_not_the_last_jobs", "value": stale,
                       "limit": 0, "ok": stale == 0})
        # the program's state goes before the reference runs
        self.engine = self.params = None
        gc.collect()
        if models is not None:
            ref = self.config["reference"]
            module = self.ctx["load_module"]("reference", ref["module"])
            t = time.perf_counter()
            checks += module.check(models[0], self.data, self.variant,
                                   ref["limits"], self.ctx["seed"])
            self.say(f"reference and comparison "
                     f"{time.perf_counter() - t:.1f}s")
        return checks
