"""Query server — `pio deploy`.

Reference: core/.../workflow/CreateServer.scala — ``MasterActor`` resolves the
latest COMPLETED EngineInstance, loads models, and spawns the spray
``ServerActor`` serving:

  POST /queries.json   query → predict → serve → JSON prediction
  GET  /               engine-instance info
  GET  /reload         hot-swap to the newest COMPLETED instance
  GET  /stop           shut down (reference web UI's stop)
  GET  /metrics        Prometheus text (cross-worker aggregate)
  GET  /stats.json     per-(route, status) request windows

The feedback loop (reference: ServerActor writing prediction events back to
the event store with ``prId`` when feedback is enabled) is implemented via
``--feedback``: every answered query logs a ``predict`` event.
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import os
import sys
import threading
import time
import uuid
from typing import Any, Callable, Dict, Optional

from predictionio_tpu.api import prefork
from predictionio_tpu.api.http_util import JsonHandler, start_server
from predictionio_tpu.obs import cluster as obs_cluster
from predictionio_tpu.obs import lineage as obs_lineage
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import slo as obs_slo
from predictionio_tpu.obs import tracing as obs_tracing
from predictionio_tpu.obs import tsdb as obs_tsdb
from predictionio_tpu.obs.exposition import StatsCollector, metrics_payload
from predictionio_tpu.obs.metrics import SIZE_BUCKETS
from predictionio_tpu.serve import response_cache as _response_cache
from predictionio_tpu.storage.locator import Storage, get_storage
from predictionio_tpu.utils import device as _device
from predictionio_tpu.workflow import core_workflow
from predictionio_tpu.workflow.create_workflow import (
    engine_from_variant,
    load_engine_variant,
    resolve_engine_id,
)

log = logging.getLogger("pio.queryserver")

_M_SERVE_BATCH = obs_metrics.get_registry().histogram(
    "pio_serve_batch_size",
    "Queries coalesced per micro-batch device dispatch",
    buckets=SIZE_BUCKETS)
_M_GENERATION = obs_metrics.get_registry().gauge(
    "pio_model_generation",
    "Monotonic generation counter of the live model: bumped by every "
    "hot-swap (follow fold, auto-reload, manual /reload) — serving "
    "caches key on the model object this counts")


def _to_jsonable(obj: Any) -> Any:
    if hasattr(obj, "to_json"):
        return obj.to_json()
    if isinstance(obj, (dict, list, str, int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "__dict__"):
        return obj.__dict__
    return str(obj)


# How long a queued query waits for its result before giving up.  A fresh
# shape bucket on TPU can compile for minutes, so this is generous; only a
# genuinely dead leader should trip it.  Module-level so tests can shrink
# it to exercise the timeout/handoff races directly.
_WAIT_TIMEOUT_S = 600.0


class _MicroBatcher:
    """Group-commit micro-batching for concurrent queries — across
    requests, threads, and (since the event-loop front end) connections.

    The first thread into an idle batcher becomes the leader and
    immediately executes whatever is queued (usually just itself);
    queries arriving WHILE a batch executes coalesce into the next batch,
    which the same leader drains before releasing leadership.  No timer,
    no added latency for a lone query — batch size adapts to load, like
    a storage group commit.

    Why: each predict is one device dispatch + one readback.  Scoring B
    queued queries as one [B, …] program amortizes both across the batch
    — the single-chip answer to concurrent serving load, where the
    reference scaled by adding spray nodes.  The http_util event
    loop executes handlers on a small pool, so queries that are
    concurrently in flight across DIFFERENT client connections (and
    different pipelined requests on one connection) meet here and leave
    as one ``serve_batch_predict`` pass — the host numpy tail is
    amortized over the whole in-flight set the same way the device
    dispatch is.

    ``PIO_SERVE_BATCH_WINDOW_MS`` (default 0) optionally makes the
    leader dwell that long before executing its first batch, trading a
    bounded p50 hit for bigger batches when callers prefer throughput;
    0 keeps the pure group-commit behavior (nothing waits on a timer).
    """

    def __init__(self, run_batch: Callable, run_one: Callable,
                 max_batch: Optional[int] = None,
                 window_s: Optional[float] = None):
        from predictionio_tpu.controller.engine import DEFAULT_SERVE_BATCH

        if max_batch is None:
            max_batch = DEFAULT_SERVE_BATCH
        if window_s is None:
            try:
                window_s = float(
                    os.environ.get("PIO_SERVE_BATCH_WINDOW_MS", "0")) / 1e3
            except ValueError:
                window_s = 0.0
        self._run = run_batch
        self._run_one = run_one
        self._max = max_batch
        self._window = max(0.0, window_s)
        self._lock = threading.Lock()
        self._queue: list = []
        self._leader_active = False

    def predict(self, query: Any) -> Any:
        item = {"q": query, "ev": threading.Event()}
        with self._lock:
            self._queue.append(item)
            lead = not self._leader_active
            if lead:
                self._leader_active = True
        while True:
            if lead:
                self._lead_until_served(item)
                lead = False  # leading guarantees our item was served
            if "r" in item or "e" in item:
                break
            # re-arm, then re-check BOTH wake sources under ONE lock hold.
            # Result writers assign r/e before set(), so a set() racing
            # our clear() is caught by the r/e re-check.  Leadership
            # nudges set() WITHOUT writing a result — a clear() could
            # swallow one — so we also probe the vacancy itself: if no
            # leader is active we claim the lead, making a swallowed
            # nudge harmless.  The r/e check MUST share the claim's lock
            # hold: results are written before leadership is released
            # (itself under the lock), so either we see our result here,
            # or the leader hasn't released yet and we won't win the
            # vacancy — never both, so a served waiter can't become a
            # leader that withholds its own finished result.
            item["ev"].clear()
            with self._lock:
                if "r" in item or "e" in item:
                    break
                lead = not self._leader_active
                if lead:
                    self._leader_active = True
            if lead:
                continue
            if not item["ev"].wait(timeout=_WAIT_TIMEOUT_S):
                with self._lock:
                    if item in self._queue:
                        self._queue.remove(item)
                    served = "r" in item or "e" in item
                    # if we were about to inherit leadership, pass the
                    # wake on so the remaining waiters aren't stranded
                    nxt = (self._queue[0]
                           if not served and not self._leader_active
                           and self._queue else None)
                if nxt is not None:
                    nxt["ev"].set()
                if not served:
                    raise TimeoutError(
                        "micro-batch not served within %.0f s (leader died?)"
                        % _WAIT_TIMEOUT_S)
                continue
            # woken: loop re-checks the result and the leadership vacancy
        if "e" in item:
            raise item["e"]
        return item["r"]

    def _lead_until_served(self, own: dict) -> None:
        """Run batches until ``own`` is served, then RELEASE leadership and
        nudge the head waiter to re-claim it under the lock.  Draining
        until the queue empties would starve the leader's own client under
        sustained load — leadership rotates instead, so every request is
        served after at most a few batches.  Leadership is never
        *transferred* to a specific thread: the nudged waiter may already
        have timed out and departed, and a transfer would then leave
        ``_leader_active`` stuck True forever (every later query waits
        600 s and fails).  Releasing means any thread — the nudged waiter
        or a fresh arrival — can claim the vacancy."""
        if self._window:
            # opt-in dwell: let concurrently-arriving queries (other
            # connections' handler threads) join this leader's first batch
            time.sleep(self._window)
        while True:
            with self._lock:
                batch = self._queue[: self._max]
                del self._queue[: self._max]
                if not batch:
                    self._leader_active = False
                    return
            _M_SERVE_BATCH.observe(len(batch))
            try:
                try:
                    results = self._run([i["q"] for i in batch])
                    # strict: a predictor returning the wrong count must
                    # fall into the serial fallback, not leave an unserved
                    # item (whose thread would spin claiming/releasing
                    # leadership)
                    for i, r in zip(batch, results, strict=True):
                        i["r"] = r
                except Exception:
                    # one poisoned query must not 500 its batchmates:
                    # re-run the batch serially so only the offender errors
                    for i in batch:
                        try:
                            i["r"] = self._run_one(i["q"])
                        except Exception as e:
                            i["e"] = e
            except BaseException as exc:
                # SystemExit/KeyboardInterrupt escape the Exception
                # clauses above; leadership and the batch's waiters must
                # not leak with them (a stuck _leader_active wedges every
                # future query)
                err = RuntimeError(f"batch leader aborted: {exc!r}")
                for i in batch:
                    if "r" not in i and "e" not in i:
                        i["e"] = err
                with self._lock:
                    self._leader_active = False
                    nxt = self._queue[0] if self._queue else None
                if nxt is not None:
                    nxt["ev"].set()
                for i in batch:
                    i["ev"].set()
                raise
            served_self = own in batch
            if served_self:
                with self._lock:
                    self._leader_active = False
                    nxt = self._queue[0] if self._queue else None
                if nxt is not None:
                    nxt["ev"].set()  # wake to re-claim the released lead
            for i in batch:
                i["ev"].set()
            if served_self:
                return


class QueryServerState:
    """Holds the deployed engine + models; supports hot reload
    (reference: MasterActor hot-swapping engine instances)."""

    def __init__(
        self,
        engine,
        engine_params,
        query_class,
        engine_id: str,
        engine_version: str,
        engine_variant: str,
        storage: Optional[Storage] = None,
        feedback: bool = False,
        feedback_app_name: str = "",
        plugins=None,
        auto_reload: float = 0.0,
        plane_dir: Optional[str] = None,
    ):
        from predictionio_tpu.api.plugins import PluginRegistry

        self.plugins = PluginRegistry()
        self.engine = engine
        self.engine_params = engine_params
        self.query_class = query_class
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant
        self.storage = storage or get_storage()
        self.feedback = feedback
        self.feedback_app_name = feedback_app_name
        self._lock = threading.Lock()
        self.instance = None
        self.predictor: Optional[Callable] = None
        self.batcher = None
        self.query_count = 0
        self.started = _dt.datetime.now(_dt.timezone.utc)
        # model-generation bookkeeping: every hot-swap (reload, auto-
        # reload, embedded follower) installs a NEW model object and
        # bumps this counter — the serving caches (rule masks, inverted
        # CSR, pop order, value masks) all live on the model object, so
        # the swap IS their invalidation
        self.generation = 0
        self.swapped_at: Optional[_dt.datetime] = None
        self.follower = None          # embedded FollowTrainer, if any
        self.follow_info: Optional[Dict] = None
        self._build_seq = 0           # install-order tickets (see _install)
        self._installed_seq = 0
        # (lineage id, generation) of the newest install whose
        # first_serve stage this worker still owes — grabbed by the
        # first predict() that runs on the new generation
        self._lineage_pending: Optional[tuple] = None
        # shared-memory model plane (streaming.plane): when a plane dir
        # is wired, this worker WATCHES the plane manifest and installs
        # each published generation as read-only mmap views — model
        # emit/fold/warm CPU and N× resident copies leave the serving
        # workers; publishing happens in the dedicated publisher process
        # (or through plane_reload / the embedded follower's
        # plane_publish in single-worker topologies)
        self.plane = None
        self.plane_watcher = None
        self.plane_generation = 0
        # plane replication endpoint hosted by THIS process (a
        # PlaneReplicator when deploy --plane-publish, a PlaneSubscriber
        # when --plane-from); freshness() surfaces its role + lag
        self.replication = None
        # what this server runs on, as JAX reports it (GET / shows it).
        # A backend that fails to initialise fails the deploy here: a
        # server quietly serving from another backend than the one it was
        # deployed for is worse than no server.
        self.device = _device.device_info()
        _device.watch_compiles()
        self.placement: Dict[str, str] = {}
        self._tune_gil_switch()
        self.reload()
        if plane_dir:
            from predictionio_tpu.streaming.plane import (
                ModelPlane, PlaneWatcher,
            )

            self.plane = ModelPlane(plane_dir)
            self.plane_watcher = PlaneWatcher(self.plane,
                                              self._install_plane)
            self.plane_watcher.start()
        # serving reads user history from the live store per query; the
        # per-entity index otherwise builds on the FIRST query — at a
        # million-event log that is seconds of JSON parsing inline in a
        # query (and contending with a follow bootstrap).  Build it on a
        # background thread now instead.
        self._warm_entity_index_async()
        # plugins start only once the state is fully initialized (they get
        # a live QueryServerState with engine/storage/predictor populated)
        for p in plugins or []:
            self.plugins.register(p)
            p.start(self)
        # auto hot-swap (reference: MasterActor watching for retrained
        # instances): poll EngineInstances; when a newer COMPLETED
        # instance appears, reload without dropping the port.  Opt-in via
        # `pio deploy --auto-reload SECS`.
        self._auto_stop = threading.Event()
        if auto_reload > 0:
            t = threading.Thread(
                target=self._auto_reload_loop, args=(float(auto_reload),),
                daemon=True, name="pio-auto-reload")
            t.start()

    @staticmethod
    def _tune_gil_switch() -> None:
        """Shorten the interpreter's GIL switch interval (default 5 ms)
        inside query-server processes: a background fold/emit tick is
        Python-heavy at small shapes and can hold the GIL a full switch
        interval at a time, adding multi-ms stalls to colliding queries'
        p95.  1 ms caps that stall at ~1 ms per handoff for negligible
        switching overhead.  PIO_GIL_SWITCH_S overrides; <= 0 leaves the
        interpreter default."""
        import sys as _sys

        try:
            s = float(os.environ.get("PIO_GIL_SWITCH_S", "0.001"))
            if s > 0:
                _sys.setswitchinterval(s)
        except (ValueError, OSError):
            pass

    def _warm_entity_index_async(self) -> None:
        """Off-thread pre-build of the event store's per-entity serving
        index (localfs/sharded; other backends simply lack the hook).
        Failure is benign — the lazy build on first lookup remains."""
        app_name = getattr(
            getattr(self.engine_params, "data_source_params", None),
            "app_name", None)
        warm = getattr(self.storage.l_events, "warm_entity_index", None)
        if not app_name or warm is None:
            return

        def run() -> None:
            try:
                app = self.storage.apps.get_by_name(app_name)
                if app is not None:
                    warm(app.id)
            except Exception:
                log.exception("entity-index warm failed (the lazy build "
                              "on first query remains)")

        threading.Thread(target=run, daemon=True,
                         name="pio-entity-index-warm").start()

    # -- model-plane integration ---------------------------------------------

    def _install_plane(self, models, info: Optional[Dict] = None) -> bool:
        """PlaneWatcher install hook: the mapped generation goes through
        the ONE build-ticket install path like every other swap."""
        info = dict(info or {})
        gen = int(info.get("planeGeneration") or 0)
        installed = self._install(models, follow_info=info)
        if gen:
            self.plane_generation = gen
        return installed

    def plane_reload(self):
        """Plane-mode /reload: load the latest persisted instance ONCE,
        publish it as a new plane generation, and install it locally —
        every prefork sibling's watcher converges on the same generation
        within one poll interval, so a single /reload reaches the WHOLE
        group (the old behavior reached only the routed worker).
        Returns ``(plane_generation, instance_id)``."""
        instance, models = core_workflow.load_latest_models(
            self.engine_id, self.engine_version, self.engine_variant,
            self.storage)
        gen = self.plane.publish(models, {
            "mode": "reload", "engineInstanceId": instance.id})
        self.plane_watcher.check_now()
        # the mapped install carries no instance row; record it here so
        # freshness reports it and the auto-reload poller sees this
        # instance as live (it would otherwise republish every tick)
        self.instance = instance
        return gen, instance.id

    def plane_publish_initial(self) -> None:
        """Prefork parent, at deploy: seed the plane with the loaded
        instance so every worker converges onto ONE mapped copy from the
        start (each worker's private startup load is transient — it
        drops as soon as the mapped generation installs).  No-op when a
        generation already exists (restart onto a live plane)."""
        if self.plane is None or self.plane.current() is not None:
            return
        self.plane_reload()

    def plane_publish(self, models, info: Optional[Dict] = None) -> None:
        """Embedded-follower publish hook for single-worker plane
        topologies (``--workers 1`` with PIO_MODEL_PLANE=on, and the
        in-process parity/test servers): emit to the arena, then install
        the MAPPED generation locally — the process serves the same
        shared bytes a sibling would."""
        from predictionio_tpu.streaming.plane import PlaneUnsupported

        try:
            self.plane.publish(models, info)
        except PlaneUnsupported as e:
            log.warning("model plane cannot carry this bundle (%s); "
                        "installing in-process", e)
            self.swap_models(models, info)
            return
        self.plane_watcher.check_now()

    def disable_plane(self) -> None:
        """Degrade to the private-model path (non-UR bundle at deploy)."""
        if self.plane_watcher is not None:
            self.plane_watcher.stop()
        self.plane = None
        self.plane_watcher = None

    def _auto_reload_loop(self, interval: float) -> None:
        while not self._auto_stop.wait(interval):
            try:
                latest = self.storage.engine_instances.get_latest_completed(
                    self.engine_id, self.engine_version, self.engine_variant)
            except Exception:
                log.exception("auto-reload: instance lookup failed")
                continue
            current = self.instance
            if latest is not None and (
                    current is None or latest.id != current.id):
                if self.plane is not None:
                    # plane mode: ONE publish converges the whole group
                    # (children are spawned without --auto-reload)
                    try:
                        gen, iid = self.plane_reload()
                        log.info("auto-reload: published instance %s as "
                                 "plane generation %d", iid, gen)
                    except Exception:
                        log.exception("auto-reload: plane publish failed; "
                                      "keeping current generation")
                    continue
                try:
                    if self.reload() is not None:
                        log.info("auto-reload: hot-swapped to instance %s",
                                 latest.id)
                    else:
                        log.info("auto-reload: instance %s dropped as "
                                 "stale (a newer generation installed "
                                 "first)", latest.id)
                except Exception:
                    # the newer instance's models may still be mid-write;
                    # keep serving the current model and retry next tick
                    log.exception("auto-reload: reload failed; keeping "
                                  "current instance")

    def stop_auto_reload(self) -> None:
        """Stop every background updater (auto-reload poller + embedded
        follower) — wired into server shutdown."""
        self._auto_stop.set()
        if self.follower is not None:
            self.follower.stop(timeout=2.0)
        if self.replication is not None:
            try:
                self.replication.stop(timeout=1.0)
            except Exception:
                log.exception("plane replication stop failed")
            self.replication = None
            # publisher-side cluster observability dies with replication
            obs_lineage.set_cluster_provider(None)
            obs_cluster.set_federation(None)
        if self.plane_watcher is not None:
            self.plane_watcher.stop()

    def reload(self) -> Optional[str]:
        """Load + install the latest persisted instance.  Returns its id,
        or None when the bundle was dropped as stale (a build that
        started later — e.g. the embedded follower's — installed first;
        the server is serving that newer generation, not this one)."""
        instance, models = core_workflow.load_latest_models(
            self.engine_id, self.engine_version, self.engine_variant,
            self.storage)
        if self._install(models, instance=instance):
            return instance.id
        return None

    def swap_models(self, models, info: Optional[Dict] = None) -> None:
        """Embedded-follower hot-swap: install already-built models
        without a persistence round trip.  The swap is atomic under the
        serving lock; in-flight queries finish on the old generation."""
        self._install(models, follow_info=info)

    def _install(self, models, instance=None,
                 follow_info: Optional[Dict] = None) -> bool:
        """The ONE model-installation path (reload, auto-reload, follower
        swap): build + warm the serving bundle OUTSIDE the lock — a warm
        can stage tens of MB to device — then swap the predictor,
        batcher and generation in one lock hold.  Concurrent builders
        (auto-reload poller + embedded follower) are ordered by a build
        ticket taken at build START: a bundle whose build began before a
        later build already installed is dropped, so a slow stale build
        can never swap in over a newer generation.  Returns False when
        the bundle was dropped as stale, True when it went live."""
        w_inst, t_inst = time.time(), time.perf_counter()
        with self._lock:
            self._build_seq += 1
            ticket = self._build_seq

        # Micro-batch concurrent queries when every algorithm supports
        # serving-safe batch prediction.  PIO_SERVE_BATCH: on | off |
        # auto (default).  Auto engages only on an accelerator backend:
        # there a batch amortizes the per-dispatch/readback overhead of
        # concurrent serving, while on CPU the scoring math is so cheap
        # that the batcher's coordination measurably LOSES (2.4k → 0.4k
        # q/s at 32 clients, CPU sandbox).
        conf = os.environ.get("PIO_SERVE_BATCH", "auto").lower()
        enable = conf in ("1", "on", "true") or (
            conf == "auto" and self.device["platform"] != "cpu")
        predictor, bp = self.engine.serving_bundle(self.engine_params, models)
        batcher = (
            _MicroBatcher(bp, predictor,
                          max_batch=getattr(bp, "max_batch", None))
            if enable and bp is not None else None)
        with self._lock:
            if ticket <= self._installed_seq:
                return False   # a build that started later already installed
            self._installed_seq = ticket
            # response cache: re-arm on the new generation BEFORE the
            # predictor goes live, sweeping exactly the entries its swap
            # provenance cannot prove unchanged (serve.response_cache);
            # the cache must never be able to break an install
            w_cache, t_cache = time.time(), time.perf_counter()
            cache_attrs = None
            try:
                cache = _response_cache.get_cache()
                cache.on_swap(models)
                cache_attrs = {
                    "start": w_cache,
                    "duration_s": time.perf_counter() - t_cache,
                    # workers without provenance flush everything — that
                    # IS the interesting outcome on a lineage waterfall
                    "outcome": ("full_flush"
                                if cache.last_swap_reason == "no_provenance"
                                else cache.last_swap_reason or "noop"),
                    "dropped": int(cache.last_swap_invalidated),
                    "entries": len(cache),
                }
            except Exception:
                log.exception("response-cache swap sweep failed — "
                              "disarming the cache")
                try:
                    _response_cache.get_cache().disarm()
                except Exception:
                    pass
            self.predictor = predictor
            self.batcher = batcher
            self.placement = getattr(predictor, "placement", {})
            if instance is not None:
                self.instance = instance
            self.generation += 1
            self.swapped_at = _dt.datetime.now(_dt.timezone.utc)
            if follow_info is not None:
                self.follow_info = dict(follow_info)
            lid = (follow_info or {}).get("lineageId")
            gen = int((follow_info or {}).get("planeGeneration")
                      or self.generation)
            if lid:
                # first_serve is owed by whichever predict() runs next on
                # this generation; newer installs overwrite the debt (the
                # superseded generation never served from this worker)
                self._lineage_pending = (lid, gen)
        _M_GENERATION.set(self.generation)
        if lid:
            lin = obs_lineage.get_lineage()
            if lin.enabled:
                lin.note_generation(lid, gen)
                if cache_attrs is not None:
                    lin.stage(lid, "cache_invalidation",
                              parent="install", **cache_attrs)
                lin.stage(lid, "install", start=w_inst,
                          duration_s=time.perf_counter() - t_inst,
                          generation=gen, flush=True)
        return True

    def freshness(self) -> Dict:
        """The /stats.json ``freshness`` key: how current the live model
        is and who keeps it that way."""
        doc: Dict[str, Any] = {
            "generation": self.generation,
            "swappedAt": (self.swapped_at.isoformat()
                          if self.swapped_at else None),
            "engineInstanceId": self.instance.id if self.instance else None,
        }
        if self.plane is not None:
            # the generation every prefork sibling converges on — equal
            # across workers means the group serves ONE mapped model
            doc["planeGeneration"] = self.plane_generation
            if self.plane.last_publish_stats:
                # this process published: surface the delta-arena write
                # profile (logical model bytes vs bytes actually written
                # — the per-generation write amplification, also on the
                # dashboard as pio_model_plane_publish_bytes_total)
                doc["planePublish"] = dict(self.plane.last_publish_stats)
        if self.replication is not None:
            # multi-node topology: which side of the replication channel
            # this node is on, and how far behind it runs — the
            # cluster-convergence analogue of planeGeneration
            try:
                doc["replication"] = self.replication.status()
            except Exception:
                pass
        if self.follower is not None:
            doc["follower"] = self.follower.status()
        elif self.follow_info is not None:
            doc["follower"] = dict(self.follow_info)
        # top-level mirror of the fold-state footprint (also a gauge:
        # pio_follow_state_bytes) so dashboards and the freshness bench
        # read one stable key regardless of follower topology
        fr = doc.get("follower")
        if isinstance(fr, dict):
            doc["stateBytes"] = fr.get("stateBytes")
            doc["stateMode"] = fr.get("stateMode")
        return doc

    def parse_query(self, body: Dict) -> Any:
        if self.query_class is not None and hasattr(self.query_class, "from_json"):
            return self.query_class.from_json(body)
        return body

    def predict(self, body: Dict) -> Any:
        query = self.parse_query(body)
        w_q, t_q = time.time(), time.perf_counter()
        with self._lock:
            predictor = self.predictor
            batcher = self.batcher
            pending, self._lineage_pending = self._lineage_pending, None
        prediction = batcher.predict(query) if batcher else predictor(query)
        if pending is not None:
            # the freshness waterfall's last hop: this worker ANSWERED a
            # query from the new generation (not merely installed it)
            lin = obs_lineage.get_lineage()
            if lin.enabled:
                lin.stage(pending[0], "first_serve", start=w_q,
                          duration_s=time.perf_counter() - t_q,
                          generation=pending[1], flush=True)
        prediction = self.plugins.apply(query, prediction)
        self.query_count += 1
        if self.feedback and self.feedback_app_name:
            self._log_feedback(body, prediction)
        return prediction

    def _log_feedback(self, query_body: Dict, prediction: Any) -> None:
        """Write the served prediction back as a `predict` event (prId links
        follow-up reward events to this prediction, as in the reference)."""
        from predictionio_tpu.events.event import DataMap, Event

        app = self.storage.apps.get_by_name(self.feedback_app_name)
        if app is None:
            return
        self.storage.l_events.insert(
            Event(
                event="predict",
                entity_type="pio_pr",
                entity_id=uuid.uuid4().hex,
                properties=DataMap(
                    {"query": query_body, "prediction": _to_jsonable(prediction)}
                ),
                pr_id=uuid.uuid4().hex,
            ),
            app.id,
        )

    def info(self) -> Dict:
        return {
            "status": "alive",
            # pid identifies WHICH prefork worker answered — the readiness
            # probe for `deploy --workers N` (poll fresh connections until
            # N distinct pids have been seen), same contract as the event
            # server's GET /
            "pid": os.getpid(),
            "workerTag": obs_metrics.worker_tag(),
            "engineId": self.engine_id,
            "engineVersion": self.engine_version,
            "variant": self.engine_variant,
            "engineInstanceId": self.instance.id if self.instance else None,
            "trainedAt": self.instance.start_time.isoformat() if self.instance else None,
            "queryCount": self.query_count,
            "startedAt": self.started.isoformat(),
            "modelGeneration": self.generation,
            # None = plane off; else this worker's installed plane
            # generation (the readiness/convergence probe for the group)
            "planeGeneration": (self.plane_generation
                                if self.plane is not None else None),
            # what serves the queries: the device as JAX reports it, the
            # placements the engine resolved to (None: the engine has no
            # such choice), whether queries go through the micro-batcher,
            # and this process's XLA compilations so far
            "device": self.device,
            "scorer": self.placement.get("scorer"),
            "tail": self.placement.get("tail"),
            "batcher": self.batcher is not None,
            "compile": _device.compile_stats(),
            # freshness is STATE, not a metric: it must stay readable
            # under PIO_METRICS=off, where /stats.json answers 503
            "freshness": self.freshness(),
        }


def _render_info_html(state: QueryServerState) -> str:
    """Deploy web UI (reference: CreateServer's engine-instance info page)."""
    import html as _html

    info = state.info()
    rows = "".join(
        f"<tr><th>{_html.escape(str(k))}</th><td>{_html.escape(str(v))}</td></tr>"
        for k, v in info.items()
    )
    plugins = ", ".join(p.name for p in state.plugins.all()) or "(none)"
    return f"""<!DOCTYPE html>
<html><head><title>PredictionIO-TPU engine server</title>
<style>body{{font-family:sans-serif;margin:2em}}table{{border-collapse:collapse}}
th,td{{border:1px solid #ccc;padding:4px 10px;text-align:left}}</style></head>
<body><h1>Engine server: {_html.escape(state.engine_id)}</h1>
<table>{rows}</table>
<p>plugins: {_html.escape(plugins)}</p>
<p>POST /queries.json &middot; GET /reload &middot; GET /stop &middot;
GET /metrics &middot; GET /stats.json</p>
</body></html>"""


def make_handler(state: QueryServerState):
    class QueryHandler(JsonHandler):
        # per-(route, status) windows for /stats.json, fed by the
        # http_util middleware; None under PIO_METRICS=off (the
        # middleware skips recording and /stats.json answers 503)
        stats_collector = (StatsCollector()
                           if obs_metrics.get_registry().enabled else None)

        def do_GET(self):
            path, _query = self.route
            if path == "/":
                accept = self.headers.get("Accept", "")
                if "text/html" in accept:
                    self.send_html(_render_info_html(state))
                else:
                    self.send_json(state.info())
            elif path == "/metrics":
                self._send_raw(200, metrics_payload(),
                               ctype="text/plain; version=0.0.4; "
                                     "charset=utf-8")
            elif obs_tracing.handle_trace_request(self, path):
                pass   # /traces.json + /traces/{rid}.json (flight recorder)
            elif obs_lineage.handle_lineage_request(self, path):
                pass   # /lineage.json + /lineage/{gen|ln-id}.json
            elif obs_tsdb.handle_history_request(self, path):
                pass   # /metrics/history.json (local time-series ring)
            elif obs_cluster.handle_cluster_request(self, path):
                pass   # /cluster/{metrics,history}.json (publisher only)
            elif obs_slo.handle_healthz_request(self, path):
                pass   # /healthz (SLO burn-rate verdicts, always 200)
            elif path == "/stats.json":
                if self.stats_collector is None:
                    self.send_error_json(
                        503, "stats disabled (PIO_METRICS=off)")
                    return
                doc = self.stats_collector.to_json()
                doc["engineId"] = state.engine_id
                doc["queryCount"] = state.query_count
                doc["startedAt"] = state.started.isoformat()
                doc["freshness"] = state.freshness()
                self.send_json(doc)
            elif path == "/reload":
                from predictionio_tpu.streaming.plane import (
                    PlaneUnsupported,
                )

                try:
                    if state.plane is not None:
                        try:
                            # plane mode: ONE reload on ANY worker
                            # publishes a plane generation the whole
                            # prefork group converges on (watchers
                            # install within a poll interval)
                            gen, iid = state.plane_reload()
                            self.send_json({"reloaded": True,
                                            "generation": gen,
                                            "engineInstanceId": iid})
                            return
                        except PlaneUnsupported:
                            pass   # non-UR bundle: private reload below
                    iid = state.reload()
                    live = state.instance.id if state.instance else None
                    self.send_json({"reloaded": iid is not None,
                                    "engineInstanceId": iid or live})
                except Exception as e:
                    self.send_error_json(500, f"reload failed: {e}")
            elif path == "/stop":
                self.send_json({"stopping": True})

                def _stop(server):
                    state.stop_auto_reload()
                    server.shutdown()
                    # close the listening socket too: shutdown() alone
                    # keeps accepting connections that nothing serves
                    # (clients would hang instead of being refused)
                    server.server_close()

                threading.Thread(target=_stop, args=(self.server,),
                                 daemon=True).start()
            else:
                self.send_error_json(404, "not found")

        def do_POST(self):
            path, _query = self.route
            if path != "/queries.json":
                self.send_error_json(404, "not found")
                return
            try:
                body = self.read_json()
            except json.JSONDecodeError as e:
                self.send_error_json(400, f"invalid JSON: {e}")
                return
            if not isinstance(body, dict):
                self.send_error_json(400, "query must be a JSON object")
                return
            try:
                prediction = state.predict(body)
            except (KeyError, ValueError, TypeError) as e:
                self.send_error_json(400, f"bad query: {e}")
                return
            except Exception as e:  # engine failure
                log.exception("prediction failed")
                self.send_error_json(500, f"prediction failed: {e}")
                return
            self.send_json(_to_jsonable(prediction))

    return QueryHandler


def deploy(
    engine_json: str = "engine.json",
    variant: str = "default",
    engine_id: Optional[str] = None,
    engine_version: str = "1",
    host: str = "0.0.0.0",
    port: int = 8000,
    feedback: bool = False,
    storage: Optional[Storage] = None,
    background: bool = False,
    plugins=None,
    auto_reload: float = 0.0,
    workers: int = 1,
    reuse_port: bool = False,
    follow: float = 0.0,
    plane_publish: Optional[str] = None,
    plane_from: Optional[str] = None,
):
    """Programmatic deploy; returns the HTTPServer (background=True) or blocks.

    ``plane_publish=\"[HOST:]PORT\"`` additionally serves this node's
    model plane to replication subscribers; ``plane_from=\"HOST:PORT\"``
    makes this node a replication SUBSCRIBER: no local folding (it
    conflicts with ``follow``), the plane dir (node-local, via
    PIO_MODEL_PLANE_DIR) is fed by the remote publisher and the normal
    watcher/compose/install path serves it.  See docs/operations.md
    "Multi-node plane replication".

    ``workers > 1`` preforks N−1 extra OS processes all serving the SAME
    port via SO_REUSEPORT (the kernel load-balances accepts): CPython's
    GIL caps one process at roughly single-core query throughput, so
    CPU-backend deployments scale across cores this way — the analogue of
    the reference running several spray nodes behind a balancer.  Only
    meaningful on CPU backends: a TPU chip is single-process-exclusive,
    so workers>1 on a TPU backend raises.  Workers resolve storage from
    the PIO_STORAGE_* environment (a programmatic ``storage`` object
    cannot cross the process boundary).

    A manual GET /reload reaches only the ONE worker the kernel routes
    it to — pair --workers with --auto-reload so every worker converges
    on a retrained instance within the polling interval.  `pio undeploy`
    handles the multi-listener teardown (it stops until the port stops
    answering).
    """
    # cheap preconditions FIRST: raising after QueryServerState exists
    # would leak its auto-reload poller and started plugins
    if plane_from and follow > 0:
        raise ValueError(
            "deploy --plane-from replaces local folding with replicated "
            "generations; drop --follow (the publisher node folds)")
    if plane_from and plane_publish:
        raise ValueError(
            "deploy cannot be a replication subscriber and publisher at "
            "once (relaying is not supported)")
    if (plane_from or plane_publish) \
            and not os.environ.get("PIO_CLUSTER_NODE"):
        # multi-node deployment: every lineage stage this node records
        # is SOURCE-stamped with a node name (obs.lineage reads the env
        # lazily) so cross-node stitching attributes per-node lanes
        # without guessing; set BEFORE the serving state exists so the
        # install/first_serve stages carry it, and prefork children
        # inherit it via os.environ.  Operators/CI set it explicitly for
        # stable names across restarts.
        import socket as _socket

        role = "sub" if plane_from else "pub"
        os.environ["PIO_CLUSTER_NODE"] = \
            f"{_socket.gethostname()}-{role}-{os.getpid()}"
    if workers > 1:
        import jax

        if jax.default_backend() not in ("cpu",):
            raise ValueError(
                "deploy --workers requires a CPU backend: an accelerator "
                "chip is single-process-exclusive (scale TPU serving with "
                "micro-batching or more chips, not prefork workers)")
        if storage is not None:
            raise ValueError(
                "deploy --workers resolves storage from PIO_STORAGE_* env "
                "in each worker; a programmatic storage object cannot "
                "cross the process boundary")
    # Orphan-watch only in children WE spawned (marked via env by the
    # prefork spawn below) — a programmatic caller passing reuse_port=True
    # behind their own balancer must not get a server that self-terminates
    # when its launcher exits.
    if workers == 1:
        prefork.maybe_watch_parent(log)   # prefork child: die when orphaned
        # prefork child spawned with a PIO_METRICS_DIR/PIO_METRICS_TAG:
        # publish snapshots so any sibling's /metrics scrape sees us
        # (no-op — pure in-memory metrics — for a true single worker)
        obs_metrics.start_worker_flusher()
        obs_metrics.mark_worker_up()
    doc = load_engine_variant(engine_json, variant)
    factory, engine, engine_params = engine_from_variant(doc)
    eid = resolve_engine_id(engine_id, doc, factory)
    query_class = getattr(factory, "query_class", None)
    feedback_app = ""
    if feedback:
        ds_params = getattr(engine_params.data_source_params, "app_name", "")
        feedback_app = ds_params
    # shared-memory model plane: with a prefork group (or PIO_MODEL_PLANE
    # =on), each model generation is emitted ONCE into an mmap-able arena
    # and every worker maps it read-only — resident model bytes N× → ~1×,
    # one fold per delta, /reload converges the whole group
    from predictionio_tpu.streaming import plane as plane_mod

    metrics_dir: Optional[str] = None
    if workers > 1:
        # the group metrics dir + the parent's worker tag exist BEFORE
        # the serving state: the plane seeds its per-worker generation/
        # rss gauges during state construction, and a later tag change
        # would strand those series under a stale pid-based label
        import tempfile

        metrics_dir = tempfile.mkdtemp(prefix="pio-metrics-")
        obs_metrics.start_worker_flusher(metrics_dir, f"w0-{os.getpid()}")
    plane_dir: Optional[str] = None
    if plane_mod.plane_wanted(workers) or plane_from or plane_publish:
        # replication implies the plane: a subscriber node IS a plane
        # consumer, a publishing node must host the dir it serves
        plane_dir = plane_mod.resolve_plane_dir(
            storage or get_storage(), eid, variant)
        if plane_dir is None:
            if plane_from or plane_publish:
                raise ValueError(
                    "plane replication needs a model-plane directory: "
                    "set PIO_MODEL_PLANE_DIR to a node-LOCAL path (or "
                    "use a localfs METADATA store); see "
                    "docs/operations.md \"Multi-node plane replication\"")
            log.warning(
                "model plane requested but no plane dir is resolvable "
                "(set PIO_MODEL_PLANE_DIR or use a localfs METADATA "
                "store; for multi-node serving see docs/operations.md "
                "\"Multi-node plane replication\"); workers serve "
                "private model copies")
    state = QueryServerState(
        engine, engine_params, query_class, eid, engine_version, variant,
        storage=storage, feedback=feedback, feedback_app_name=feedback_app,
        plugins=plugins, auto_reload=auto_reload, plane_dir=plane_dir,
    )
    if state.plane is not None and plane_from is not None:
        # subscriber node: the plane dir belongs to the remote publisher
        # (via the subscriber daemon below) — seeding it locally would
        # be the exact split-brain the replication marker guards against.
        # Until the first replicated flip lands, workers serve the
        # privately loaded startup model.
        pass
    elif state.plane is not None and not prefork.is_prefork_child():
        # seed the plane with the loaded instance so the group converges
        # onto one mapped copy from the start; a bundle the plane cannot
        # carry (non-UR) degrades the WHOLE deploy to private models —
        # decided here, before workers/publisher are spawned
        try:
            state.plane_publish_initial()
        except plane_mod.PlaneUnsupported as e:
            log.warning("model plane disabled for this engine (%s); "
                        "workers serve private model copies", e)
            state.disable_plane()
            plane_dir = None
        except Exception:
            # e.g. a read-only shared store: a plane that cannot be
            # written is useless — degrade to private models (the
            # pre-plane behavior) instead of failing the deploy
            log.exception("model plane seed publish failed; disabling "
                          "the plane — workers serve private model "
                          "copies")
            state.disable_plane()
            plane_dir = None
    if follow > 0 and plane_dir is not None and workers > 1:
        # prefork plane group: NO worker folds — a dedicated publisher
        # process (spawned below, next to the workers) hosts the one
        # follower and emits each generation into the arena
        pass
    elif follow > 0:
        # embedded follow-trainer: tail the event store every SECS and
        # hot-swap the in-process model (no persistence round trip).
        # Reached only outside prefork plane groups: a lone worker (with
        # or without the plane) hosts the one follower itself; plane-off
        # prefork workers each host their own (the legacy N-fold path —
        # PIO_MODEL_PLANE=off is the parity oracle).
        from predictionio_tpu.streaming.fold import FoldUnsupported
        from predictionio_tpu.streaming.follow import FollowTrainer

        try:
            state.follower = FollowTrainer(
                engine, engine_params, eid, engine_version, variant,
                storage=state.storage, interval=follow,
                # single-worker plane topology: the embedded follower IS
                # the publisher — emit to the arena, serve the mapped copy
                on_publish=(state.plane_publish if state.plane is not None
                            else state.swap_models),
                persist=False)
        except FoldUnsupported as e:
            # e.g. a data source with no app_name: nothing to tail —
            # serve without a follower rather than raising here, which
            # would leak the already-started auto-reload poller/plugins
            log.warning("--follow unsupported for this engine (%s); "
                        "deploying without a follower", e)
        else:
            state.follower.start()
    if plane_publish is not None and state.plane is not None:
        # publisher side of multi-node replication: stream every new
        # generation file + manifest flip to connected subscribers.  The
        # dir watcher covers publishes from the dedicated publisher
        # child; an embedded follower also pokes it directly.
        from predictionio_tpu.streaming.replicate import PlaneReplicator

        repl = PlaneReplicator(state.plane, bind=plane_publish)
        repl.start()
        state.replication = repl
        if state.follower is not None:
            state.follower.add_publish_listener(repl.poke)
    elif plane_from is not None and state.plane is not None:
        # subscriber side: land replicated containers into the local
        # plane dir; the PlaneWatcher started by QueryServerState (and
        # by every prefork sibling) installs them exactly as if a local
        # publisher had flipped the manifest
        from predictionio_tpu.streaming.replicate import PlaneSubscriber

        sub = PlaneSubscriber(state.plane.dir, plane_from)
        state.replication = sub
        # started below once the HTTP port is bound: every sync frame
        # then announces this node's endpoint, so the publisher's
        # federation can scrape /metrics and pull /lineage here
    child_procs: list = []
    # flight recorder: prefork children resolve the group's traces dir
    # from PIO_METRICS_DIR; single workers persist next to the storage
    # spans dir so the dashboard can merge them
    obs_tracing.arm(storage=state.storage)
    # lineage records persist next to the traces (children resolve the
    # group dir from PIO_METRICS_DIR); the history sampler gives every
    # serving process its /metrics/history.json ring + SLO gauges
    obs_lineage.arm(storage=state.storage)
    if obs_metrics.get_registry().enabled:
        obs_tsdb.start_sampler()
    httpd = start_server(make_handler(state), host, port,
                         background=background,
                         reuse_port=workers > 1 or reuse_port)
    bound_port = httpd.server_address[1]
    if plane_from is not None and state.replication is not None:
        state.replication.http_port = bound_port
        state.replication.start()
    elif plane_publish is not None and state.replication is not None:
        # cluster observability fabric (publisher only): lineage reads
        # answer with the stitched cross-node outcome, the federation
        # thread scrapes every subscriber's metrics/lineage, and the
        # cluster-scope SLO rows ride /healthz like any local SLO
        repl = state.replication
        obs_lineage.set_cluster_provider(repl.cluster_view)
        if obs_metrics.get_registry().enabled:
            fed = obs_cluster.ClusterFederation(repl.peers)
            fed.start()
            obs_cluster.set_federation(fed)
            obs_slo.arm_cluster_slos()
    if workers > 1:
        obs_tracing.arm(directory=os.path.join(metrics_dir, "traces"),
                        tag=f"w0-{os.getpid()}")
        obs_lineage.arm(directory=os.path.join(metrics_dir, "lineage"),
                        tag=f"w0-{os.getpid()}")
        # plane mode: children are pure consumers — no per-worker
        # follower (ONE fold per delta, in the publisher process below)
        # and no per-worker auto-reload poller (the parent's poller
        # publishes through the plane, converging everyone)
        plane_child_env = (
            {"PIO_MODEL_PLANE": "on", "PIO_MODEL_PLANE_DIR": plane_dir}
            if plane_dir is not None else {})
        child_procs = prefork.spawn_workers(
            workers - 1,
            lambda w: (
                [sys.executable, "-m", "predictionio_tpu.cli.main",
                 "deploy", "--engine-json", str(engine_json),
                 "--variant", variant,
                 "--engine-version", engine_version,
                 "--ip", host, "--port", str(bound_port), "--reuse-port"]
                + (["--engine-id", engine_id] if engine_id else [])
                + (["--feedback"] if feedback else [])
                + (["--auto-reload", str(auto_reload)]
                   if auto_reload and plane_dir is None else [])
                + (["--follow", str(follow)]
                   if follow and plane_dir is None else [])
            ),
            build_env=lambda w: {
                "PIO_METRICS_TAG": f"w{w + 1}-{os.getpid()}",
                "PIO_METRICS_DIR": metrics_dir,
                **plane_child_env},
            log=log,
        )
        if plane_dir is not None and follow > 0:
            # the ONE fold/emit/warm process per node: hosts the only
            # follower, publishes each generation into the arena, serves
            # no queries — fold CPU leaves the serving workers entirely.
            # Its metrics flush into the group dir, so any worker's
            # /metrics scrape shows the (single) fold counters.
            child_procs += prefork.spawn_workers(
                1,
                lambda w: (
                    [sys.executable, "-m", "predictionio_tpu.cli.main",
                     "deploy", "--engine-json", str(engine_json),
                     "--variant", variant,
                     "--engine-version", engine_version,
                     "--follow", str(follow), "--plane-publisher"]
                    + (["--engine-id", engine_id] if engine_id else [])
                ),
                build_env=lambda w: {
                    "PIO_METRICS_TAG": f"pub-{os.getpid()}",
                    "PIO_METRICS_DIR": metrics_dir,
                    "PIO_MODEL_PLANE_DIR": plane_dir},
                log=log,
            )
    log.info("Query server for %s listening on %s:%d", eid, host, bound_port)
    httpd.pio_state = state  # handle for tests/tools
    httpd.pio_workers = child_procs
    # the auto-reload poller (and any prefork workers) must die with the
    # server, however it is shut down (shutdown()/server_close(), /stop,
    # or pio undeploy)
    prefork.wire_shutdown(httpd, child_procs, before=state.stop_auto_reload)
    if metrics_dir is not None:
        prefork.wire_metrics_cleanup(httpd, metrics_dir)
    if background:
        return httpd
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
    return 0


def run_plane_publisher(
    engine_json: str,
    variant: str = "default",
    engine_id: Optional[str] = None,
    engine_version: str = "1",
    follow: float = 2.0,
) -> int:
    """The model plane's dedicated fold/emit process: hosts the ONE
    follow-trainer for a prefork group and publishes every generation
    into the arena (``on_publish`` = :meth:`ModelPlane.publish`) instead
    of serving queries.  Spawned by ``deploy --workers N --follow`` in
    plane mode (internal ``--plane-publisher`` flag); dies with the
    parent like any prefork child."""
    from predictionio_tpu.streaming.fold import FoldUnsupported
    from predictionio_tpu.streaming.follow import FollowTrainer
    from predictionio_tpu.streaming.plane import ModelPlane

    plane_dir = os.environ.get("PIO_MODEL_PLANE_DIR")
    if not plane_dir:
        print("Error: --plane-publisher requires PIO_MODEL_PLANE_DIR",
              file=sys.stderr)
        return 1
    prefork.maybe_watch_parent(log)
    obs_metrics.start_worker_flusher()
    obs_metrics.mark_worker_up()
    # the publisher OPENS every lineage record (fold + publish stages);
    # PIO_METRICS_DIR is in its spawn env, so arm() lands the records in
    # the group dir the serving workers merge from
    obs_lineage.arm()
    doc = load_engine_variant(engine_json, variant)
    factory, engine, engine_params = engine_from_variant(doc)
    eid = resolve_engine_id(engine_id, doc, factory)
    plane = ModelPlane(plane_dir)
    try:
        trainer = FollowTrainer(
            engine, engine_params, eid, engine_version, variant,
            interval=follow, on_publish=plane.publish, persist=False)
    except FoldUnsupported as e:
        # nothing to tail (no app_name): the workers keep their private
        # startup models; exiting loudly beats a zombie publisher
        print(f"Error: plane publisher cannot follow this engine: {e}",
              file=sys.stderr)
        return 1
    log.info("model-plane publisher for %s: folding every %.2fs into %s",
             eid, trainer.interval, plane_dir)
    try:
        trainer.run_forever()
    except KeyboardInterrupt:
        pass
    return 0


def run_server_from_args(args) -> int:
    from predictionio_tpu.workflow.create_workflow import resolve_variant_path

    if getattr(args, "plane_publisher", False):
        try:
            return run_plane_publisher(
                engine_json=resolve_variant_path(args),
                variant=args.variant,
                engine_id=args.engine_id,
                engine_version=args.engine_version,
                follow=getattr(args, "follow", 0.0) or 2.0,
            )
        except Exception as e:
            print(f"Error: {e}", file=sys.stderr)
            return 1
    try:
        result = deploy(
            engine_json=resolve_variant_path(args),
            variant=args.variant,
            engine_id=args.engine_id,
            engine_version=args.engine_version,
            host=args.ip,
            port=args.port,
            feedback=args.feedback,
            auto_reload=getattr(args, "auto_reload", 0.0) or 0.0,
            workers=getattr(args, "workers", 1) or 1,
            reuse_port=getattr(args, "reuse_port", False),
            follow=getattr(args, "follow", 0.0) or 0.0,
            plane_publish=getattr(args, "plane_publish", None),
            plane_from=getattr(args, "plane_from", None),
        )
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    return 0 if result == 0 else 0
