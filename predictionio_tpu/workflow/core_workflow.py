"""Training/evaluation orchestration.

Reference: core/.../workflow/CoreWorkflow.scala — ``runTrain`` records an
EngineInstance (INIT→TRAINING→COMPLETED/FAILED), runs Engine.train, persists
models; ``runEval`` runs the Evaluation and records an EvaluationInstance.
The spark-submit process boundary of the reference collapses to an in-process
call on the TPU VM (SURVEY.md §3 'pio train' stack).
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import traceback
from typing import Any, List, Optional

from predictionio_tpu.controller.engine import Engine, EngineParams, serialize_engine_params
from predictionio_tpu.controller.evaluation import Evaluation, MetricEvaluatorResult
from predictionio_tpu.core.base import doer_name
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.obs.metrics import get_registry
from predictionio_tpu.storage.base import EngineInstance, EvaluationInstance
from predictionio_tpu.storage.locator import Storage, get_storage
from predictionio_tpu.workflow import persistence

log = logging.getLogger("pio.workflow")

_REG = get_registry()
_M_TRAINS = _REG.counter(
    "pio_train_runs_total", "Training runs by final status")
_M_TRAIN_S = _REG.histogram(
    "pio_train_duration_seconds", "Wall-clock duration of training runs")
_M_EVALS = _REG.counter(
    "pio_eval_runs_total", "Evaluation runs by final status")
_M_TRAIN_STAGED = _REG.counter(
    "pio_train_staged_events_total",
    "Events staged during training runs, by source: snapshot = mmap'd "
    "columns, tail = JSONL past snapshot coverage, delta = JSONL past a "
    "retained batch's watermark (delta-aware retrain)")


def _staging_delta(before):
    """Per-mode staged-event counts accrued since ``before`` (a
    store.event_store.staging_counts snapshot)."""
    from predictionio_tpu.store.event_store import staging_counts

    after = staging_counts()
    return {mode: after[mode] - before.get(mode, 0.0) for mode in after}


def _now() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
    engine_factory: str = "",
    storage: Optional[Storage] = None,
    retries: Optional[int] = None,
) -> EngineInstance:
    """Train and persist: returns the COMPLETED EngineInstance (or raises,
    leaving a FAILED instance recorded).

    ``retries`` (default: PIO_TRAIN_RETRIES env, 0) re-runs Engine.train
    after a failure — the elastic-recovery analogue of Spark task retry in
    the reference.  Algorithms that checkpoint (e.g. ALS with
    checkpointEvery) resume from their newest snapshot instead of redoing
    completed sweeps.
    """
    import os

    from predictionio_tpu.ops.pallas_kernels import pallas_mode
    from predictionio_tpu.utils import device as _device

    # what this run executes on, as JAX reports it — logged, and stamped
    # on the journal's root span below (a backend that fails to come up
    # fails the train here, before an instance is recorded)
    _device.watch_compiles()
    runtime = {"device": _device.device_info(), "pallas": pallas_mode()}
    log.info("training on %s", runtime)
    storage = storage or get_storage()
    if retries is None:
        retries = int(os.environ.get("PIO_TRAIN_RETRIES", "0"))
    params_json = serialize_engine_params(engine_params)
    instance = EngineInstance(
        id="",
        status="INIT",
        start_time=_now(),
        end_time=None,
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory or engine_id,
        data_source_params=params_json["data_source_params"],
        preparator_params=params_json["preparator_params"],
        algorithms_params=params_json["algorithms_params"],
        serving_params=params_json["serving_params"],
    )
    instance_id = storage.engine_instances.insert(instance)
    instance.status = "TRAINING"
    storage.engine_instances.update(instance)
    attempt = 0
    # span journal persisted next to the engine instance: every span
    # opened inside engine.train and save_models nests under this run's
    # root span, and `pio dashboard` renders the breakdown per completed
    # train
    journal = _spans.SpanJournal(_spans.journal_path(storage, instance_id))
    t_run = _dt.datetime.now(_dt.timezone.utc).timestamp()
    with journal.activate():
        with journal.span("train", engine_id=engine_id,
                          instance_id=instance_id, **runtime) as root:
            while True:
                try:
                    log.info("training engine %s (instance %s, attempt %d)",
                             engine_id, instance_id, attempt + 1)
                    from predictionio_tpu.store.event_store import staging_counts

                    stage_before = staging_counts()
                    with journal.span("engine_train", attempt=attempt + 1):
                        models = engine.train(engine_params)
                    # delta-aware retrain accounting: how many events this
                    # run staged from where (mmap'd snapshot vs parsed
                    # tail vs past-watermark delta) — recorded as a span
                    # attribute per run and a cross-run counter.  An
                    # all-zero read means the engine staged through a
                    # non-snapshot path (memory/sql/native full scan).
                    staged = _staging_delta(stage_before)
                    with journal.span("staging_summary", **{
                            f"staged_{k}": int(v) for k, v in staged.items()}):
                        pass
                    for mode, v in staged.items():
                        if v:
                            _M_TRAIN_STAGED.inc(v, mode=mode)
                    with journal.span("save_models"):
                        persistence.save_models(storage, instance_id, models)
                        # COMPLETED is what makes the stored model the one a
                        # deploy loads: the commit of persisting it, and a
                        # rewrite of the whole instance document that no
                        # span named before
                        instance.status = "COMPLETED"
                        instance.end_time = _now()
                        storage.engine_instances.update(instance)
                    root["attrs"].update(
                        compile=_device.compile_stats(),
                        peak_memory_bytes=_device.peak_memory_bytes())
                    log.info("training done: instance %s COMPLETED",
                             instance_id)
                    _M_TRAINS.inc(1, status="COMPLETED")
                    _M_TRAIN_S.observe(
                        _dt.datetime.now(_dt.timezone.utc).timestamp() - t_run)
                    return instance
                except Exception:
                    attempt += 1
                    if attempt <= retries:
                        log.warning(
                            "training attempt %d failed, retrying (%d left):\n%s",
                            attempt, retries - attempt + 1,
                            traceback.format_exc())
                        continue
                    instance.status = "FAILED"
                    instance.end_time = _now()
                    storage.engine_instances.update(instance)
                    log.error("training FAILED: %s", traceback.format_exc())
                    _M_TRAINS.inc(1, status="FAILED")
                    raise


def load_latest_models(
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
    storage: Optional[Storage] = None,
) -> tuple:
    """(instance, models) for the latest COMPLETED engine instance —
    the deploy-time lookup (reference: CreateServer resolving EngineInstance)."""
    storage = storage or get_storage()
    instance = storage.engine_instances.get_latest_completed(
        engine_id, engine_version, engine_variant
    )
    if instance is None:
        raise LookupError(
            f"no COMPLETED engine instance for {engine_id} v{engine_version} ({engine_variant}); "
            "run `pio train` first"
        )
    models = persistence.load_models(storage, instance.id)
    return instance, models


def _eval_results_html(result: MetricEvaluatorResult) -> str:
    """Candidate table for the dashboard (reference: EvaluationInstances'
    evaluatorResultsHTML rendered by the dashboard module)."""
    import html as _html

    rows = "".join(
        "<tr{hl}><td>{i}</td><td>{score:.6f}</td><td>{others}</td>"
        "<td><pre>{params}</pre></td></tr>".format(
            hl=' style="background:#e8f4e8"' if i == result.best_index else "",
            i=i + 1,
            score=score,
            others=_html.escape(", ".join(f"{o:.4f}" for o in others)),
            params=_html.escape(json.dumps(ep.to_json(), indent=1)[:2000]),
        )
        for i, (ep, score, others) in enumerate(result.engine_params_scores)
    )
    return (
        f"<h3>{_html.escape(result.metric_header)}</h3>"
        f"<table><tr><th>#</th><th>{_html.escape(result.metric_header)}</th>"
        f"<th>{_html.escape(', '.join(result.other_metric_headers))}</th>"
        f"<th>engine params</th></tr>{rows}</table>"
    )


def run_eval(
    evaluation: Evaluation,
    evaluation_class: str = "",
    storage: Optional[Storage] = None,
) -> MetricEvaluatorResult:
    """Run an Evaluation, record the EvaluationInstance, return the result."""
    storage = storage or get_storage()
    instance = EvaluationInstance(
        id="",
        status="EVALRUNNING",
        start_time=_now(),
        end_time=None,
        evaluation_class=evaluation_class or doer_name(evaluation),
    )
    instance_id = storage.evaluation_instances.insert(instance)
    journal = _spans.SpanJournal(_spans.journal_path(storage, instance_id))
    try:
        with journal.activate(), journal.span(
                "eval", instance_id=instance_id,
                evaluation_class=instance.evaluation_class):
            result = evaluation.run()
        instance.status = "EVALCOMPLETED"
        instance.end_time = _now()
        instance.evaluator_results = (
            f"{result.metric_header}: best={result.best_score:.6f} "
            f"(candidate {result.best_index + 1}/{len(result.engine_params_scores)})"
        )
        instance.evaluator_results_json = json.dumps(result.to_json())
        instance.evaluator_results_html = _eval_results_html(result)
        storage.evaluation_instances.update(instance)
        # counted only after the instance is durably COMPLETED: a
        # serialization/persistence failure above lands in the except
        # block, and one run must never count under both statuses
        _M_EVALS.inc(1, status="EVALCOMPLETED")
        return result
    except Exception:
        _M_EVALS.inc(1, status="EVALFAILED")
        instance.status = "EVALFAILED"
        instance.end_time = _now()
        storage.evaluation_instances.update(instance)
        raise
