"""CLI→workflow glue (reference: core/.../workflow/CreateWorkflow.scala +
WorkflowUtils engine-variant parsing).

Resolves the engine factory named in engine.json (dotted import path or a
built-in template shortname from models.ENGINE_FACTORIES), binds the variant's
params blocks to typed EngineParams, and dispatches to CoreWorkflow.
"""

from __future__ import annotations

import importlib
import json
import logging
import sys
from pathlib import Path
from typing import Any, Dict, Optional, Tuple, Type

from predictionio_tpu.controller.engine import Engine, EngineFactory, EngineParams
from predictionio_tpu.models import ENGINE_FACTORIES
from predictionio_tpu.workflow import core_workflow

log = logging.getLogger("pio.workflow")


def resolve_engine_factory(name: str) -> Type[EngineFactory]:
    """Import the EngineFactory class for a dotted path or template shortname."""
    dotted = ENGINE_FACTORIES.get(name, name)
    module_name, _, cls_name = dotted.rpartition(".")
    if not module_name:
        raise ValueError(
            f"engineFactory {name!r} is not a dotted path or known template "
            f"({sorted(ENGINE_FACTORIES)})"
        )
    # engine.json lives next to user code; make its directory importable the
    # way the reference adds the engine assembly jar to the classpath.
    module = importlib.import_module(module_name)
    factory = getattr(module, cls_name)
    if not (isinstance(factory, type) and issubclass(factory, EngineFactory)):
        raise TypeError(f"{dotted} is not an EngineFactory subclass")
    return factory


def load_engine_variant(engine_json: str, variant_id: str = "default") -> Dict[str, Any]:
    """Load engine.json; supports both a single variant document and the
    reference's ``engineFactory`` + per-variant files."""
    path = Path(engine_json)
    if not path.exists():
        raise FileNotFoundError(f"engine variant file {engine_json!r} not found")
    doc = json.loads(path.read_text())
    if "engineFactory" not in doc:
        raise ValueError(f"{engine_json}: missing required key 'engineFactory'")
    # engine.json lives next to user code; make its directory importable the
    # way the reference adds the engine assembly jar to the classpath, so
    # engineFactory can name a module local to the engine directory.
    parent = str(path.resolve().parent)
    if parent not in sys.path:
        sys.path.insert(0, parent)
    return doc


def resolve_variant_path(args) -> str:
    """Resolve the engine.json path for a workflow command: the --engine-json
    path if it exists, else the file registered by `pio build` for
    (--engine-id, --engine-version) (reference: RunWorkflow resolving the
    engine via its EngineManifest)."""
    if Path(args.engine_json).exists():
        return args.engine_json
    engine_id = getattr(args, "engine_id", None)
    if engine_id:
        from predictionio_tpu.storage import get_storage

        manifest = get_storage().engine_manifests.get(
            engine_id, getattr(args, "engine_version", "1")
        )
        if manifest and manifest.files and Path(manifest.files[0]).exists():
            log.info("resolved engine %s via manifest: %s", engine_id, manifest.files[0])
            return manifest.files[0]
    return args.engine_json  # let load_engine_variant raise FileNotFoundError


def engine_from_variant(
    variant: Dict[str, Any]
) -> Tuple[Type[EngineFactory], Engine, EngineParams]:
    factory = resolve_engine_factory(variant["engineFactory"])
    engine = factory.apply()
    engine_params = engine.engine_params_from_variant(variant)
    return factory, engine, engine_params


def resolve_engine_id(
    cli_engine_id: Optional[str], variant: Dict[str, Any], factory: Type[EngineFactory]
) -> str:
    """Single precedence rule for the engine id, shared by build/train/deploy:
    explicit --engine-id > engine.json "id" > factory class name."""
    return cli_engine_id or variant.get("id") or factory.engine_id()


def _describe(obj) -> str:
    """One-line structural summary of a training-data object for the
    stop-after-read/prepare debug output."""
    import dataclasses as _dc

    import numpy as _np

    bits = [type(obj).__name__]
    if _dc.is_dataclass(obj) and not isinstance(obj, type):
        for f in _dc.fields(obj):
            v = getattr(obj, f.name)
            if isinstance(v, _np.ndarray):
                bits.append(f"{f.name}[{v.shape} {v.dtype}]")
            elif isinstance(v, dict):
                bits.append(f"{f.name}{{{len(v)}}}")
            elif hasattr(v, "__len__"):
                bits.append(f"{f.name}({len(v)})")
    elif hasattr(obj, "__len__"):
        bits.append(f"len={len(obj)}")
    return " ".join(bits)


def run_train_from_args(args) -> int:
    """`pio train` entry (reference: Console.train → RunWorkflow →
    CreateWorkflow.main)."""
    try:
        # no-op single-process; on a multi-host fleet (PIO_COORDINATOR_ADDRESS
        # et al.) this joins the global runtime before any mesh is built
        from predictionio_tpu.parallel.distributed import init_distributed

        init_distributed()
        variant = load_engine_variant(resolve_variant_path(args), args.variant)
        factory, engine, engine_params = engine_from_variant(variant)
        engine_id = resolve_engine_id(args.engine_id, variant, factory)
        stop_read = getattr(args, "stop_after_read", False)
        stop_prepare = getattr(args, "stop_after_prepare", False)
        if stop_read or stop_prepare:
            # reference WorkflowParams stopAfterRead/stopAfterPrepare:
            # sanity-check the data pipeline without training/persisting
            data_source, preparator, _algos, _serving = engine.make_components(
                engine_params)
            td = data_source.read_training()
            print(f"read_training -> {_describe(td)}")
            if stop_prepare:
                pd = preparator.prepare(td)
                print(f"prepare -> {_describe(pd)}")
            print("Stopped before training (debug flag).")
            return 0
        if getattr(args, "follow", False):
            return _run_follow(args, variant, engine, engine_params,
                               engine_id)
        from predictionio_tpu.utils.device import device_info

        dev = device_info()
        print(f"Training on {dev['count']} {dev['platform']} device(s) "
              f"({dev['kind']}).", flush=True)
        instance = core_workflow.run_train(
            engine,
            engine_params,
            engine_id=engine_id,
            engine_version=args.engine_version,
            engine_variant=args.variant,
            engine_factory=variant["engineFactory"],
        )
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    print(f"Training completed. Engine instance id: {instance.id}")
    return 0


def _run_follow(args, variant, engine, engine_params, engine_id: str) -> int:
    """`pio train --follow` — the resident follow-trainer daemon: train
    (or resume from the persisted watermark), then tail the event store
    and publish an incrementally-folded COMPLETED engine instance per
    batch of new events.  Deployments started with ``--auto-reload``
    hot-swap to each generation within their poll interval."""
    from predictionio_tpu.streaming.follow import FollowTrainer

    trainer = FollowTrainer(
        engine, engine_params, engine_id=engine_id,
        engine_version=args.engine_version, engine_variant=args.variant,
        engine_factory=variant["engineFactory"],
        interval=getattr(args, "follow_interval", 0.0) or None,
        persist=True)
    print(f"Follow-trainer for {engine_id} resident "
          f"(mode={trainer.mode}, interval={trainer.interval:g}s); "
          "Ctrl-C stops.")
    try:
        trainer.run_forever()
    except KeyboardInterrupt:
        pass
    return 0


def run_build_from_args(args) -> int:
    """`pio build` entry (reference: Console.build → sbt assembly +
    RegisterEngine writing an EngineManifest).  There is no jar to compile
    here; "build" = validate the engine variant end to end (factory import,
    engine construction, params binding) and register the manifest so train/
    deploy can resolve the engine by (id, version)."""
    from predictionio_tpu.storage import EngineManifest, get_storage

    try:
        variant = load_engine_variant(args.engine_json, getattr(args, "variant", "default"))
        factory, engine, engine_params = engine_from_variant(variant)
        engine_id = resolve_engine_id(getattr(args, "engine_id", None), variant, factory)
        version = getattr(args, "engine_version", "1")
        manifest = EngineManifest(
            id=engine_id,
            version=version,
            name=variant.get("id", engine_id),
            description=variant.get("description", ""),
            files=[str(Path(args.engine_json).resolve())],
            engine_factory=variant["engineFactory"],
        )
        get_storage().engine_manifests.insert(manifest)
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    n_algos = len(engine_params.algorithm_params_list)
    print(
        f"Build successful. Registered engine {engine_id} {version} "
        f"(factory {variant['engineFactory']}, {n_algos} algorithm(s))."
    )
    return 0


def _load_dotted(path: str, what: str):
    module_name, _, attr = path.rpartition(".")
    if not module_name:
        raise ValueError(f"{what} {path!r} must be a dotted path")
    return getattr(importlib.import_module(module_name), attr)


def run_eval_from_args(args) -> int:
    """`pio eval` entry — evaluation_class is a dotted path to an Evaluation
    subclass or instance; an optional EngineParamsGenerator dotted path
    supplies the candidate grid (reference: Console.eval taking
    <Evaluation> [<EngineParamsGenerator>] → EvaluationWorkflow)."""
    from predictionio_tpu.controller.evaluation import Evaluation, EngineParamsGenerator

    try:
        obj = _load_dotted(args.evaluation_class, "evaluation class")
        evaluation = obj() if isinstance(obj, type) else obj
        if not isinstance(evaluation, Evaluation):
            raise TypeError(f"{args.evaluation_class} is not an Evaluation")
        gen_path = getattr(args, "params_generator", None)
        if gen_path:
            gobj = _load_dotted(gen_path, "engine params generator")
            gen = gobj() if isinstance(gobj, type) else gobj
            if not isinstance(gen, EngineParamsGenerator):
                raise TypeError(f"{gen_path} is not an EngineParamsGenerator")
            evaluation.engine_params_list = list(gen.engine_params_list)
        result = core_workflow.run_eval(evaluation, evaluation_class=args.evaluation_class)
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    print(f"Evaluation completed: {result.metric_header} best={result.best_score:.6f}")
    # per-candidate table incl. side metrics (reference MetricEvaluator
    # prints the full candidate/metric matrix, not only the winner)
    headers = [result.metric_header] + list(result.other_metric_headers)
    for i, (_ep, score, others) in enumerate(result.engine_params_scores):
        marker = "*" if i == result.best_index else " "
        cells = "  ".join(f"{h}={v:.6f}" for h, v in zip(headers, [score] + list(others)))
        print(f"  {marker} candidate {i}: {cells}")
    print("Best engine params:")
    print(json.dumps(result.best_engine_params.to_json(), indent=2))
    return 0
