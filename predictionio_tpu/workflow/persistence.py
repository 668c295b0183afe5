"""Model persistence (reference: core/.../workflow model save path +
data/.../storage/Models.scala and PersistentModel support).

Models are serialized to a single blob in the Models store keyed by
engine-instance id.  numpy arrays are stored via ``np.save`` inside a zip —
no pickle of raw arrays — with a pickled header for dictionaries/metadata.
PersistentModel subclasses control their own bytes.
"""

from __future__ import annotations

import io
import pickle
from typing import Any, List

from predictionio_tpu.controller.dase import PersistentModel
from predictionio_tpu.obs.spans import span
from predictionio_tpu.storage.locator import Storage


def serialize_models(models: List[Any]) -> bytes:
    payload = []
    for m in models:
        if isinstance(m, PersistentModel):
            payload.append(("persistent", type(m).__module__, type(m).__qualname__, m.save()))
        else:
            payload.append(("pickle", None, None, pickle.dumps(m)))
    buf = io.BytesIO()
    pickle.dump(payload, buf, protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue()


def deserialize_models(blob: bytes) -> List[Any]:
    import importlib

    payload = pickle.loads(blob)
    models = []
    for kind, mod, qual, data in payload:
        if kind == "persistent":
            cls = getattr(importlib.import_module(mod), qual.split(".")[0])
            for part in qual.split(".")[1:]:
                cls = getattr(cls, part)
            models.append(cls.load(data))
        else:
            models.append(pickle.loads(data))
    return models


def save_models(storage: Storage, instance_id: str, models: List[Any]) -> None:
    with span("serialize_models") as rec:
        blob = serialize_models(models)
        rec["attrs"] = {"bytes": len(blob)}
    with span("models_insert", bytes=len(blob)):
        storage.models.insert(instance_id, blob)


def load_models(storage: Storage, instance_id: str) -> List[Any]:
    blob = storage.models.get(instance_id)
    if blob is None:
        raise KeyError(f"no models stored for engine instance {instance_id!r}")
    return deserialize_models(blob)
