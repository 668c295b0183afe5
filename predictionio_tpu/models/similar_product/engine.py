"""Similar-Product engine template.

Capability parity with the reference Similar Product template (template repo;
SURVEY.md §2 'Similar-Product': item-item similarity from view events via
ALS item factors, with category/white/black-list filters) and its
cooccurrence variant.

Wire format (reference template):
  query    {"items": ["i1", "i2"], "num": 4,
            "categories": ["c"], "whiteList": [...], "blackList": [...]}
  response {"itemScores": [{"item": "i5", "score": 0.9}, ...]}

Algorithms:
- "als":          implicit-feedback ALS on (user, item) views; similarity =
                  cosine over item factors, computed as one jitted matmul.
- "cooccurrence": LLR item-item cooccurrence via ops.cco (exclude_self).
"""

from __future__ import annotations

import dataclasses

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    Params,
    PersistentModel,
    Preparator,
)
from predictionio_tpu.models.recommendation.engine import ItemScore, PredictedResult
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.ops import cco as cco_ops
from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh
from predictionio_tpu.models.common import (
    CategoryRulesMixin,
    opt_str_list,
    reindex_interactions,
)
from predictionio_tpu.store.columnar import IdDict, category_masks
from predictionio_tpu.store.event_store import PEventStore


@dataclasses.dataclass
class SimilarProductQuery:
    items: List[str]
    num: int = 10
    categories: Optional[List[str]] = None
    white_list: Optional[List[str]] = None
    black_list: Optional[List[str]] = None

    @classmethod
    def from_json(cls, d: Dict) -> "SimilarProductQuery":
        # empty-vs-absent semantics: see models.common.opt_str_list
        return cls(
            items=[str(i) for i in d["items"]],
            num=int(d.get("num", 10)),
            categories=opt_str_list(d, "categories"),
            white_list=opt_str_list(d, "whiteList"),
            black_list=opt_str_list(d, "blackList"),
        )


@dataclasses.dataclass
class SPDataSourceParams(Params):
    app_name: str = "default"
    event_names: List[str] = dataclasses.field(default_factory=lambda: ["view"])
    item_entity_type: str = "item"


@dataclasses.dataclass
class SPTrainingData:
    user_idx: np.ndarray
    item_idx: np.ndarray
    user_dict: IdDict
    item_dict: IdDict
    item_categories: Dict[str, List[str]]


class SPDataSource(DataSource):
    params_class = SPDataSourceParams

    def read_training(self) -> SPTrainingData:
        """Columnar batch read (native C++ scan on segment-file backends) +
        vectorized dictionary translation — no per-event Python loop."""
        batch = PEventStore.batch(
            self.params.app_name, event_names=list(self.params.event_names))
        users, items, user_dict, item_dict = reindex_interactions(batch)
        props = PEventStore.aggregate_properties(
            self.params.app_name, self.params.item_entity_type
        )
        cats = {}
        for item, pm in props.items():
            v = pm.get("categories")
            if v is not None:
                cats[item] = [str(c) for c in (v if isinstance(v, list) else [v])]
        return SPTrainingData(
            user_idx=users,
            item_idx=items,
            user_dict=user_dict,
            item_dict=item_dict,
            item_categories=cats,
        )


class SPPreparator(Preparator):
    def prepare(self, td: SPTrainingData) -> SPTrainingData:
        return td


class SPModel(CategoryRulesMixin, PersistentModel):
    """Either item factors (als) or an indicator table (cooccurrence);
    scoring normalizes both to an item->similar-items lookup.

    Serving state is device-resident (``warm``): row-normalized factors OR
    the indicator table, plus the [C, n_items] category masks — per query
    only small padded id lists upload and one stacked [2, k] array returns
    (each extra fetch would be its own device sync)."""

    def __init__(self, kind, item_dict, item_categories,
                 item_factors=None, indicator_idx=None, indicator_llr=None):
        self.kind = kind
        self.item_dict = item_dict
        self.item_categories = item_categories
        self.item_factors = item_factors
        self.indicator_idx = indicator_idx
        self.indicator_llr = indicator_llr
        self.cat_dict, self.cat_masks = category_masks(item_categories, item_dict)

    def __getstate__(self):
        return {
            "kind": self.kind, "items": self.item_dict.to_state(),
            "cats": self.item_categories, "factors": self.item_factors,
            "idx": self.indicator_idx, "llr": self.indicator_llr,
        }

    def __setstate__(self, s):
        self.kind = s["kind"]
        self.item_dict = IdDict.from_state(s["items"])
        self.item_categories = s["cats"]
        self.item_factors = s["factors"]
        self.indicator_idx = s["idx"]
        self.indicator_llr = s["llr"]
        self.cat_dict, self.cat_masks = category_masks(
            self.item_categories, self.item_dict)

    def factors_norm_device(self):
        """Row-normalized factors so ``Yn @ q`` is cosine · |q| — staged
        once; the |q| rescale happens host-side on k scores."""
        def build():
            f = np.asarray(self.item_factors, np.float32)
            norms = np.maximum(np.linalg.norm(f, axis=1, keepdims=True), 1e-8)
            return jax.device_put(jnp.asarray(f / norms))

        return self._device("_fn_dev", build)

    def indicators_device(self):
        return self._device("_ind_dev", lambda: (
            jax.device_put(jnp.asarray(self.indicator_idx)),
            jax.device_put(jnp.asarray(self.indicator_llr))))

    def warm(self) -> None:
        if len(self.item_dict) == 0:
            return
        if self.kind == "als" and self.item_factors is not None and len(self.item_factors):
            self.factors_norm_device()
        if self.kind == "cooccurrence" and self.indicator_idx is not None and len(self.indicator_idx):
            self.indicators_device()
        self.cat_masks_device()


# shared indicator-table serving kernels (also used by the
# complementary-purchase template) live beside the other serving ops
_indicator_scatter_scores = als_ops.indicator_scatter_scores
_indicator_scatter_scores_batch = als_ops.indicator_scatter_scores_batch


@dataclasses.dataclass
class SPALSParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0      # implicit-feedback confidence slope
    seed: int = 7
    mesh_dp: int = 0


class SPALSAlgorithm(Algorithm):
    params_class = SPALSParams

    def train(self, td: SPTrainingData) -> SPModel:
        n_users, n_items = len(td.user_dict), len(td.item_dict)
        if n_items == 0:
            return SPModel("als", td.item_dict, td.item_categories,
                           item_factors=np.zeros((0, self.params.rank), np.float32))
        dp = self.params.mesh_dp or len(jax.devices())
        mesh = create_mesh(MeshSpec(dp=dp, mp=1)) if dp > 1 else None
        # true implicit feedback (MLlib ALS.trainImplicit, as the reference
        # template calls): view COUNTS become confidences c = 1 + alpha*r
        cell = td.user_idx.astype(np.int64) * n_items + td.item_idx
        uniq, counts = np.unique(cell, return_counts=True)
        users = (uniq // n_items).astype(np.int32)
        items = (uniq % n_items).astype(np.int32)
        data = als_ops.prepare_als_data(
            users, items, counts.astype(np.float32), n_users, n_items, dp=dp
        )
        _, Y = als_ops.als_train(
            data, k=self.params.rank, reg=self.params.lambda_,
            iterations=self.params.num_iterations, mesh=mesh, seed=self.params.seed,
            implicit=True, alpha=self.params.alpha,
        )
        return SPModel("als", td.item_dict, td.item_categories, item_factors=Y)

    def warm(self, model: SPModel) -> None:
        model.warm()

    def predict(self, model: SPModel, query: SimilarProductQuery) -> PredictedResult:
        return _sp_predict(model, query)

    def serve_batch_predict(self, model: SPModel, queries):
        return _sp_predict_batch(model, queries)


@dataclasses.dataclass
class SPCooccurrenceParams(Params):
    max_correlators_per_item: int = 50
    min_llr: float = 0.0
    user_block: int = 0     # 0: derived from the bytes (ops/cco._block_plan)
    item_tile: int = 4096
    mesh_dp: int = 0


class SPCooccurrenceAlgorithm(Algorithm):
    params_class = SPCooccurrenceParams

    def train(self, td: SPTrainingData) -> SPModel:
        n_users, n_items = len(td.user_dict), len(td.item_dict)
        if n_items == 0:
            return SPModel("cooccurrence", td.item_dict, td.item_categories,
                           indicator_idx=np.zeros((0, 1), np.int32),
                           indicator_llr=np.zeros((0, 1), np.float32))
        dp = self.params.mesh_dp or len(jax.devices())
        mesh = create_mesh(MeshSpec(dp=dp, mp=1)) if dp > 1 else None
        scores, idx = cco_ops.cco_indicators_coo(
            td.user_idx, td.item_idx, td.user_idx, td.item_idx,
            n_users, n_items, n_items,
            top_k=self.params.max_correlators_per_item,
            llr_threshold=self.params.min_llr,
            user_block=self.params.user_block,
            item_tile=self.params.item_tile,
            mesh=mesh, exclude_self=True,
        )
        return SPModel(
            "cooccurrence", td.item_dict, td.item_categories,
            indicator_idx=idx.astype(np.int32),
            indicator_llr=np.where(np.isfinite(scores), scores, 0.0).astype(np.float32),
        )

    def warm(self, model: SPModel) -> None:
        model.warm()

    def predict(self, model: SPModel, query: SimilarProductQuery) -> PredictedResult:
        return _sp_predict(model, query)

    def serve_batch_predict(self, model: SPModel, queries):
        return _sp_predict_batch(model, queries)


def _sp_predict(model: SPModel, query: SimilarProductQuery) -> PredictedResult:
    """Device-final similarity serving (was: full-score-vector download +
    O(n_items) Python filter loops per query): rules mask and top-k run on
    device via ops.als, ONE stacked [2, k] readback per query."""
    n_items = len(model.item_dict)
    if n_items == 0:
        return PredictedResult([])
    prepped = _sp_rule_ids(model, query)
    if prepped is None:   # no resolvable items, or unresolvable constraint
        return PredictedResult([])
    qids, cat_ids, white, excl = prepped
    cat_ids = np.asarray(cat_ids, np.int32)
    white = np.asarray(white, np.int32)
    num = min(query.num, n_items)
    k = min(als_ops.bucket_width(num), n_items)
    q_pad = als_ops.pad_ids(qids)
    scale = 1.0
    if model.kind == "als":
        qvec = np.asarray(model.item_factors, np.float32)[np.asarray(qids)].mean(axis=0)
        qnorm = float(np.linalg.norm(qvec))
        scale = 1.0 / max(qnorm, 1e-8)   # Yn @ qvec = cosine · |qvec|
        out = als_ops.recommend_scores_rules(
            jnp.asarray(qvec), model.factors_norm_device(),
            model.cat_masks_device(), als_ops.pad_ids(cat_ids),
            als_ops.pad_ids(white), als_ops.pad_ids(np.asarray(excl, np.int32)), k)
    else:
        idx_dev, llr_dev = model.indicators_device()
        scores = _indicator_scatter_scores(idx_dev, llr_dev, jnp.asarray(q_pad))
        out = als_ops.scores_rules_topk(
            scores, model.cat_masks_device(), als_ops.pad_ids(cat_ids),
            als_ops.pad_ids(white), als_ops.pad_ids(np.asarray(excl, np.int32)), k)
    out = np.asarray(out)                # the single device sync per query
    st, si = out[0] * scale, out[1].astype(np.int32)
    return PredictedResult(
        [ItemScore(model.item_dict.str(int(j)), float(s))
         for s, j in zip(st[:num], si[:num]) if np.isfinite(s) and s > 0]
    )


def _sp_rule_ids(model: SPModel, query: SimilarProductQuery):
    """(qids, cat_ids, white, excl) for one query, or None when a host
    short-circuit applies (no resolvable query items, or a present-but-
    unresolvable category/whiteList constraint) — mirrors _sp_predict's
    early returns exactly."""
    qids = [model.item_dict.id(i) for i in query.items]
    qids = [q for q in qids if q is not None]
    if not qids:
        return None
    cat_ids = [c for c in (model.cat_dict.id(n) for n in query.categories or [])
               if c is not None]
    if query.categories is not None and len(cat_ids) == 0:
        return None
    white = [i for i in (model.item_dict.id(n) for n in query.white_list or [])
             if i is not None]
    if query.white_list is not None and len(white) == 0:
        return None
    excl = list(qids)
    for bl in query.black_list or []:
        bid = model.item_dict.id(bl)
        if bid is not None:
            excl.append(bid)
    return qids, cat_ids, white, excl


def _sp_predict_batch(model: SPModel,
                      queries) -> List[PredictedResult]:
    """Micro-batch serving: every query's rules + top-k in ONE device
    program and one [B, 2, k] readback (see create_server._MicroBatcher);
    host short-circuits (empty/unresolvable queries) answer without
    touching the device, exactly as _sp_predict does."""
    n_items = len(model.item_dict)
    results: List[Optional[PredictedResult]] = [None] * len(queries)
    live: List[int] = []
    prepped = []
    for i, q in enumerate(queries):
        p = _sp_rule_ids(model, q) if n_items else None
        if p is None:
            results[i] = PredictedResult([])
        else:
            live.append(i)
            prepped.append(p)
    if not live:
        return results
    bp = als_ops.bucket_width(len(live), min_width=1)
    pad = bp - len(live)
    qm = als_ops.pad_id_rows([p[0] for p in prepped] + [[]] * pad)
    cm = als_ops.pad_id_rows([p[1] for p in prepped] + [[]] * pad)
    wm = als_ops.pad_id_rows([p[2] for p in prepped] + [[]] * pad)
    em = als_ops.pad_id_rows([p[3] for p in prepped] + [[]] * pad)
    nums = [min(queries[i].num, n_items) for i in live]
    k = min(als_ops.bucket_width(max(nums)), n_items)
    scales = np.ones(len(live), np.float64)
    if model.kind == "als":
        f = np.asarray(model.item_factors, np.float32)
        vecs = np.zeros((bp, f.shape[1]), np.float32)
        for r, p in enumerate(prepped):
            v = f[np.asarray(p[0])].mean(axis=0)
            vecs[r] = v
            scales[r] = 1.0 / max(float(np.linalg.norm(v)), 1e-8)
        out = als_ops.recommend_batch_rules(
            jnp.asarray(vecs), model.factors_norm_device(),
            model.cat_masks_device(), jnp.asarray(cm), jnp.asarray(wm),
            jnp.asarray(em), k)
    else:
        idx_dev, llr_dev = model.indicators_device()
        scores = _indicator_scatter_scores_batch(
            idx_dev, llr_dev, jnp.asarray(qm))
        out = als_ops.scores_rules_topk_batch(
            scores, model.cat_masks_device(), jnp.asarray(cm),
            jnp.asarray(wm), jnp.asarray(em), k)
    out = np.asarray(out)                # ONE readback for the batch
    for r, i in enumerate(live):
        st = out[r, 0] * scales[r]
        si = out[r, 1].astype(np.int32)
        n = nums[r]
        results[i] = PredictedResult(
            [ItemScore(model.item_dict.str(int(j)), float(s))
             for s, j in zip(st[:n], si[:n]) if np.isfinite(s) and s > 0])
    return results


class SimilarProductEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source_class=SPDataSource,
            preparator_class=SPPreparator,
            algorithm_classes={
                "als": SPALSAlgorithm,
                "cooccurrence": SPCooccurrenceAlgorithm,
            },
            serving_class=FirstServing,
        )

    query_class = SimilarProductQuery
