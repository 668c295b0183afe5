"""Complementary Purchase engine template (shopping-basket rules).

The template is ``apache/predictionio-template-complementary-purchase``;
its published ``engine.json`` loads here key for key: ``appName`` on the
data source, and on the one algorithm ``basketWindow`` (seconds),
``maxRuleLength``, ``minSupport``, ``minConfidence``, ``minLift``,
``minBasketSize``, ``maxNumRulesPerCond``.  Semantics, as the template's
documentation describes them:

- a basket is one user's ``buy`` events, each within ``basketWindow``
  seconds of the one before; a basket of fewer than ``minBasketSize``
  distinct items is dropped; N is the number of baskets kept;
- for items i ≠ j: support = c_ij / N, confidence(i → j) = c_ij / c_i,
  lift = confidence / (c_j / N); a rule is kept at support ≥
  ``minSupport``, confidence ≥ ``minConfidence`` and lift ≥ ``minLift``;
  each condition item keeps its ``maxNumRulesPerCond`` best by lift.

TPU-first redesign, not a translation: the template mines frequent
itemsets (FP-Growth on Spark), a sequential pointer-chasing algorithm with
no MXU mapping.  At ``maxRuleLength`` 2 its rules are exactly the pair
rules, and pair counts over all item pairs at once are one basket×item
scatter-densify plus one MXU matmul (BᵀB): ``ops.cco.basket_rules``
computes every support/confidence/lift in one compiled program and keeps
the per-item top-k by lift.  Carts of several items are served by
aggregating the single-item rules over the cart on device (the
gather+scatter scorer the similar-product template uses).

Departures from the template, each also under ``assumed`` of
``benchmark/configs/cp-ecom-100k.json``:

- ``maxRuleLength`` above 2 is refused: rules with two or more condition
  items are not computed, and nothing stands in for them;
- N counts the baskets kept after the ``minBasketSize`` drop (the
  documentation, as remembered, does not say whether dropped baskets
  count towards support);
- a gap of exactly ``basketWindow`` stays in the basket (``>`` splits);
- the cuts are compared as float32 products of counts (c ≥ minSupport·N,
  c ≥ minConfidence·c_i, c·N ≥ minLift·c_i·c_j), exact while the products
  fit float32's 24 bits; the benchmark's reference forgives a rule whose
  float64 value lies within 1e-6 of a cut;
- rules of equal lift are kept in the order the merge finds them;
- the defaults of ``CPAlgorithmParams`` are this repo's (no cut, baskets
  of one item kept, 20 rules, a window of one hour), not the published
  file's values: an engine.json states its own.

Two older spellings still load: ``maxRulesPerItem`` (=
``maxNumRulesPerCond``), and ``basketWindow`` as a duration string on
the data source, which feeds the same ``basket_window_seconds``.

Wire format (reference template):
  query    {"items": ["i1", "i2"], "num": 3}
  response {"itemScores": [{"item": "i9", "score": 1.7}, ...]}
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

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
from predictionio_tpu.models.common import CategoryRulesMixin
from predictionio_tpu.models.recommendation.engine import ItemScore, PredictedResult
from predictionio_tpu.ops.als import indicator_scatter_scores as _indicator_scatter_scores
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.obs.spans import span
from predictionio_tpu.ops import cco as cco_ops
from predictionio_tpu.store.columnar import IdDict
from predictionio_tpu.store.event_store import PEventStore
from predictionio_tpu.models.universal_recommender.popmodel import parse_duration


@dataclasses.dataclass
class CPQuery:
    items: List[str]
    num: int = 10

    @classmethod
    def from_json(cls, d: Dict) -> "CPQuery":
        return cls(items=[str(i) for i in d["items"]],
                   num=int(d.get("num", 10)))


@dataclasses.dataclass
class CPDataSourceParams(Params):
    app_name: str = "default"
    event_name: str = "buy"
    # the older place of the basket window, a duration string ("10
    # minutes"); the template has it on the algorithm, in seconds
    basket_window: Optional[str] = None


@dataclasses.dataclass
class CPTrainingData:
    user_idx: np.ndarray      # int32 per buy event
    item_idx: np.ndarray      # int32 per buy event
    times_us: np.ndarray      # int64 per buy event
    item_dict: IdDict
    basket_window: Optional[str] = None   # the data source's, if it set one


class CPDataSource(DataSource):
    """Reads the buy events in one columnar read: who, what, when.  The
    baskets are the algorithm's to form (its ``basketWindow``)."""

    params_class = CPDataSourceParams

    def read_training(self) -> CPTrainingData:
        batch = PEventStore.batch(
            self.params.app_name, event_names=[self.params.event_name])
        has_t = batch.target_ids >= 0
        t_codes = batch.target_ids[has_t]
        uniq = np.unique(t_codes)
        item_dict = IdDict([batch.target_dict.str(int(c)) for c in uniq])
        t_map = np.full(max(len(batch.target_dict), 1), -1, np.int32)
        t_map[uniq] = np.arange(len(uniq), dtype=np.int32)
        return CPTrainingData(
            user_idx=batch.entity_ids[has_t].astype(np.int32),
            item_idx=t_map[t_codes],
            times_us=batch.times_us[has_t].astype(np.int64),
            item_dict=item_dict,
            basket_window=self.params.basket_window,
        )


class CPPreparator(Preparator):
    def prepare(self, td: CPTrainingData) -> CPTrainingData:
        return td


_OLDER_KEYS = {"maxRulesPerItem": "maxNumRulesPerCond",
               "max_rules_per_item": "max_num_rules_per_cond"}


@dataclasses.dataclass
class CPAlgorithmParams(Params):
    """The template's algorithm parameters, under its own names."""

    basket_window: Optional[float] = None    # seconds
    max_rule_length: int = 2
    min_support: float = 0.0
    min_confidence: float = 0.0
    min_lift: float = 0.0
    min_basket_size: int = 1
    max_num_rules_per_cond: int = 20
    # not the template's: the program's item tile, as UR's ``itemTile``
    item_tile: int = 4096

    def __post_init__(self):
        if self.max_rule_length != 2:
            raise ValueError(
                f"maxRuleLength {self.max_rule_length}: only pair rules "
                "(one condition item -> one item, maxRuleLength 2) are "
                "computed; rules over larger itemsets cannot run and are "
                "not approximated")

    @classmethod
    def from_json(cls, data):
        if isinstance(data, dict):
            data = {_OLDER_KEYS.get(k, k): v for k, v in data.items()}
        return super().from_json(data)


def basket_window_seconds(params: CPAlgorithmParams,
                          td: CPTrainingData) -> float:
    """The one place that decides the basket window: the algorithm's
    ``basketWindow`` (seconds), else the data source's older duration
    string, else one hour; two that disagree are an error."""
    older = (None if td.basket_window is None
             else float(parse_duration(td.basket_window)))
    if params.basket_window is None:
        return 3600.0 if older is None else older
    if older is not None and older != float(params.basket_window):
        raise ValueError(
            f"basketWindow is {params.basket_window} s on the algorithm and "
            f"{td.basket_window!r} on the data source: set one")
    return float(params.basket_window)


class CPModel(CategoryRulesMixin, PersistentModel):
    """Per-item complement lists: ids + lift scores.  Staged to device at
    warm(); a query ships only the padded cart ids and one stacked [2, k]
    array returns.  (Rule confidences are an op-level output —
    ops.cco.basket_rules — not serving state.)"""

    def __init__(self, item_dict: IdDict, comp_idx: np.ndarray,
                 comp_lift: np.ndarray):
        self.item_dict = item_dict
        self.comp_idx = comp_idx
        self.comp_lift = comp_lift
        # no category rules in this template: empty mask set (the shared
        # rules scorer still wants its device-resident dummy)
        self.cat_masks = np.zeros((0, max(len(item_dict), 1)), bool)

    def __getstate__(self):
        return {"items": self.item_dict.to_state(), "idx": self.comp_idx,
                "lift": self.comp_lift}

    def __setstate__(self, s):
        self.item_dict = IdDict.from_state(s["items"])
        self.comp_idx = s["idx"]
        self.comp_lift = s["lift"]
        self.cat_masks = np.zeros((0, max(len(self.item_dict), 1)), bool)

    def tables_device(self):
        return self._device("_tab_dev", lambda: (
            jax.device_put(jnp.asarray(self.comp_idx)),
            jax.device_put(jnp.asarray(
                np.where(np.isfinite(self.comp_lift), self.comp_lift, 0.0)
                .astype(np.float32)))))

    def warm(self) -> None:
        if len(self.item_dict):
            self.tables_device()


class CPAlgorithm(Algorithm):
    params_class = CPAlgorithmParams

    def train(self, td: CPTrainingData) -> CPModel:
        p = self.params
        n_items = len(td.item_dict)
        window_us = int(basket_window_seconds(p, td) * 1e6)
        with span("layout", events=len(td.user_idx)) as rec:
            basket_idx, item_idx, n_baskets = cco_ops.session_baskets(
                td.user_idx, td.item_idx, td.times_us, window_us)
            rec["attrs"]["baskets_formed"] = n_baskets
        if n_items == 0 or n_baskets == 0:
            k = max(p.max_num_rules_per_cond, 1)
            return CPModel(td.item_dict,
                           np.full((n_items, k), -1, np.int32),
                           np.full((n_items, k), -np.inf, np.float32))
        lift, idx, _conf = cco_ops.basket_rules(
            basket_idx, item_idx, n_baskets, n_items,
            top_k=p.max_num_rules_per_cond,
            min_support=p.min_support, min_confidence=p.min_confidence,
            min_lift=p.min_lift, min_basket_size=p.min_basket_size,
            item_tile=p.item_tile)
        return CPModel(td.item_dict, idx, lift)

    def warm(self, model: CPModel) -> None:
        model.warm()

    def predict(self, model: CPModel, query: CPQuery) -> PredictedResult:
        n_items = len(model.item_dict)
        if n_items == 0:
            return PredictedResult([])
        cart = [model.item_dict.id(i) for i in query.items]
        cart = [c for c in cart if c is not None]
        if not cart:
            return PredictedResult([])
        idx_dev, lift_dev = model.tables_device()
        q_pad = als_ops.pad_ids(cart)
        # aggregate lift over the cart items (device gather+scatter), then
        # top-k excluding the cart itself — ONE stacked readback
        scores = _indicator_scatter_scores(idx_dev, lift_dev, jnp.asarray(q_pad))
        num = min(query.num, n_items)
        k = min(als_ops.bucket_width(num), n_items)
        out = np.asarray(als_ops.scores_rules_topk(
            scores, model.cat_masks_device(), als_ops.pad_ids([]),
            als_ops.pad_ids([]), als_ops.pad_ids(np.asarray(cart, np.int32)), k))
        st, si = out[0], out[1].astype(np.int32)
        return PredictedResult(
            [ItemScore(model.item_dict.str(int(j)), float(s))
             for s, j in zip(st[:num], si[:num])
             if np.isfinite(s) and s > 0])

    def serve_batch_predict(self, model: CPModel, queries):
        """Micro-batch serving: every cart's rule aggregation + top-k in
        ONE device program and one [B, 2, k] readback; empty/unresolvable
        carts answer host-side like predict."""
        n_items = len(model.item_dict)
        results = [None] * len(queries)
        live, carts = [], []
        for qi, query in enumerate(queries):
            cart = [model.item_dict.id(i) for i in query.items]
            cart = [c for c in cart if c is not None]
            if n_items == 0 or not cart:
                results[qi] = PredictedResult([])
            else:
                live.append(qi)
                carts.append(cart)
        if not live:
            return results
        bp = als_ops.bucket_width(len(live), min_width=1)
        qm = als_ops.pad_id_rows(carts + [[]] * (bp - len(live)))
        idx_dev, lift_dev = model.tables_device()
        scores = als_ops.indicator_scatter_scores_batch(
            idx_dev, lift_dev, jnp.asarray(qm))
        nums = [min(queries[i].num, n_items) for i in live]
        k = min(als_ops.bucket_width(max(nums)), n_items)
        none = np.full((bp, 16), -1, np.int32)
        out = np.asarray(als_ops.scores_rules_topk_batch(
            scores, model.cat_masks_device(), jnp.asarray(none),
            jnp.asarray(none), jnp.asarray(qm), k))
        for r, qi in enumerate(live):
            st = out[r, 0]
            si = out[r, 1].astype(np.int32)
            n = nums[r]
            results[qi] = PredictedResult(
                [ItemScore(model.item_dict.str(int(j)), float(s))
                 for s, j in zip(st[:n], si[:n])
                 if np.isfinite(s) and s > 0])
        return results


class ComplementaryPurchaseEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source_class=CPDataSource,
            preparator_class=CPPreparator,
            algorithm_classes={"rules": CPAlgorithm},
            serving_class=FirstServing,
        )

    query_class = CPQuery
