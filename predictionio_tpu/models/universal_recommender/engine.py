"""Universal Recommender engine template (CCO).

Capability parity with ActionML's UR (repo actionml/universal-recommender:
URAlgorithm.scala / URModel.scala / EsClient.scala, per SURVEY.md §2): the
reference computes LLR-thresholded cross-occurrence indicators with
Mahout-Samsara on Spark and serves by sending the user's recent history as an
Elasticsearch boolean-OR query over indicator fields, with business rules,
blacklists and a popularity fallback.

TPU-native redesign (SURVEY.md §7.5): indicators come from
``predictionio_tpu.ops.cco`` (blocked MXU matmuls + LLR + top-k on device);
serving replaces Elasticsearch with a resident jitted scorer — the user's
history becomes a multi-hot vector per indicator type and scoring is one
gather+reduce over the [n_items, top_k] indicator table.

Wire format (UR):
  query    {"user": "u1", "num": 10}
           {"item": "i1"}                              (item-similarity)
           {"user": "u1", "fields": [{"name": "category",
             "values": ["phones"], "bias": -1}],        (-1 filter, >0 boost)
            "blacklistItems": ["i3"]}
  response {"itemScores": [{"item": "i5", "score": 2.1}, ...]}
"""

from __future__ import annotations

import dataclasses
import math
import os as _os
import threading as _threading
import time as _time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

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
from predictionio_tpu.models.common import (
    LRUCache,
    gather_csr_rows,
    host_topk_desc,
)
from predictionio_tpu.native import core as _ncore
from predictionio_tpu.obs import metrics as _obs_metrics
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.obs import tracing as _tracing
from predictionio_tpu.ops import cco as cco_ops
from predictionio_tpu.ops.als import (
    bucket_width,
    check_f32_id_range,
    pad_ids as als_pad_ids,
)
from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh
from predictionio_tpu.serve import history_cache as _history_cache
from predictionio_tpu.serve import response_cache as _resp_cache
from predictionio_tpu.store.columnar import CSRLookup, IdDict, fold_properties
from predictionio_tpu.store.event_store import LEventStore, PEventStore

# -- serving instruments (obs registry; linted by check_metrics_names) -------

_REG = _obs_metrics.get_registry()
_M_STAGE = _REG.histogram(
    "pio_ur_serve_stage_duration_seconds",
    "UR serve-tail stage wall time by stage (history/score/mask/topk/"
    "assemble) and resolved tail (host/device)")
_M_MASK_CACHE = _REG.counter(
    "pio_ur_rule_mask_cache_total",
    "Composed business-rule mask cache lookups by outcome "
    "(hit/miss/evict); one entry per (model generation, canonical rule "
    "set, tail)")
_M_SERVE_CACHE = _REG.counter(
    "pio_ur_serve_cache_total",
    "Serving lookup-cache events by cache (value_mask/date) and outcome "
    "(hit/miss/evict)")
_M_INV_BUILD = _REG.gauge(
    "pio_ur_host_inverted_build_seconds",
    "Wall seconds spent building the host inverted postings index, by "
    "event type (set once per model load)")
_M_INV_BYTES = _REG.gauge(
    "pio_ur_host_inverted_bytes",
    "Resident bytes of the host inverted postings index (CSR indptr + "
    "rows + weights), by event type (set once per build) — the memory "
    "the candidate-pruned serve path keeps hot per million-item catalog")
_M_CAND = _REG.counter(
    "pio_ur_serve_candidate_total",
    "Candidate-pruned host-tail decisions by outcome: pruned (served "
    "from the posting-union candidate set), fallback_no_candidates "
    "(cold user / empty postings -> dense tail), "
    "fallback_backfill_reorder (boost mask + backfill shortfall -> "
    "dense tail), fallback_backfill_scan (rare-match rule blew the "
    "backfill scan budget -> dense tail)")
_M_CAND_FRAC = _REG.histogram(
    "pio_ur_serve_candidate_frac",
    "Fraction of the catalog a candidate-pruned query touched "
    "(|candidates| / n_items); the lever that keeps serve p50 flat as "
    "the catalog grows",
    buckets=(1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 0.01, 0.03,
             0.1, 0.3, 1.0))


def _cache_event(cache: str):
    def on_event(outcome: str) -> None:
        _M_SERVE_CACHE.inc(1, cache=cache, outcome=outcome)
    return on_event


def _mask_cache_event(outcome: str) -> None:
    _M_MASK_CACHE.inc(1, outcome=outcome)


# guards creation of the PER-EVENT-TYPE build locks only (never held
# across a build): inversions of different event types proceed in
# parallel — warm() builds them on one thread each — while two
# concurrent first queries of the SAME type still share one argsort
# (double-checked per-name lock)
_HOST_INV_LOCK = _threading.Lock()


# -- query / result ----------------------------------------------------------


def _iso_ts(v) -> Optional[float]:
    """Date value → epoch seconds via the event pipeline's own coercion
    (events.event.parse_time: ISO-8601 string, numeric epoch, or datetime;
    naive treated as UTC); None if unparseable.

    Unlike raw parse_time, None and booleans return None here — parse_time
    maps None to "now" and bool is an int subclass, either of which would
    turn a malformed query date into a silently wrong hard filter."""
    from predictionio_tpu.events.event import parse_time

    if v is None or isinstance(v, bool):
        return None
    try:
        return parse_time(v).timestamp()
    except (ValueError, OSError, OverflowError):
        return None


def _query_ts(v, field: str) -> float:
    """Strict variant for query-supplied dates: malformed input rejects the
    query (the server maps ValueError to HTTP 400) instead of silently
    disabling a hard filter."""
    ts = _iso_ts(v)
    if ts is None:
        raise ValueError(f"{field}: {v!r} is not an ISO-8601 date")
    return ts


@dataclasses.dataclass
class FieldRule:
    name: str
    values: List[str]
    bias: float  # -1 => hard filter; >0 => multiplicative boost

    @classmethod
    def from_json(cls, d: Dict) -> "FieldRule":
        return cls(name=str(d["name"]), values=[str(v) for v in d["values"]],
                   bias=float(d.get("bias", 1.0)))


@dataclasses.dataclass
class DateRange:
    """Hard filter on an item date property (reference UR: query dateRange
    with name/before/after ISO-8601 bounds)."""

    name: str
    after: Optional[str] = None    # keep items with prop >= after
    before: Optional[str] = None   # keep items with prop <= before

    @classmethod
    def from_json(cls, d: Dict) -> "DateRange":
        return cls(name=str(d["name"]),
                   after=d.get("after"), before=d.get("before"))


@dataclasses.dataclass
class URQuery:
    user: Optional[str] = None
    item: Optional[str] = None
    # shopping-cart style: recommend for a SET of items (reference UR
    # itemSet queries — wishlist/cart complements)
    item_set: List[str] = dataclasses.field(default_factory=list)
    num: int = 20
    fields: List[FieldRule] = dataclasses.field(default_factory=list)
    blacklist_items: List[str] = dataclasses.field(default_factory=list)
    return_self: bool = False
    date_range: Optional[DateRange] = None
    # "now" for availableDateName/expireDateName checks; ISO-8601
    # (reference UR: currentDate query field)
    current_date: Optional[str] = None

    def __post_init__(self):
        self.fields = [
            f if isinstance(f, FieldRule) else FieldRule.from_json(f) for f in self.fields
        ]
        if self.date_range is not None and not isinstance(self.date_range, DateRange):
            self.date_range = DateRange.from_json(self.date_range)

    @classmethod
    def from_json(cls, d: Dict) -> "URQuery":
        return cls(
            user=str(d["user"]) if d.get("user") is not None else None,
            item=str(d["item"]) if d.get("item") is not None else None,
            item_set=[str(i) for i in d.get("itemSet", [])],
            num=int(d.get("num", 20)),
            fields=[FieldRule.from_json(f) for f in d.get("fields", [])],
            blacklist_items=[str(b) for b in d.get("blacklistItems", [])],
            return_self=bool(d.get("returnSelf", False)),
            date_range=DateRange.from_json(d["dateRange"]) if d.get("dateRange") else None,
            current_date=d.get("currentDate"),
        )


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float

    def to_json(self) -> Dict:
        return {"item": self.item, "score": self.score}


@dataclasses.dataclass
class URResult:
    item_scores: List[ItemScore]

    def to_json(self) -> Dict:
        return {"itemScores": [s.to_json() for s in self.item_scores]}


# -- DASE: data source -------------------------------------------------------


@dataclasses.dataclass
class URDataSourceParams(Params):
    app_name: str = "default"
    event_names: List[str] = dataclasses.field(default_factory=lambda: ["purchase", "view"])
    item_entity_type: str = "item"
    # offline evaluation (`pio eval`): leave-one-out — hold out each
    # qualifying user's LAST primary event; 0 disables, else caps how many
    # users are evaluated
    eval_users: int = 0
    eval_num: int = 10
    eval_seed: int = 0  # seeds the holdout-user sample when eval_users caps


@dataclasses.dataclass
class URTrainingData:
    """Per-event-type COO with a shared user dictionary.

    interactions[event_name] = (user_idx, item_idx, item_dict, times); the
    primary event is event_names[0] and defines the recommendable item
    space; ``times`` is epoch seconds per event (feeds the PopModel
    backfill windows).
    """

    event_names: List[str]
    user_dict: IdDict
    interactions: Dict[str, Tuple[np.ndarray, np.ndarray, IdDict, np.ndarray]]
    item_properties: Dict[str, Dict[str, Any]]  # item id -> property map

    def __len__(self) -> int:
        """Interaction events read, over all event types."""
        return sum(len(v[0]) for v in self.interactions.values())


class URDataSource(DataSource):
    params_class = URDataSourceParams

    def read_training(self) -> URTrainingData:
        """One columnar batch read for ALL event types (native C++ scan on
        segment-file backends — no per-event Python loop), then vectorized
        per-type dictionary translation."""
        user_dict = IdDict()
        interactions: Dict[str, Tuple[np.ndarray, np.ndarray, IdDict, np.ndarray]] = {}
        # ONE scan serves both the interaction columns and the $set folds —
        # the old batch() + aggregate_properties() pair re-scanned the same
        # segments twice, a measured 2x on read_training wall time (the
        # translate loops below are ~5% of it)
        full = PEventStore.native_batch(self.params.app_name)
        if full is not None and full.prop_columns is not None:
            # interactions never read property columns; dropping them
            # BEFORE select_events keeps subset() from remapping every
            # column
            batch = dataclasses.replace(
                full, prop_columns=None).select_events(
                    list(self.params.event_names))
            props = fold_properties(full, self.params.item_entity_type)
        else:
            batch = PEventStore.batch(
                self.params.app_name,
                event_names=list(self.params.event_names))
            batch = dataclasses.replace(batch, prop_columns=None)
            props = PEventStore.aggregate_properties(
                self.params.app_name, self.params.item_entity_type)
        # entity codes → one global user id space.  Only codes REFERENCED by
        # interaction rows enroll (the scan's shared entity_dict also holds
        # $set item ids etc.; enrolling those would inflate n_users and
        # corrupt the LLR population total).
        user_of_code = np.full(max(len(batch.entity_dict), 1), -1, np.int32)
        for name in self.params.event_names:
            sel = batch.select_events([name])
            has_t = sel.target_ids >= 0
            for c in np.unique(sel.entity_ids[has_t]):
                if user_of_code[c] < 0:
                    user_of_code[c] = user_dict.add(batch.entity_dict.str(int(c)))
            t_codes = sel.target_ids[has_t]
            uniq = np.unique(t_codes)
            item_dict = IdDict(
                [batch.target_dict.str(int(c)) for c in uniq])
            local_of_target = np.full(max(len(batch.target_dict), 1), -1, np.int32)
            local_of_target[uniq] = np.arange(len(uniq), dtype=np.int32)
            interactions[name] = (
                user_of_code[sel.entity_ids[has_t]].astype(np.int32),
                local_of_target[t_codes].astype(np.int32),
                item_dict,
                sel.times_us[has_t].astype(np.float64) / 1e6,
            )
        return URTrainingData(
            event_names=list(self.params.event_names),
            user_dict=user_dict,
            interactions=interactions,
            item_properties={k: dict(v) for k, v in props.items()},
        )


    def read_eval(self):
        """Leave-one-out evaluation folds: each qualifying user's LAST
        primary event (by eventTime) is held out; training sees the rest.
        The reference UR ships no evaluation at all — this wires the
        flagship template into the framework's `pio eval` workflow with
        the standard implicit-feedback protocol."""
        if self.params.eval_users <= 0:
            return []
        td = self.read_training()
        primary = td.event_names[0]
        u, i, item_dict, times = td.interactions[primary]
        if len(u) == 0:
            return []
        order = np.lexsort((times, u))     # by user, then time
        us, is_, ts_ = u[order], i[order], times[order]
        last_of_user = np.flatnonzero(
            np.concatenate((us[1:] != us[:-1], [True])))
        counts = np.bincount(us, minlength=0)
        holdout_rows = last_of_user[counts[us[last_of_user]] >= 2]
        # sample (not first-N) when capping: stores are commonly sorted by
        # entity id, so taking qualifying users in array order would bias a
        # grid search toward whichever users sort first
        rng = np.random.default_rng(self.params.eval_seed)
        holdout_rows = rng.permutation(holdout_rows)[: self.params.eval_users]
        drop = np.zeros(len(us), bool)
        drop[holdout_rows] = True
        interactions = dict(td.interactions)
        interactions[primary] = (us[~drop], is_[~drop], item_dict, ts_[~drop])
        fold_td = URTrainingData(
            event_names=td.event_names,
            user_dict=td.user_dict,
            interactions=interactions,
            item_properties=td.item_properties,
        )
        qa = [
            (URQuery(user=td.user_dict.str(int(us[r])), num=self.params.eval_num),
             item_dict.str(int(is_[r])))
            for r in holdout_rows
        ]
        return [(fold_td, {"fold": "leave-one-out"}, qa)]


class _RankMetric:
    """Base for rank metrics over URResult predictions with a single
    held-out relevant item (the leave-one-out protocol of read_eval).
    Subclasses score one ranked list by the 0-based rank of the actual
    item, or None when it is absent."""

    higher_is_better = True

    def header(self) -> str:
        raise NotImplementedError   # subclasses name themselves

    def score_rank(self, rank) -> float:
        raise NotImplementedError

    def calculate(self, eval_data) -> float:
        total = 0
        score = 0.0
        for _info, qpa in eval_data:
            for _q, p, actual in qpa:
                total += 1
                rank = next((r for r, s in enumerate(p.item_scores)
                             if s.item == actual), None)
                score += self.score_rank(rank)
        return score / total if total else 0.0

    def compare(self, a: float, b: float) -> int:
        return 0 if a == b else (1 if a > b else -1)


class HitRateMetric(_RankMetric):
    """hit@num: fraction of held-out items anywhere in the result list."""

    def header(self) -> str:
        return "HitRate"

    def score_rank(self, rank) -> float:
        return 1.0 if rank is not None else 0.0


class NDCGMetric(_RankMetric):
    """NDCG@num with one relevant item: 1/log2(rank+2), 0 on a miss —
    the ideal DCG is 1, so no normalization divisor is needed."""

    def header(self) -> str:
        return "NDCG"

    def score_rank(self, rank) -> float:
        return 1.0 / math.log2(rank + 2) if rank is not None else 0.0


class PrecisionAtKMetric(_RankMetric):
    """precision@k with one relevant item: 1/k when the item ranks in the
    top k, else 0 (reference e2 evaluation's precision family)."""

    def __init__(self, k: int = 10):
        self.k = k

    def header(self) -> str:
        return f"Precision@{self.k}"

    def score_rank(self, rank) -> float:
        return 1.0 / self.k if rank is not None and rank < self.k else 0.0


class MRRMetric(_RankMetric):
    """Mean reciprocal rank: 1/(rank+1), 0 on a miss."""

    def header(self) -> str:
        return "MRR"

    def score_rank(self, rank) -> float:
        return 1.0 / (rank + 1) if rank is not None else 0.0


class URPreparator(Preparator):
    """Identity — dedup/blocking happens in the algorithm where the mesh
    shape is known (reference URPreparator builds Mahout IndexedDatasets)."""

    def prepare(self, td: URTrainingData) -> URTrainingData:
        return td


def _rule_mask_cache_max() -> int:
    """PIO_UR_RULE_MASK_CACHE bounds the composed rule-mask LRU per model
    generation × tail kind (default 128 canonical rule sets; each cached
    mask is an n_items f32 vector — 400 KB at a 100k catalog, so the
    default caps the cache at ~50 MB of host RAM or device HBM)."""
    try:
        return max(int(_os.environ.get("PIO_UR_RULE_MASK_CACHE", "128")), 1)
    except ValueError:
        return 128


# -- model -------------------------------------------------------------------


class URModel(PersistentModel):
    """Indicator tables per event type + popularity + item properties.

    For event type t: ``indicator_idx[t]`` [I_p, K] holds correlated item ids
    in t's item space (-1 padding), ``indicator_llr[t]`` the LLR strengths.
    ``user_seen`` is a CSR lookup (user → primary items) — flat arrays, so
    the model blob stays sub-linear in users.
    """

    def __init__(
        self,
        primary_event: str,
        item_dict: IdDict,
        user_dict: IdDict,
        indicator_idx: Dict[str, np.ndarray],
        indicator_llr: Dict[str, np.ndarray],
        event_item_dicts: Dict[str, IdDict],
        popularity: np.ndarray,
        item_properties: Dict[str, Dict[str, Any]],
        user_seen: CSRLookup,
        user_seen_by_event: Optional[Dict[str, CSRLookup]] = None,
    ):
        self.primary_event = primary_event
        self.item_dict = item_dict
        self.user_dict = user_dict
        self.indicator_idx = indicator_idx
        self.indicator_llr = indicator_llr
        self.event_item_dicts = event_item_dicts
        self.popularity = popularity
        self.item_properties = item_properties
        self.user_seen = user_seen
        # non-primary blacklist_events: user → seen items mapped into the
        # PRIMARY item space (reference UR blacklists from every configured
        # event type, not just the conversion event)
        self.user_seen_by_event = user_seen_by_event or {}

    def __getstate__(self):
        return {
            "primary_event": self.primary_event,
            "items": self.item_dict.to_state(),
            "users": self.user_dict.to_state(),
            "indicator_idx": self.indicator_idx,
            "indicator_llr": self.indicator_llr,
            "event_items": {k: d.to_state() for k, d in self.event_item_dicts.items()},
            "popularity": self.popularity,
            "item_properties": self.item_properties,
            "user_seen": self.user_seen.to_state(),
            "user_seen_by_event": {
                k: c.to_state() for k, c in self.user_seen_by_event.items()},
        }

    def __setstate__(self, s):
        self.primary_event = s["primary_event"]
        self.item_dict = IdDict.from_state(s["items"])
        self.user_dict = IdDict.from_state(s["users"])
        self.indicator_idx = s["indicator_idx"]
        self.indicator_llr = s["indicator_llr"]
        self.event_item_dicts = {k: IdDict.from_state(v) for k, v in s["event_items"].items()}
        self.popularity = s["popularity"]
        self.item_properties = s["item_properties"]
        self.user_seen = CSRLookup.from_state(s["user_seen"])
        self.user_seen_by_event = {
            k: CSRLookup.from_state(v)
            for k, v in s.get("user_seen_by_event", {}).items()}

    def device_indicators(self) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]:
        """Indicator tables staged to device ONCE per load/reload and cached
        on the instance (never serialized; rebuilt lazily after unpickle).
        Serving must not re-upload the model per query — at 100k items ×
        top-50 an indicator table is ~20 MB per event type."""
        dev = self.__dict__.get("_dev_indicators")
        if dev is None:
            dev = {
                name: (
                    jax.device_put(jnp.asarray(self.indicator_idx[name])),
                    jax.device_put(jnp.asarray(self.indicator_llr[name])),
                )
                for name in self.indicator_idx
            }
            self.__dict__["_dev_indicators"] = dev
        return dev

    def host_inverted(self, name: str) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
        """CSR inversion of one event type's indicator table, keyed by
        TARGET item id: ``(indptr [n_t+1], rows [nnz], weights [nnz])``
        where rows are the primary items listing target t as a correlator.
        Lazily built and cached (never serialized — derived data).

        Why: the device scorer gathers the history multi-hot at every
        [I_p, K] table cell — ideal for the VPU, but ~5M random gathers
        per event type on CPU (~6 ms/query at 100k items).  The inversion
        turns a query into |hist| posting-list slices and ~|hist|·K/I_t·I_p
        scatter-adds — microseconds of host work."""
        cache = self.__dict__.setdefault("_host_inv", {})
        hit = cache.get(name)
        if hit is not None:
            return hit
        # build ONCE under a PER-NAME lock: two concurrent first queries
        # of the same type share one argsort/bincount build (the loser
        # of the race reuses the winner's arrays), while DIFFERENT event
        # types build concurrently — warm() fans the types out across
        # threads, so a two-type model inverts in the time of the
        # slower table
        with _HOST_INV_LOCK:
            locks = self.__dict__.setdefault("_host_inv_locks", {})
            lock = locks.get(name)
            if lock is None:
                lock = locks[name] = _threading.Lock()
        with lock:
            hit = cache.get(name)
            if hit is not None:
                return hit
            t0 = _time.perf_counter()
            idx, llr = self.indicator_idx[name], self.indicator_llr[name]
            if idx.ndim != 2:
                # degenerate table (no [I_p, K] shape to invert): an empty
                # CSR — every posting list empty — not the old (0, 0)
                # fallback, whose arange(0) rows were then boolean-indexed
                # with the FULL idx length (IndexError for any non-empty
                # non-2D input)
                n_t = max(len(self.event_item_dicts[name]), 1)
                built = (np.zeros(n_t + 1, dtype=np.int64),
                         np.zeros(0, dtype=np.int32),
                         np.zeros(0, dtype=np.float32))
            else:
                i_p, k = idx.shape
                valid = idx >= 0
                rows = np.repeat(
                    np.arange(i_p, dtype=np.int32), k)[valid.ravel()]
                tgt = idx[valid]
                w = llr[valid].astype(np.float32)
                order = np.argsort(tgt, kind="stable")
                tgt, rows, w = tgt[order], rows[order], w[order]
                n_t = max(len(self.event_item_dicts[name]), 1)
                indptr = np.concatenate(
                    [[0], np.cumsum(np.bincount(tgt, minlength=n_t))]
                ).astype(np.int64)
                built = (indptr, rows, w)
            cache[name] = built
            _M_INV_BUILD.set(_time.perf_counter() - t0, event=name)
            _M_INV_BYTES.set(
                sum(int(a.nbytes) for a in built), event=name)
            return built

    def warm(self) -> None:
        # stage only what the resolved scorer AND tail will read: the
        # device tables are the model's largest arrays (~80 MB at 100k
        # items × 2 event types) and the host scorer never touches them —
        # and vice versa, the CSR inversion is an argsort over ~I_p·K
        # entries per event type that must not stall the first query's
        # micro-batch leader.  Both stay lazy, so a runtime scorer/tail
        # switch still works — it just pays its build on first use.
        if _serve_scorer() == "host":
            names = list(self.indicator_idx)
            # one thread per extra event type: the per-name build locks
            # let the CSR inversions run concurrently (argsort releases
            # the GIL on large arrays), so warm() pays for the slowest
            # table instead of the sum.  Thread failures re-raise HERE:
            # a build that cannot complete (OOM on a huge CSR, corrupt
            # table) must fail deploy-time warm-up, not the first
            # serving query
            errors: List[BaseException] = []

            def build(n: str) -> None:
                try:
                    self.host_inverted(n)
                except BaseException as e:
                    errors.append(e)

            extra = [
                _threading.Thread(target=build, args=(n,), daemon=True)
                for n in names[1:]
            ]
            for t in extra:
                t.start()
            # the main-thread build goes through the same collector, so
            # a failure still JOINS the siblings first — deploy unwind
            # must not race half-built threads mutating the model
            if names:
                build(names[0])
            for t in extra:
                t.join()
            if errors:
                raise errors[0]
        else:
            self.device_indicators()
        if _serve_tail() == "host":
            self.host_popularity()
            self.host_zeros()
            if _serve_candidates() == "on":
                self.host_pop_order()
        else:
            self.device_popularity()
            self.device_ones()
            self.device_zeros()
        self.pop_norm()

    def ensure_host_serving_state(self) -> None:
        """Materialize every host-side derived serving structure —
        the CSR postings inversions, the popularity total order, the
        f32 popularity view and its norm — regardless of how the
        scorer/tail env would resolve in THIS process.  The model-plane
        publisher calls this before serializing a generation so the
        mapping workers never rebuild derived state: the publisher pays
        the one build (or the fold engine's incremental patch) per
        node."""
        for name in self.indicator_idx:
            self.host_inverted(name)
        self.host_popularity()
        self.host_pop_order()
        self.pop_norm()

    def pop_norm(self) -> float:
        norm = self.__dict__.get("_pop_norm")
        if norm is None:
            norm = max(float(np.abs(self.popularity).max()), 1.0) \
                if len(self.popularity) else 1.0
            self.__dict__["_pop_norm"] = norm
        return norm

    # -- device-resident serving state (lazily cached, never serialized) ----

    def device_popularity(self) -> jnp.ndarray:
        dev = self.__dict__.get("_dev_pop")
        if dev is None:
            dev = jax.device_put(jnp.asarray(self.popularity, jnp.float32))
            self.__dict__["_dev_pop"] = dev
        return dev

    def device_ones(self) -> jnp.ndarray:
        dev = self.__dict__.get("_dev_ones")
        if dev is None:
            dev = jax.device_put(jnp.ones(len(self.item_dict), jnp.float32))
            self.__dict__["_dev_ones"] = dev
        return dev

    def device_zeros(self) -> jnp.ndarray:
        dev = self.__dict__.get("_dev_zeros")
        if dev is None:
            dev = jax.device_put(jnp.zeros(len(self.item_dict), jnp.float32))
            self.__dict__["_dev_zeros"] = dev
        return dev

    # -- host-resident serving state (the zero-dispatch serve tail) ---------

    def host_popularity(self) -> np.ndarray:
        """float32 backfill scores on host — same values device_popularity
        stages (both cast the stored array to f32), so the two tails rank
        the fallback identically."""
        pop = self.__dict__.get("_host_pop")
        if pop is None:
            pop = np.asarray(self.popularity, np.float32)
            self.__dict__["_host_pop"] = pop
        return pop

    def host_zeros(self) -> np.ndarray:
        """Shared read-only zero signal (callers must never mutate it —
        the host tail copies before writing exclusions)."""
        z = self.__dict__.get("_host_zeros")
        if z is None:
            z = np.zeros(len(self.item_dict), np.float32)
            self.__dict__["_host_zeros"] = z
        return z

    def host_pop_order(self) -> np.ndarray:
        """Every item id in the backfill tail's TOTAL order — popularity
        descending, id ascending on ties, exactly host_topk_desc /
        ``lax.top_k``'s order — precomputed once per model generation
        (benign build race: idempotent).  The candidate-pruned serve
        tail merges popularity backfill by walking this order and
        skipping ineligible ids, so a backfill pick costs O(num) instead
        of an [I_p] materialize + top-k per query."""
        order = self.__dict__.get("_host_pop_order")
        if order is None:
            _, order = host_topk_desc(self.host_popularity(),
                                      len(self.item_dict))
            self.__dict__["_host_pop_order"] = order
        return order

    _VALUE_MASK_CACHE_MAX = 512
    _DATE_CACHE_MAX = 512

    def _lru(self, attr: str, max_entries: int, metric_cache: str) -> LRUCache:
        cache = self.__dict__.get(attr)
        if cache is None:
            # dict.setdefault is atomic under the GIL: racing creators
            # both construct, one instance wins, both use it
            cache = self.__dict__.setdefault(
                attr, LRUCache(max_entries, on_event=_cache_event(metric_cache)
                               if metric_cache != "rule_mask"
                               else _mask_cache_event))
        return cache

    def rule_mask_cache(self, kind: str) -> LRUCache:
        """Composed business-rule masks, one LRU per (model generation,
        tail kind).  Living in ``__dict__`` (never pickled) means a
        hot-swap/auto-reload — which loads a NEW model object — starts
        from an empty cache... UNLESS swap provenance proves the mask
        inputs untouched, in which case :meth:`adopt_rule_caches`
        carries the LRU objects to the new generation."""
        return self._lru(f"_rule_mask_{kind}", _rule_mask_cache_max(),
                         "rule_mask")

    # serving caches that are pure functions of (item_dict,
    # item_properties): when a swap proves both unchanged, the LRU
    # OBJECTS carry to the new generation (values are read-only by
    # contract, the LRUs are thread-safe, and in-flight queries on the
    # old generation share them harmlessly — the entries are
    # bit-identical for both)
    _SWAP_CARRY_ATTRS = ("_rule_mask_host", "_rule_mask_device",
                         "_host_value_mask", "_dev_value_mask",
                         "_date_off", "_dev_date")

    def adopt_rule_caches(self, prev: "URModel", carry: bool) -> None:
        """Swap-survival for the PR-4 rule caches: composed rule masks,
        value-mask bitsets and date offsets/arrays depend ONLY on the
        item dictionary and item properties, so a generation swap whose
        provenance proves both untouched (fold: same catalog + props
        carried by object; plane: item crc + propsCrc equal) keeps every
        entry hot instead of flushing wholesale at fold-tick rates.
        ``carry=False`` records the flush that used to be silent —
        carried vs dropped land in pio_ur_rule_mask_cache_total."""
        n_rules = 0
        for attr in ("_rule_mask_host", "_rule_mask_device"):
            c = prev.__dict__.get(attr)
            if c is not None:
                n_rules += len(c)
        if not carry:
            if n_rules:
                _M_MASK_CACHE.inc(n_rules, outcome="dropped")
            return
        for attr in self._SWAP_CARRY_ATTRS:
            c = prev.__dict__.get(attr)
            if c is not None:
                self.__dict__.setdefault(attr, c)
        if n_rules:
            _M_MASK_CACHE.inc(n_rules, outcome="carried")

    def known_prop_names(self) -> frozenset:
        """Property names that exist on at least one item — the gate that
        keeps query-supplied field/date names from triggering O(n_items)
        index builds or device-array caching for properties that cannot
        match anything (ES semantics: a filter on a nonexistent field
        matches no documents)."""
        names = self.__dict__.get("_known_prop_names")
        if names is None:
            names = frozenset(
                k for props in self.item_properties.values() for k in props)
            self.__dict__["_known_prop_names"] = names
        return names

    def _value_mask_ids(self, name: str, value: str) -> Optional[np.ndarray]:
        """Item ids holding (name, value); None for unknown names/values
        (the match-nothing case — callers substitute their zero mask
        WITHOUT caching: query fields are user input, caching unknowns
        would let arbitrary queries pin unbounded memory)."""
        if name not in self.known_prop_names():
            return None
        return self.prop_value_index(name).get(value)

    def _ids_to_mask(self, ids: np.ndarray) -> np.ndarray:
        m = np.zeros(len(self.item_dict), np.float32)
        m[ids] = 1.0
        return m

    def host_value_mask(self, name: str, value: str) -> np.ndarray:
        """Host twin of device_value_mask; both tails derive their bitsets
        from the same _ids_to_mask build, so they match bit-for-bit.  The
        O(n_items) build runs only on a cache MISS — a hit costs the id
        lookup plus one LRU probe."""
        ids = self._value_mask_ids(name, value)
        if ids is None:
            return self.host_zeros()
        cache = self._lru("_host_value_mask", self._VALUE_MASK_CACHE_MAX,
                          "value_mask")
        return cache.get_or_build((name, value),
                                  lambda: self._ids_to_mask(ids))

    def device_value_mask(self, name: str, value: str) -> jnp.ndarray:
        """0/1 device mask of items whose property ``name`` holds ``value``
        — the Elasticsearch-filter-bitset analogue, cached per (name, value)
        so repeated business rules cost one gather-free multiply.  The
        cache is a bounded thread-safe LRU (touch-on-hit): hot values stay
        resident under concurrent serving threads instead of aging out in
        insertion order."""
        ids = self._value_mask_ids(name, value)
        if ids is None:
            return self.device_zeros()
        cache = self._lru("_dev_value_mask", self._VALUE_MASK_CACHE_MAX,
                          "value_mask_dev")
        return cache.get_or_build(
            (name, value),
            lambda: jax.device_put(jnp.asarray(self._ids_to_mask(ids))))

    def date_offsets(self, name: str) -> Optional[Tuple[float, np.ndarray]]:
        """(base_epoch_s, int32 offsets) for a date property; -1 where
        missing; None when NO item has the property (callers must treat
        that as match-nothing — and it keeps query-supplied names from
        growing the cache).  Integer seconds relative to the earliest
        value keep boundary comparisons EXACT (f32 epoch offsets would
        quantize to ~32 s over decade spans); sub-second precision is
        rounded, matching the second-granularity date semantics of the
        reference's ES range filters.  This is the ONE canonical
        computation — the device path stages exactly these offsets, so
        host and device tails agree on every boundary instant."""
        if name not in self.known_prop_names():
            return None
        cache = self._lru("_date_off", self._DATE_CACHE_MAX, "date")

        def build():
            ts = self.prop_date_array(name)
            missing = np.isnan(ts)
            finite = ts[~missing]
            base = float(finite.min()) if len(finite) else 0.0
            off = np.where(missing, -1.0, np.rint(ts - base))
            return base, np.clip(off, -1, 2**31 - 2).astype(np.int32)

        return cache.get_or_build(name, build)

    def device_date(self, name: str) -> Optional[Tuple[float, jnp.ndarray]]:
        """Device staging of date_offsets (same base, same int32 array).
        Separate metric label ("date_dev") so the offsets cache and its
        device staging don't fold into one hit-ratio series."""
        d = self.date_offsets(name)
        if d is None:
            return None
        cache = self._lru("_dev_date", self._DATE_CACHE_MAX, "date_dev")
        return cache.get_or_build(
            name, lambda: (d[0], jax.device_put(jnp.asarray(d[1]))))

    # -- serving-time property indexes (built lazily, never serialized) ----

    def prop_value_index(self, name: str) -> Dict[str, np.ndarray]:
        """value -> item ids holding it, for one property — lets field rules
        apply as a few array writes instead of a per-item Python loop."""
        cache = self.__dict__.setdefault("_prop_value_index", {})
        if name not in cache:
            idx: Dict[str, list] = {}
            for j in range(len(self.item_dict)):
                v = self.item_properties.get(self.item_dict.str(j), {}).get(name)
                if v is None:
                    continue
                for x in (v if isinstance(v, list) else [v]):
                    idx.setdefault(str(x), []).append(j)
            cache[name] = {k: np.asarray(v, np.int32) for k, v in idx.items()}
        return cache[name]

    def prop_date_array(self, name: str) -> np.ndarray:
        """Per-item epoch seconds of a date property (NaN where missing)."""
        cache = self.__dict__.setdefault("_prop_date_array", {})
        if name not in cache:
            out = np.full(len(self.item_dict), np.nan)
            for j in range(len(self.item_dict)):
                v = self.item_properties.get(self.item_dict.str(j), {}).get(name)
                if v is None:
                    continue
                ts = _iso_ts(v)  # lenient: bad item data skips, query-side is strict
                if ts is not None:
                    out[j] = ts
            cache[name] = out
        return cache[name]


@partial(jax.jit, static_argnames=("n_items_t",))
def _indicator_score_ids_batch(
    idx: jnp.ndarray,       # [I_p, K] device-resident indicator table
    llr: jnp.ndarray,       # [I_p, K] LLR strengths
    hist_ids: jnp.ndarray,  # [B, W] per-query history ids, -1 padding
    use_llr: jnp.ndarray,
    n_items_t: int,
) -> jnp.ndarray:           # [B, I_p]
    """Batched _indicator_score_ids: one device program scores a whole
    micro-batch's histories against the resident indicator table (rows
    whose history is all -1 padding score 0 everywhere, so event types
    missing for some queries need no host-side regrouping)."""
    h_valid = hist_ids >= 0
    b = hist_ids.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    hvec = jnp.zeros((b, n_items_t), jnp.float32).at[
        rows, jnp.where(h_valid, hist_ids, 0)
    ].max(h_valid.astype(jnp.float32))
    valid = idx >= 0
    matched = hvec[:, jnp.where(valid, idx, 0)] * valid    # [B, I_p, K]
    w = jnp.where(use_llr, jnp.where(valid, llr, 0.0), 1.0)
    return (matched * w).sum(-1)


@partial(jax.jit, static_argnames=("k",))
def _serve_topk_batch(signal, mask, bf, black_ids, k: int):
    """Batched _serve_topk: both top-ks for B queries in one program, ONE
    [B, 4, k] readback for the whole micro-batch instead of B of them."""
    check_f32_id_range(signal.shape[1])
    b = signal.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    valid = black_ids >= 0
    excl = jnp.zeros_like(signal).at[
        rows, jnp.where(valid, black_ids, 0)
    ].max(valid.astype(signal.dtype))
    s = jnp.where(excl > 0, -jnp.inf, signal * mask)
    st, si = jax.lax.top_k(s, k)
    bfm = jnp.where((mask > 0) & (excl <= 0), bf[None, :] * mask, -jnp.inf)
    bt, bi = jax.lax.top_k(bfm, k)
    return jnp.stack(
        [st, si.astype(jnp.float32), bt, bi.astype(jnp.float32)], axis=1)


def _serve_scorer() -> str:
    """'device' | 'host' — which history scorer serves queries.

    auto (default): host on the CPU backend (the inverted-index path is
    ~10× the gather program there — see _score_history), device
    everywhere else (the gather program keeps the [I_p] signal on the
    accelerator and ships only id lists).  PIO_UR_SERVE_SCORER forces."""
    conf = _os.environ.get("PIO_UR_SERVE_SCORER", "auto").lower()
    if conf in ("host", "device"):
        return conf
    return "host" if jax.default_backend() == "cpu" else "device"


def _serve_tail() -> str:
    """'device' | 'host' — which serve TAIL finishes queries (business-rule
    mask, blacklist, both top-ks, readback).

    auto (default): host on the CPU backend — the jax CPU tail was the
    measured 58% of predict at 100k items (two full-width lax.top_k
    programs + dispatch + readback for work argpartition does in
    microseconds), device everywhere else (on an accelerator the signal
    already lives device-side and only [4, k] crosses back).
    PIO_UR_SERVE_TAIL forces.  Both tails are exact twins: same items,
    same scores, same tie order (host_topk_desc reproduces lax.top_k)."""
    conf = _os.environ.get("PIO_UR_SERVE_TAIL", "auto").lower()
    if conf in ("host", "device"):
        return conf
    return "host" if jax.default_backend() == "cpu" else "device"


def _sorted_member(ids: np.ndarray,
                   sorted_ids: Optional[np.ndarray]) -> np.ndarray:
    """Boolean membership of ``ids`` in an ASCENDING id array via
    searchsorted — np.isin re-sorts its second argument on every call,
    which the pruned backfill walk would pay per chunk per field value;
    the prop_value_index id lists are built ascending, so the sort is
    free."""
    if sorted_ids is None or len(sorted_ids) == 0:
        return np.zeros(len(ids), bool)
    pos = np.searchsorted(sorted_ids, ids)
    np.minimum(pos, len(sorted_ids) - 1, out=pos)
    return sorted_ids[pos] == ids


def _serve_candidates() -> str:
    """'on' | 'off' — whether the host tail serves from the pruned
    posting-union candidate set instead of dense [I_p] passes.

    auto (default) and on: candidates whenever BOTH the scorer and the
    tail resolve to host (the sparse set only exists on the host side —
    the device paths keep [I_p] vectors resident where they belong);
    off forces the dense tail.  Per QUERY the pruned path still falls
    back to dense when it cannot be exact: no candidates at all (cold
    user / empty postings) or a value-boosted mask with a backfill
    shortfall — so on/auto never change responses, only cost
    (pio_ur_serve_candidate_total counts the outcomes)."""
    conf = _os.environ.get("PIO_UR_SERVE_CANDIDATES", "auto").lower()
    if conf == "off":
        return "off"
    if _serve_scorer() == "host" and _serve_tail() == "host":
        return "on"
    return "off"


@partial(jax.jit, static_argnames=("n_items_t",))
def _indicator_score_ids(
    idx: jnp.ndarray,       # [I_p, K] device-resident indicator table
    llr: jnp.ndarray,       # [I_p, K] LLR strengths
    hist_ids: jnp.ndarray,  # [W] history item ids in t-space, -1 padding
    use_llr: jnp.ndarray,
    n_items_t: int,
):
    """score[i] = Σ_k 1[idx[i,k] ∈ hist] · w[i,k].

    The history multi-hot is built ON DEVICE from a small padded id list
    (≤ max_query_events ints), so a query transfers a few hundred bytes —
    never an [n_items] vector and never the indicator table itself."""
    h_valid = hist_ids >= 0
    hvec = jnp.zeros((n_items_t,), jnp.float32).at[
        jnp.where(h_valid, hist_ids, 0)
    ].max(h_valid.astype(jnp.float32))
    valid = idx >= 0
    matched = hvec[jnp.where(valid, idx, 0)] * valid
    w = jnp.where(use_llr, jnp.where(valid, llr, 0.0), 1.0)
    return (matched * w).sum(-1)


# -- device mask composition (tiny jitted combinators; python-float biases
#    and bounds trace as 0-d weak-typed scalars, so no recompile per value) --


@jax.jit
def _m_or(a, b):
    return jnp.maximum(a, b)


@jax.jit
def _m_hard(mask, match):
    return mask * match


@jax.jit
def _m_boost(mask, match, bias):
    return mask * jnp.where(match > 0, bias, 1.0)


# date arrays are int32 second-offsets with -1 = property missing; every
# check requires presence (ES range filters match only docs with the field)


@jax.jit
def _m_present(mask, ts):
    return mask * (ts >= 0).astype(jnp.float32)


@jax.jit
def _m_ge(mask, ts, bound):
    return mask * ((ts >= bound) & (ts >= 0)).astype(jnp.float32)


@jax.jit
def _m_le(mask, ts, bound):
    return mask * ((ts <= bound) & (ts >= 0)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("k",))
def _serve_topk(signal, mask, bf, black_ids, k: int):
    """The device-final serving tail: apply business-rule mask + blacklist,
    take top-k of the signal AND top-k of the backfill eligibility in one
    program — one stacked [4, k] array crosses back to host, never an
    [n_items] vector (at 100k+ items the old full-vector download plus
    host masking/argpartition was the serving bottleneck) and never
    multiple fetches (each is its own device sync).  Index rows are exact
    in f32 below 2^24 items — enforced at trace time."""
    check_f32_id_range(signal.shape[0])
    valid = black_ids >= 0
    excl = jnp.zeros_like(signal).at[
        jnp.where(valid, black_ids, 0)
    ].max(valid.astype(signal.dtype))
    s = jnp.where(excl > 0, -jnp.inf, signal * mask)
    st, si = jax.lax.top_k(s, k)
    # backfill ranks by bf * mask so field boosts reorder the fallback list
    # exactly as they reorder signal scores; mask > 0 is the eligibility cut
    bfm = jnp.where((mask > 0) & (excl <= 0), bf * mask, -jnp.inf)
    bt, bi = jax.lax.top_k(bfm, k)
    return jnp.stack(
        [st, si.astype(jnp.float32), bt, bi.astype(jnp.float32)])


# -- algorithm ---------------------------------------------------------------


@dataclasses.dataclass
class URAlgorithmParams(Params):
    app_name: str = "default"
    event_names: List[str] = dataclasses.field(default_factory=list)  # default: data source's
    max_correlators_per_item: int = 50
    min_llr: float = 0.0
    max_query_events: int = 100
    num: int = 20
    user_block: int = 0     # 0: derived from the bytes (ops/cco._block_plan)
    item_tile: int = 4096
    mesh_dp: int = 0
    use_llr_weights: bool = False
    blacklist_events: List[str] = dataclasses.field(default_factory=list)  # default: primary
    # per-event-type tuning overrides (reference UR: indicators config),
    # e.g. {"view": {"maxCorrelatorsPerItem": 25, "minLLR": 4.0}}
    indicator_params: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    backfill_type: str = "popular"  # popular | trending | hot | none
    # PopModel window (reference UR backfillField.duration); halves/thirds
    # of this window feed trending/hot velocity and acceleration
    backfill_duration: str = "3650 days"
    # event types whose volume feeds the backfill ranking (reference UR
    # backfillField.eventNames); default: the primary event only
    backfill_event_names: List[str] = dataclasses.field(default_factory=list)
    # per-event-type indicator snapshots: a crashed/retried train resumes
    # past completed event types (reference has NO mid-training
    # checkpointing; dir defaults to PIO_CHECKPOINT_DIR/ur/<fingerprint>).
    # Enabling this runs event types sequentially (durability over the
    # host/device overlap of the one-shot path).
    checkpoint: bool = False
    checkpoint_dir: str = ""
    indicator_weights: Dict[str, float] = dataclasses.field(default_factory=dict)
    # item date properties checked against the query's currentDate
    # (reference UR: availableDateName / expireDateName engine params)
    available_date_name: str = ""
    expire_date_name: str = ""


class URAlgorithm(Algorithm):
    params_class = URAlgorithmParams
    # cap the serving micro-batch: the batched indicator scorer's
    # [B, I_p, K] gather is the transient; 16 × 100k items × 50 × 4 B
    # ≈ 320 MB worst-case, comfortable next to the resident model
    serve_batch_max = 16

    @staticmethod
    def per_type_tuning(params: URAlgorithmParams,
                        event_names: Sequence[str],
                        ) -> Dict[str, Tuple[int, float]]:
        """Per-event-type (max_correlators, min_llr) overrides parsed from
        ``indicator_params`` — shared by train() and the streaming fold
        engine so both derive the identical tuning per type."""
        per_type: Dict[str, Tuple[int, float]] = {}
        for name, over in (params.indicator_params or {}).items():
            # validate against the CONFIGURED types, not the data-dependent
            # set (a type with zero events this window is still valid)
            if name not in event_names:
                raise ValueError(
                    f"indicator_params names unknown event type {name!r}; "
                    f"configured event_names: {list(event_names)}")
            t_k = params.max_correlators_per_item
            t_llr = params.min_llr
            for key, val in over.items():
                norm = key.replace("_", "").lower()   # minLLR/minLlr/min_llr
                if norm == "maxcorrelatorsperitem":
                    t_k = int(val)
                elif norm == "minllr":
                    t_llr = float(val)
                else:
                    raise ValueError(
                        f"indicator_params[{name!r}]: unknown key {key!r} "
                        "(expected maxCorrelatorsPerItem / minLLR)")
            per_type[name] = (t_k, t_llr)
        return per_type

    def train(self, td: URTrainingData) -> URModel:
        primary = td.event_names[0]
        p_user, p_item, p_item_dict, p_times = td.interactions[primary]
        n_users = len(td.user_dict)
        n_items = len(p_item_dict)
        if n_items == 0:
            raise ValueError(f"no {primary!r} events to train on")
        blacklist_events = self.params.blacklist_events or [primary]
        unknown = [b for b in blacklist_events if b not in td.event_names]
        if unknown:
            raise ValueError(
                f"blacklist_events {unknown} not in event_names {td.event_names}")
        # a stated meshDp takes the first meshDp devices (and raises above
        # the machine's count); unstated, every device the machine shows
        dp = self.params.mesh_dp or len(jax.devices())
        mesh = create_mesh(MeshSpec(dp=dp, mp=1),
                           devices=jax.devices()[:dp]) if dp > 1 else None
        # one call for all event types: cco_train_indicators plans each
        # type's strategy, its dense and host-sparse runners stage the
        # primary once, and no host dedup runs on a device strategy (the
        # scatter-max densify is the dedup)
        others = []
        event_item_dicts: Dict[str, IdDict] = {}
        for name in td.event_names:
            u, i, item_dict, _ = td.interactions[name]
            if name != primary and len(item_dict) == 0:
                continue
            if name == primary:
                u, i = p_user, p_item  # identity → self-pair kernel reuse
            others.append((name, u, i, len(item_dict)))
            event_item_dicts[name] = item_dict
        per_type = self.per_type_tuning(self.params, td.event_names)
        common = dict(
            top_k=self.params.max_correlators_per_item,
            llr_threshold=self.params.min_llr,
            mesh=mesh,
            exclude_self_for=primary,
            user_block=self.params.user_block,
            item_tile=self.params.item_tile,
            per_type=per_type,
        )
        if self.params.checkpoint:
            results = self._train_checkpointed(
                p_user, p_item, others, n_users, n_items, common)
        else:
            results = cco_ops.cco_train_indicators(
                p_user, p_item, others, n_users, n_items, **common)
        indicator_idx: Dict[str, np.ndarray] = {}
        indicator_llr: Dict[str, np.ndarray] = {}
        for name, (scores, idx) in results.items():
            indicator_idx[name] = idx.astype(np.int32)
            indicator_llr[name] = np.where(np.isfinite(scores), scores, 0.0).astype(np.float32)
        # CSR dedups (user, item) internally
        user_seen = CSRLookup.from_pairs(p_user, p_item, n_users)
        # PopModel backfill scores over the configured event-time window
        # (raw events, not distinct pairs: popularity ranks by volume);
        # backfill_event_names widens the counted types beyond the primary
        # (reference UR backfillField.eventNames), with items translated
        # into the primary space
        from predictionio_tpu.models.universal_recommender.popmodel import (
            backfill_scores, parse_duration)

        bf_names = self.params.backfill_event_names or [primary]
        unknown_bf = [b for b in bf_names if b not in td.event_names]
        if unknown_bf:
            raise ValueError(
                f"backfill_event_names {unknown_bf} not in event_names "
                f"{td.event_names}")
        bf_items, bf_times = [], []
        for name in bf_names:
            u, i, item_dict_t, times = td.interactions[name]
            if name == primary:
                bf_items.append(p_item)
                bf_times.append(p_times)
            else:
                translate = p_item_dict.lookup_many(item_dict_t.strings())
                mapped = translate[i]
                keep = mapped >= 0
                bf_items.append(mapped[keep])
                bf_times.append(times[keep])
        popularity = backfill_scores(
            self.params.backfill_type,
            np.concatenate(bf_items) if bf_items else p_item,
            np.concatenate(bf_times) if bf_times else p_times,
            n_items,
            parse_duration(self.params.backfill_duration),
        )
        # per-event seen CSRs for non-primary blacklist_events, with items
        # translated into the primary item space
        user_seen_by_event: Dict[str, CSRLookup] = {}
        for name in blacklist_events:
            if name == primary or name not in event_item_dicts:
                continue
            u, i, item_dict, _ = td.interactions[name]
            translate = p_item_dict.lookup_many(item_dict.strings())
            mapped = translate[i]
            keep = mapped >= 0
            user_seen_by_event[name] = CSRLookup.from_pairs(
                u[keep], mapped[keep], n_users)
        return URModel(
            primary_event=primary,
            item_dict=p_item_dict,
            user_dict=td.user_dict,
            indicator_idx=indicator_idx,
            indicator_llr=indicator_llr,
            event_item_dicts=event_item_dicts,
            popularity=popularity,
            item_properties=td.item_properties,
            user_seen=user_seen,
            user_seen_by_event=user_seen_by_event,
        )

    def _train_checkpointed(self, p_user, p_item, others,
                            n_users, n_items, common):
        """One cco_train_indicators call PER event type, snapshotting each
        type's indicators — a retried train (core_workflow.run_train /
        PIO_TRAIN_RETRIES) resumes past completed types instead of
        recomputing the whole pass."""
        import hashlib
        import os

        from predictionio_tpu.utils.checkpoint import (
            CheckpointStore, maybe_inject, prune_stale_runs)

        h = hashlib.sha1()
        h.update(repr((n_users, n_items, common["top_k"],
                       common["llr_threshold"], common["per_type"])).encode())
        for name, u, i, n_t in others:
            # hash the FULL arrays: a prefix sample could collide with
            # changed data and silently resume stale snapshots (~10 ms per
            # 10M events — nothing next to a checkpointed training run)
            h.update(name.encode())
            h.update(np.asarray([len(u), n_t], np.int64).tobytes())
            h.update(np.ascontiguousarray(u).tobytes())
            h.update(np.ascontiguousarray(i).tobytes())
        base = self.params.checkpoint_dir or os.path.join(
            os.environ.get("PIO_CHECKPOINT_DIR", ".pio_checkpoints"), "ur")
        prune_stale_runs(base)
        # keep=0: every event type's snapshot must survive until the run
        # completes (steps are types, not a rolling window)
        store = CheckpointStore(os.path.join(base, h.hexdigest()[:16]), keep=0)
        done_steps = set(store.steps())
        results = {}
        for step, (name, u, i, n_t) in enumerate(others):
            if step in done_steps:
                state = store.restore(step)
                results[name] = (state["scores"], state["idx"])
                continue
            maybe_inject("ur.indicators")
            out = cco_ops.cco_train_indicators(
                p_user, p_item, [(name, u, i, n_t)], n_users, n_items,
                **common)
            results[name] = out[name]
            store.save(step, {"scores": results[name][0],
                              "idx": results[name][1]})
        store.clear(remove_dir=True)   # run complete; the dir is never reused
        return results

    # -- serving -------------------------------------------------------------

    def _user_history(self, model: URModel, user: str) -> Dict[str, np.ndarray]:
        """Recent item ids per event type, from the live event store
        (reference: URAlgorithm.predict reading LEventStore).

        The store read goes through the append-invalidated per-worker
        history cache (serve/history_cache): the cached value is the raw
        target-entity-id strings — model-independent, so it survives
        generation swaps — and the per-model ``item_dict`` mapping runs
        per query.  ``PIO_HISTORY_CACHE=off`` reads the store every time
        (the staleness oracle)."""
        hist: Dict[str, np.ndarray] = {}
        for name, item_dict in model.event_item_dicts.items():
            raw = _history_cache.user_history_targets(
                self.params.app_name, "user", user, name,
                self.params.max_query_events)
            ids = {item_dict.id(t) for t in raw}
            ids.discard(None)
            hist[name] = np.asarray(sorted(ids), np.int32)
        return hist

    @staticmethod
    def serving_placement() -> Dict[str, str]:
        """Which scorer and tail this process serves queries with."""
        return {"scorer": _serve_scorer(), "tail": _serve_tail()}

    def warm(self, model: URModel) -> None:
        model.warm()

    def _score_history(
        self, model: URModel, hist: Dict[str, np.ndarray]
    ) -> Optional[jnp.ndarray]:
        """Run the scorer over every event type's history.

        device (TPU default): the resident-table gather program — a query
        ships a few hundred bytes and the [I_p] signal never leaves the
        device for the serving tail.  host (CPU default): posting-list
        scatter-adds over the inverted indicator index (see
        URModel.host_inverted) — the gather program's ~5M random accesses
        per event type are the measured CPU serving bottleneck at 100k
        items (13 ms of a 15.6 ms p50).  PIO_UR_SERVE_SCORER overrides."""
        if _serve_scorer() == "host":
            # stays a NUMPY array: under the host tail the signal never
            # touches the device at all; the device tail uploads it
            return self._sparse_signal_dense(
                len(model.item_dict), self._score_history_host(model, hist))
        use_llr = jnp.asarray(self.params.use_llr_weights)
        total = None
        for name, (idx_dev, llr_dev) in model.device_indicators().items():
            h_ids = hist.get(name)
            if h_ids is None or len(h_ids) == 0:
                continue
            n_t = max(len(model.event_item_dicts[name]), 1)
            s = _indicator_score_ids(
                idx_dev, llr_dev, als_pad_ids(h_ids), use_llr, n_t
            )
            weight = float(self.params.indicator_weights.get(name, 1.0))
            s = s * weight if weight != 1.0 else s
            total = s if total is None else total + s
        return total

    def _score_history_host(
        self, model: URModel, hist: Dict[str, np.ndarray]
    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Inverted-index twin of the device scorer, SPARSE: returns
        ``(candidate_ids, candidate_scores)`` — the ascending unique
        union of posting-list rows across every event type's history,
        and the f32 signal at exactly those rows (every other row scores
        exactly 0.0) — or None when the history carries no event type.

        Posting segments come from ONE fancy-index of each CSR's indptr
        (gather_csr_rows — no per-history-id Python loop), and scoring
        is a weighted ``np.bincount`` over the COMPACTED candidate space
        instead of an [I_p] zeros + scatter-add, so cost scales with the
        user's posting footprint (typically a few thousand rows), not
        the catalog.  The dense signal, where a caller needs it, is an
        exact scatter of this result (_sparse_signal_dense) — one
        scoring implementation serves both tails.  Vs the device scorer
        the float32 sums may differ in the last ulp (addition order)."""
        per_type: List[Tuple[str, np.ndarray, Optional[np.ndarray]]] = []
        for name in model.indicator_idx:
            h_ids = hist.get(name)
            if h_ids is None or len(h_ids) == 0:
                continue
            indptr, rows, w = model.host_inverted(name)
            if self.params.use_llr_weights:
                cat_rows, cat_w = gather_csr_rows(indptr, h_ids, rows, w)
            else:
                (cat_rows,), cat_w = gather_csr_rows(indptr, h_ids,
                                                     rows), None
            per_type.append((name, cat_rows, cat_w))
        if not per_type:
            return None
        if _ncore.serve_enabled():
            # fully-native tail: unique + per-type compacted bincount run
            # with the GIL dropped; bit-exact vs the numpy path below
            # (same f64 accumulate order, f32 cast, f32 weight multiply,
            # f32 type-order total adds)
            try:
                cand = _ncore.unique_i32(
                    np.concatenate([r for _, r, _ in per_type]))
                scratch = np.empty(len(cand), np.float64)
                ntotal = np.empty(len(cand), np.float32)
                first = True
                for name, cat_rows, cat_w in per_type:
                    weight = float(
                        self.params.indicator_weights.get(name, 1.0))
                    _ncore.score_accum(cand, cat_rows, cat_w, weight,
                                       scratch, ntotal, first)
                    first = False
                _ncore.note_call("serve")
                return cand, ntotal
            except Exception:
                _ncore.note_fallback("error")
        cand = np.unique(
            np.concatenate([r for _, r, _ in per_type])).astype(np.int32)
        total: Optional[np.ndarray] = None
        for name, cat_rows, cat_w in per_type:
            rel = np.searchsorted(cand, cat_rows)
            if cat_w is not None:
                score = np.bincount(rel, weights=cat_w,
                                    minlength=len(cand)).astype(np.float32)
            else:
                score = np.bincount(
                    rel, minlength=len(cand)).astype(np.float32)
            weight = float(self.params.indicator_weights.get(name, 1.0))
            if weight != 1.0:
                score *= weight
            total = score if total is None else total + score
        return cand, total

    @staticmethod
    def _sparse_signal_dense(
        n_items: int, sparse: Optional[Tuple[np.ndarray, np.ndarray]]
    ) -> Optional[np.ndarray]:
        """Dense [n_items] signal from the sparse scorer's result — an
        exact scatter (rows outside the candidate set are exactly 0.0,
        which is also what the dense accumulation produced)."""
        if sparse is None:
            return None
        ids, sc = sparse
        out = np.zeros(n_items, np.float32)
        out[ids] = sc
        return out

    def batch_predict(self, model: URModel, queries) -> List[URResult]:
        """Eval-time predictions: user history comes from the MODEL's
        training interactions (user_seen), never the live event store —
        during `pio eval` the held-out events are still in the store and
        would otherwise leak into history and the seen-item blacklist."""
        out = []
        for q in queries:
            hist: Dict[str, np.ndarray] = {}
            if q.user is not None:
                uid = model.user_dict.id(q.user)
                if uid is not None:
                    row = model.user_seen.row(uid)
                    if len(row):
                        hist[model.primary_event] = row.astype(np.int32)
            out.append(self.predict(model, q, hist_override=hist))
        return out

    def predict(self, model: URModel, query: URQuery,
                hist_override: Optional[Dict[str, np.ndarray]] = None) -> URResult:
        """Serve one query through the resolved tail (_serve_tail):

        device — signal accumulation, business-rule masks, blacklist, and
        BOTH top-ks (signal + backfill) run on device; only 4 [k]-sized
        arrays and the small history/blacklist id lists cross the host
        boundary.  Query shapes are bucketed (pad_ids, k buckets) so
        every shape traces once per deployment.

        host — the whole tail is numpy: cached rule masks compose as one
        boolean/bias pass over the scores, top-k is argpartition + a
        stable tie-order sort reproducing lax.top_k exactly, ZERO device
        dispatch and zero readback when the scorer is already host-side.

        Tail-stage wall times land in pio_ur_serve_stage_duration_seconds
        and, when a span journal is active (eval/batch runs) or a request
        trace is live (the flight recorder), as a per-query ``ur_predict``
        span — under a trace the stage laps also become child spans, so
        /traces/<rid>.json shows the history→score→mask→topk→assemble
        waterfall."""
        stages: List[Tuple[str, float]] = []
        meta: Dict[str, str] = {}
        sink = _spans.active_collector()
        if sink is None:
            return self._predict_staged(model, query, hist_override, stages,
                                        meta)
        trace = _tracing.current_trace()
        with sink.span("ur_predict") as rec:
            res = self._predict_staged(model, query, hist_override, stages,
                                       meta)
            rec["attrs"] = {"tail": _serve_tail(),
                            "candidates": meta.get("candidates", "off"),
                            **{f"{n}_ms": round(dt * 1e3, 4)
                               for n, dt in stages}}
        if trace is not None:
            # laps are strictly sequential, so reconstructed offsets give
            # exact child-span boundaries without a contextmanager per
            # stage on the serve hot path
            off = rec["start"]
            for n, dt in stages:
                trace.add_span(n, off, dt, parent=rec["id"])
                off += dt
        return res

    def _predict_staged(self, model: URModel, query: URQuery,
                        hist_override, stages: List[Tuple[str, float]],
                        meta: Optional[Dict[str, str]] = None) -> URResult:
        n_items = len(model.item_dict)
        if n_items == 0:
            return URResult([])
        tail = _serve_tail()
        t = [_time.perf_counter()]

        def lap(name: str) -> None:
            now = _time.perf_counter()
            stages.append((name, now - t[0]))
            t[0] = now

        hist = self._query_hist(model, query, hist_override)
        lap("history")
        num = min(query.num, n_items)
        cand_label = "off"
        # -- provenance-invalidated response cache (serve.response_cache)
        # consulted before any scoring.  The key covers everything the
        # answer depends on (k, canonical rules, history ids, blacklist
        # ids — the latter two recomputed fresh, so user drift reroutes
        # to a new key instead of needing invalidation); a hit is
        # bit-identical to the tail by the swap-sweep proof, spot-checked
        # online every PIO_SERVE_CACHE_AUDIT_N hits.  hist_override
        # (eval's anti-leakage path) always bypasses.
        cache = _resp_cache.get_cache()
        ckey = rkey = cached_items = None
        audit = False
        if cache.armed_for(model):
            if hist_override is not None:
                cache.count_bypass()
            else:
                # strict date parsing (400 on malformed) runs in the key
                # builder, exactly as the uncached mask path would
                rkey = self._mask_rule_key(query)
                ckey = _resp_cache.make_key(
                    num, rkey, hist, self._blacklist_ids(model, query))
                cached_items, audit = cache.lookup(model, ckey)
                lap("cache")
                if cached_items is not None and not audit:
                    if meta is not None:
                        meta["candidates"] = "cache"
                    for name, dt in stages:
                        _M_STAGE.observe(dt, stage=name, tail=tail,
                                         candidates="cache")
                    return URResult([ItemScore(n, s)
                                     for n, s in cached_items])
        fill: Optional[dict] = {} if ckey is not None else None
        if tail == "host" and _serve_candidates() == "on":
            # candidate-pruned tail: the sparse scorer result feeds a
            # pruned mask/topk/backfill pass; a per-query fallback
            # (None) re-runs the dense tail on the scattered signal with
            # fresh stage laps, so mixed traffic stays exact AND
            # correctly attributed in the stage histogram
            sparse = (self._score_history_host(model, hist)
                      if hist is not None else None)
            lap("score")
            sub: List[Tuple[str, float]] = []

            def sub_lap(name: str) -> None:
                now = _time.perf_counter()
                sub.append((name, now - t[0]))
                t[0] = now

            res = self._host_tail_pruned(model, query, sparse, num, sub_lap,
                                         fill=fill)
            if res is not None:
                stages.extend(sub)
                cand_label = "on"
            else:
                t[0] = _time.perf_counter()   # discard the aborted laps
                res = self._host_tail(
                    model, query,
                    self._sparse_signal_dense(n_items, sparse), num, lap,
                    fill=fill)
        else:
            signal = (self._score_history(model, hist)
                      if hist is not None else None)
            lap("score")
            have_signal = signal is not None
            if tail == "host":
                sig_np = None if signal is None else np.asarray(signal)
                res = self._host_tail(model, query, sig_np, num, lap,
                                      fill=fill)
            else:
                res = self._device_tail(model, query, signal, have_signal,
                                        num, lap, fill=fill)
        if ckey is not None:
            self._cache_settle(cache, model, ckey, rkey, res, cached_items,
                               hist, fill, num)
        if meta is not None:
            meta["candidates"] = cand_label
        for name, dt in stages:
            _M_STAGE.observe(dt, stage=name, tail=tail,
                             candidates=cand_label)
        return res

    def _cache_settle(self, cache, model: URModel, ckey: tuple,
                      rkey: Optional[tuple], res: URResult,
                      cached_items, hist, fill: Optional[dict],
                      num: int) -> None:
        """Post-tail response-cache bookkeeping: fill after a miss, or —
        on an audited hit — compare the fresh answer bit-for-bit against
        the cached one (a mismatch means the invalidation proof broke:
        count it, full-flush, and the caller serves the FRESH result)."""
        items = tuple((r.item, float(r.score)) for r in res.item_scores)
        if cached_items is not None:
            if items != cached_items:
                cache.audit_mismatch(ckey)
            return
        used_backfill = bool((fill or {}).get("backfill")) or (
            len(items) < num and self.params.backfill_type != "none")
        cache.put(model, ckey, items, hist, (fill or {}).get("ids", ()),
                  used_backfill, rkey is not None,
                  bool(self.params.use_llr_weights))

    def _device_tail(self, model: URModel, query: URQuery, signal,
                     have_signal: bool, num: int, lap,
                     fill: Optional[dict] = None) -> URResult:
        mask = self._mask_for(model, query, host=False)
        black_ids = self._blacklist_ids(model, query)
        lap("mask")
        sig = model.device_zeros() if signal is None else jnp.asarray(signal)
        # k covers the worst case: every signal pick also occupying a
        # backfill slot; bucketed so distinct nums share compiles
        k = min(bucket_width(2 * num, 16), len(model.item_dict))
        out = np.asarray(_serve_topk(
            sig, mask if mask is not None else model.device_ones(),
            model.device_popularity(),
            jnp.asarray(als_pad_ids(black_ids)), k))  # ONE [4, k] readback
        lap("topk")
        res = self._assemble(model, num, have_signal,
                             out[0], out[1].astype(np.int32),
                             out[2], out[3].astype(np.int32), fill=fill)
        lap("assemble")
        return res

    def _host_tail(self, model: URModel, query: URQuery,
                   signal: Optional[np.ndarray], num: int,
                   lap=None, fill: Optional[dict] = None) -> URResult:
        """The zero-dispatch serve tail: same math as _serve_topk, in
        numpy, with the composed rule mask cached per canonical rule set.
        Elementwise f32 products match XLA's bit-for-bit and
        host_topk_desc reproduces lax.top_k's tie order, so this tail is
        EXACTLY the device tail's output."""
        n_items = len(model.item_dict)
        mask = self._mask_for(model, query, host=True)
        black = self._blacklist_ids(model, query)
        if lap is not None:
            lap("mask")
        k = min(bucket_width(2 * num, 16), n_items)
        bidx = np.asarray(black, np.int32) if black else None
        # signal top-k over only the POSITIVE entries: _assemble accepts a
        # signal pick only when finite and > 0, so the candidate set is
        # s > 0 minus the blacklist — typically a few thousand items of a
        # 100k catalog, and a cold query skips the pass entirely.  The
        # subset preserves index order, so (value desc, index asc) over it
        # is exactly the device tail's tie order.
        st = si = None
        if signal is not None:
            s = signal * mask if mask is not None else signal
            pos = np.flatnonzero(s > 0)
            if bidx is not None and len(pos):
                pos = pos[np.isin(pos, bidx, invert=True)]
            if len(pos):
                vals, oi = host_topk_desc(s[pos], min(k, len(pos)))
                st, si = vals, pos[oi].astype(np.int32)
        n_signal = min(len(st) if st is not None else 0, num)
        # the backfill ranking only matters when the signal picks leave
        # slots to pad — the device tail computes it unconditionally (it
        # is one fused program), the host tail just skips it
        bt = bi = None
        if n_signal < num and self.params.backfill_type != "none":
            bf = model.host_popularity()
            bfm = bf * mask if mask is not None else bf.copy()
            if mask is not None:
                bfm[mask <= 0] = -np.inf
            if bidx is not None:
                bfm[bidx] = -np.inf
            bt, bi = host_topk_desc(bfm, k)
        if lap is not None:
            lap("topk")
        empty_f = np.zeros(0, np.float32)
        empty_i = np.zeros(0, np.int32)
        res = self._assemble(
            model, num, st is not None,
            st if st is not None else empty_f,
            si if si is not None else empty_i,
            bt if bt is not None else empty_f,
            bi if bi is not None else empty_i, fill=fill)
        if lap is not None:
            lap("assemble")
        return res

    def _host_tail_pruned(self, model: URModel, query: URQuery,
                          sparse: Optional[Tuple[np.ndarray, np.ndarray]],
                          num: int, lap=None,
                          fill: Optional[dict] = None
                          ) -> Optional[URResult]:
        """Candidate-pruned host tail: mask composition, blacklist,
        signal top-k, and popularity backfill all touch ONLY the sparse
        scorer's candidate rows (plus an O(num) walk of the precomputed
        popularity order for backfill) — never an [I_p] temporary — so
        per-query cost is flat in catalog size.

        Exactness-parity with _host_tail by construction: candidate
        scores ARE the dense signal at those rows and the dense signal
        is exactly 0 elsewhere, so the dense positive set is a subset of
        the candidates; the sliced mask equals the full mask gathered
        (elementwise factors commute with the gather); candidates are
        id-ascending, so subset top-k reproduces the dense tie order;
        and the backfill merge walks host_pop_order, which IS the dense
        ``host_topk_desc(bf * mask)`` order whenever the mask is binary.

        Returns None when this query must fall back to the dense tail:
        no candidates at all (cold user / empty postings — nothing to
        prune, and backfill would still rank the whole catalog), a
        value-boosted (non-binary) mask with a backfill shortfall (where
        eligibility order is no longer the precomputed popularity
        order), or a backfill walk that blows its scan budget (a
        rare-match rule — the dense pass bounds the cost and caches the
        mask).  Fallbacks and pruned serves are counted in
        pio_ur_serve_candidate_total."""
        if sparse is None or len(sparse[0]) == 0:
            _M_CAND.inc(1, outcome="fallback_no_candidates")
            return None
        cand, sc = sparse
        n_items = len(model.item_dict)
        # strict date parsing happens in the key builder, before any
        # cache or mask work — malformed dates 400 exactly as the dense
        # tail does
        key = self._mask_rule_key(query)
        mask_at = None
        mask_c = None
        if key is not None:
            # peek, not get: this probe never populates, so counting it
            # in the hit/miss telemetry would flatline the dense cache's
            # hit ratio under pruned traffic
            full = model.rule_mask_cache("host").peek(key)
            if full is not None:
                # a dense query (or tail switch) already composed this
                # rule set: gather the per-generation cached full mask
                def mask_at(ids, _full=full):
                    return _full[ids]
            else:
                def mask_at(ids):
                    return self._mask_from_key_host_sliced(model, key, ids)
            mask_c = mask_at(cand)
        black = self._blacklist_ids(model, query)
        if lap is not None:
            lap("mask")
        k = min(bucket_width(2 * num, 16), n_items)
        s = sc * mask_c if mask_c is not None else sc
        pos = np.flatnonzero(s > 0)
        # sort the blacklist ONCE: both the signal filter here and the
        # backfill walk probe it via _sorted_member
        sb = np.sort(np.asarray(black, np.int32)) if black else None
        if sb is not None and len(pos):
            pos = pos[~_sorted_member(cand[pos], sb)]
        st = si = None
        if len(pos):
            vals, oi = host_topk_desc(s[pos], min(k, len(pos)))
            st, si = vals, cand[pos][oi].astype(np.int32)
        n_signal = min(len(st) if st is not None else 0, num)
        bt = bi = None
        if n_signal < num and self.params.backfill_type != "none":
            if key is not None and not self._mask_key_is_binary(key):
                # a boost bias scales backfill scores, so eligible-item
                # order diverges from the precomputed popularity order —
                # only the dense [I_p] top-k ranks that exactly
                _M_CAND.inc(1, outcome="fallback_backfill_reorder")
                return None
            merged = self._backfill_merge(model, mask_at, sb, k)
            if merged is None:
                # the walk blew its scan budget (a rare-match rule over a
                # big catalog): the dense tail bounds the cost at one
                # [I_p] pass AND populates the rule-mask cache, so
                # repeats of this rule set get the cached-mask gather
                _M_CAND.inc(1, outcome="fallback_backfill_scan")
                return None
            bt, bi = merged
        if lap is not None:
            lap("topk")
        _M_CAND.inc(1, outcome="pruned")
        _M_CAND_FRAC.observe(len(cand) / max(n_items, 1))
        empty_f = np.zeros(0, np.float32)
        empty_i = np.zeros(0, np.int32)
        res = self._assemble(
            model, num, st is not None,
            st if st is not None else empty_f,
            si if si is not None else empty_i,
            bt if bt is not None else empty_f,
            bi if bi is not None else empty_i, fill=fill)
        if lap is not None:
            lap("assemble")
        return res

    @staticmethod
    def _mask_key_is_binary(key: tuple) -> bool:
        """True when the composed mask can only take values in {0, 1}:
        every field bias is a hard filter (< 0), a zero-boost (0.0, which
        excludes like a filter) or the identity boost (1.0) — dateRange
        and currentDate factors are always 0/1.  Binary masks never
        REORDER backfill scores (x * 1.0 == x in f32), so the pruned
        tail's popularity-order merge stays exact."""
        return all(bias < 0.0 or bias in (0.0, 1.0)
                   for _name, _values, bias in key[0])

    # ids a pruned-tail backfill walk may scan before giving up and
    # falling back to the dense tail: bounds the per-query sliced
    # predicate work to a CATALOG-INDEPENDENT constant when a rule
    # matches almost nothing (the dense pass is O(I_p) once and its
    # full mask is then cached for repeats, where the walk would
    # re-evaluate the slice every query)
    _BACKFILL_SCAN_BUDGET = 1 << 16

    def _backfill_merge(self, model: URModel, mask_at, sb, k: int,
                        ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Backfill picks for the pruned tail: walk popmodel's
        precomputed (popularity desc, id asc) total order in doubling
        chunks, dropping blacklisted (``sb``: pre-sorted id array or
        None) and rule-masked-out ids, until k survive — never an [I_p]
        temporary.  Only called under a binary mask, where survivor
        order along the walk IS the dense tail's ``host_topk_desc(bf *
        mask)`` order and survivor scores are exactly ``bf`` (the dense
        tail's -inf rows are the ones dropped here, and _assemble skips
        them there).  Returns None when the walk exceeds
        _BACKFILL_SCAN_BUDGET scanned ids with survivors still owed and
        catalog left to scan — the caller serves that query dense."""
        order = model.host_pop_order()
        bf = model.host_popularity()
        n = len(order)
        picks: List[np.ndarray] = []
        taken = 0
        start, chunk = 0, max(4 * k, 64)
        while taken < k and start < n:
            if start >= self._BACKFILL_SCAN_BUDGET:
                return None
            ids = order[start:start + chunk]
            start += len(ids)
            chunk = min(chunk * 2, 1 << 16)
            keep = np.ones(len(ids), bool)
            if sb is not None:
                keep &= ~_sorted_member(ids, sb)
            if mask_at is not None:
                keep &= mask_at(ids) > 0
            sel = ids[keep]
            if len(sel):
                picks.append(sel[: k - taken])
                taken += len(picks[-1])
        if not picks:
            return np.zeros(0, np.float32), np.zeros(0, np.int32)
        bi = np.concatenate(picks).astype(np.int32)
        return bf[bi], bi

    def _mask_from_key_host_sliced(self, model: URModel, key: tuple,
                                   ids: np.ndarray) -> np.ndarray:
        """Evaluate the canonical rule key's mask at ``ids`` only —
        exactly ``_mask_from_key_host(...)[ids]`` without the [I_p]
        build and without a cache entry (candidate slices are
        query-specific; the shared per-(property, value) id indexes and
        date-offset caches still back the factors).  Exactness is
        structural: both paths run the SAME factor composition
        (_compose_mask_host) and every factor is elementwise, so
        evaluation commutes with the gather; only the accessors differ
        (sorted-membership probe vs cached full bitset, ts gather vs
        full ts)."""
        zeros = np.zeros(len(ids), np.float32)
        return self._compose_mask_host(
            model, key,
            # prop_value_index id lists are ascending by construction,
            # so membership needs no per-call sort
            value_match=lambda name, val: _sorted_member(
                ids, model._value_mask_ids(name, val)).astype(np.float32),
            date_ts=lambda ts: ts[ids],
            zeros=lambda: zeros,
            n=len(ids))

    def _query_hist(self, model: URModel, query: URQuery,
                    hist_override: Optional[Dict[str, np.ndarray]] = None,
                    ) -> Optional[Dict[str, np.ndarray]]:
        """Per-event-type history ids driving the signal, or None when the
        query carries no personalization handle (pure backfill)."""
        set_ids = [model.item_dict.id(i) for i in query.item_set]
        set_ids = [i for i in set_ids if i is not None]
        if query.item is not None or set_ids:
            # item-similarity / itemSet (cart): the query items' OWN
            # indicator lists act as a virtual history on each event type's
            # field (reference URAlgorithm getBiasedSimilarItems / itemSet
            # queries building the ES query from item-document indicators)
            if query.item is not None:
                iid = model.item_dict.id(query.item)
                if iid is not None:
                    set_ids.append(iid)
            if not set_ids:
                return None
            hist: Dict[str, np.ndarray] = {}
            for name, idx in model.indicator_idx.items():
                rows = idx[np.asarray(set_ids, np.int32)]
                ids = np.unique(rows[rows >= 0])
                if len(ids):
                    hist[name] = ids.astype(np.int32)
            return hist
        if query.user is not None:
            return (hist_override if hist_override is not None
                    else self._user_history(model, query.user))
        return None

    def _assemble(self, model: URModel, num: int, have_signal: bool,
                  st, si, bt, bi, fill: Optional[dict] = None) -> URResult:
        """Host tail shared by predict and serve_batch_predict: signal
        picks first, then popularity backfill PADS short lists up to num
        (reference UR appends popRank-ordered items).  ``fill``, when
        given, receives the response cache's entry facts — the picked
        item ids and how many came from backfill."""
        results: List[ItemScore] = []
        chosen = set()
        bf_ids: List[int] = []
        if have_signal:
            for s, j in zip(st, si):
                if np.isfinite(s) and s > 0 and len(results) < num:
                    results.append(ItemScore(model.item_dict.str(int(j)), float(s)))
                    chosen.add(int(j))
        if len(results) < num and self.params.backfill_type != "none":
            norm = model.pop_norm()
            for s, j in zip(bt, bi):
                if len(results) >= num:
                    break
                if int(j) in chosen or not np.isfinite(s):
                    continue
                results.append(ItemScore(model.item_dict.str(int(j)), float(s) / norm))
                bf_ids.append(int(j))
        if fill is not None:
            fill["ids"] = list(chosen) + bf_ids
            fill["backfill"] = len(bf_ids)
        return URResult(results)

    def serve_batch_predict(self, model: URModel,
                            queries: Sequence[URQuery]) -> List[URResult]:
        """Deploy-time micro-batch scoring: every query's history scores
        against the resident indicator tables in ONE device program per
        event type, and both top-ks for the whole batch come back in ONE
        [B, 4, k] readback (vs 1 readback per query serially).
        Live-store semantics identical to predict(); the separate
        eval-only batch_predict (model-history, anti-leakage) is
        untouched.

        Shares the serial path's response cache (serve.response_cache)
        with per-row outcome counting: cached rows peel off before any
        device work, only the miss subset runs the batched tail, and the
        misses fill the same cache serial predict consults — one cache
        contract for both paths.
        """
        n_items = len(model.item_dict)
        if not queries or n_items == 0:
            return [URResult([]) for _ in queries]
        hists = [self._query_hist(model, q) for q in queries]
        cache = _resp_cache.get_cache()
        if not cache.armed_for(model):
            return self._serve_batch_uncached(model, queries, hists)
        keys: List[Tuple[tuple, Optional[tuple], int]] = []
        out: List[Optional[URResult]] = [None] * len(queries)
        misses: List[int] = []
        audited: Dict[int, tuple] = {}
        for r, q in enumerate(queries):
            num = min(q.num, n_items)
            rkey = self._mask_rule_key(q)
            ckey = _resp_cache.make_key(
                num, rkey, hists[r], self._blacklist_ids(model, q))
            keys.append((ckey, rkey, num))
            items, audit = cache.lookup(model, ckey)
            if items is not None and not audit:
                out[r] = URResult([ItemScore(n, s) for n, s in items])
            else:
                misses.append(r)
                if items is not None:
                    audited[r] = items
        if misses:
            fills: List[dict] = [{} for _ in misses]
            fresh = self._serve_batch_uncached(
                model, [queries[r] for r in misses],
                [hists[r] for r in misses], fills)
            for i, r in enumerate(misses):
                out[r] = fresh[i]
                ckey, rkey, num = keys[r]
                self._cache_settle(cache, model, ckey, rkey, fresh[i],
                                   audited.get(r), hists[r], fills[i], num)
        return out

    def _serve_batch_uncached(self, model: URModel,
                              queries: Sequence[URQuery], hists,
                              fills: Optional[List[dict]] = None,
                              ) -> List[URResult]:
        """The batched tail itself (histories already fetched), shared
        by the cache-armed wrapper (miss subset) and unarmed serving."""
        n_items = len(model.item_dict)
        b = len(queries)
        bp = bucket_width(b, min_width=1)
        have_signal = [h is not None and any(len(v) for v in h.values())
                       for h in hists]
        scorer = _serve_scorer()
        if _serve_tail() == "host":
            # host tail per query.  With the host scorer nothing touches
            # the device at all; with the device scorer the batched gather
            # program still amortizes dispatch and every row comes back in
            # ONE readback before the numpy tails run.
            if scorer == "host":
                sparses = [self._score_history_host(model, h) if h else None
                           for h in hists]
                if _serve_candidates() == "on":
                    # candidate branch: each query's pruned tail runs
                    # straight off its sparse row — micro-batched
                    # queries keep one-pass assembly and the same
                    # per-query dense fallback as serial predict
                    out = []
                    for r, q in enumerate(queries):
                        nm = min(q.num, n_items)
                        f = fills[r] if fills is not None else None
                        res = self._host_tail_pruned(model, q, sparses[r],
                                                     nm, fill=f)
                        if res is None:
                            res = self._host_tail(
                                model, q,
                                self._sparse_signal_dense(n_items,
                                                          sparses[r]), nm,
                                fill=f)
                        out.append(res)
                    return out
                rows = [self._sparse_signal_dense(n_items, s)
                        for s in sparses]
            else:
                total = self._score_batch_device(model, hists, bp, n_items)
                rows_all = (None if total is None
                            else np.asarray(total)[:b])
                rows = [rows_all[r] if rows_all is not None and have_signal[r]
                        else None for r in range(b)]
            return [
                self._host_tail(model, q, rows[r], min(q.num, n_items),
                                fill=fills[r] if fills is not None else None)
                for r, q in enumerate(queries)
            ]
        total = None
        if scorer == "host":
            rows_np = [
                self._sparse_signal_dense(
                    n_items, self._score_history_host(model, h))
                if h else None for h in hists]
            if any(r is not None for r in rows_np):
                total = jnp.asarray(np.stack(
                    [r if r is not None else np.zeros(n_items, np.float32)
                     for r in rows_np]
                    + [np.zeros(n_items, np.float32)] * (bp - b)))
        else:
            total = self._score_batch_device(model, hists, bp, n_items)
        if total is None:
            total = jnp.zeros((bp, n_items), jnp.float32)
        masks = jnp.stack(
            [m if (m := self._mask_for(model, q, host=False)) is not None
             else model.device_ones() for q in queries]
            + [model.device_zeros()] * (bp - b))
        blacks = [self._blacklist_ids(model, q) for q in queries]
        wb = bucket_width(max((len(x) for x in blacks), default=1))
        bm = np.full((bp, wb), -1, np.int32)
        for r, ids in enumerate(blacks):
            bm[r, : len(ids)] = ids
        nums = [min(q.num, n_items) for q in queries]
        k = min(bucket_width(2 * max(nums), 16), n_items)
        out = np.asarray(_serve_topk_batch(
            total, masks, model.device_popularity(), jnp.asarray(bm), k))
        return [
            self._assemble(model, nums[r], have_signal[r],
                           out[r, 0], out[r, 1].astype(np.int32),
                           out[r, 2], out[r, 3].astype(np.int32),
                           fill=fills[r] if fills is not None else None)
            for r in range(b)
        ]

    def _score_batch_device(self, model: URModel, hists, bp: int,
                            n_items: int) -> Optional[jnp.ndarray]:
        """The batched device gather scorer: every event type's histories
        score against the resident table in one [B, I_p, K] program;
        None when no query carries any history."""
        total = None
        use_llr = jnp.asarray(self.params.use_llr_weights)
        for name, (idx_dev, llr_dev) in model.device_indicators().items():
            lens = [len(h[name]) if h and name in h else 0 for h in hists]
            if not any(lens):
                continue
            w = bucket_width(max(lens))
            hm = np.full((bp, w), -1, np.int32)
            for r, h in enumerate(hists):
                if h and name in h and len(h[name]):
                    hm[r, : len(h[name])] = h[name]
            n_t = max(len(model.event_item_dicts[name]), 1)
            s = _indicator_score_ids_batch(
                idx_dev, llr_dev, jnp.asarray(hm), use_llr, n_t)
            weight = float(self.params.indicator_weights.get(name, 1.0))
            s = s * weight if weight != 1.0 else s
            total = s if total is None else total + s
        return total

    def _blacklist_ids(self, model: URModel, query: URQuery) -> List[int]:
        """Item ids to exclude: the user's seen items under every configured
        blacklist event type (reference UR blacklists from all of
        blackListEvents, not only the primary), query blacklistItems, and
        self for item queries."""
        ids: List[int] = []
        if query.user is not None:
            uid = model.user_dict.id(query.user)
            if uid is not None:
                blacklist_events = self.params.blacklist_events or [model.primary_event]
                for name in blacklist_events:
                    if name == model.primary_event:
                        ids.extend(model.user_seen.row(uid).tolist())
                    else:
                        csr = model.user_seen_by_event.get(name)
                        if csr is not None:
                            ids.extend(csr.row(uid).tolist())
        black = set(query.blacklist_items)
        if not query.return_self:
            if query.item is not None:
                black.add(query.item)
            black.update(query.item_set)
        for b in black:
            bid = model.item_dict.id(b)
            if bid is not None:
                ids.append(bid)
        return ids

    def _mask_rule_key(self, query: URQuery) -> Optional[tuple]:
        """Canonical business-rule key for the mask cache, or None when
        the query carries no rules at all (the fast path: no mask work).

        Canonical = field rules sorted (mask composition is a product, so
        order never changes the value; sorting makes differently-ordered
        but equivalent queries share one cache entry) and query dates
        parsed to epoch seconds QUANTIZED to whole seconds — the mask
        only ever consumes second-granularity offsets, and live traffic
        sending ``currentDate=now()`` would otherwise mint a unique key
        (and pin a full-catalog mask) per query.  Strict date parsing
        happens HERE, before any cache interaction, so a malformed date
        still rejects the query with 400 and never poisons the cache."""
        def q_ts(raw, field):
            # falsy (absent/empty) date fields stay unset, as before
            return None if not raw else int(np.rint(_query_ts(raw, field)))

        fields = tuple(sorted(
            (r.name, tuple(r.values), float(r.bias)) for r in query.fields))
        dr = query.date_range
        drk = None
        if dr is not None:
            drk = (dr.name,
                   q_ts(dr.after, "dateRange.after"),
                   q_ts(dr.before, "dateRange.before"))
        # strict-parse currentDate even when no avail/expire property is
        # configured (a malformed date is a 400 regardless), but an INERT
        # currentDate must not force mask builds or unique cache entries
        now = q_ts(query.current_date, "currentDate")
        if not (self.params.available_date_name
                or self.params.expire_date_name):
            now = None
        if not fields and drk is None and now is None:
            return None
        # the avail/expire property names are engine params, constant per
        # deployment — included so a params change can't alias an entry
        return (fields, drk, now, self.params.available_date_name,
                self.params.expire_date_name)

    def _mask_for(self, model: URModel, query: URQuery, host: bool):
        """The composed business-rule mask for one query, memoized per
        (model generation, canonical rule set, tail kind) in a bounded
        thread-safe LRU — steady-state queries with repeated rules skip
        mask construction entirely (hit/miss/evict in
        pio_ur_rule_mask_cache_total).  None = no rules (all-ones)."""
        key = self._mask_rule_key(query)
        if key is None:
            return None
        cache = model.rule_mask_cache("host" if host else "device")
        return cache.get_or_build(
            key, lambda: self._mask_from_key(model, key, host))

    def _mask_from_key(self, model: URModel, key: tuple, host: bool):
        """Build the mask from the CANONICAL key (not the query object):
        both tails compose the identical factors in the identical order,
        so host and device masks agree bit-for-bit even for float biases.

        Semantics are the Elasticsearch filter/boost analogue (reference:
        URAlgorithm field biases and date rules as ES bool-query
        filters); items missing a checked date property fail the check,
        like ES range filters."""
        fields, drk, now, avail, expire = key
        if host:
            return self._mask_from_key_host(model, fields, drk, now,
                                            avail, expire)
        return self._mask_from_key_device(model, fields, drk, now,
                                          avail, expire)

    @staticmethod
    def _date_bound(epoch_s: float, base: float) -> int:
        # same rounding as the item offsets → exact boundary equality
        return int(np.clip(np.rint(epoch_s - base), -1, 2**31 - 2))

    def _mask_from_key_host(self, model, fields, drk, now, avail, expire
                            ) -> np.ndarray:
        return self._compose_mask_host(
            model, (fields, drk, now, avail, expire),
            value_match=model.host_value_mask,   # cached full f32 bitsets
            date_ts=lambda ts: ts,
            zeros=model.host_zeros,
            n=len(model.item_dict))

    def _compose_mask_host(self, model, key: tuple, value_match, date_ts,
                           zeros, n: int) -> np.ndarray:
        """The ONE host factor composition behind both the full mask and
        the candidate slice — pruned≡dense exactness depends on both
        paths multiplying the identical elementwise factors in the
        identical order, so the composition exists exactly once and the
        two callers only swap accessors: ``value_match(name, val)`` →
        f32 0/1 match over the domain, ``date_ts(full_ts)`` → the
        domain's slice of a date-offset array, ``zeros()`` → the
        match-nothing result, ``n`` = domain length."""
        fields, drk, now, avail, expire = key
        one = np.float32(1.0)
        mask = np.ones(n, np.float32)
        for name, values, bias in fields:
            match = None
            for val in values:
                m = value_match(name, val)
                match = m if match is None else np.maximum(match, m)
            if match is None:
                match = zeros()
            if bias < 0:
                mask = mask * match              # hard filter
            else:
                mask = mask * np.where(match > 0, np.float32(bias), one)
        if drk is not None:
            name, after_s, before_s = drk
            d = model.date_offsets(name)
            if d is None:            # no item has the property: match nothing
                return zeros()
            base, ts = d
            ts = date_ts(ts)
            present = (ts >= 0)
            mask = mask * present.astype(np.float32)
            if after_s is not None:
                mask = mask * ((ts >= self._date_bound(after_s, base))
                               & present).astype(np.float32)
            if before_s is not None:
                mask = mask * ((ts <= self._date_bound(before_s, base))
                               & present).astype(np.float32)
        if now is not None:
            for prop, op in ((avail, np.less_equal), (expire,
                                                      np.greater_equal)):
                # available <= now <= expire; boundary instants still valid
                if not prop:
                    continue
                d = model.date_offsets(prop)
                if d is None:
                    return zeros()
                base, ts = d
                ts = date_ts(ts)
                b = self._date_bound(now, base)
                mask = mask * (op(ts, b) & (ts >= 0)).astype(np.float32)
        return mask

    def _mask_from_key_device(self, model, fields, drk, now, avail, expire
                              ) -> jnp.ndarray:
        mask = model.device_ones()
        for name, values, bias in fields:
            match = None
            for val in values:
                m = model.device_value_mask(name, val)
                match = m if match is None else _m_or(match, m)
            if match is None:
                match = model.device_zeros()
            if bias < 0:
                mask = _m_hard(mask, match)      # hard filter
            else:
                mask = _m_boost(mask, match, float(bias))
        if drk is not None:
            name, after_s, before_s = drk
            dd = model.device_date(name)
            if dd is None:           # no item has the property: match nothing
                return model.device_zeros()
            base, ts = dd
            mask = _m_present(mask, ts)
            if after_s is not None:
                mask = _m_ge(mask, ts, self._date_bound(after_s, base))
            if before_s is not None:
                mask = _m_le(mask, ts, self._date_bound(before_s, base))
        if now is not None:
            for prop, op in ((avail, _m_le), (expire, _m_ge)):
                # available <= now <= expire; boundary instants still valid
                if not prop:
                    continue
                dd = model.device_date(prop)
                if dd is None:
                    return model.device_zeros()
                base, ts = dd
                mask = op(mask, ts, self._date_bound(now, base))
        return mask


class UniversalRecommenderEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source_class=URDataSource,
            preparator_class=URPreparator,
            algorithm_classes={"ur": URAlgorithm},
            serving_class=FirstServing,
        )

    query_class = URQuery
