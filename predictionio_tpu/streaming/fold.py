"""Incremental CCO fold: delta events → updated URModel, exactly.

A full UR retrain is (a) stage/parse the whole log, (b) translate to
dense id spaces, (c) the O(U·I_p·I_t) co-occurrence count pass, (d) LLR +
per-row top-k, (e) popularity/CSR/property epilogues.  PR 3's delta
staging already made (a) incremental; this module makes (b)–(e)
incremental too, by exploiting that CCO counts are ADDITIVE:

- :class:`URFoldState` keeps, per event type, the deduped (user, item)
  pair set, the co-occurrence counts and the LLR marginals
  (distinct-user row/column counts).  Counts are **sorted-COO by
  default** (:class:`_SparseCounts`: one int64 ``(row<<32|col)`` key +
  int32 count per nonzero cell — O(nnz), so a 1M-item catalog whose
  dense matrix would be 4 TB fits in tens of MB); the legacy dense
  int32 ``[I_p, I_t]`` matrices remain behind ``PIO_FOLLOW_STATE=dense``
  as an escape hatch and as the bit-exactness oracle the property tests
  compare against.  A delta fold applies ``C_new = C + Δpᵀ·A_old +
  P_newᵀ·Δa`` as vectorized scatter-adds (dense) or one sorted merge
  (sparse) over the delta's cross-join — O(delta footprint), never
  O(U·I²).
- LLR + top-k re-runs through the SAME scoring chain training uses
  (``ops.cco._llr_mask_scores`` / ``_llr_cells`` — XLA elementwise math
  is element-value-deterministic regardless of tensor shape), so every
  recomputed cell is bit-identical to a from-scratch retrain's value —
  exactness by construction, not by tolerance.  Sparse state routes
  re-LLR through ``ops.cco._llr_topk_sparse_rows`` (the row-scoped
  variant of the training host tail — same scores, same lax.top_k tie
  order); dense state through the row-sliced ``_llr_topk_rows_jit``.
  Only *affected* rows recompute: a delta that changes no global LLR
  input (no new users, no new target-side pairs for the type) re-LLRs
  just the touched primary rows; a marginal change (new user → N, new
  target pairs → column counts) forces that type's full re-LLR, because
  Dunning G² couples every cell to N and its column marginal.
- The emitted model is a NEW ``URModel`` object per fold — PR 4/7's
  generation-keyed serving caches (rule-mask LRU, value-mask/date LRUs,
  ``host_pop_order``) invalidate by model identity, so hot-swap
  correctness needs no extra plumbing.  Where cheap and provably safe,
  derived serving state carries over instead of rebuilding: the
  ``host_inverted`` CSR is row-patched when few indicator rows changed
  (``_patch_inverted_csr`` — array-identical to a from-scratch
  inversion), and the property indexes carry when no ``$set``-family
  event arrived.

State is bounded by ``PIO_FOLLOW_STATE_BYTES`` (default 1 GiB: counts
plus the log-proportional parts — accumulated batch, pair sets, raw
popularity inputs, indicator tables); past it :class:`FoldUnsupported`
tells the follower to fall back to full (delta-staged) retrains per
tick, which stay exact — the budget gates cost, never correctness.
With sparse counts the resident total is ≈ f(events), not catalog², so
the default budget holds fold mode at million-item catalogs.

The state is also checkpointable (``checkpoint_arrays`` /
``restore_checkpoint`` + the accumulated batch via
``store.columnar.write_batch``): the follower persists it beside its
watermark so a SIGKILL restart re-folds only the unapplied suffix
instead of reparsing the covered prefix (see ``streaming.follow``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import metrics as _obs_metrics
from predictionio_tpu.ops.cco import _llr_mask_scores
from predictionio_tpu.store.columnar import (
    CSRLookup,
    EventBatch,
    IdDict,
    fold_properties,
)

_LOW32 = np.int64((1 << 32) - 1)

_REG = _obs_metrics.get_registry()
_M_RELLR_ROWS = _REG.counter(
    "pio_follow_rellr_rows_total",
    "Primary rows handled by a full (marginal-coupled) re-LLR pass, by "
    "outcome: certified (the selection-stability certificate proved the "
    "row's stored top-k keeps membership AND order under the new scores "
    "— its k stored scores refresh in O(k), no per-row sort) vs "
    "selected (routed through the per-row top-k re-selection)")
_M_EMIT = _REG.counter(
    "pio_follow_emit_total",
    "Derived-serving-state emissions by component (inverted | pop_order "
    "| popularity | user_seen | seen_by_event | props) and path: "
    "carried (previous "
    "generation's object reused, provably identical), patched "
    "(incremental splice/merge/weight-regather), rebuilt (from scratch)")


def rellr_prune_enabled() -> bool:
    """``PIO_FOLLOW_RELLR_PRUNE=off`` disables the selection-stability
    certificate — every full re-LLR re-selects every row (the PR-8/11
    behavior, kept as the exactness oracle the pruning property tests
    compare against)."""
    return os.environ.get("PIO_FOLLOW_RELLR_PRUNE", "").lower() not in (
        "off", "0", "false")


def rellr_workers() -> int:
    """``PIO_FOLLOW_RELLR_WORKERS``: worker threads for the chunked
    per-row top-k re-selection (the lexsort is the dominant full-re-LLR
    term and is embarrassingly row-parallel — numpy's sorts release the
    GIL on large arrays).  Default min(4, cores); 1 = inline."""
    try:
        w = int(os.environ.get("PIO_FOLLOW_RELLR_WORKERS", "0"))
    except ValueError:
        w = 0
    if w <= 0:
        w = min(4, os.cpu_count() or 1)
    return max(w, 1)


# below this many cells the pool's handoff overhead exceeds the sort
_RELLR_CHUNK_MIN_CELLS = 262_144


def _select_topk_chunked(rows: np.ndarray, cols: np.ndarray,
                         scores: np.ndarray, n_rows: int, width: int):
    """``ops.cco._select_topk_cells`` partitioned at row boundaries
    across a small thread pool (``PIO_FOLLOW_RELLR_WORKERS``).  Selection
    is independent per row, so the chunked outputs are identical to one
    global pass; ``rows`` must be sorted ascending (cell order)."""
    from predictionio_tpu.ops.cco import _select_topk_cells

    workers = rellr_workers()
    if workers <= 1 or len(rows) < _RELLR_CHUNK_MIN_CELLS or n_rows < 2:
        return _select_topk_cells(rows, cols, scores, n_rows, width)
    import concurrent.futures as _cf

    out_s = np.full((n_rows, width), -np.inf, np.float32)
    out_i = np.full((n_rows, width), -1, np.int32)
    n_chunks = min(workers * 2, n_rows)
    # split at row boundaries near equal CELL counts (not equal row
    # counts — cell skew is what unbalances the sorts)
    marks = (np.arange(1, n_chunks) * (len(rows) / n_chunks)).astype(np.int64)
    edges, prev = [0], 0
    for m in marks:
        r = int(rows[min(int(m), len(rows) - 1)])
        if r > prev:
            edges.append(r)
            prev = r
    edges.append(n_rows)

    def work(r0: int, r1: int) -> None:
        lo = np.searchsorted(rows, r0, side="left")
        hi = np.searchsorted(rows, r1, side="left")
        s, i = _select_topk_cells(rows[lo:hi] - r0, cols[lo:hi],
                                  scores[lo:hi], r1 - r0, width)
        out_s[r0:r1] = s
        out_i[r0:r1] = i

    with _cf.ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(lambda b: work(*b), zip(edges[:-1], edges[1:])))
    return out_s, out_i


def _merge_pop_order(old_order: np.ndarray, new_pop: np.ndarray,
                     changed_ids: np.ndarray) -> np.ndarray:
    """Incrementally maintain ``URModel.host_pop_order``: remove the
    changed ids from the previous generation's order (unchanged members
    keep their relative order — their keys didn't move), rank the
    changed ids by the SAME composite key ``host_topk_desc`` sorts by,
    and splice them in.  Array-identical to
    ``host_topk_desc(new_pop, n)[1]`` whenever ``changed_ids`` contains
    every id whose popularity differs from the old generation's plus
    every NEW id (supersets are fine — an unchanged member re-inserts at
    exactly its old slot, keys being distinct per id)."""
    from predictionio_tpu.models.common import topk_order_keys

    changed = np.asarray(changed_ids, np.int64)
    if len(changed) == 0:
        return old_order
    keys = topk_order_keys(np.asarray(new_pop, np.float32))
    keep = ~_in_sorted(old_order.astype(np.int64), changed)
    base = old_order[keep].astype(np.int32, copy=False)
    corder = changed[np.argsort(-keys[changed])].astype(np.int32)
    pos = np.searchsorted(-keys[base.astype(np.int64)],
                          -keys[corder.astype(np.int64)])
    return np.insert(base, pos, corder)


def _inverted_perm(idx: np.ndarray) -> np.ndarray:
    """The row-major flat positions of ``idx``'s valid cells in
    host_inverted CSR order (stable sort by target): the rebuild's
    weight array is exactly ``llr.ravel()[perm]``, so a generation whose
    CSR STRUCTURE is unchanged (same idx) refreshes its weights with one
    gather instead of re-inverting."""
    valid = idx >= 0
    flat = np.flatnonzero(valid.ravel())
    return flat[np.argsort(idx.ravel()[flat], kind="stable")]


def state_budget_bytes() -> int:
    """PIO_FOLLOW_STATE_BYTES caps the resident fold state — the count
    matrices (I_p·I_t·4 per event type) PLUS the log-proportional parts
    (accumulated columnar batch, pair sets, raw popularity inputs).
    Past it the follower retrains instead of folding (exact either way;
    the budget trades memory for fold latency)."""
    try:
        return max(int(os.environ.get("PIO_FOLLOW_STATE_BYTES",
                                      str(1 << 30))), 1)
    except ValueError:
        return 1 << 30


def fold_state_impl() -> str:
    """``PIO_FOLLOW_STATE``: 'sparse' (default) keeps sorted-COO counts —
    O(nnz) resident bytes, the representation that holds fold mode at
    million-item catalogs; 'dense' keeps the legacy [I_p, I_t] int32
    matrices (escape hatch + the oracle the sparse≡dense property tests
    compare against)."""
    conf = os.environ.get("PIO_FOLLOW_STATE", "auto").lower()
    return "dense" if conf == "dense" else "sparse"


def _dense_rellr_bytes() -> int:
    """Small-catalog fast path: a sparse-state FULL re-LLR whose dense
    [I_p, I_t] f32 matrix fits this budget (PIO_FOLLOW_DENSE_RELLR_BYTES,
    default 4 MiB) materializes it transiently and runs the jitted dense
    kernels — at tiny shapes (the sub-ms regime) the dense jit beats the
    sparse gather+lexsort ~2×, and it is the exact path the dense state
    (and PR 8) always took.  0 forces the sparse tail everywhere (the
    property tests use it so the sparse kernels stay covered at small
    shapes)."""
    try:
        return max(int(os.environ.get("PIO_FOLLOW_DENSE_RELLR_BYTES",
                                      str(4 << 20))), 0)
    except ValueError:
        return 4 << 20


class FoldUnsupported(RuntimeError):
    """The fold engine cannot (or should not) maintain incremental state
    for this engine/shape — the follower falls back to retrain mode."""


class _SparseCounts:
    """Sorted-COO co-occurrence counts: ``keys`` holds one int64
    ``(row << 32) | col`` per nonzero cell, ascending; ``counts`` the
    int32 count at that cell.  All mutations preserve the sort:

    - increments merge via searchsorted + np.insert (new cells land at
      their exact slots);
    - row/col remaps apply a STRICTLY INCREASING permutation (the
      old→new local-id map ``_extend_item_space`` computes is a
      searchsorted into the union of two sorted sets, hence monotone),
      so remapped keys stay ascending without a re-sort.
    """

    __slots__ = ("keys", "counts")

    def __init__(self, keys: np.ndarray, counts: np.ndarray):
        self.keys = np.asarray(keys, np.int64)
        self.counts = np.asarray(counts, np.int32)

    @classmethod
    def empty(cls) -> "_SparseCounts":
        return cls(np.zeros(0, np.int64), np.zeros(0, np.int32))

    @classmethod
    def from_dense(cls, C: np.ndarray) -> "_SparseCounts":
        rows, cols = np.nonzero(C)
        return cls(_pair_key(rows, cols), C[rows, cols].astype(np.int32))

    @property
    def nnz(self) -> int:
        return len(self.keys)

    @property
    def nbytes(self) -> int:
        return int(self.keys.nbytes) + int(self.counts.nbytes)

    def add_pairs(self, rows: np.ndarray, cols: np.ndarray) -> None:
        """counts[r, c] += multiplicity of (r, c) in the given pairs."""
        if len(rows) == 0:
            return
        uniq, inc = np.unique(_pair_key(rows, cols), return_counts=True)
        pos = np.searchsorted(self.keys, uniq)
        hit = np.zeros(len(uniq), bool)
        in_range = pos < len(self.keys)
        hit[in_range] = self.keys[pos[in_range]] == uniq[in_range]
        if hit.any():
            self.counts[pos[hit]] += inc[hit].astype(np.int32)
        miss = ~hit
        if miss.any():
            self.keys = np.insert(self.keys, pos[miss], uniq[miss])
            self.counts = np.insert(self.counts, pos[miss],
                                    inc[miss].astype(np.int32))

    def all_cells(self):
        """(rows, cols, counts) of every nonzero cell, (row, col)-asc."""
        return (self.keys >> np.int64(32), self.keys & _LOW32, self.counts)

    def row_cells(self, rows: np.ndarray):
        """Gather the cells of a sorted unique row subset: returns
        (local row index into ``rows``, col, count) — each row's cells
        are one contiguous key segment, bounded by two searchsorteds
        (the same repeat/arange expansion as ``_cross_scatter``)."""
        rows = np.asarray(rows, np.int64)
        starts = np.searchsorted(self.keys, rows << np.int64(32))
        ends = np.searchsorted(self.keys, (rows + 1) << np.int64(32))
        seg = ends - starts
        total = int(seg.sum())
        if total == 0:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.int32))
        csum = np.cumsum(seg)
        within = np.arange(total, dtype=np.int64) - np.repeat(csum - seg, seg)
        idx = np.repeat(starts, seg) + within
        local = np.repeat(np.arange(len(rows), dtype=np.int64), seg)
        return local, self.keys[idx] & _LOW32, self.counts[idx]

    def remap_cols(self, perm: np.ndarray) -> None:
        """col → perm[col] (perm strictly increasing: order preserved)."""
        if self.nnz and len(perm):
            self.keys = (self.keys & ~_LOW32) \
                | np.asarray(perm, np.int64)[self.keys & _LOW32]

    def remap_rows(self, perm: np.ndarray) -> None:
        """row → perm[row] (perm strictly increasing: order preserved)."""
        if self.nnz and len(perm):
            self.keys = (np.asarray(perm, np.int64)[self.keys >> np.int64(32)]
                         << np.int64(32)) | (self.keys & _LOW32)

    def to_dense(self, n_rows: int, n_cols: int) -> np.ndarray:
        C = np.zeros((n_rows, n_cols), np.int32)
        if self.nnz:
            C[self.keys >> np.int64(32), self.keys & _LOW32] = self.counts
        return C


def _pair_key(u: np.ndarray, i: np.ndarray) -> np.ndarray:
    """(user id, type-local item id) → one sortable int64 key."""
    return (np.asarray(u, np.int64) << np.int64(32)) | np.asarray(i, np.int64)


def _key_item(key: np.ndarray) -> np.ndarray:
    return (key & _LOW32).astype(np.int64)


def _key_user(key: np.ndarray) -> np.ndarray:
    return (key >> np.int64(32)).astype(np.int64)


def _in_sorted(values: np.ndarray, sorted_arr: np.ndarray) -> np.ndarray:
    """Boolean membership of ``values`` in an ascending array."""
    if len(sorted_arr) == 0 or len(values) == 0:
        return np.zeros(len(values), bool)
    pos = np.searchsorted(sorted_arr, values)
    np.minimum(pos, len(sorted_arr) - 1, out=pos)
    return sorted_arr[pos] == values


def _cross_partners(pairs_sorted: np.ndarray, du: np.ndarray,
                    di: np.ndarray, rows_from_delta: bool):
    """Expand one side of the count update into its (row, col) increment
    pairs — shared by both count representations.

    For every delta pair (du[e], di[e]) and every partner item j in the
    OTHER side's per-user segment of ``pairs_sorted`` (deduped composite
    keys, (user, item)-ascending):

    - rows_from_delta=True:  (di[e], j)   (Δpᵀ·A — delta items are
      primary rows, partners are columns)
    - rows_from_delta=False: (j, di[e])   (Pᵀ·Δa — partners are
      primary rows, delta items are columns)

    One searchsorted pair bounds each user's partner segment; the flat
    expansion mirrors ``models.common.gather_csr_rows`` (repeat/arange,
    no per-pair Python loop).
    """
    if len(du) == 0 or len(pairs_sorted) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    starts = np.searchsorted(pairs_sorted,
                             np.asarray(du, np.int64) << np.int64(32))
    ends = np.searchsorted(pairs_sorted,
                           (np.asarray(du, np.int64) + 1) << np.int64(32))
    seg = ends - starts                       # partners per delta pair
    total = int(seg.sum())
    if total == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    csum = np.cumsum(seg)
    within = np.arange(total, dtype=np.int64) - np.repeat(csum - seg, seg)
    partners = _key_item(pairs_sorted[np.repeat(starts, seg) + within])
    own = np.repeat(np.asarray(di, np.int64), seg)
    if rows_from_delta:
        return own, partners
    return partners, own


def _cross_scatter(counts, pairs_sorted: np.ndarray,
                   du: np.ndarray, di: np.ndarray,
                   rows_from_delta: bool) -> np.ndarray:
    """Apply one side of the count update (see ``_cross_partners``) to
    ``counts`` — a dense int32 matrix (scatter-add) or a
    :class:`_SparseCounts` (sorted merge) — and return the touched
    primary-row ids."""
    rows, cols = _cross_partners(pairs_sorted, du, di, rows_from_delta)
    if len(rows) == 0:
        return np.zeros(0, np.int64)
    if isinstance(counts, _SparseCounts):
        counts.add_pairs(rows, cols)
    else:
        np.add.at(counts, (rows, cols), 1)
    return np.unique(rows)


@partial(jax.jit, static_argnames=("top_k", "pallas"))
def _llr_topk_rows_jit(C_rows, rc_rows, cc, n_total, llr_threshold,
                       self_cols, top_k: int, pallas: str = "off"):
    """Row-sliced twin of ``ops.cco._llr_topk_dense``: the identical
    elementwise score chain (so each cell's f32 value is bit-identical —
    XLA elementwise math is element-value-deterministic regardless of
    tensor shape), the identical -inf self-pair placement (``self_cols``
    holds each row's GLOBAL primary id, -1 for non-primary types), the
    identical ``lax.top_k`` tie order."""
    scores = _llr_mask_scores(
        C_rows.astype(jnp.float32), rc_rows.astype(jnp.float32),
        cc.astype(jnp.float32), n_total, llr_threshold, pallas)
    cols = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    is_self = (cols == self_cols[:, None]) & (self_cols[:, None] >= 0)
    scores = jnp.where(is_self, -jnp.inf, scores)
    s, i = jax.lax.top_k(scores, top_k)
    return s, i.astype(jnp.int32)


def _llr_topk_rows(C_rows: np.ndarray, rc_rows: np.ndarray,
                   cc: np.ndarray, n_total: float, llr_threshold: float,
                   self_rows: Optional[np.ndarray], top_k: int,
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: pad the row count to the next power of two so the
    jit compiles once per bucket, not per distinct slice size (padding
    rows score -inf everywhere — zero counts — and are dropped)."""
    n = C_rows.shape[0]
    pad = 1 << max((n - 1).bit_length(), 0)
    sc = np.full(pad, -1, np.int32)
    if self_rows is not None:
        sc[:n] = self_rows.astype(np.int32)
    if pad > n:
        C_rows = np.concatenate(
            [C_rows, np.zeros((pad - n, C_rows.shape[1]), C_rows.dtype)])
        rc_rows = np.concatenate(
            [rc_rows, np.zeros(pad - n, rc_rows.dtype)])
    s, i = _llr_topk_rows_jit(
        jnp.asarray(C_rows), jnp.asarray(rc_rows), jnp.asarray(cc),
        float(n_total), float(llr_threshold), jnp.asarray(sc),
        top_k=top_k)
    return np.asarray(s)[:n], np.asarray(i)[:n]


def _patch_inverted_csr(old_indptr: np.ndarray, old_rows: np.ndarray,
                        old_perm: np.ndarray, changed_rows: np.ndarray,
                        old_idx: np.ndarray, new_idx: np.ndarray,
                        n_t: int, i_p: int,
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-patch a host_inverted CSR's STRUCTURE: drop every posting
    entry whose primary row changed, insert the changed rows' new
    entries at their (target, row) positions, and splice the weight
    permutation (``_inverted_perm``) the same way — the caller gathers
    weights as ``new_llr.ravel()[perm]``, so the weights of UNCHANGED
    rows still refresh (an N bump moves every LLR value without moving
    any structure).  ``indptr`` updates as an indptr-delta splice: old
    prefix sums plus the prefix sums of (inserted − removed) per target
    — O(n_t + changed·K), never a full posting recount — and extends
    for target-space growth (new targets at the end) and primary-row
    growth (``changed_rows`` may exceed ``old_idx``'s rows), so pure
    catalog growth patches instead of rebuilding.  Output is
    ARRAY-IDENTICAL to rebuilding the inversion from the new indicator
    table (the rebuild's stable sort orders entries by (target, row);
    kept entries already follow that order and inserts go to their
    exact slots)."""
    k = new_idx.shape[1]
    changed_rows = np.asarray(changed_rows, np.int64)
    if len(old_indptr) < n_t + 1:
        old_indptr = np.concatenate([
            old_indptr,
            np.full(n_t + 1 - len(old_indptr), old_indptr[-1], np.int64)])
    tgt_of = np.repeat(np.arange(n_t, dtype=np.int64), np.diff(old_indptr))
    keep = ~_in_sorted(old_rows.astype(np.int64), changed_rows)
    k_t, k_r, k_p = tgt_of[keep], old_rows[keep], old_perm[keep]
    changed_old = changed_rows[changed_rows < old_idx.shape[0]]
    rem = old_idx[changed_old]
    rem_t = rem[rem >= 0].astype(np.int64)
    sub = new_idx[changed_rows]
    valid = sub >= 0
    n_r = np.repeat(changed_rows, k)[valid.ravel()]
    n_tg = sub[valid].astype(np.int64)
    n_flat = (changed_rows[:, None] * k
              + np.arange(k, dtype=np.int64)).ravel()[valid.ravel()]
    order = np.lexsort((n_r, n_tg))
    n_tg, n_r, n_flat = n_tg[order], n_r[order], n_flat[order]
    pos = np.searchsorted(k_t * i_p + k_r.astype(np.int64),
                          n_tg * i_p + n_r)
    rows2 = np.insert(k_r, pos, n_r.astype(np.int32)).astype(np.int32)
    perm2 = np.insert(k_p, pos, n_flat)
    delta = (np.bincount(n_tg, minlength=n_t)
             - np.bincount(rem_t, minlength=n_t))
    indptr2 = (old_indptr
               + np.concatenate([[0], np.cumsum(delta)])).astype(np.int64)
    return indptr2, rows2, perm2


@dataclasses.dataclass
class _TypeState:
    """Per-event-type incremental state.  Exactly one of ``C`` (dense
    impl) / ``sc`` (sparse impl) holds the co-occurrence counts."""

    codes: np.ndarray            # int64 sorted unique target-dict codes
    item_dict: IdDict            # strings of ``codes`` (id = position)
    local_of_target: np.ndarray  # target code → local item id (-1 unknown)
    pairs: np.ndarray            # int64 sorted deduped (u<<32 | i) keys
    col_counts: np.ndarray       # int64 [I_t] distinct users per target
    raw_items: List[np.ndarray]  # per-fold raw event items (local ids)
    raw_times: List[np.ndarray]  # per-fold raw event epoch seconds
    C: Optional[np.ndarray] = None       # int32 [I_p, I_t] counts (dense)
    sc: Optional[_SparseCounts] = None   # sorted-COO counts (sparse)
    idx: Optional[np.ndarray] = None   # int32 [I_p, K] indicator ids
    llr: Optional[np.ndarray] = None   # f32   [I_p, K] indicator scores
    # copy-on-write marks: an emitted model shares idx/llr and item_dict
    # by reference (the emit may run on the publisher thread); any
    # in-place mutation must clone first
    shared_tables: bool = False
    shared_dict: bool = False

    def mutable_tables(self) -> None:
        """COW guard before an IN-PLACE idx/llr write (sliced re-LLR,
        certified-score refresh): the emitted model may share these
        arrays."""
        if self.shared_tables:
            if self.idx is not None:
                self.idx = self.idx.copy()
                self.llr = self.llr.copy()
            self.shared_tables = False

    @property
    def n_items(self) -> int:
        return len(self.codes)

    @property
    def counts(self):
        return self.sc if self.sc is not None else self.C


@dataclasses.dataclass
class _EmitSnapshot:
    """Consistent emission view captured by ``URFoldState.fold_apply``:
    structure references (replaced-on-change) plus COW-marked shared
    arrays/dictionaries, so ``emit_snapshot`` — and the serving-bundle
    warm behind it — can run on the follower's publisher thread while
    the next delta applies on the fold loop."""

    generation: int
    n_users: int
    user_dict: IdDict
    types: Dict[str, dict]
    props: Dict[str, dict]
    pop_f32: Optional[np.ndarray]
    pop_changed: Optional[np.ndarray]
    remap: dict
    hints: Dict[str, dict]


class URFoldState:
    """Resident incremental-training state for ONE Universal Recommender
    algorithm.  ``fold(delta_batch)`` folds a columnar delta (sharing
    this state's dictionaries — the scan_tail contract) and returns a
    fresh :class:`URModel` whose responses are identical to
    ``URAlgorithm.train`` over the full accumulated batch."""

    def __init__(self, algo_params, ds_params):
        from predictionio_tpu.models.universal_recommender.engine import (
            URAlgorithm,
        )

        self.params = algo_params
        self.ds_params = ds_params
        self.event_names: List[str] = list(ds_params.event_names)
        if not self.event_names:
            raise FoldUnsupported("no event_names configured")
        self.primary = self.event_names[0]
        blacklist = self.params.blacklist_events or [self.primary]
        unknown = [b for b in blacklist if b not in self.event_names]
        if unknown:
            raise ValueError(
                f"blacklist_events {unknown} not in event_names "
                f"{self.event_names}")
        bf_names = self.params.backfill_event_names or [self.primary]
        unknown_bf = [b for b in bf_names if b not in self.event_names]
        if unknown_bf:
            raise ValueError(
                f"backfill_event_names {unknown_bf} not in event_names "
                f"{self.event_names}")
        if self.params.checkpoint:
            raise FoldUnsupported(
                "checkpointed training is a batch-durability feature; "
                "the follower's unit of durability is the watermark")
        self.per_type = URAlgorithm.per_type_tuning(algo_params,
                                                    self.event_names)
        self.impl = fold_state_impl()
        self.user_dict = IdDict()
        self.user_of_code = np.full(1, -1, np.int32)
        self.row_counts = np.zeros(0, np.int64)
        self.types: Dict[str, _TypeState] = {
            name: _TypeState(
                codes=np.zeros(0, np.int64), item_dict=IdDict(),
                local_of_target=np.full(1, -1, np.int64),
                pairs=np.zeros(0, np.int64),
                C=(np.zeros((0, 0), np.int32) if self.impl == "dense"
                   else None),
                sc=(_SparseCounts.empty() if self.impl == "sparse"
                    else None),
                col_counts=np.zeros(0, np.int64),
                raw_items=[], raw_times=[])
            for name in self.event_names
        }
        self.batch: Optional[EventBatch] = None
        self._props: Dict[str, dict] = {}
        self._props_ever = False
        self._primary_perm = np.zeros(0, np.int64)
        self.generation = 0
        self.model = None
        self.last_fold_stats: Dict[str, dict] = {}
        self.last_rellr_stats: Dict[str, dict] = {}
        self.last_phase_s: Dict[str, float] = {}
        self._rellr_s = 0.0
        self._user_dict_shared = False
        self._emit_hints: Dict[str, dict] = {}
        self._reshape_identity: Dict[str, bool] = {}
        # incremental popularity: running int64 per-item event counts +
        # observed time range, valid while the backfill window covers
        # every event (the default 3650-day window practically always
        # does); outside the supported config the emit recomputes from
        # the raw lists exactly as before
        bf_names = list(self.params.backfill_event_names or [self.primary])
        self._pop_incremental = (self.params.backfill_type == "popular"
                                 and bf_names == [self.primary])
        self._pop_duration = 0.0
        if self._pop_incremental:
            from predictionio_tpu.models.universal_recommender.popmodel \
                import parse_duration
            try:
                self._pop_duration = parse_duration(
                    self.params.backfill_duration)
            except (ValueError, TypeError):
                self._pop_incremental = False
        self._pop: Optional[list] = None     # [counts, t_min, t_max]
        self._pop_changed_now: Optional[np.ndarray] = None
        # emit-side caches (touched only by emit_snapshot, which runs
        # serialized — at most one emit at a time, in snapshot order)
        self._user_seen_cache: Optional[tuple] = None
        self._seen_by_ev_cache: Dict[str, tuple] = {}
        self._inv_cache: Dict[str, dict] = {}

    # -- public entry ---------------------------------------------------------

    def fold(self, delta: EventBatch):
        """Fold one columnar delta (built with ``base=self.batch`` so the
        dictionaries are shared — the first call bootstraps from scratch)
        and return the new URModel."""
        return self.emit_snapshot(self.fold_apply(delta))

    def fold_apply(self, delta: EventBatch) -> "_EmitSnapshot":
        """Apply one columnar delta to the resident state and return an
        emission snapshot — everything :meth:`emit_snapshot` needs,
        captured by reference for replace-on-change structures and
        marked copy-on-write for the in-place-mutated ones.  The split
        lets the follower run the emit (and the serving-bundle warm
        behind it) on its publisher thread while the NEXT delta applies
        on the fold loop — ticks pipeline instead of serializing
        fold+emit+warm."""
        t0 = time.perf_counter()
        self._rellr_s = 0.0
        if self.batch is None:
            self.batch = delta
        elif len(delta):
            self.batch = EventBatch.concat([self.batch, delta])
        self._apply(delta)
        self._check_budget()
        self.last_phase_s = {
            "apply": max(time.perf_counter() - t0 - self._rellr_s, 0.0),
            "rellr": self._rellr_s,
        }
        snap = self._snapshot()
        self.generation += 1
        return snap

    @classmethod
    def bootstrap(cls, algo_params, ds_params,
                  batch: EventBatch) -> "URFoldState":
        """Build state + first model from a full columnar batch."""
        state = cls(algo_params, ds_params)
        state.fold(batch)
        return state

    @property
    def state_mode(self) -> str:
        """'sparse' | 'dense' — the resident count representation (the
        pio_follow_state_mode gauge and /stats.json surface this)."""
        return self.impl

    def state_bytes(self) -> int:
        """Total resident bytes of the incremental state: the counts
        (sorted-COO cells — O(nnz) — or the legacy dense matrices) plus
        everything that GROWS with the log — the accumulated columnar
        batch, pair sets, raw popularity inputs and indicator tables.
        This is what ``PIO_FOLLOW_STATE_BYTES`` bounds: a long-lived
        follower at a steady event rate demotes to retrain mode when its
        resident history outgrows the budget, instead of leaking without
        limit."""
        total = 0
        for t in self.types.values():
            total += (t.sc.nbytes if t.sc is not None
                      else int(t.C.nbytes)) + int(t.pairs.nbytes)
            total += int(t.col_counts.nbytes) + int(t.local_of_target.nbytes)
            total += sum(int(a.nbytes) for a in t.raw_items)
            total += sum(int(a.nbytes) for a in t.raw_times)
            if t.idx is not None:
                total += int(t.idx.nbytes) + int(t.llr.nbytes)
        if self._pop is not None:
            total += int(self._pop[0].nbytes)
        # list(): the publisher thread's emit may be (re)installing cache
        # entries concurrently with this read-only walk
        for inv in list(self._inv_cache.values()):
            total += int(inv["perm"].nbytes)
        if self.batch is not None:
            b = self.batch
            for arr in (b.event_codes, b.entity_type_codes, b.entity_ids,
                        b.target_ids, b.times_us, b.ratings):
                total += int(arr.nbytes)
        return total

    # -- delta application ----------------------------------------------------

    def _check_budget(self) -> None:
        if self.state_bytes() > state_budget_bytes():
            raise FoldUnsupported(
                f"fold state {self.state_bytes()} B exceeds "
                f"PIO_FOLLOW_STATE_BYTES={state_budget_bytes()}")

    @staticmethod
    def _grow_translate(arr: np.ndarray, n: int) -> np.ndarray:
        if len(arr) >= n:
            return arr
        out = np.full(max(n, 1), -1, arr.dtype)
        out[: len(arr)] = arr
        return out

    def _apply(self, delta: EventBatch) -> None:
        """Mirror URDataSource.read_training incrementally over ``delta``
        and fold the translated pairs into the count state."""
        from predictionio_tpu.events.event import SPECIAL_EVENTS

        self.last_fold_stats = {}
        self.last_rellr_stats = {}
        self._emit_hints = {}
        self._reshape_identity = {}
        self._pop_changed_now = None
        special = [delta.event_dict.id(n) for n in SPECIAL_EVENTS]
        special = np.asarray([c for c in special if c is not None], np.int32)
        props_changed = bool(len(delta)) and bool(
            np.isin(delta.event_codes, special).any())
        view = dataclasses.replace(delta, prop_columns=None)
        per_type_raw: Dict[str, tuple] = {}
        for name in self.event_names:
            sel = view.select_events([name])
            has_t = sel.target_ids >= 0
            per_type_raw[name] = (sel.entity_ids[has_t],
                                  sel.target_ids[has_t],
                                  sel.times_us[has_t].astype(np.float64) / 1e6)
        # users enroll exactly as read_training's per-type unique pass
        # does; enrollment ORDER only assigns internal user ids, and
        # responses are user-id-order independent (items carry the
        # tie-breaking ids)
        self.user_of_code = self._grow_translate(
            self.user_of_code, len(delta.entity_dict))
        n_users_before = len(self.user_dict)
        for name in self.event_names:
            e_codes = per_type_raw[name][0]
            for c in np.unique(e_codes):
                if self.user_of_code[c] < 0:
                    if self._user_dict_shared:
                        # COW: the emitted model shares this dictionary
                        self.user_dict = self.user_dict.clone()
                        self._user_dict_shared = False
                    self.user_of_code[c] = self.user_dict.add(
                        delta.entity_dict.str(int(c)))
        new_users = len(self.user_dict) != n_users_before
        # item spaces: keep each type's sorted-unique target-code set —
        # the same set read_training's np.unique produces over the full
        # batch, so local item ids (and their tie order) match a
        # from-scratch retrain exactly even when an OLD code first
        # appears under a new type (mid-array insert + state remap)
        reshaped: Dict[str, bool] = {}
        for name in self.event_names:
            reshaped[name] = self._extend_item_space(
                name, per_type_raw[name][1], delta)
        primary_reshaped = reshaped[self.primary]
        if primary_reshaped:
            self._reshape_primary_rows()
        # translate + append raw events (popularity inputs)
        deltas: Dict[str, np.ndarray] = {}
        for name in self.event_names:
            st = self.types[name]
            e_codes, t_codes, times = per_type_raw[name]
            u = self.user_of_code[e_codes].astype(np.int64)
            i = st.local_of_target[t_codes]
            if len(i):
                st.raw_items.append(i.astype(np.int32))
                st.raw_times.append(times)
            if name == self.primary and self._pop_incremental:
                n_p_now = st.n_items
                if self._pop is None:
                    self._pop = [np.zeros(max(n_p_now, 1), np.int64),
                                 np.inf, -np.inf]
                cnts = self._pop[0]
                if len(cnts) < n_p_now:   # growth the reshape didn't see
                    grown = np.zeros(n_p_now, np.int64)
                    grown[:len(cnts)] = cnts
                    self._pop[0] = cnts = grown
                if len(i):
                    cnts += np.bincount(i, minlength=len(cnts))
                    self._pop[1] = min(self._pop[1], float(times.min()))
                    self._pop[2] = max(self._pop[2], float(times.max()))
                    self._pop_changed_now = np.unique(i).astype(np.int64)
                else:
                    self._pop_changed_now = np.zeros(0, np.int64)
            keys = (np.unique(_pair_key(u, i)) if len(u)
                    else np.zeros(0, np.int64))
            if len(keys):
                keys = keys[~_in_sorted(keys, st.pairs)]
            deltas[name] = keys
        # counts: C_new = C + Δpᵀ·A_old + P_newᵀ·Δa per type (for the
        # primary, A ≡ P and the two terms cover (P+Δ)ᵀ(P+Δ) exactly —
        # the ΔᵀΔ diagonal term rides P_newᵀΔ).  Step A must see every
        # type's PRE-delta pair set; step C the POST-delta primary set.
        p_st = self.types[self.primary]
        dp = deltas[self.primary]
        dp_u, dp_i = _key_user(dp), _key_item(dp)
        touched: Dict[str, List[np.ndarray]] = {
            n: [] for n in self.event_names}
        for name in self.event_names:
            st = self.types[name]
            touched[name].append(_cross_scatter(
                st.counts, st.pairs, dp_u, dp_i, rows_from_delta=True))
        if len(dp):
            p_st.pairs = np.sort(np.concatenate([p_st.pairs, dp]))
            self.row_counts += np.bincount(dp_i, minlength=p_st.n_items)
        for name in self.event_names:
            st = self.types[name]
            da = deltas[name]
            if len(da) == 0:
                continue
            touched[name].append(_cross_scatter(
                st.counts, p_st.pairs, _key_user(da), _key_item(da),
                rows_from_delta=False))
            st.col_counts += np.bincount(_key_item(da),
                                         minlength=st.n_items)
            if name != self.primary:
                st.pairs = np.sort(np.concatenate([st.pairs, da]))
        # re-LLR scope per type (exact): a changed N or column marginal
        # couples every cell of that type; otherwise only rows whose C
        # cells or row marginal changed can differ
        rc_rows = np.unique(dp_i) if len(dp) else np.zeros(0, np.int64)
        for name in self.event_names:
            st = self.types[name]
            if st.n_items == 0 or p_st.n_items == 0:
                continue
            if (new_users or len(deltas[name]) or reshaped[name]
                    or primary_reshaped or st.idx is None):
                self._rellr_type(name, rows=None)
                continue
            parts = [rc_rows] + touched[name]
            rows = np.unique(np.concatenate(parts)) if parts else rc_rows
            if len(rows) == 0:
                self.last_fold_stats[name] = {"rows": 0, "mode": "skip"}
                self._emit_hints[name] = {
                    "idx_rows": np.zeros(0, np.int64), "llr_changed": False}
                continue
            self._rellr_type(name, rows=rows.astype(np.int64))
        if props_changed or not self._props_ever:
            # full-history recompute, not a delta merge: properties apply
            # in (eventTime, row) order, so a delta $set carrying an
            # EARLIER eventTime than an applied one must lose — an
            # append-order merge would get that wrong.  Cost is bounded
            # by PIO_FOLLOW_STATE_BYTES (breach demotes to retrain).
            self._props = {
                k: dict(v) for k, v in fold_properties(
                    self.batch, self.ds_params.item_entity_type).items()}
            self._props_ever = True
        self._last_remap = {
            "primary": primary_reshaped,
            "primary_identity": self._reshape_identity.get(
                self.primary, True),
            "types": dict(reshaped),
            "type_identity": dict(self._reshape_identity),
            "props": props_changed,
        }

    def _extend_item_space(self, name: str, t_codes: np.ndarray,
                           delta: EventBatch) -> bool:
        """Merge new target codes into the type's sorted code set;
        returns True when the type's item-id space changed shape (grew
        and/or existing ids shifted)."""
        st = self.types[name]
        st.local_of_target = self._grow_translate(
            st.local_of_target, len(delta.target_dict))
        if len(t_codes) == 0:
            return False
        uniq = np.unique(t_codes.astype(np.int64))
        new = uniq[~_in_sorted(uniq, st.codes)]
        if len(new) == 0:
            return False
        merged = np.union1d(st.codes, new)
        perm = np.searchsorted(merged, st.codes)  # old local → new local
        remapped = bool(len(st.codes)) and bool(
            (perm != np.arange(len(st.codes))).any())
        n_old = len(st.codes)
        st.codes = merged
        self._reshape_identity[name] = not remapped
        if remapped or n_old == 0:
            st.item_dict = IdDict(
                [delta.target_dict.str(int(c)) for c in merged])
            st.shared_dict = False
        else:
            # pure end growth (every new code sorts after every old one):
            # existing local ids are stable, so the dictionary APPENDS
            # instead of rebuilding — O(new items), not O(catalog) —
            # with a COW clone when an emitted model shares it
            if st.shared_dict:
                st.item_dict = st.item_dict.clone()
                st.shared_dict = False
            for c in merged[n_old:]:
                st.item_dict.add(delta.target_dict.str(int(c)))
        lot = np.full(len(st.local_of_target), -1, np.int64)
        lot[merged] = np.arange(len(merged), dtype=np.int64)
        st.local_of_target = lot
        if remapped:
            # existing local ids shifted: remap everything keyed on them
            st.pairs = np.sort(
                (st.pairs & ~_LOW32) | perm[_key_item(st.pairs)])
            st.raw_items = [perm[a].astype(np.int32) for a in st.raw_items]
        # grow/permute the column-indexed state
        cc = np.zeros(len(merged), np.int64)
        if len(perm):
            cc[perm] = st.col_counts
        st.col_counts = cc
        if st.sc is not None:
            # absent cells stay absent; existing cells' cols follow the
            # (monotone) perm — no growth array needed, and pure growth
            # at the end (identity perm) costs nothing
            if remapped:
                st.sc.remap_cols(perm)
        else:
            C = np.zeros((st.C.shape[0], len(merged)), np.int32)
            if len(perm) and st.C.size:
                C[:, perm] = st.C
            st.C = C
        if remapped:
            # mid-array insert: stored indicator COLUMN ids shifted —
            # the full re-LLR rebuilds the tables from scratch
            st.idx = st.llr = None
        # else: pure end growth keeps every stored column id valid; the
        # marginal-triggered full re-LLR re-certifies each row against
        # the new columns (a new column can only ENTER a row's top-k
        # through the certificate's re-selection route)
        if name == self.primary:
            self._primary_perm = perm
        return True

    def _reshape_primary_rows(self) -> None:
        """The PRIMARY item space changed shape: every type's C rows, the
        row marginals and indicator tables follow the new id order (the
        old→new row permutation _extend_item_space just computed)."""
        p_st = self.types[self.primary]
        n_p = p_st.n_items
        # primary pairs were already remapped; rebuild the row marginal
        # from them (delta pairs merge afterwards, in _apply)
        self.row_counts = (
            np.bincount(_key_item(p_st.pairs), minlength=n_p)
            .astype(np.int64) if len(p_st.pairs)
            else np.zeros(n_p, np.int64))
        perm = self._primary_perm
        identity = self._reshape_identity.get(self.primary, True)
        if self._pop is not None:
            cnts = np.zeros(n_p, np.int64)
            if len(perm):
                cnts[perm] = self._pop[0][:len(perm)]
            self._pop[0] = cnts
        for name in self.event_names:
            st = self.types[name]
            if st.sc is not None:
                st.sc.remap_rows(perm)
            else:
                C = np.zeros((n_p, st.C.shape[1]), np.int32)
                if len(perm) and st.C.size:
                    C[perm, :] = st.C
                st.C = C
            if identity and st.idx is not None and st.idx.shape[0] <= n_p:
                # pure end growth of the primary space: existing rows
                # keep their ids — extend the indicator tables with
                # empty rows (the new rows re-select through their own
                # delta pairs) instead of discarding every stored
                # selection
                pad = n_p - st.idx.shape[0]
                if pad:
                    st.idx = np.concatenate([st.idx, np.full(
                        (pad, st.idx.shape[1]), -1, np.int32)])
                    st.llr = np.concatenate([st.llr, np.zeros(
                        (pad, st.llr.shape[1]), np.float32)])
                    st.shared_tables = False
            else:
                st.idx = st.llr = None

    def _rellr_type(self, name: str, rows: Optional[np.ndarray]) -> None:
        """Recompute LLR + top-k for ``rows`` of one type (None = all),
        bit-identically to what training would compute: sparse state
        routes through the cell-scoring + selection tail shared with the
        training host path (same ``_llr_cells`` elementwise scores, same
        lax.top_k tie order) — full passes PRUNED by the selection-
        stability certificate (:meth:`_rellr_full_sparse`) — dense state
        through the same jitted dense kernels as before."""
        t0 = time.perf_counter()
        try:
            self._rellr_type_inner(name, rows)
        finally:
            self._rellr_s += time.perf_counter() - t0

    def _rellr_type_inner(self, name: str,
                          rows: Optional[np.ndarray]) -> None:
        from predictionio_tpu.ops.cco import (
            _DenseRunner,
            _llr_topk_dense,
            _llr_topk_sparse_rows,
        )
        from predictionio_tpu.ops.pallas_kernels import pallas_mode

        st = self.types[name]
        p_st = self.types[self.primary]
        t_k, t_llr = self.per_type.get(
            name, (self.params.max_correlators_per_item,
                   self.params.min_llr))
        excl = name == self.primary
        n_t = st.n_items
        n_p = p_st.n_items
        n_total = float(len(self.user_dict))
        default_kernels = pallas_mode() == "off"
        small_dense = (default_kernels
                       and n_p * n_t * 4 <= _dense_rellr_bytes())
        if st.sc is not None and default_kernels and not small_dense:
            # the sparse tail: score only the resident nonzero cells
            # through the row-scoped variant of the training host tail
            width = min(t_k, n_t)
            if rows is None:
                self._rellr_full_sparse(name, st, width, t_k,
                                        float(t_llr), excl, n_p, n_t,
                                        n_total)
                return
            crows, ccols, ccnt = st.sc.row_cells(rows)
            rc_rows = self.row_counts[rows]
            self_cols = rows if excl else None
            s, i = _llr_topk_sparse_rows(
                crows, ccols, ccnt, rc_rows, st.col_counts, n_total,
                float(t_llr), top_k=width, n_rows=len(rows), n_cols=n_t,
                self_cols=self_cols)
            scores, idx = _DenseRunner.collect((s, i, n_t, t_k))
            st.mutable_tables()
            st.idx[rows] = idx.astype(np.int32)
            st.llr[rows] = np.where(np.isfinite(scores), scores,
                                    0.0).astype(np.float32)
            self.last_fold_stats[name] = {"rows": int(len(rows)),
                                          "mode": "sliced"}
            self._emit_hints[name] = {"idx_rows": rows,
                                      "llr_changed": True}
            return
        if st.sc is not None:
            # dense kernels over a transient materialization: the tiny-
            # catalog fast path (sub-ms regime, where the dense jit beats
            # the sparse gather+lexsort ~2× — and exactly the code path
            # the dense state and PR 8 always took), or the Pallas LLR
            # kernel, whose only entry points are dense — there,
            # unaffordable means the follower must retrain
            if not small_dense and n_p * n_t * 4 > state_budget_bytes():
                raise FoldUnsupported(
                    f"non-default kernels (PIO_PALLAS {pallas_mode()}) "
                    f"need a dense [{n_p}, {n_t}] count pass that exceeds "
                    "PIO_FOLLOW_STATE_BYTES")
            C_full = st.sc.to_dense(n_p, n_t)
        else:
            C_full = st.C
        # the Pallas LLR kernel has only full-matrix entry points — take
        # the full path so the fold reproduces exactly what training
        # would have computed
        if rows is None or not default_kernels:
            s, i = _llr_topk_dense(
                jnp.asarray(C_full), jnp.asarray(self.row_counts),
                jnp.asarray(st.col_counts), n_total, float(t_llr),
                top_k=min(t_k, n_t), exclude_self=bool(excl),
                pallas=pallas_mode())
            scores, idx = _DenseRunner.collect((s, i, n_t, t_k))
            st.idx = idx.astype(np.int32)
            st.llr = np.where(np.isfinite(scores), scores,
                              0.0).astype(np.float32)
            st.shared_tables = False
            self.last_fold_stats[name] = {"rows": C_full.shape[0],
                                          "mode": "full"}
            self._emit_hints[name] = {"idx_rows": None,
                                      "llr_changed": True}
            return
        scores, idx = _llr_topk_rows(
            C_full[rows], self.row_counts[rows], st.col_counts, n_total,
            float(t_llr), rows if excl else None, min(t_k, n_t))
        scores, idx = _DenseRunner.collect((scores, idx, n_t, t_k))
        st.mutable_tables()
        st.idx[rows] = idx.astype(np.int32)
        st.llr[rows] = np.where(np.isfinite(scores), scores,
                                0.0).astype(np.float32)
        self.last_fold_stats[name] = {"rows": int(len(rows)),
                                      "mode": "sliced"}
        self._emit_hints[name] = {"idx_rows": rows, "llr_changed": True}

    def _rellr_full_sparse(self, name: str, st: _TypeState, width: int,
                           t_k: int, t_llr: float, excl: bool,
                           n_p: int, n_t: int, n_total: float) -> None:
        """Full (marginal-coupled) re-LLR of one type over the sparse
        state, PRUNED: ONE vectorized G² score pass over every resident
        nonzero cell — the same power-of-two-padded ``_llr_cells``
        program the unpruned tail runs, so every emitted score is
        bit-exact — followed by per-row top-k re-selection only where
        the selection could have moved.

        The per-row certificate is exact, not a bound, because it
        compares the NEW scores directly: a row keeps its stored
        selection iff (a) membership holds — with a full selection its
        weakest selected cell strictly beats its best non-selected cell
        (score TIES route to re-selection: the column tie-break could
        flip membership); with a deficit selection (< ``width`` stored)
        no non-selected cell scores finite and no selected cell fell to
        -inf — and (b) the stored order is still (score desc, col asc)-
        sorted under the new scores.  Certified rows provably keep
        membership AND order, so they refresh their k stored scores by
        one gather (O(k)) and skip the lexsort entirely; the rest
        re-select through ``_select_topk_cells``, chunked across
        ``PIO_FOLLOW_RELLR_WORKERS``.  ``PIO_FOLLOW_RELLR_PRUNE=off``
        forces every row down the re-selection route (the exactness
        oracle)."""
        from predictionio_tpu.ops.cco import _DenseRunner, _score_llr_cells

        crows, ccols, ccnt = st.sc.all_cells()
        if excl and len(crows):
            off = ccols != crows
            crows, ccols, ccnt = crows[off], ccols[off], ccnt[off]
        scores = _score_llr_cells(
            ccnt.astype(np.float32),
            self.row_counts[crows].astype(np.float32),
            st.col_counts[ccols].astype(np.float32), n_total, t_llr)
        old_idx = st.idx if (rellr_prune_enabled() and st.idx is not None
                             and st.llr is not None
                             and st.idx.shape == (n_p, t_k)) else None
        self.last_fold_stats[name] = {"rows": n_p, "mode": "full"}
        if old_idx is None:
            keep = scores > -np.inf
            s, i = _select_topk_chunked(
                crows[keep], ccols[keep], scores[keep], n_p, width)
            sc2, idx2 = _DenseRunner.collect((s, i, n_t, t_k))
            st.idx = idx2.astype(np.int32)
            st.llr = np.where(np.isfinite(sc2), sc2,
                              0.0).astype(np.float32)
            st.shared_tables = False
            if n_p:
                _M_RELLR_ROWS.inc(n_p, outcome="selected")
            self.last_rellr_stats[name] = {"certified": 0,
                                           "selected": int(n_p)}
            self._emit_hints[name] = {"idx_rows": None,
                                      "llr_changed": True}
            return
        # -- certification ------------------------------------------------
        valid = old_idx >= 0
        sel_count = valid.sum(axis=1)
        span = np.int64(n_t + 1)
        cell_flat = crows * span + ccols
        # ONE searchsorted: locate every stored cell among the COO cells
        # (they must exist — counts never decrease; a miss = corrupt
        # state degrades to -inf, which fails certification and
        # re-selects the row from the actual cells).  The located
        # positions both refresh the stored scores AND mark the cells as
        # selected — no second membership pass.
        vr, vj = np.nonzero(valid)
        vc = old_idx[vr, vj].astype(np.int64)
        new_sel = np.full((n_p, t_k), -np.inf, np.float32)
        is_sel = np.zeros(len(cell_flat), bool)
        if len(vr) and len(cell_flat):
            key = vr.astype(np.int64) * span + vc
            pos = np.searchsorted(cell_flat, key)
            np.minimum(pos, len(cell_flat) - 1, out=pos)
            hit = cell_flat[pos] == key
            is_sel[pos[hit]] = True
            new_sel[vr[hit], vj[hit]] = scores[pos[hit]]
        # per-row best non-selected contender (segment max; cells are
        # (row, col)-sorted so each row is one contiguous run)
        max_nonsel = np.full(n_p, -np.inf, np.float32)
        starts = np.zeros(0, np.int64)
        if len(crows):
            non_scores = np.where(is_sel, np.float32(-np.inf), scores)
            starts = np.concatenate(
                [[0], np.flatnonzero(np.diff(crows)) + 1])
            max_nonsel[crows[starts]] = np.maximum.reduceat(
                non_scores, starts)
        min_sel = np.where(valid, new_sel, np.inf).min(axis=1)
        # a SCORE tie at the membership boundary is still exactly
        # decidable: under (score desc, col asc) the tied selected cells
        # win iff their largest column is below the tied contenders'
        # smallest column (common in uniform-count catalogs, where whole
        # rows share one score — without this, every such row would
        # re-sort on every N bump)
        nonsel_tie_min = np.full(n_p, int(span), np.int64)
        if len(crows):
            tie_cols = np.where(~is_sel & (scores == max_nonsel[crows]),
                                ccols, span)
            nonsel_tie_min[crows[starts]] = np.minimum.reduceat(
                tie_cols, starts)
        sel_tie_max = np.where(
            valid & (new_sel == min_sel[:, None]), old_idx,
            -1).max(axis=1).astype(np.int64) if t_k else \
            np.full(n_p, -1, np.int64)
        tie_ok = (min_sel > -np.inf) & (sel_tie_max < nonsel_tie_min)
        member_ok = np.where(
            sel_count == width,
            (min_sel > max_nonsel)
            | ((min_sel == max_nonsel) & tie_ok),
            (max_nonsel == -np.inf) & (min_sel > -np.inf))
        if t_k > 1:
            s0, s1 = new_sel[:, :-1], new_sel[:, 1:]
            i0 = old_idx[:, :-1].astype(np.int64)
            i1 = old_idx[:, 1:].astype(np.int64)
            # padding forms a suffix, so valid[:, 1:] marks exactly the
            # adjacent pairs that are BOTH valid
            pair_ok = ((s0 > s1) | ((s0 == s1) & (i0 < i1))
                       | ~valid[:, 1:])
            certified = member_ok & pair_ok.all(axis=1)
        else:
            certified = member_ok
        uncert = np.flatnonzero(~certified).astype(np.int64)
        idx_new = old_idx.copy()
        llr_new = np.zeros((n_p, t_k), np.float32)
        cert2d = certified[:, None] & valid
        llr_new[cert2d] = new_sel[cert2d]
        if len(uncert):
            keep = scores > -np.inf
            kr, kc, ks = crows[keep], ccols[keep], scores[keep]
            lo = np.searchsorted(kr, uncert, side="left")
            hi = np.searchsorted(kr, uncert, side="right")
            seg = hi - lo
            total = int(seg.sum())
            if total:
                csum = np.cumsum(seg)
                within = np.arange(total, dtype=np.int64) \
                    - np.repeat(csum - seg, seg)
                gidx = np.repeat(lo, seg) + within
                local = np.repeat(
                    np.arange(len(uncert), dtype=np.int64), seg)
                s_u, i_u = _select_topk_chunked(
                    local, kc[gidx], ks[gidx], len(uncert), width)
            else:
                s_u = np.full((len(uncert), width), -np.inf, np.float32)
                i_u = np.full((len(uncert), width), -1, np.int32)
            sc2, idx2 = _DenseRunner.collect((s_u, i_u, n_t, t_k))
            idx_new[uncert] = idx2.astype(np.int32)
            llr_new[uncert] = np.where(np.isfinite(sc2), sc2,
                                       0.0).astype(np.float32)
        st.idx, st.llr = idx_new, llr_new
        st.shared_tables = False
        n_cert = int(n_p - len(uncert))
        if n_cert:
            _M_RELLR_ROWS.inc(n_cert, outcome="certified")
        if len(uncert):
            _M_RELLR_ROWS.inc(int(len(uncert)), outcome="selected")
        self.last_rellr_stats[name] = {"certified": n_cert,
                                       "selected": int(len(uncert))}
        self._emit_hints[name] = {"idx_rows": uncert, "llr_changed": True}

    # -- model emission -------------------------------------------------------

    def _snapshot(self) -> "_EmitSnapshot":
        """Capture a consistent emission view of the state: references
        for structures that are REPLACED on change (pairs, dicts, props,
        per-fold raw arrays), copies for the in-place-mutated popularity
        counts, and copy-on-write marks on the indicator tables and
        dictionaries the emitted model will share.  After this call the
        fold loop may apply the next delta while the emit runs."""
        pop_f32, pop_changed = self._pop_view()
        types: Dict[str, dict] = {}
        for name in self.event_names:
            st = self.types[name]
            types[name] = {
                "idx": st.idx, "llr": st.llr, "pairs": st.pairs,
                "item_dict": st.item_dict, "n_items": st.n_items,
                "raw_items": list(st.raw_items),
                "raw_times": list(st.raw_times),
            }
            st.shared_tables = True
            st.shared_dict = True
        self._user_dict_shared = True
        return _EmitSnapshot(
            generation=self.generation + 1,
            n_users=len(self.user_dict),
            user_dict=self.user_dict,
            types=types,
            props=self._props,
            pop_f32=pop_f32,
            pop_changed=pop_changed,
            remap=dict(getattr(self, "_last_remap", None)
                       or {"primary": True, "primary_identity": False,
                           "types": {}, "type_identity": {},
                           "props": True}),
            hints=dict(self._emit_hints),
        )

    def _pop_view(self):
        """(popularity f32, changed ids) when the incremental counts
        are valid — the counts convert to EXACTLY what backfill_scores
        computes, provided no event has fallen out of the (end-anchored)
        window: end = max_t + 1e-6 shifts with every append, so validity
        is min_t >= end - duration, the same float64 arithmetic the full
        recompute applies.  (None, None) otherwise → full recompute."""
        if not self._pop_incremental or self._pop is None:
            return None, None
        cnts, t_min, t_max = self._pop
        if np.isfinite(t_max) \
                and t_min < (float(t_max) + 1e-6) - float(self._pop_duration):
            return None, None
        return cnts.astype(np.float32), \
            (self._pop_changed_now if self._pop_changed_now is not None
             else None)

    def _emit(self):
        """Build a fresh URModel from the current state (snapshot taken
        inline) — the restore/bootstrap entry; the follower's pipelined
        path uses fold_apply + emit_snapshot instead."""
        return self.emit_snapshot(self._snapshot())

    def emit_snapshot(self, snap: "_EmitSnapshot"):
        """Build the URModel one snapshot describes — array-identical to
        the construction ``URAlgorithm.train`` performs — reusing
        derived serving state across generations wherever provably
        identical.  Runs off the fold loop when the follower pipelines
        (streaming.follow's publisher thread); emits are serialized and
        in snapshot order, so the prev-generation chain (``self.model``)
        stays consistent."""
        from predictionio_tpu.models.universal_recommender.engine import (
            URModel,
        )
        from predictionio_tpu.models.universal_recommender.popmodel import (
            backfill_scores,
            parse_duration,
        )

        t0 = time.perf_counter()
        p = snap.types[self.primary]
        n_items = p["n_items"]
        n_users = snap.n_users
        if n_items == 0:
            raise ValueError(f"no {self.primary!r} events to train on")
        indicator_idx: Dict[str, np.ndarray] = {}
        indicator_llr: Dict[str, np.ndarray] = {}
        event_item_dicts: Dict[str, IdDict] = {}
        for name in self.event_names:
            t = snap.types[name]
            if name != self.primary and t["n_items"] == 0:
                continue
            event_item_dicts[name] = t["item_dict"]
            indicator_idx[name] = t["idx"]
            indicator_llr[name] = t["llr"]
        # user → seen primary items: the resident pair set is already
        # (user, item)-sorted and deduped, so a changed generation
        # rebuilds in O(pairs) with NO sort; an untouched one carries
        # the previous CSR object outright
        pairs = p["pairs"]
        us_cache = self._user_seen_cache
        if us_cache is not None and us_cache[0] is pairs \
                and us_cache[1] == n_users:
            user_seen = us_cache[2]
            _M_EMIT.inc(1, component="user_seen", path="carried")
        else:
            user_seen = CSRLookup.from_sorted_pairs(
                _key_user(pairs), _key_item(pairs), n_users)
            self._user_seen_cache = (pairs, n_users, user_seen)
            _M_EMIT.inc(1, component="user_seen", path="rebuilt")
        bf_names = self.params.backfill_event_names or [self.primary]
        if snap.pop_f32 is not None:
            popularity = snap.pop_f32
            _M_EMIT.inc(1, component="popularity", path="patched")
        else:
            _M_EMIT.inc(1, component="popularity", path="rebuilt")
            bf_items, bf_times = [], []
            for name in bf_names:
                t = snap.types[name]
                items = (np.concatenate(t["raw_items"]) if t["raw_items"]
                         else np.zeros(0, np.int32))
                times = (np.concatenate(t["raw_times"]) if t["raw_times"]
                         else np.zeros(0, np.float64))
                if name == self.primary:
                    bf_items.append(items)
                    bf_times.append(times)
                else:
                    translate = p["item_dict"].lookup_many(
                        t["item_dict"].strings())
                    mapped = translate[items] if len(items) else items
                    keep = mapped >= 0
                    bf_items.append(mapped[keep])
                    bf_times.append(times[keep])
            popularity = backfill_scores(
                self.params.backfill_type,
                np.concatenate(bf_items) if bf_items
                else np.zeros(0, np.int32),
                np.concatenate(bf_times) if bf_times
                else np.zeros(0, np.float64),
                n_items,
                parse_duration(self.params.backfill_duration),
            )
        blacklist_events = self.params.blacklist_events or [self.primary]
        user_seen_by_event: Dict[str, CSRLookup] = {}
        for name in blacklist_events:
            if name == self.primary or name not in event_item_dicts:
                continue
            t = snap.types[name]
            cache = self._seen_by_ev_cache.get(name)
            if cache is not None and cache[0] is t["pairs"] \
                    and cache[1] is p["item_dict"] \
                    and cache[2] is t["item_dict"] and cache[3] == n_users:
                user_seen_by_event[name] = cache[4]
                _M_EMIT.inc(1, component="seen_by_event", path="carried")
                continue
            translate = p["item_dict"].lookup_many(
                t["item_dict"].strings())
            u, i = _key_user(t["pairs"]), _key_item(t["pairs"])
            mapped = translate[i] if len(i) else i
            keep = mapped >= 0
            csr = CSRLookup.from_pairs(u[keep], mapped[keep], n_users)
            user_seen_by_event[name] = csr
            self._seen_by_ev_cache[name] = (
                t["pairs"], p["item_dict"], t["item_dict"], n_users, csr)
            _M_EMIT.inc(1, component="seen_by_event", path="rebuilt")
        prev = self.model
        model = URModel(
            primary_event=self.primary,
            item_dict=p["item_dict"],
            user_dict=snap.user_dict,
            indicator_idx=indicator_idx,
            indicator_llr=indicator_llr,
            event_item_dicts=event_item_dicts,
            popularity=popularity,
            item_properties=snap.props,
            user_seen=user_seen,
            user_seen_by_event=user_seen_by_event,
        )
        self._carry_serving_state(model, prev, snap)
        self.model = model
        self.last_emit_s = time.perf_counter() - t0
        return model

    def _carry_serving_state(self, model, prev,
                             snap: "_EmitSnapshot") -> None:
        """Incremental serving-state handoff to the new generation, only
        where provably identical to a from-scratch rebuild; everything
        else stays generation-keyed (a fresh ``__dict__`` IS the
        invalidation).  Pure end growth of the catalog (identity perms)
        patches rather than invalidates: the host_inverted CSR splices
        the changed rows (and regathers ALL weights through the cached
        inversion permutation — an N bump moves every LLR value without
        moving structure), and host_pop_order merges (changed ∪ new)
        ids into the previous order by the exact host_topk_desc key."""
        if prev is None:
            return
        # provenance for the model plane's delta publisher: which ids
        # moved in pop_order and which indicator rows changed per type —
        # the EXACT arguments of the patch/merge replays below, so the
        # publisher can ship instructions instead of rewritten arrays
        # and plane workers replay the same functions bit-exactly
        # (streaming.plane).  Keyed to ``prev`` by weakref: the stash is
        # only valid relative to the generation it patched from.
        import weakref

        prov: Dict[str, object] = {"prev": weakref.ref(prev), "inv": {}}
        model.__dict__["_plane_prov"] = prov
        remap = snap.remap
        same_catalog = (not remap["primary"]
                        and len(model.item_dict) == len(prev.item_dict))
        grown_ok = same_catalog or (remap["primary"]
                                    and remap.get("primary_identity"))
        props_carried = (same_catalog and not remap["props"]
                         and model.item_properties is prev.item_properties)
        if props_carried:
            carried = False
            for attr in ("_prop_value_index", "_prop_date_array",
                         "_known_prop_names", "_date_off"):
                v = prev.__dict__.get(attr)
                if v is not None:
                    model.__dict__[attr] = v
                    carried = True
            if carried:
                _M_EMIT.inc(1, component="props", path="carried")
        # rule-mask / value-mask / date caches: pure functions of
        # (item_dict, item_properties) — exactly what props_carried
        # proves unchanged, so the LRU objects survive the swap (and a
        # props change records the drop instead of flushing silently)
        model.adopt_rule_caches(prev, carry=props_carried)
        if not grown_ok:
            return
        # -- serve-level provenance (serve.response_cache) ---------------
        # The response cache needs per-type changed primary rows and
        # changed popularity ids INDEPENDENT of whether this process ever
        # built the host inverted index or pop order, so they come
        # straight from the emit hints (the same rows the CSR patch
        # trusts for bit-exactness) + COW object identity for untouched
        # types.  Any unknowable piece (full re-select, column remap,
        # non-incremental popularity) withholds the stash entirely — the
        # cache then full-flushes, never serves stale.
        n_new, n_old = len(model.item_dict), len(prev.item_dict)
        grow = (np.arange(n_old, n_new, dtype=np.int64) if n_new > n_old
                else None)
        sinv: Dict[str, np.ndarray] = {}
        serve_ok = set(model.indicator_idx) == set(prev.indicator_idx)
        for name in (model.indicator_idx if serve_ok else ()):
            if remap["types"].get(name) \
                    and not remap["type_identity"].get(name):
                serve_ok = False   # target-column ids shifted
                break
            new_idx = model.indicator_idx[name]
            old_idx = prev.indicator_idx.get(name)
            if new_idx is old_idx:
                changed = np.zeros(0, np.int64)   # COW: provably untouched
            elif new_idx is None or old_idx is None:
                serve_ok = False
                break
            else:
                hint = snap.hints.get(name)
                if hint is None or hint.get("idx_rows") is None:
                    serve_ok = False   # full re-select: any row may move
                    break
                changed = np.asarray(hint["idx_rows"], np.int64)
                if new_idx.shape[0] > old_idx.shape[0]:
                    changed = np.union1d(changed, np.arange(
                        old_idx.shape[0], new_idx.shape[0],
                        dtype=np.int64))
            sinv[name] = changed
        if serve_ok and snap.pop_changed is not None:
            pchg = np.asarray(snap.pop_changed, np.int64)
            if grow is not None:
                pchg = np.union1d(pchg, grow)
            prov["serve"] = {"inv": sinv, "pop": pchg}
        # -- host_pop_order: incremental merge of (changed ∪ new) ids ----
        old_order = prev.__dict__.get("_host_pop_order")
        if old_order is not None and snap.pop_changed is not None:
            n_new, n_old = len(model.item_dict), len(prev.item_dict)
            changed = snap.pop_changed
            if n_new > n_old:
                changed = np.union1d(
                    changed, np.arange(n_old, n_new, dtype=np.int64))
            model.__dict__["_host_pop_order"] = _merge_pop_order(
                old_order, np.asarray(model.popularity, np.float32),
                changed)
            prov["pop_order"] = np.asarray(changed, np.int64)
            _M_EMIT.inc(1, component="pop_order",
                        path="patched" if len(changed) else "carried")
        # -- host_inverted CSR: carry / weight-regather / row-patch ------
        inv_prev = prev.__dict__.get("_host_inv") or {}
        for name, old in inv_prev.items():
            if name not in model.indicator_idx:
                continue
            if remap["types"].get(name) \
                    and not remap["type_identity"].get(name):
                self._inv_cache.pop(name, None)
                continue   # column ids shifted: rebuild from scratch
            new_idx = model.indicator_idx[name]
            old_idx = prev.indicator_idx.get(name)
            if old_idx is None or old_idx.ndim != 2 or new_idx.ndim != 2 \
                    or old_idx.shape[1] != new_idx.shape[1] \
                    or old_idx.shape[0] > new_idx.shape[0]:
                self._inv_cache.pop(name, None)
                continue
            new_llr = model.indicator_llr[name]
            i_p = new_idx.shape[0]
            n_t = max(len(model.event_item_dicts[name]), 1)
            hint = snap.hints.get(name)
            if hint is not None and hint["idx_rows"] is not None:
                changed = np.asarray(hint["idx_rows"], np.int64)
                llr_changed = bool(hint["llr_changed"])
            else:
                # no hint (restored state / non-default kernels): full
                # structural diff, row-extended for catalog growth
                rows_eq = min(old_idx.shape[0], i_p)
                diff = (new_idx[:rows_eq] != old_idx[:rows_eq]).any(axis=1)
                changed = np.flatnonzero(diff).astype(np.int64)
                llr_changed = True
            if old_idx.shape[0] < i_p:
                changed = np.union1d(
                    changed,
                    np.arange(old_idx.shape[0], i_p, dtype=np.int64))
            if len(changed) * 2 > i_p:
                self._inv_cache.pop(name, None)
                continue   # most rows moved: a from-scratch inversion
                # (in the warm, off the fold loop) is the better deal
            cache = self._inv_cache.get(name)
            if cache is not None and cache["for_idx"] is old_idx:
                perm = cache["perm"]
            else:
                perm = _inverted_perm(old_idx)
            if len(changed) == 0:
                if not llr_changed:
                    model.__dict__.setdefault("_host_inv", {})[name] = old
                    self._inv_cache[name] = {"for_idx": new_idx,
                                             "perm": perm}
                    _M_EMIT.inc(1, component="inverted", path="carried")
                    continue
                indptr, rows = old[0], old[1]
                if len(indptr) < n_t + 1:
                    indptr = np.concatenate([indptr, np.full(
                        n_t + 1 - len(indptr), indptr[-1], np.int64)])
            else:
                indptr, rows, perm = _patch_inverted_csr(
                    old[0], old[1], perm, changed, old_idx, new_idx,
                    n_t, i_p)
            w = new_llr.ravel()[perm].astype(np.float32, copy=False)
            model.__dict__.setdefault("_host_inv", {})[name] = \
                (indptr, rows, w)
            self._inv_cache[name] = {"for_idx": new_idx, "perm": perm}
            prov["inv"][name] = np.asarray(changed, np.int64)
            _M_EMIT.inc(1, component="inverted", path="patched")

    # -- checkpointing --------------------------------------------------------
    #
    # The numeric state serializes to one flat array dict (npz-able, no
    # pickle) + a small JSON meta; the accumulated EventBatch persists
    # separately through store.columnar.write_batch (which carries the
    # dictionaries and property columns).  Strings are NOT duplicated:
    # the user/item dictionaries reconstruct from the batch's dicts plus
    # the stored code maps.  ``state_fingerprint`` (crc32 over pairs +
    # marginals + code sets) makes bit-rot detectable: restore verifies
    # it and the caller restages on mismatch.

    def state_fingerprint(self) -> int:
        import zlib

        h = zlib.crc32(self.row_counts.tobytes())
        for name in self.event_names:
            st = self.types[name]
            h = zlib.crc32(np.ascontiguousarray(st.pairs).tobytes(), h)
            h = zlib.crc32(np.ascontiguousarray(st.col_counts).tobytes(), h)
            h = zlib.crc32(np.ascontiguousarray(st.codes).tobytes(), h)
        return int(h)

    def checkpoint_arrays(self) -> Tuple[Dict[str, np.ndarray], dict]:
        """(arrays, meta) capturing everything but the batch."""
        arrays: Dict[str, np.ndarray] = {
            "user_of_code": self.user_of_code,
            "row_counts": self.row_counts,
        }
        meta = {
            "version": 1,
            "impl": self.impl,
            "event_names": list(self.event_names),
            "n_users": len(self.user_dict),
            "props_ever": bool(self._props_ever),
            "generation": int(self.generation),
            "fingerprint": self.state_fingerprint(),
        }
        for k, name in enumerate(self.event_names):
            st = self.types[name]
            p = f"t{k}_"
            arrays[p + "codes"] = st.codes
            arrays[p + "local_of_target"] = st.local_of_target
            arrays[p + "pairs"] = st.pairs
            arrays[p + "col_counts"] = st.col_counts
            arrays[p + "raw_items"] = (
                np.concatenate(st.raw_items) if st.raw_items
                else np.zeros(0, np.int32))
            arrays[p + "raw_times"] = (
                np.concatenate(st.raw_times) if st.raw_times
                else np.zeros(0, np.float64))
            if st.idx is not None:
                arrays[p + "idx"] = st.idx
                arrays[p + "llr"] = st.llr
            if st.sc is not None:
                arrays[p + "cell_keys"] = st.sc.keys
                arrays[p + "cell_counts"] = st.sc.counts
            else:
                arrays[p + "dense_C"] = st.C
        return arrays, meta

    @classmethod
    def restore_checkpoint(cls, algo_params, ds_params, batch,
                           arrays, meta) -> "URFoldState":
        """Rebuild a fold state from ``checkpoint_arrays`` output + the
        persisted accumulated batch, verify the integrity fingerprint,
        and emit the model it describes.  Raises ValueError on ANY
        mismatch (version, config drift, corrupt arrays) — callers
        restage from the log."""
        if meta.get("version") != 1:
            raise ValueError(f"unknown checkpoint version {meta.get('version')}")
        state = cls(algo_params, ds_params)
        if list(meta.get("event_names") or []) != state.event_names:
            raise ValueError("checkpoint event_names do not match the "
                             "current engine params")
        state.batch = batch
        state.user_of_code = np.array(arrays["user_of_code"], np.int32)
        state.row_counts = np.array(arrays["row_counts"], np.int64)
        # the user dictionary reconstructs by inverting user_of_code
        # over the batch's entity dictionary (enrollment order is the
        # value order of the map)
        n_users = int(meta["n_users"])
        order = np.full(n_users, -1, np.int64)
        valid = np.flatnonzero(state.user_of_code >= 0)
        order[state.user_of_code[valid]] = valid
        if n_users and (order < 0).any():
            raise ValueError("checkpoint user map is not a bijection")
        state.user_dict = IdDict(
            [batch.entity_dict.str(int(c)) for c in order])
        state.impl = str(meta.get("impl") or "sparse")
        for k, name in enumerate(state.event_names):
            st = state.types[name]
            p = f"t{k}_"
            st.codes = np.array(arrays[p + "codes"], np.int64)
            st.item_dict = IdDict(
                [batch.target_dict.str(int(c)) for c in st.codes])
            st.local_of_target = np.array(arrays[p + "local_of_target"],
                                          np.int64)
            st.pairs = np.array(arrays[p + "pairs"], np.int64)
            st.col_counts = np.array(arrays[p + "col_counts"], np.int64)
            ri = np.array(arrays[p + "raw_items"], np.int32)
            rt = np.array(arrays[p + "raw_times"], np.float64)
            if len(ri) != len(rt):
                raise ValueError("checkpoint raw popularity arrays torn")
            st.raw_items = [ri] if len(ri) else []
            st.raw_times = [rt] if len(rt) else []
            if p + "idx" in arrays:
                st.idx = np.array(arrays[p + "idx"], np.int32)
                st.llr = np.array(arrays[p + "llr"], np.float32)
            if p + "cell_keys" in arrays:
                st.sc = _SparseCounts(np.array(arrays[p + "cell_keys"]),
                                      np.array(arrays[p + "cell_counts"]))
                st.C = None
            elif p + "dense_C" in arrays:
                st.C = np.array(arrays[p + "dense_C"], np.int32)
                st.sc = None
            else:
                raise ValueError(f"checkpoint carries no counts for {name}")
        if state.state_fingerprint() != int(meta["fingerprint"]):
            raise ValueError("checkpoint integrity fingerprint mismatch")
        if meta.get("props_ever"):
            state._props = {
                k2: dict(v) for k2, v in fold_properties(
                    batch, ds_params.item_entity_type).items()}
            state._props_ever = True
        state.generation = int(meta.get("generation", 0))
        if state._pop_incremental:
            # the running popularity counts are derived state — rebuild
            # from the restored raw lists so post-restore folds keep the
            # incremental path (counts-then-astype equals the full
            # recompute exactly)
            p_st2 = state.types[state.primary]
            items = (np.concatenate(p_st2.raw_items) if p_st2.raw_items
                     else np.zeros(0, np.int32))
            times = (np.concatenate(p_st2.raw_times) if p_st2.raw_times
                     else np.zeros(0, np.float64))
            state._pop = [
                np.bincount(items, minlength=max(p_st2.n_items, 1))
                .astype(np.int64),
                float(times.min()) if len(times) else np.inf,
                float(times.max()) if len(times) else -np.inf,
            ]
        state.model = None
        state.model = state._emit()
        return state
