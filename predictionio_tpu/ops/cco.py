"""Correlated Cross-Occurrence (CCO) — the Universal Recommender's core op.

Reference: ActionML's URAlgorithm delegates to Mahout-Samsara
``SimilarityAnalysis.cooccurrencesIDSs`` (Spark DRM block matmuls of
``P'ᵀ·A_t`` + Dunning LLR + per-row top-k; SURVEY.md §2 'Universal
Recommender').  TPU-first re-expression (SURVEY.md §7.5):

- Interactions arrive as raw (user, item) COO pairs per event type — **no
  host dedup pass**: the device densify is a scatter-max, and users are
  unique within a chunk, so duplicate pairs collapse on device and the LLR
  marginals (distinct-user counts) fall out of the densified matrices as
  column sums.  The O(E log E) host ``np.unique`` that would dominate at
  billion-event scale never runs.
- Users are processed in fixed-size chunks: each chunk densifies to 0/1
  matrices ``P_b [B, I_p]`` / ``A_b [B, I_t]`` by scatter, then
  ``C += P_bᵀ @ A_b`` — an MXU matmul with exact int32 count accumulation
  (bf16 inputs by default; int8 — 2× MXU rate on v5e — via
  PIO_CCO_MM_DTYPE once measured faster).  ``lax.scan`` over chunks keeps
  it one compiled program.
- Every strategy takes what the engines have: COO pairs.  ``_plan``
  decides the strategy of an event type once, from the sizes, the backend
  and the mesh, before any host layout; ``cco_train_indicators`` (all of
  a train's event types) and ``cco_indicators_coo`` (one) run it.  The
  dense and host-sparse runners stage the primary once a train and
  dispatch each event type asynchronously — host layout of event type
  t+1 overlaps device compute of event type t, results download at the
  end; the tiled strategies stage and densify it once per event type and
  wait for each.
- Huge item catalogs take the tiled path: item columns are processed in
  tiles, each tile's LLR scores merging into a running per-row top-k
  (concat + ``lax.top_k``), so the full I_p×I_t count matrix is never
  materialized.  Marginals accumulate on device inside the same scan.
- Multi-device: users are sharded over the mesh's ``dp`` axis, and counts
  are the only quantity that crosses chips.  The dense strategy ``psum``s
  its count matrix.  The P-resident tiled strategy is the one-device
  program with an axis name: each chip keeps the densified primary of its
  own contiguous range of users, multiplies locally, and every partial
  count tile is reduce-scattered over the primary's item rows
  (``psum_scatter``), so a chip scores, selects and carries only its own
  rows (two programs an event type, as on one device).  The chunked tiled
  strategy, for a primary that no chip's share keeps resident, still
  dispatches one sharded step a tile and ``psum``s the whole tile.

LLR is Dunning's G² exactly as Mahout's ``LogLikelihood.logLikelihoodRatio``
computes it (determinant formulation; see ``llr_score``).
"""

from __future__ import annotations

import dataclasses
import math
import os as _os
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs.spans import span
from predictionio_tpu.utils.device import PLAN_BYTES, noted, stage


# ---------------------------------------------------------------------------
# host-side layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BlockedInteractions:
    """COO pairs grouped into fixed-size user blocks, padded to equal length:
    the layout of the chunked tiled strategy, and of no other.

    local_u[b, e] is the in-block user row, item[b, e] the item id, for the
    first count[b] slots of block b; the slots after them are padding (0).
    Block b covers global users [b*block, (b+1)*block).  Pairs need NOT be
    unique: every device consumer densifies by scatter-max, which collapses
    duplicates.
    """

    local_u: np.ndarray   # int32 [n_blocks, E]
    item: np.ndarray      # int32 [n_blocks, E]
    count: np.ndarray     # int32 [n_blocks]: the valid slots of each block
    n_users: int
    n_items: int
    user_block: int

    @property
    def n_blocks(self) -> int:
        return self.local_u.shape[0]

    @property
    def mask(self) -> np.ndarray:
        """bool [n_blocks, E]: which slots hold a pair."""
        return np.arange(self.local_u.shape[1]) < self.count[:, None]


def block_interactions(
    user: np.ndarray,
    item: np.ndarray,
    n_users: int,
    n_items: int,
    user_block: int = 1024,
    pad_multiple: int = 8,
) -> BlockedInteractions:
    """Group raw COO by user block.  No dedup: the device consumer dedups
    by construction (scatter-max densify)."""
    user = np.asarray(user, np.int32)
    item = np.asarray(item, np.int32)
    n_blocks = max(math.ceil(n_users / user_block), 1)
    if len(user) and 0 <= int(user.min()) and int(user.max()) < n_blocks * user_block:
        from predictionio_tpu.native import layout_chunks

        native = layout_chunks(user, item, user_block, n_blocks, pad_multiple)
        if native is not None:
            lu, it, cnt = native
            return BlockedInteractions(lu, it, cnt, n_users, n_items,
                                       user_block)
    return block_interactions_stream(
        [(user, item)], n_users, n_items,
        user_block=user_block, pad_multiple=pad_multiple,
    )


def block_interactions_stream(
    batches,
    n_users: int,
    n_items: int,
    user_block: int = 1024,
    pad_multiple: int = 8,
) -> BlockedInteractions:
    """``block_interactions`` over an ITERATOR of (user, item) array batches
    — the host-staging path for event logs larger than comfortable as one
    array (SURVEY.md §7 hard part (a)).  Peak host memory is the grouped
    per-block copies plus the padded layout (~2× the data, freed block by
    block as the layout fills) — it avoids the raw + sorted + layout 3×
    peak of a one-shot argsort, not the copies themselves."""
    n_blocks = max(math.ceil(n_users / user_block), 1)
    per_block_u: List[List[np.ndarray]] = [[] for _ in range(n_blocks)]
    per_block_i: List[List[np.ndarray]] = [[] for _ in range(n_blocks)]
    for user, item in batches:
        user = np.asarray(user, np.int32)
        item = np.asarray(item, np.int32)
        blk = user // user_block
        order = np.argsort(blk, kind="stable")
        user, item, blk = user[order], item[order], blk[order]
        counts = np.bincount(blk, minlength=n_blocks)
        start = 0
        for b in range(n_blocks):
            c = int(counts[b])
            if c:
                sl = slice(start, start + c)
                per_block_u[b].append(user[sl] % user_block)
                per_block_i[b].append(item[sl])
                start += c
    sizes = [sum(len(a) for a in lists) for lists in per_block_u]
    width = max(max(sizes) if sizes else 1, 1)
    width = ((width + pad_multiple - 1) // pad_multiple) * pad_multiple
    lu = np.zeros((n_blocks, width), np.int32)
    it = np.zeros((n_blocks, width), np.int32)
    for b in range(n_blocks):
        c = sizes[b]
        if c:
            lu[b, :c] = np.concatenate(per_block_u[b])
            it[b, :c] = np.concatenate(per_block_i[b])
        per_block_u[b] = per_block_i[b] = []  # free as we go
    return BlockedInteractions(lu, it, np.asarray(sizes, np.int32),
                               n_users, n_items, user_block)


def dedup_pairs(user: np.ndarray, item: np.ndarray, n_items: int):
    """Dedup (user, item) pairs — CCO is binary occurrence.  Host-side
    O(E log E); the training hot path no longer calls this (device
    scatter-max dedups), it remains for CSR construction and tests."""
    user = np.asarray(user, np.int64)
    item = np.asarray(item, np.int64)
    if not len(user):
        return user.astype(np.int32), item.astype(np.int32)
    flat = np.unique(user * n_items + item)
    return (flat // n_items).astype(np.int32), (flat % n_items).astype(np.int32)


# ---------------------------------------------------------------------------
# LLR
# ---------------------------------------------------------------------------


def _llr_mask_scores(c, row_counts, col_counts, n_total, llr_threshold,
                     pallas: str, col_start=None, width: Optional[int] = None,
                     diagonal=None):
    """Shared LLR scoring + masking used by EVERY strategy (dense, chunked
    tiled, P-resident tiled): G² over the 2×2 table, -inf where there is no
    cooccurrence or the score misses the significance threshold.  The
    counts may be of any number type.  ``width``: score that many columns
    of ``c`` from ``col_start``, a multiple of ``width`` (a tile read in
    its group).  ``diagonal``: -inf too where row − column == diagonal,
    the item against itself (``_self_pair_diagonal``); this is the one
    place the UR programs mask self-pairs, so every tile is masked once."""
    if pallas != "off":
        from predictionio_tpu.ops.pallas_kernels import llr_masked_scores

        return llr_masked_scores(c, row_counts, col_counts, n_total,
                                 llr_threshold, col_start=col_start,
                                 width=width, diagonal=diagonal)
    if width is not None and width != c.shape[1]:
        c = jax.lax.dynamic_slice_in_dim(c, col_start, width, 1)
        col_counts = jax.lax.dynamic_slice_in_dim(col_counts, col_start, width)
    c = c.astype(jnp.float32)
    k11 = c
    k12 = row_counts.astype(jnp.float32)[:, None] - c
    k21 = col_counts.astype(jnp.float32)[None, :] - c
    k22 = n_total - k11 - k12 - k21
    scores = llr_score(k11, k12, k21, k22)
    scores = jnp.where(c > 0, scores, -jnp.inf)
    scores = jnp.where(scores >= llr_threshold, scores, -jnp.inf)
    return _mask_self_pairs(scores, diagonal)


def _self_pair_diagonal(exclude_self: bool, tile_start, row_offset=None):
    """Where a tile's self-pairs lie: row − column of the cell that pairs
    an item with itself (the tile's first column ``tile_start`` less the
    primary item of row 0, ``row_offset``, where the rows are one chip's
    share), or None where nothing is masked."""
    if not exclude_self:
        return None
    return tile_start if row_offset is None else tile_start - row_offset


def _mask_self_pairs(scores, diagonal):
    """-inf where row − column == ``diagonal`` (None: as they are): the
    XLA form of the LLR kernel's mask, fused by XLA into the scores."""
    if diagonal is None:
        return scores
    rows = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
    cols = diagonal + jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(rows == cols, -jnp.inf, scores)


def _llr_attrs(pallas: str, rows: int, width: int,
               exclude_self: bool) -> dict:
    """What a tiled UR program's ``dispatch`` span says of its LLR: the
    kernel's block (``llr_block``), the rows of its partial last block
    (``llr_edge_rows``, 0 where the blocks divide the rows) and where the
    self-pairs are masked (``llr_mask``: in the kernel, by XLA, none)."""
    if pallas == "off":
        return {"llr_mask": "xla" if exclude_self else "none"}
    from predictionio_tpu.ops.pallas_kernels import llr_blocks

    tile_r, tile_c = llr_blocks(rows, width)
    return {"llr_block": f"{tile_r}x{tile_c}", "llr_edge_rows": rows % tile_r,
            "llr_mask": "kernel" if exclude_self else "none"}


def topk_impl() -> str:
    """'pallas' | 'lax' for the tiled running top-k merge's per-tile
    selection, derived from ``pallas_mode()``: the in-VMEM tournament
    (pallas_kernels.tile_topk_desc) wherever Pallas kernels run — a TPU
    backend, or ``PIO_PALLAS=interpret`` — and ``lax.top_k`` otherwise
    (a Mosaic kernel compiles only for a TPU).

    Why: in ``ur-ecom-100k.train`` the lax merge's tile-wide sort and the
    index gather after it took 16.31 + 4.46 s of a 32.0 s job (ledger,
    PR 24); the tournament took 7.64 s of a job while its network ran
    along the lanes (PR 25) and takes 0.7 s since it runs across whole
    vregs (14.5 ms a tile, chip run, PR 32: PERF.md section 6)."""
    from predictionio_tpu.ops.pallas_kernels import pallas_mode

    return "lax" if pallas_mode() == "off" else "pallas"


def _carry_width(top_k: int, impl: str) -> int:
    """Running-merge carry width: the Pallas network needs a pow2 block."""
    if impl == "pallas":
        from predictionio_tpu.ops.topk import block_width

        return block_width(top_k)
    return top_k


def _topk_attrs(impl: str, tile: int, top_k: int) -> dict:
    """What a tiled program's ``dispatch`` span says of its selection:
    which merge, and for the tournament the network the kernel is traced
    with at this (tile, carry) — ``pallas_kernels.topk_plan``."""
    if impl != "pallas":
        return {"topk": impl}
    from predictionio_tpu.ops.pallas_kernels import topk_plan

    plan = topk_plan(tile, _carry_width(top_k, impl))
    return {"topk": impl, "topk_block": plan.block,
            "topk_slab_stages": plan.slab_stages,
            "topk_lane_stages": plan.lane_stages}


def _merge_topk(best_scores, best_idx, scores, tile_start, tile: int,
                top_k: int, impl: str = "lax"):
    """Shared running top-k merge for the tiled strategies.  The scores
    come with their self-pairs masked already (by the scoring step, so
    every row still gets a full top_k correlators).

    impl='lax': top_k over concat(carry, tile), then a gather of the
    kept indices — on a TPU XLA's full variadic row sort, 326 + 89 ms a
    [100000, 4096] tile (ledger, PR 24); the off-TPU path and the parity
    reference.
    impl='pallas': one in-VMEM bitonic pass selects the tile's top block
    (pallas_kernels.tile_topk_desc), then a log2(b)-stage sorted merge
    with the carry on [I, 2b] — the tile-wide sort never happens: 14.5 ms
    a tile for the kernel at a carry of 64 and 4.7 ms at 8 (chip run,
    PR 32; 153 ms before it), ~2 ms for the merge (chip run, PR 25).  The
    carry is then [I, block_width(top_k)], sorted desc; _finalize_topk
    slices back to top_k.
    """
    tile_idx = tile_start + jnp.arange(tile, dtype=jnp.int32)[None, :]
    if impl == "pallas":
        from predictionio_tpu.ops.pallas_kernels import tile_topk_desc
        from predictionio_tpu.ops.topk import merge_desc

        b = best_scores.shape[1]
        with stage("cco.topk_merge"):
            ts, ti = tile_topk_desc(scores, b)
            return merge_desc(best_scores, best_idx, ts, tile_start + ti)
    with stage("cco.topk_merge"):
        all_scores = jnp.concatenate([best_scores, scores], axis=1)
        all_idx = jnp.concatenate(
            [best_idx, jnp.broadcast_to(tile_idx, scores.shape)], axis=1)
        new_scores, pos = jax.lax.top_k(all_scores, top_k)
    with stage("cco.topk_gather"):
        return new_scores, jnp.take_along_axis(all_idx, pos, axis=1)


def _fetch(a) -> np.ndarray:
    """A device array on the host; a row-sharded one whose mesh spans
    processes is gathered first (np.asarray reads only what this process
    can address)."""
    if not getattr(a, "is_fully_addressable", True):
        from jax.experimental import multihost_utils

        a = multihost_utils.process_allgather(a, tiled=True)
    return np.asarray(a)


def _finalize_topk(best_scores, best_idx, n_items_t: int,
                   top_k: Optional[int] = None,
                   n_rows: Optional[int] = None):
    """Shared host epilogue: -1-pad entries that are -inf or tile padding;
    slice a pow2-widened pallas-merge carry back to the requested top_k,
    and a row-sharded carry (read back whole from its chips) to the
    ``n_rows`` primary items that are no padding."""
    if isinstance(best_scores, np.ndarray):     # the host tail's own arrays
        scores, idx = best_scores, np.asarray(best_idx)
    else:
        with span("device_wait", bytes=best_scores.nbytes + best_idx.nbytes):
            scores = _fetch(best_scores)
            idx = _fetch(best_idx)
    if n_rows is not None and scores.shape[0] > n_rows:
        scores, idx = scores[:n_rows], idx[:n_rows]
    if top_k is not None and scores.shape[1] > top_k:
        scores, idx = scores[:, :top_k], idx[:, :top_k]
    idx = np.where((scores > -np.inf) & (idx < n_items_t), idx, -1)
    return np.where(idx >= 0, scores, -np.inf), idx


def _llr_term(k, sign_d, d, row_marg, col_marg):
    # k·log(k·N/(row·col)) rewritten as k·log1p(±D/(row·col)); the ±1e-9
    # clamp guards fp drift past the log1p pole when k·N ≪ row·col.
    arg = sign_d * d / jnp.maximum(row_marg * col_marg, 1e-30)
    return jnp.where(k > 0, k * jnp.log1p(jnp.maximum(arg, -1.0 + 1e-9)), 0.0)


def llr_score(k11, k12, k21, k22):
    """Dunning G² (Mahout LogLikelihood.logLikelihoodRatio), in the
    determinant form: for a 2×2 table, k_ij·N − r_i·c_j = ±D with
    D = k11·k22 − k12·k21, so G² = 2·Σ k·log1p(±D/(r·c)).

    Unlike the textbook entropy form (±Σ xlogx over marginals), every term
    here is O(k·log-ratio) — no cancellation of O(N·logN) quantities — so
    f32 on the VPU stays accurate at billion-event N where the entropy form
    quantizes G² to multiples of eps·N·logN.
    """
    r1, r2 = k11 + k12, k21 + k22
    c1, c2 = k11 + k21, k12 + k22
    d = k11 * k22 - k12 * k21
    g2 = 2.0 * (
        _llr_term(k11, 1.0, d, r1, c1)
        + _llr_term(k12, -1.0, d, r1, c2)
        + _llr_term(k21, -1.0, d, r2, c1)
        + _llr_term(k22, 1.0, d, r2, c2)
    )
    return jnp.maximum(g2, 0.0)


# ---------------------------------------------------------------------------
# device kernels — shared pieces
# ---------------------------------------------------------------------------


def _matmul_dtype() -> str:
    """'bf16' (default) or 'int8' via PIO_CCO_MM_DTYPE.

    Both are exact for 0/1 inputs.  int8 runs the v5e MXU at 2× the bf16
    rate on paper; no chip run has timed it (ROADMAP S3), so int8 stays
    opt-in until one shows the MXU lowering wins."""
    conf = _os.environ.get("PIO_CCO_MM_DTYPE", "bf16").lower()
    return conf if conf in ("int8", "bf16") else "bf16"


def _densify(local_u, item_local, valid, block: int, width: int, dtype):
    """0/1 matrix [block, width] from in-block COO (scatter-max collapses
    duplicate pairs — this IS the dedup)."""
    m = jnp.zeros((block, width), dtype)
    return m.at[local_u, item_local].max(valid.astype(dtype))


def _count_matmul(Pm, Am, mm: str):
    """One user-chunk's count contribution, EXACT as int32 either way:
    int8 accumulates in int32 natively; bf16 accumulates the chunk in f32
    (per-chunk counts ≤ chunk size ≪ 2²⁴, so exactly representable) and
    casts — cross-chunk accumulation then stays integer to 2³¹, where
    f32 += 1 would silently saturate at 2²⁴."""
    if mm == "int8":
        return jax.lax.dot_general(
            Pm, Am, (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return jax.lax.dot_general(
        Pm, Am, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def _col_count(M) -> jnp.ndarray:
    """Per-chunk column marginal, exact int32 (see _count_matmul)."""
    if M.dtype == jnp.int8:
        return M.sum(0, dtype=jnp.int32)
    return M.sum(0, dtype=jnp.float32).astype(jnp.int32)


def _mm_in_dtype():
    return jnp.int8 if _matmul_dtype() == "int8" else jnp.bfloat16


def _varying(tree, axis_name: Optional[str]):
    """A scan's initial carry as one that differs from chip to chip: under
    ``shard_map`` (``axis_name``) a carry built from constants has to say
    so before a chip's own values are folded into it; as it is without."""
    if axis_name is None:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.pcast(x, (axis_name,), to="varying"), tree)


def _pad128(n: int) -> int:
    """``n`` rounded up to whole 128-wide tiles, at least one."""
    return max(((n + 127) // 128) * 128, 128)


def _tiling(n_items_t: int, item_tile: int) -> Tuple[int, int]:
    """``(tile, n_tiles)`` of an event type's items under ``itemTile``."""
    tile = min(item_tile, max(n_items_t, 1))
    return tile, math.ceil(n_items_t / tile)


# ---------------------------------------------------------------------------
# P-resident tiled path (huge catalogs, but the densified primary fits HBM)
# ---------------------------------------------------------------------------

# Budget for the P-resident program's plan (see _plan for what the plan
# counts).  The rest of the chip is for what the plan does not count: the
# COO arrays and the top-k carry of the event type in flight, the results
# of the one before it, and the allocator's own slack.
_TILED_P_BYTES = PLAN_BYTES


def _densify_coo(gu, gi, valid, n_rows: int, n_cols: int):
    """One scatter-max of global COO into a 0/1 matrix [n_rows, n_cols]."""
    dtype = _mm_in_dtype()
    return jnp.zeros((n_rows, n_cols), dtype).at[
        jnp.where(valid, gu, 0), jnp.where(valid, gi, 0)
    ].max(valid.astype(dtype))


@partial(jax.jit, static_argnames=("n_rows", "n_cols"))
def _densify_global(gu, gi, valid, n_rows: int, n_cols: int):
    """The primary's one-off densify into a resident 0/1 matrix."""
    with stage("cco.densify_primary"):
        return _densify_coo(gu, gi, valid, n_rows, n_cols)


@jax.jit
def _primary_counts(Pm):
    """The resident primary's items' user counts (``_col_count``), one
    program where two eager operations ran."""
    with stage("cco.densify_primary"):
        return _col_count(Pm)


def _cco_tile_body_resident(
    P, rc, a_gu, a_gi, a_valid,
    n_total, best_scores, best_idx, tile_start,
    tile: int, top_k: int, llr_threshold,
    exclude_self: bool, pallas: str, mm: str, topk: str = "lax",
    axis_name: Optional[str] = None,
):
    """One item tile against the RESIDENT densified primary: densify only
    this tile's slice of A (one scatter), one matmul, LLR, top-k merge —
    the primary is never re-densified per tile, unlike the chunked tiled
    path which pays n_tiles × that cost.

    With ``axis_name`` (under ``shard_map``) ``P`` and the pairs are one
    chip's range of users, so the product is a partial count tile: it is
    reduce-scattered over the primary's item rows, the tile's column
    counts are ``psum``'d, and ``rc``, the scores and the carry are this
    chip's rows alone.  Counts stay exact: each chip's partial is an
    integer below 2²⁴ in float32, and so is their sum (``_plan``)."""
    n_rows = P.shape[0]
    row_offset = None
    with stage("cco.densify_tile"):
        a_local = a_gi - tile_start
        in_tile = a_valid & (a_local >= 0) & (a_local < tile)
        A_t = _densify_coo(a_gu, jnp.where(in_tile, a_local, 0), in_tile,
                           n_rows, tile)
    with stage("cco.count_matmul"):
        c = _count_matmul(P, A_t, mm).astype(jnp.float32)
        cct = _col_count(A_t).astype(jnp.float32)
    if axis_name is not None:
        with stage("cco.exchange"):
            c = jax.lax.psum_scatter(c, axis_name, scatter_dimension=0,
                                     tiled=True)
            cct = jax.lax.psum(cct, axis_name)
        row_offset = jax.lax.axis_index(axis_name) * c.shape[0]
    with stage("cco.llr"):
        scores = _llr_mask_scores(
            c, rc, cct, n_total, llr_threshold, pallas,
            diagonal=_self_pair_diagonal(exclude_self, tile_start, row_offset))
    return _merge_topk(best_scores, best_idx, scores, tile_start, tile,
                       top_k, impl=topk)


def _scan_tiles(step, n_items_p: int, n_tiles: int, tile: int, top_k: int,
                carry_k: Optional[int] = None,
                axis_name: Optional[str] = None):
    """Shared scan harness for the tiled strategies: run ``step(bs, bi,
    tile_start)`` over every tile start in ONE compiled program.

    A Python-level tile loop pays a dispatch per tile and blocks XLA
    from pipelining the scatter of tile t+1 under the matmul of tile t;
    the scan removes both.  ``carry_k`` widens the running-merge carry to
    the pallas merge's pow2 block (see _carry_width).  Under ``shard_map``
    (``axis_name``) the carry is one chip's ``n_items_p`` rows."""
    init = (jnp.full((n_items_p, carry_k or top_k), -jnp.inf, jnp.float32),
            jnp.zeros((n_items_p, carry_k or top_k), jnp.int32))
    init = _varying(init, axis_name)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile

    def body(carry, tile_start):
        return step(*carry, tile_start), None

    (best_scores, best_idx), _ = jax.lax.scan(body, init, starts)
    return best_scores, best_idx


def _resident_all_tiles(
    P, rc, a_gu, a_gi, a_valid, n_total,
    n_tiles: int, tile: int, top_k: int, llr_threshold,
    exclude_self: bool, pallas: str, mm: str, topk: str,
    axis_name: Optional[str] = None,
):
    """The scan over the RESIDENT path's item tiles (_scan_tiles), traced
    by the one-device program and, with an axis name, by the sharded one:
    ``rc`` has one entry for each row of the carry."""

    def step(bs, bi, tile_start):
        return _cco_tile_body_resident(
            P, rc, a_gu, a_gi, a_valid, n_total, bs, bi, tile_start,
            tile=tile, top_k=top_k, llr_threshold=llr_threshold,
            exclude_self=exclude_self, pallas=pallas, mm=mm, topk=topk,
            axis_name=axis_name)

    return _scan_tiles(step, rc.shape[0], n_tiles, tile, top_k,
                       carry_k=_carry_width(top_k, topk),
                       axis_name=axis_name)


@partial(jax.jit, static_argnames=(
    "n_tiles", "tile", "top_k", "exclude_self", "pallas", "mm", "topk"))
def _cco_resident_all_tiles(
    P, rc, a_gu, a_gi, a_valid, n_total,
    n_tiles: int, tile: int, top_k: int, llr_threshold,
    exclude_self: bool, pallas: str, mm: str, topk: str = "lax",
):
    """All RESIDENT-path item tiles in one compiled program (_scan_tiles)."""
    return _resident_all_tiles(
        P, rc, a_gu, a_gi, a_valid, n_total, n_tiles=n_tiles, tile=tile,
        top_k=top_k, llr_threshold=llr_threshold, exclude_self=exclude_self,
        pallas=pallas, mm=mm, topk=topk)


def _valid_slots(count, width: int):
    """bool [width]: the first ``count[0]`` slots of one chip's row of a
    ``_StagedCOO`` hold a pair."""
    return jax.lax.iota(jnp.int32, width) < count[0]


@partial(jax.jit, static_argnames=("mesh", "n_rows", "n_cols"))
def _densify_sharded(lu, it, cnt, mesh: Mesh, n_rows: int, n_cols: int):
    """``_densify_global`` on every chip of ``mesh`` for its own range of
    users: the primary as ``[dp * n_rows, n_cols]`` sharded by rows, and
    its items' user counts summed over the chips and scattered like the
    count tiles (chip d holds the counts of its ``n_cols / dp`` items)."""

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"),) * 3,
             out_specs=(P("dp"), P("dp")))
    def run(lu, it, cnt):
        Pm = _densify_global(lu[0], it[0], _valid_slots(cnt, lu.shape[1]),
                             n_rows, n_cols)
        with stage("cco.densify_primary"):
            counts = _col_count(Pm)
        with stage("cco.exchange"):
            return Pm, jax.lax.psum_scatter(counts, "dp", tiled=True)

    return run(lu, it, cnt)


@partial(jax.jit, static_argnames=(
    "mesh", "n_tiles", "tile", "top_k", "exclude_self", "pallas", "mm",
    "topk"))
def _cco_sharded_all_tiles(
    Pm, rc, a_lu, a_it, a_cnt, n_total, mesh: Mesh,
    n_tiles: int, tile: int, top_k: int, llr_threshold,
    exclude_self: bool, pallas: str, mm: str, topk: str = "lax",
):
    """``_cco_resident_all_tiles`` with the users sharded over ``dp``: one
    compiled program of the same scan under ``shard_map``, the carry
    sharded by the primary's item rows."""
    rows, rep = P("dp"), P()

    # the Pallas interpreter slices a chip's own block by a loop index
    # that is the same on every chip, which the varying-axes check refuses
    @partial(jax.shard_map, mesh=mesh, in_specs=(rows,) * 5 + (rep, rep),
             out_specs=(rows, rows), check_vma=pallas != "interpret")
    def run(Pm, rc, a_lu, a_it, a_cnt, n_total, llr_threshold):
        return _resident_all_tiles(
            Pm, rc, a_lu[0], a_it[0], _valid_slots(a_cnt, a_lu.shape[1]),
            n_total, n_tiles=n_tiles, tile=tile, top_k=top_k,
            llr_threshold=llr_threshold, exclude_self=exclude_self,
            pallas=pallas, mm=mm, topk=topk, axis_name="dp")

    return run(Pm, rc, a_lu, a_it, a_cnt, n_total, llr_threshold)


def _pad_items(n_items_p: int, dp: int) -> int:
    """The primary's item rows as a program over ``dp`` chips holds them:
    whole 128-row tiles a chip, so that the reduce-scatter's shards are
    the chips' own rows as they lie (at a multiple of 8 the TPU compiler
    pads each shard to 128 itself and moves the difference between
    neighbours, a pad, a permute and a concatenate a tile [AOT, PR 33]);
    as they are on one device."""
    return n_items_p if dp == 1 else math.ceil(n_items_p / (dp * 128)) * dp * 128


def _cco_resident(
    pu: np.ndarray, pi: np.ndarray, au: np.ndarray, ai: np.ndarray,
    n_users: int, n_items_p: int, n_items_t: int,
    top_k: int, llr_threshold: float, item_tile: int, exclude_self: bool,
    mesh: Optional[Mesh] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """One event type on the P-resident program, from the pairs as the
    engine has them.  On one device the int32 casts are all the host
    layout there is; on a mesh the pairs are bucketed into ``dp``
    contiguous ranges of users (``_stage_chunked``), one a chip."""
    from predictionio_tpu.ops.pallas_kernels import pallas_mode

    tile = min(item_tile, max(n_items_t, 1))
    n_tiles = math.ceil(n_items_t / tile)
    topk = topk_impl()
    static = dict(n_tiles=n_tiles, tile=tile, top_k=top_k,
                  exclude_self=exclude_self, pallas=pallas_mode(),
                  mm=_matmul_dtype(), topk=topk)
    if mesh is None:
        with span("layout"):
            pu, pi = np.asarray(pu, np.int32), np.asarray(pi, np.int32)
            au, ai = np.asarray(au, np.int32), np.asarray(ai, np.int32)
        n_rows = _pad128(n_users)
        with span("h2d", bytes=pu.nbytes + pi.nbytes):
            p_gu, p_gi = jnp.asarray(pu), jnp.asarray(pi)
            p_valid = jnp.ones(len(pu), bool)
        with span("dispatch", program="_densify_global"):
            Pm = noted(_densify_global, p_gu, p_gi, p_valid, n_rows,
                       n_items_p)
            rc = noted(_primary_counts, Pm)
        with span("h2d", bytes=au.nbytes + ai.nbytes):
            a_gu, a_gi = jnp.asarray(au), jnp.asarray(ai)
            a_valid = jnp.ones(len(au), bool)
        with span("dispatch", program="_cco_resident_all_tiles",
                  **_topk_attrs(topk, tile, top_k),
                  **_llr_attrs(static["pallas"], n_items_p, tile,
                               exclude_self)):
            best_scores, best_idx = noted(
                _cco_resident_all_tiles,
                Pm, rc, a_gu, a_gi, a_valid, float(n_users),
                llr_threshold=float(llr_threshold), **static)
        return _finalize_topk(best_scores, best_idx, n_items_t, top_k)

    dp = mesh.shape["dp"]
    users_chip = math.ceil(max(n_users, 1) / dp)
    rows = _pad_items(n_items_p, dp)
    by_user = NamedSharding(mesh, P("dp"))
    p = _stage_chunked(pu, pi, users_chip, dp, by_user, by_chip=True)
    with span("dispatch", program="_densify_sharded", dp=dp):
        Pm, rc = noted(_densify_sharded, p.local_u, p.item, p.count,
                       mesh=mesh, n_rows=_pad128(users_chip), n_cols=rows)
    # the primary against itself: the pairs are staged already
    a = p if au is pu and ai is pi else _stage_chunked(
        au, ai, users_chip, dp, by_user, by_chip=True)
    with span("dispatch", program="_cco_sharded_all_tiles", dp=dp,
              tiles=n_tiles, rows_per_chip=rows // dp,
              # what one chip sends in the job's reduce-scatters: all of
              # every float32 partial count tile but its own rows
              exchange_mb=n_tiles * (rows - rows // dp) * tile * 4 / 1e6,
              **_topk_attrs(topk, tile, top_k),
              **_llr_attrs(static["pallas"], rows // dp, tile, exclude_self)):
        best_scores, best_idx = noted(
            _cco_sharded_all_tiles,
            Pm, rc, a.local_u, a.item, a.count, float(n_users),
            llr_threshold=float(llr_threshold), mesh=mesh, **static)
    return _finalize_topk(best_scores, best_idx, n_items_t, top_k,
                          n_rows=n_items_p)


# ---------------------------------------------------------------------------
# tiled path (huge item catalogs; the count matrix never materializes)
# ---------------------------------------------------------------------------


def _cooccurrence_group(
    p_lu, p_it, p_cnt,       # primary blocks [n_blocks, E_p], [n_blocks]
    a_lu, a_it, a_cnt,       # other blocks   [n_blocks, E_a], [n_blocks]
    rc, count_rows,
    block: int,
    n_items_p: int,
    start,
    tile: int,
    group: int,
    axis_name: Optional[str] = None,
):
    """The counts of ``group`` adjacent item tiles from column ``start``
    AND the LLR marginals, on device, every user block densified once
    for all of them:
    C [I_p, group·tile] = Σ_b P_bᵀ A_b[:, start : start + group·tile];
    cc = Σ_b colsum(A_b[:, the same columns]); and, where ``count_rows``
    (a bool, traced or not: the primary's counts are the same numbers in
    every group, so one group of an event type sums them), ``rc`` +
    Σ_b colsum(P_b).  Marginals come from the densified (hence dedup'd)
    matrices — no host unique pass feeds this path.  ``p_cnt``/``a_cnt``
    give each block's valid slots; validity is an iota comparison on
    device, as in ``_cco_counts_dense``."""
    in_dtype = _mm_in_dtype()
    mm = _matmul_dtype()
    e_p, e_a = p_lu.shape[1], a_lu.shape[1]
    width = group * tile

    def body(carry, xs):
        C, rc, cc = carry
        plu, pit, pcnt, alu, ait, acnt = xs
        with stage("cco.densify_block"):
            pvalid = jax.lax.iota(jnp.int32, e_p) < pcnt
            pb = _densify(plu, pit, pvalid, block, n_items_p, in_dtype)
            a_local = ait - start
            in_group = ((jax.lax.iota(jnp.int32, e_a) < acnt)
                        & (a_local >= 0) & (a_local < width))
            ab = _densify(alu, jnp.where(in_group, a_local, 0), in_group,
                          block, width, in_dtype)
        with stage("cco.count_matmul"):
            C = C + _count_matmul(pb, ab, mm)
            rc = jax.lax.cond(count_rows, lambda: rc + _col_count(pb),
                              lambda: rc)
            cc = cc + _col_count(ab)
        return (C, rc, cc), None

    init = (jnp.zeros((n_items_p, width), jnp.int32), rc,
            jnp.zeros((width,), jnp.int32))
    out, _ = jax.lax.scan(body, _varying(init, axis_name),
                          (p_lu, p_it, p_cnt, a_lu, a_it, a_cnt))
    return out


@partial(
    jax.jit,
    static_argnames=(
        "block", "n_items_p", "tile", "group", "top_k", "axis_name",
        "pallas", "exclude_self", "topk",
    ),
)
def _cco_group_step(
    p_lu, p_it, p_cnt, a_lu, a_it, a_cnt,
    n_total,
    best_scores, best_idx, rc, count_rows,
    start,
    block: int, n_items_p: int, tile: int, group: int, top_k: int,
    llr_threshold: float,
    axis_name: Optional[str] = None,
    pallas: str = "off",
    exclude_self: bool = False,
    topk: str = "lax",
):
    """Process one group of item tiles: cooccurrence counts against each
    user block densified once, then tile by tile as a lone tile would be,
    LLR → merge into top-k.  Returns the carry and the primary's counts
    (``rc`` as given where ``count_rows`` is false)."""
    c, rc, cc = _cooccurrence_group(
        p_lu, p_it, p_cnt, a_lu, a_it, a_cnt, rc, count_rows, block,
        n_items_p, start, tile, group, axis_name,
    )
    if axis_name is not None:
        with stage("cco.exchange"):
            c, rc, cc = jax.lax.psum((c, rc, cc), axis_name)

    def one_tile(g, best):
        # one tile at a time: a loop, so that the compiler holds one
        # tile's scores, not the group's; the kernel reads the tile
        # inside the carried int32 group
        tile_start = start + g * tile
        with stage("cco.llr"):
            scores = _llr_mask_scores(
                c, rc, cc, n_total, llr_threshold, pallas,
                col_start=g * tile, width=tile,
                diagonal=_self_pair_diagonal(exclude_self, tile_start))
        return _merge_topk(*best, scores, tile_start, tile, top_k, impl=topk)

    best = jax.lax.fori_loop(0, group, one_tile, (best_scores, best_idx))
    return (*best, rc)


@partial(
    jax.jit,
    static_argnames=(
        "n_tiles", "group", "block", "n_items_p", "tile", "top_k", "pallas",
        "exclude_self", "topk",
    ),
)
def _cco_chunked_all_tiles(
    p_lu, p_it, p_cnt, a_lu, a_it, a_cnt, n_total,
    n_tiles: int, group: int, block: int, n_items_p: int, tile: int,
    top_k: int, llr_threshold, pallas: str, exclude_self: bool,
    topk: str = "lax",
):
    """All chunked-path item tiles in one compiled program: a scan over
    the whole groups of ``group`` tiles, the first of which sums the
    primary's counts, and the tiles left over (``n_tiles`` mod ``group``)
    as one step of their own size, not a group padded with empty tiles."""

    def step(carry, start, size, count_rows):
        return _cco_group_step(
            p_lu, p_it, p_cnt, a_lu, a_it, a_cnt, n_total, *carry,
            count_rows, start, block=block, n_items_p=n_items_p, tile=tile,
            group=size, top_k=top_k, llr_threshold=llr_threshold,
            pallas=pallas, exclude_self=exclude_self, topk=topk)

    carry_k = _carry_width(top_k, topk)
    carry = (jnp.full((n_items_p, carry_k), -jnp.inf, jnp.float32),
             jnp.zeros((n_items_p, carry_k), jnp.int32),
             jnp.zeros((n_items_p,), jnp.int32))
    whole, rest = divmod(n_tiles, group)
    starts = jnp.arange(whole, dtype=jnp.int32) * (group * tile)
    carry, _ = jax.lax.scan(
        lambda c, s: (step(c, s, group, s == 0), None), carry, starts)
    if rest:
        carry = step(carry, jnp.int32(whole * group * tile), rest, whole == 0)
    return carry[0], carry[1]


# ---------------------------------------------------------------------------
# dense user-chunked path (default when the count matrix fits HBM)
# ---------------------------------------------------------------------------

# Budgets are sized for one v5e chip (16 GB HBM): the densified chunk pair
# plus the count matrix plus XLA transients.
_DENSE_CHUNK_BYTES = 1 << 30   # per-chunk densified P+A budget
_DENSE_C_BYTES = 2 << 30       # full count-matrix budget (4-byte accum)


def _dense_chunk_users(n_items_p: int, it_pad: int, n_users: int, dp: int = 1) -> int:
    """Chunk size minimizing padded-user waste: pick the number of chunks
    the HBM budget forces (×dp for sharding), then split users evenly —
    NOT budget-rounded chunks, which at e.g. 100k users and a 32k budget
    would pad to 131k users (31% wasted MXU work)."""
    bytes_per_cell = 2 if _matmul_dtype() == "bf16" else 1
    per_user = (n_items_p + it_pad) * bytes_per_cell
    max_chunk = max(_DENSE_CHUNK_BYTES // max(per_user, 1), 256)
    n_chunks = max(math.ceil(n_users / max_chunk), 1)
    n_chunks = math.ceil(n_chunks / dp) * dp
    chunk = math.ceil(n_users / n_chunks / 256) * 256
    return max(chunk, 256)


@partial(jax.jit, static_argnames=("chunk", "n_items_p", "it_pad", "axis_name",
                                   "self_pair", "mm"))
def _cco_counts_dense(
    p_lu, p_it, p_cnt, a_lu, a_it, a_cnt,
    chunk: int, n_items_p: int, it_pad: int,
    axis_name: Optional[str] = None,
    self_pair: bool = False,
    mm: str = "bf16",
):
    """Scan user chunks: densify to 0/1 (dtype per PIO_CCO_MM_DTYPE),
    C += PᵀA on the MXU with exact int32 accumulation (see _count_matmul),
    marginals as column sums — no host-side dedup or counting anywhere.
    ``self_pair`` reuses the densified P as A (primary×primary), halving
    scatter work.  ``p_cnt``/``a_cnt`` give the valid-entry count per
    chunk; validity is an iota comparison on device, so the f32 mask array
    never crosses the wire."""
    in_dtype = jnp.int8 if mm == "int8" else jnp.bfloat16
    e_p = p_lu.shape[1]
    e_a = a_lu.shape[1]

    def body(carry, xs):
        C, rc, cc = carry
        plu, pit, pcnt, alu, ait, acnt = xs
        with stage("cco.densify_block"):
            pvalid = jax.lax.iota(jnp.int32, e_p) < pcnt
            Pm = _densify(plu, pit, pvalid, chunk, n_items_p, in_dtype)
            if self_pair:
                Am = Pm
            else:
                avalid = jax.lax.iota(jnp.int32, e_a) < acnt
                Am = _densify(alu, ait, avalid, chunk, it_pad, in_dtype)
        with stage("cco.count_matmul"):
            C = C + _count_matmul(Pm, Am, mm)
            rc = rc + _col_count(Pm)
            cc = cc + _col_count(Am)
        return (C, rc, cc), None

    init = (
        jnp.zeros((n_items_p, it_pad), jnp.int32),
        jnp.zeros((n_items_p,), jnp.int32),
        jnp.zeros((it_pad,), jnp.int32),
    )
    (C, rc, cc), _ = jax.lax.scan(body, _varying(init, axis_name),
                                  (p_lu, p_it, p_cnt, a_lu, a_it, a_cnt))
    if axis_name is not None:
        with stage("cco.exchange"):
            C, rc, cc = jax.lax.psum((C, rc, cc), axis_name)
    return C, rc, cc


@lru_cache(maxsize=16)
def _counts_dense_sharded_fn(mesh: Mesh, chunk: int, n_items_p: int,
                             it_pad: int, self_pair: bool, mm: str):
    """``_cco_counts_dense`` with the user chunks sharded over ``dp``, built
    once for a (mesh, shape) and jitted: a wrapper rebuilt a dispatch
    would re-trace the sharded program every call, and one that closed
    over its runner would keep the runner's staged arrays alive in
    ``utils.device``'s table of noted programs."""
    spec, rep = P("dp"), P()

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,) * 6,
             out_specs=(rep, rep, rep))
    def counts_sharded(plu, pit, pcnt, alu, ait, acnt):
        return _cco_counts_dense(
            plu, pit, pcnt, alu, ait, acnt,
            chunk=chunk, n_items_p=n_items_p, it_pad=it_pad,
            axis_name="dp", self_pair=self_pair, mm=mm)

    return counts_sharded


@partial(jax.jit, static_argnames=("top_k", "exclude_self", "pallas"))
def _llr_topk_dense(
    C, rc, cc, n_total, llr_threshold,
    top_k: int, exclude_self: bool, pallas: str,
):
    """LLR + whole-row top-k over the full count matrix: ``lax.top_k`` on
    every backend.  A row here is the whole target catalogue; the tiled
    merge's ``tile_topk_desc`` has been measured at the tile's width
    alone."""
    with stage("cco.llr"):
        scores = _llr_mask_scores(
            C, rc, cc, n_total, llr_threshold, pallas,
            diagonal=_self_pair_diagonal(exclude_self, 0))
    with stage("cco.topk_merge"):
        best_scores, best_idx = jax.lax.top_k(scores, top_k)
        return best_scores, best_idx.astype(jnp.int32)


@dataclasses.dataclass
class _StagedCOO:
    """Chunk-grouped pairs staged to device: int32 [n_chunks, E] ids plus a
    per-chunk valid count — 8 bytes/event over the wire (vs 12 with an f32
    mask array), and no dedup/unique pass behind it."""

    local_u: jax.Array    # [n_chunks, E]
    item: jax.Array       # [n_chunks, E]
    count: jax.Array      # [n_chunks]


def _stage_chunked(
    user: np.ndarray, item: np.ndarray,
    chunk: int, n_chunks: int, sharding=None, by_chip: bool = False,
) -> _StagedCOO:
    """Pairs bucketed into ``n_chunks`` contiguous ranges of ``chunk``
    users (in-range user index, padded to the fullest range, a count of
    valid slots a range) and handed to the device.  ``by_chip``: a range
    is one chip's users (``sharding`` puts row d on chip d), and the
    ``layout`` span says how evenly the events fell."""
    from predictionio_tpu.native import layout_chunks

    user = np.asarray(user, np.int32)
    item = np.asarray(item, np.int32)
    if len(user) != len(item):
        raise ValueError(f"user/item length mismatch: {len(user)} vs {len(item)}")
    if len(user) and (int(user.min()) < 0 or int(user.max()) >= chunk * n_chunks):
        raise ValueError(
            f"user ids outside [0, {chunk * n_chunks}) in _stage_chunked")
    with span("layout") as rec:
        native = (layout_chunks(user, item, chunk, n_chunks)
                  if len(user) else None)
        if native is not None:
            lu, it, counts = native   # O(E) two-pass counting layout in C++
        else:
            # numpy fallback: reuse the one shared layout implementation
            b = block_interactions_stream(
                [(user, item)], n_chunks * chunk, 0, user_block=chunk)
            lu, it = b.local_u[:n_chunks], b.item[:n_chunks]
            counts = b.count[:n_chunks]
        if by_chip:
            rec["attrs"] = {
                "dp": n_chunks, "users_per_chip": chunk,
                "events_max_chip": int(counts.max()),
                "pad_events": int(lu.size - counts.sum())}
    if sharding is not None:
        from predictionio_tpu.parallel.sharding import stage_global

        put = lambda x: stage_global(np.asarray(x), sharding)  # noqa: E731
    else:
        put = jnp.asarray
    with span("h2d", bytes=lu.nbytes + it.nbytes + counts.nbytes):
        return _StagedCOO(put(lu), put(it), put(counts))


# ---------------------------------------------------------------------------
# host sparse-count path (CPU backend, low-density workloads)
# ---------------------------------------------------------------------------

# Budgets for the host path: the expanded per-user cross-join and the host
# count matrix.  Past either, the device matmul path is the better deal
# even on CPU.
_SPARSE_PAIR_BUDGET = 200_000_000
_SPARSE_C_BYTES = 512 << 20
_SPARSE_CHUNK_PAIRS = 8_000_000   # cross-join temporaries cap (~64 MB/chunk)
# Matrices at or under this cell count may use the bincount accumulation
# branch (which loses per-cell identities — a chunk that takes it
# downgrades want_coo to a final flatnonzero scan, bounded by this same
# size, instead of returning collected cells).
_SPARSE_BINCOUNT_CELLS = 16 << 20
# Touched-cell collection holds up to one int64 per cross-join pair across
# the per-chunk unique arrays (+ ~the same again transiently in the final
# concatenate+unique) — unbudgeted, that can dwarf _SPARSE_C_BYTES.  Past
# this pair count the tail falls back to one flatnonzero scan of C
# (O(cells), bounded by the 512 MB C budget) instead of collecting.
_SPARSE_COO_PAIRS = 32_000_000   # ~0.25 GB int64 + transient ≈ C budget


class _SparseHostCSR:
    """One event type's deduped (user, item) pairs, user-sorted, with
    degrees — the reusable half of a host cross-join.

    dedup_pairs sorts by flat user·n_items+item, so its output is already
    user-sorted; no extra sort happens here."""

    def __init__(self, user: np.ndarray, item: np.ndarray, n_items: int,
                 n_users: int):
        self.user, self.item = dedup_pairs(user, item, n_items)
        self.n_items = n_items
        self.deg = np.bincount(self.user, minlength=n_users).astype(np.int64)
        self.start = np.concatenate([[0], np.cumsum(self.deg)])
        self.col_counts = np.bincount(
            self.item, minlength=n_items).astype(np.int32)


def _cross_join_pairs(p: _SparseHostCSR, a: _SparseHostCSR) -> int:
    """Σ_u deg_P(u)·deg_A(u) — the exact cross-join expansion size, an
    upper bound on the count matrix's nnz."""
    n = min(len(p.deg), len(a.deg))
    return int((p.deg[:n] * a.deg[:n]).sum())


def _cross_join_flat_chunks(p: _SparseHostCSR, a: _SparseHostCSR):
    """Yield the cross-join's flat cell indices (p_item·I_t + a_item,
    int64) in chunks of ≤ ~_SPARSE_CHUNK_PAIRS pairs — the ONE
    expansion loop behind every host count strategy.  Chunking over
    primary entries keeps the ~5 pair-length temporaries bounded
    (~8·chunk bytes each) instead of scaling with the full pair
    budget."""
    I_t = a.n_items
    rep_all = a.deg[p.user]                   # partners per primary entry
    csum_all = np.cumsum(rep_all)
    lo = 0
    while lo < len(p.user):
        hi = int(np.searchsorted(
            csum_all, (csum_all[lo - 1] if lo else 0) + _SPARSE_CHUNK_PAIRS,
            side="left")) + 1
        hi = min(max(hi, lo + 1), len(p.user))
        rep = rep_all[lo:hi]
        chunk = int(rep.sum())
        if chunk:
            p_rep = np.repeat(p.item[lo:hi], rep)
            offs = np.repeat(a.start[p.user[lo:hi]], rep)
            csum = np.cumsum(rep)
            within = np.arange(chunk, dtype=np.int64) - np.repeat(
                csum - rep, rep)
            yield p_rep.astype(np.int64) * I_t + a.item[offs + within]
        lo = hi


def _sparse_counts(p: _SparseHostCSR, a: _SparseHostCSR,
                   want_coo: bool = False,
                   total_pairs: Optional[int] = None):
    """Exact cooccurrence counts C[i, j] = |users with both| via a
    vectorized per-user cross-join + bincount — O(E + Σ_u deg_P·deg_A)
    host work, no densified matrices anywhere.  Returns None when the
    expansion or the count matrix would blow the host budgets (caller
    falls back to the device path).  Bit-identical to the device counts:
    both count distinct (user, item) pairs.

    ``want_coo=True`` returns ``(C, flat)`` where ``flat`` is the sorted
    unique flat indices of C's nonzero cells.  They are collected from
    the unique-branch chunks whenever the cross-join pair count fits
    the collection's own memory budget (_SPARSE_COO_PAIRS), so the
    sparse LLR tail normally never re-scans the dense matrix; past the
    budget, or when a bincount-branch chunk ran (losing cell
    identities — only possible at ≤ _SPARSE_BINCOUNT_CELLS), one final
    flatnonzero scan recovers them instead."""
    I_p, I_t = p.n_items, a.n_items
    if I_p * I_t * 4 > _SPARSE_C_BYTES:       # true peak: C is int32 below
        return None
    total = _cross_join_pairs(p, a) if total_pairs is None else total_pairs
    if total > _SPARSE_PAIR_BUDGET:
        return None
    # touched-cell tracking: collect from every unique-branch chunk so
    # the tail never has to rescan the dense matrix.  Gated on the pair
    # count only (past the budget, the collection's int64 arrays and
    # their concatenate+unique transients would dwarf the C budget and
    # the flatnonzero fallback is cheaper); a bincount-branch chunk
    # loses cell identities and downgrades to that fallback too.
    touched: Optional[list] = (
        [] if want_coo and total <= _SPARSE_COO_PAIRS else None)
    C = np.zeros(I_p * I_t, np.int32)         # counts ≤ n_users < 2³¹
    if total == 0:
        empty = np.empty(0, np.int64)
        return (C.reshape(I_p, I_t), empty) if want_coo \
            else C.reshape(I_p, I_t)
    for flat in _cross_join_flat_chunks(p, a):
        if I_p * I_t <= _SPARSE_BINCOUNT_CELLS and len(flat) * 8 >= I_p * I_t:
            # dense-ish chunk over a small matrix: an O(n + cells)
            # bincount pass beats the sort-based unique.  Gated on
            # BOTH sizes — with few pairs the per-chunk full-width
            # histogram (+ astype + add over every cell) would be a
            # constant-factor and 128 MB-peak regression exactly in
            # the low-density regime this path serves.
            C += np.bincount(flat, minlength=I_p * I_t).astype(np.int32)
            touched = None   # identities lost; tail rescans (≤ gate)
        else:
            cells, counts = np.unique(flat, return_counts=True)
            C[cells] += counts.astype(np.int32)
            if touched is not None:
                touched.append(cells)
    if not want_coo:
        return C.reshape(I_p, I_t)
    if touched is None:
        flat_nz = np.flatnonzero(C)
    elif touched:
        flat_nz = np.unique(np.concatenate(touched))
    else:
        flat_nz = np.empty(0, np.int64)
    return C.reshape(I_p, I_t), flat_nz


@jax.jit
def _llr_cells(k11, rc_g, cc_g, n_total, llr_threshold):
    """Elementwise LLR + masking on GATHERED nonzero cells — the same op
    sequence as _llr_mask_scores applied to 1-D gathers, so each cell's
    float32 score is bit-identical to the dense [I_p, I_t] tail's value
    at that cell (XLA elementwise math is element-value-deterministic,
    independent of tensor shape)."""
    k12 = rc_g - k11
    k21 = cc_g - k11
    k22 = n_total - k11 - k12 - k21
    s = llr_score(k11, k12, k21, k22)
    s = jnp.where(k11 > 0, s, -jnp.inf)
    return jnp.where(s >= llr_threshold, s, -jnp.inf)


def _score_llr_cells(k11, rc_g, cc_g, n_total, llr_threshold) -> np.ndarray:
    """One vectorized ``_llr_cells`` pass over pre-gathered cells,
    bucketed to the next power of two (zero-padded k11 scores to -inf
    and is sliced off) so the jit compiles once per bucket, not once per
    distinct nnz.  Returns the float32 score per input cell (-inf =
    masked).  This is the ONE scoring entry for every sparse tail — the
    fold engine's pruned re-LLR scores all cells through the same padded
    program the unpruned selection uses, which is what makes pruning
    bit-exact rather than merely close."""
    nnz = len(k11)
    if nnz == 0:
        return np.zeros(0, np.float32)
    pad = 1 << (nnz - 1).bit_length()
    k11_p = np.zeros(pad, np.float32)
    rc_p = np.ones(pad, np.float32)
    cc_p = np.ones(pad, np.float32)
    k11_p[:nnz] = k11
    rc_p[:nnz] = rc_g
    cc_p[:nnz] = cc_g
    return np.asarray(_llr_cells(
        k11_p, rc_p, cc_p,
        jnp.float32(n_total), jnp.float32(llr_threshold)))[:nnz]


def _select_topk_cells(rows, cols, scores, n_rows: int, width: int):
    """Selection half of ``_llr_topk_cells``: given FINITE-scored cells
    (``rows`` output-local in ``[0, n_rows)``), select each row's top
    ``width`` by (score desc, column asc) — exactly ``lax.top_k``'s
    stable tie order — into ``[n_rows, width]`` outputs.  Selection is
    independent per row, so callers may partition the cells at row
    boundaries and run chunks concurrently: the per-chunk results are
    identical to one global pass (the fold engine's re-LLR does exactly
    that across a small worker pool)."""
    out_s = np.full((n_rows, width), -np.inf, np.float32)
    out_i = np.full((n_rows, width), -1, np.int32)
    if len(rows):
        # row-major, score desc within row, column asc on ties
        order = np.lexsort((cols, -scores, rows))
        rows, cols, scores = rows[order], cols[order], scores[order]
        starts = np.concatenate([[0], np.flatnonzero(np.diff(rows)) + 1])
        counts = np.diff(np.concatenate([starts, [len(rows)]]))
        rank = np.arange(len(rows)) - np.repeat(starts, counts)
        sel = rank < width
        out_s[rows[sel], rank[sel]] = scores[sel]
        out_i[rows[sel], rank[sel]] = cols[sel]
    return out_s, out_i


def _llr_topk_cells(rows, cols, k11, rc_g, cc_g, n_total, llr_threshold,
                    n_rows: int, width: int):
    """Shared sparse selection tail: score pre-gathered nonzero cells
    (``_score_llr_cells`` → ``_llr_cells`` — the identical elementwise
    chain as the dense tail, so each cell's f32 value is bit-identical)
    and select each row's top ``width`` (``_select_topk_cells``).
    ``rows`` are output-local row indices in ``[0, n_rows)``."""
    if len(rows):
        scores = _score_llr_cells(k11, rc_g, cc_g, n_total, llr_threshold)
        keep = scores > -np.inf
        rows, cols, scores = rows[keep], cols[keep], scores[keep]
    else:
        scores = np.zeros(0, np.float32)
    return _select_topk_cells(rows, cols, scores, n_rows, width)


def _llr_topk_sparse_host(C, rc, cc, n_total, llr_threshold,
                          top_k: int, exclude_self: bool,
                          flat: Optional[np.ndarray] = None):
    """Sparse-aware LLR + top-k for the host path: score ONLY the nonzero
    cells of C (the dense tail masks c==0 to -inf anyway, so the zeros
    carry no information), then per-row top-k on host via one lexsort.

    At the low occupancies this path serves (events ≪ users·items, e.g.
    ~0.6% at 4k users, 5k items, 120k events) the dense [I_p, I_t] LLR +
    lax.top_k tail does ~99% wasted work on CPU; this is O(nnz) scoring +
    O(nnz·log nnz) selection.  Output is bit-identical to _llr_topk_dense: scores come
    from the same jitted elementwise chain, and ties at equal scores pick
    the smaller column index — exactly lax.top_k's stable order.

    ``flat`` (from ``_sparse_counts(..., want_coo=True)``): sorted unique
    flat indices of the nonzero cells, so no O(I_p·I_t) scan happens
    here."""
    I_p, I_t = C.shape
    if flat is not None:
        rows, cols = np.divmod(flat, I_t)
    else:
        rows, cols = np.nonzero(C)
    if exclude_self:
        off_diag = rows != cols
        rows, cols = rows[off_diag], cols[off_diag]
    return _llr_topk_cells(rows, cols, C[rows, cols], rc[rows], cc[cols],
                           n_total, llr_threshold, I_p, min(top_k, I_t))


def _llr_topk_sparse_rows(cell_rows, cell_cols, cell_counts, rc_rows, cc,
                          n_total, llr_threshold, top_k: int,
                          n_rows: int, n_cols: int,
                          self_cols: Optional[np.ndarray] = None):
    """Row-scoped twin of ``_llr_topk_sparse_host`` working straight from
    COO cells — the fold engine's re-LLR tail, and the pure-COO training
    tail's core.  ``cell_rows`` are LOCAL row indices in ``[0, n_rows)``
    (a subset gather of the resident sparse count state), ``rc_rows``
    the row marginals FOR THOSE ROWS, ``cc`` the full column marginal.
    ``self_cols[r]`` is row r's GLOBAL column id to exclude (the
    self-pair when the rows are a slice of the primary×primary type);
    None disables the mask.  Output is bit-identical to slicing
    ``_llr_topk_dense``'s result at the same rows: the scores come from
    the same elementwise chain and the selection reproduces lax.top_k's
    (score desc, column asc) order."""
    rows = np.asarray(cell_rows, np.int64)
    cols = np.asarray(cell_cols, np.int64)
    counts = np.asarray(cell_counts)
    if self_cols is not None and len(rows):
        keep = cols != np.asarray(self_cols, np.int64)[rows]
        rows, cols, counts = rows[keep], cols[keep], counts[keep]
    rc_rows = np.asarray(rc_rows)
    cc = np.asarray(cc)
    return _llr_topk_cells(rows, cols, counts.astype(np.float32),
                           rc_rows[rows], cc[cols], n_total, llr_threshold,
                           n_rows, min(top_k, n_cols))


def _sparse_counts_coo(p: _SparseHostCSR, a: _SparseHostCSR,
                       total_pairs: Optional[int] = None):
    """Pure-COO cooccurrence counts: (sorted unique flat cell indices,
    int32 counts) WITHOUT ever materializing the dense [I_p, I_t] matrix
    — the count path for catalogs whose I_p·I_t·4 blows _SPARSE_C_BYTES
    (a two-type 1M-item catalog would need 4 TB dense; its nnz is
    bounded by the cross-join).  Same expansion chunking as
    _sparse_counts; per-chunk uniques merge at the end with one argsort
    + segment-sum.  Returns None when the cross-join exceeds
    _SPARSE_COO_PAIRS (the collection's own memory budget — past it the
    caller must use a dense-capable strategy)."""
    total = _cross_join_pairs(p, a) if total_pairs is None else total_pairs
    if total > _SPARSE_COO_PAIRS:
        return None
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int32)
    cells_parts: List[np.ndarray] = []
    count_parts: List[np.ndarray] = []
    for flat in _cross_join_flat_chunks(p, a):
        cells, counts = np.unique(flat, return_counts=True)
        cells_parts.append(cells)
        count_parts.append(counts.astype(np.int32))
    if len(cells_parts) == 1:
        return cells_parts[0], count_parts[0]
    cells = np.concatenate(cells_parts)
    counts = np.concatenate(count_parts)
    order = np.argsort(cells, kind="stable")
    cells, counts = cells[order], counts[order]
    new = np.concatenate(([True], cells[1:] != cells[:-1]))
    starts = np.flatnonzero(new)
    summed = np.add.reduceat(counts.astype(np.int64), starts)
    return cells[starts], summed.astype(np.int32)


class _SparseHostRunner:
    """Host-count twin of _DenseRunner: same dispatch/collect contract,
    and a bit-identical tail — the sparse host LLR/top-k (same elementwise
    scores, same tie order as the device tail) or the device
    _llr_topk_dense, chosen per event type by pair density.  Only the
    count production ever differs from the dense strategy: it never does.
    dispatch returns None when budgets say 'use the device'.

    Two count representations: the dense host matrix (original path,
    ≤ _SPARSE_C_BYTES) and a pure-COO path for catalogs whose dense
    count matrix can never exist (1M×1M ≈ 4 TB) but whose nnz is small —
    there counts AND the LLR/top-k tail run entirely from sorted COO
    cells (``_sparse_counts_coo`` + ``_llr_topk_sparse_rows``), making
    million-item CPU training O(nnz + I·K) instead of impossible."""

    def __init__(self, p_user, p_item, n_users: int, n_items_p: int):
        self.n_users = n_users
        self.n_items_p = n_items_p
        with span("layout"):
            self.p = _SparseHostCSR(p_user, p_item, n_items_p, n_users)

    def _dispatch_coo(self, a: _SparseHostCSR, n_items_t: int, top_k: int,
                      llr_threshold: float, exclude_self: bool,
                      pairs: int):
        """Dense-free dispatch: COO counts + row-scoped sparse tail.
        None when the cross-join blows the COO collection budget."""
        got = _sparse_counts_coo(self.p, a, total_pairs=pairs)
        if got is None:
            return None
        cells, counts = got
        rows, cols = np.divmod(cells, n_items_t)
        self_cols = (np.arange(self.n_items_p, dtype=np.int64)
                     if exclude_self else None)
        s, i = _llr_topk_sparse_rows(
            rows, cols, counts, self.p.col_counts, a.col_counts,
            float(self.n_users), float(llr_threshold),
            top_k=top_k, n_rows=self.n_items_p, n_cols=n_items_t,
            self_cols=self_cols)
        return s, i, n_items_t, top_k

    def dispatch(self, a_user, a_item, n_items_t: int, top_k: int,
                 llr_threshold: float, exclude_self: bool,
                 self_pair: bool = False, tail: Optional[str] = None):
        """``tail`` ('host' | 'device') is for the test that holds the two
        tails to each other; left None it follows the pair density."""
        # the counting, and the host tail where it is taken, are the
        # host's own compute: one span
        with span("host_compute"):
            a = self.p if self_pair else _SparseHostCSR(
                a_user, a_item, n_items_t, self.n_users)
            pairs = _cross_join_pairs(self.p, a)
            if tail is None:
                # the host tail's cost scales with the nonzero cells, the
                # device tail's with ALL cells.  nnz ≤ total cross-join
                # pairs, so pairs/cells bounds the occupancy the host tail
                # would have to sort; past ~0.25 the dense device tail is
                # the better deal (the crossover a CPU sweep found)
                tail = "host" if pairs * 4 < self.n_items_p * n_items_t \
                    else "device"
            host_tail = tail == "host"
            if host_tail and self.n_items_p * n_items_t * 4 > _SPARSE_C_BYTES:
                # the dense count matrix cannot exist at this catalog
                # size; the pure-COO path is the only O(nnz) strategy left
                return self._dispatch_coo(a, n_items_t, top_k, llr_threshold,
                                          exclude_self, pairs)
            got = _sparse_counts(self.p, a, want_coo=host_tail,
                                 total_pairs=pairs)
            if got is None:
                return None
            if host_tail:
                C, flat = got
                s, i = _llr_topk_sparse_host(
                    C, self.p.col_counts, a.col_counts,
                    float(self.n_users), float(llr_threshold),
                    top_k=top_k, exclude_self=bool(exclude_self), flat=flat)
                return s, i, n_items_t, top_k
        # imported here, not at dispatch entry: the pallas machinery
        # is a ~0.35 s one-time import the host tail never needs
        from predictionio_tpu.ops.pallas_kernels import pallas_mode

        C = got
        with span("h2d", bytes=C.nbytes + self.p.col_counts.nbytes
                  + a.col_counts.nbytes):
            C_d, rc_d, cc_d = (jnp.asarray(C), jnp.asarray(self.p.col_counts),
                               jnp.asarray(a.col_counts))
        with span("dispatch", program="_llr_topk_dense"):
            s, i = noted(
                _llr_topk_dense,
                C_d, rc_d, cc_d,
                float(self.n_users), float(llr_threshold),
                top_k=min(top_k, C.shape[1]),
                exclude_self=bool(exclude_self),
                pallas=pallas_mode(),
            )
        return s, i, n_items_t, top_k

class _DenseRunner:
    """Stages a primary event type once and runs per-event-type dense CCO
    against it, dispatching asynchronously (device results; download via
    ``collect``).  One instance per training run."""

    def __init__(self, p_user, p_item, n_users: int, n_items_p: int,
                 it_pad_max: int, mesh: Optional[Mesh],
                 n_total_users: Optional[int] = None):
        dp = mesh.shape["dp"] if mesh is not None else 1
        self.mesh = mesh
        self.n_users = n_users
        # LLR population total: may exceed n_users when these interactions
        # are one shard of a larger user space
        self.n_total_users = n_total_users if n_total_users else n_users
        self.n_items_p = n_items_p
        self.chunk = _dense_chunk_users(n_items_p, it_pad_max, n_users, dp)
        self.n_chunks = math.ceil(max(n_users, 1) / self.chunk)
        self.n_chunks = math.ceil(self.n_chunks / dp) * dp
        self.sharding = (
            NamedSharding(mesh, P("dp")) if mesh is not None else None)
        self.p = _stage_chunked(p_user, p_item,
                                self.chunk, self.n_chunks, self.sharding)

    def _counts(self, a: _StagedCOO, it_pad: int, self_pair: bool):
        mm = _matmul_dtype()
        if self.mesh is None:
            return noted(
                _cco_counts_dense,
                self.p.local_u, self.p.item, self.p.count,
                a.local_u, a.item, a.count,
                chunk=self.chunk, n_items_p=self.n_items_p, it_pad=it_pad,
                self_pair=self_pair, mm=mm,
            )
        counts_sharded = _counts_dense_sharded_fn(
            self.mesh, self.chunk, self.n_items_p, it_pad, self_pair, mm)
        return noted(counts_sharded, self.p.local_u, self.p.item,
                     self.p.count, a.local_u, a.item, a.count)

    def dispatch(self, a_user, a_item, n_items_t: int, top_k: int,
                 llr_threshold: float, exclude_self: bool,
                 self_pair: bool = False):
        """Queue one event type's CCO; returns device (scores, idx)."""
        from predictionio_tpu.ops.pallas_kernels import pallas_mode

        if self_pair:
            it_pad = self.n_items_p
            a = self.p
        else:
            it_pad = _pad128(n_items_t)
            a = _stage_chunked(a_user, a_item,
                               self.chunk, self.n_chunks, self.sharding)
        with span("dispatch", program="_cco_counts_dense"):
            C, rc, cc = self._counts(a, it_pad, self_pair)
        k = min(top_k, it_pad)
        with span("dispatch", program="_llr_topk_dense"):
            s, i = noted(
                _llr_topk_dense,
                C, rc, cc, float(self.n_total_users), float(llr_threshold),
                top_k=k, exclude_self=bool(exclude_self),
                pallas=pallas_mode(),
            )
        return s, i, n_items_t, top_k

    @staticmethod
    def collect(dispatched) -> Tuple[np.ndarray, np.ndarray]:
        s_dev, i_dev, n_items_t, req_k = dispatched
        # drop indicator columns that are padding (item id >= n_items_t or
        # -inf score) and restore the promised [I_p, req_k] width
        scores, idx = _finalize_topk(s_dev, i_dev, n_items_t)
        k = scores.shape[1]
        if req_k > k:
            pad = req_k - k
            scores = np.pad(scores, ((0, 0), (0, pad)), constant_values=-np.inf)
            idx = np.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
        return scores, idx


# ---------------------------------------------------------------------------
# the plan, and the two ways in
# ---------------------------------------------------------------------------


def _switch(name: str) -> Optional[bool]:
    """An on/off environment switch; None when unset or 'auto'."""
    conf = _os.environ.get(name, "auto").lower()
    if conf in ("1", "on", "true"):
        return True
    if conf in ("0", "off", "false"):
        return False
    return None


def _plan(n_users: int, n_items_p: int, n_items_t: int,
          mesh: Optional[Mesh], item_tile: int) -> Tuple[str, ...]:
    """The strategies one event type tries, in order: ``host_sparse``
    where it applies (it may decline from the data: its dispatch returns
    None when its budgets say 'use the device'), then the one device
    strategy that always answers.  Decided here and nowhere else, from
    the sizes, the backend and the mesh, before any host layout.

    - ``host_sparse`` (one device; PIO_CCO_SPARSE): a CPU-backend
      specialization.  At low occupancy (events ≪ users×items) the
      densified count matmul does O(U·I_p·I_t) work for O(E) information:
      25× slower on a CPU than a host bincount at 4k users, 5k items, 120k
      events.  On a TPU the MXU inverts the comparison, so auto never
      picks it there.
    - ``dense`` (PIO_CCO_DENSE): the full I_p×I_t 32-bit count matrix fits
      ``_DENSE_C_BYTES``.
    - ``resident``: tiled over items with the densified primary kept in
      HBM, when what ONE chip holds of the program's plan fits
      ``_TILED_P_BYTES`` and counts stay exact: bf16 contracts a chip's
      users in one f32 pass and the chips' partial counts are summed in
      f32, so n_users must stay below 2²⁴ (int8 accumulates int32 and has
      no such cap).  ``dp`` is the mesh's, 1 without one.  The plan is
      what the compiler holds for ``_cco_resident_all_tiles``, or on a
      mesh for ``_cco_sharded_all_tiles`` on each chip: the densified
      primary of the chip's ``n_users / dp`` users (padded to 128) as an
      argument, and per tile the densified slab of the other type for
      those users, the float32 count tile and the float32 scores made
      from it.  On a mesh the count tile a chip computes is a partial one
      and is held whole, and what the reduce-scatter leaves it, and the
      scores, are a ``dp``-th each.  At 32,768 × 100,000, tile 4,096,
      bf16, one device: 6.55 + 0.27 + 2 × 1.64 = 10.10 GB; the TPU
      compiler plans 6.11 GiB of arguments + 3.20 GiB of temporaries =
      10.0 GB there [AOT, PR 25] and the chip's peak read 10.08 GB (chip
      run, PR 25).  At 131,072 × 100,000 over four chips (the item rows
      padded to 100,352, ``_pad_items``): 6.58 + 0.27 + 1.64 + 2 × 0.41 =
      9.31 GB a chip; the TPU compiler plans 6.13 GiB of arguments + 1.93
      GiB of temporaries = 8.66 GB [AOT, PR 33].
    - ``chunked``: tiled over items, the primary re-densified per user
      block and GROUP of tiles; whatever is left.  The block and the
      group are ``_block_plan``'s, from the bytes this budget leaves.  On
      a mesh it is one sharded step a tile, dispatched from a Python
      loop, the whole count tile ``psum``'d and scored on every chip
      alike."""
    host: Tuple[str, ...] = ()
    if mesh is None:
        sparse = _switch("PIO_CCO_SPARSE")
        if sparse is None:
            sparse = jax.default_backend() != "tpu"
        if sparse:
            host = ("host_sparse",)
    dense = _switch("PIO_CCO_DENSE")
    if dense is None:
        dense = n_items_p * _pad128(n_items_t) * 4 <= _DENSE_C_BYTES
    if dense:
        return host + ("dense",)
    dp = 1 if mesh is None else mesh.shape["dp"]
    int8 = _matmul_dtype() == "int8"
    n_rows = _pad128(math.ceil(n_users / dp))
    rows = _pad_items(n_items_p, dp)
    tile = min(item_tile, max(n_items_t, 1))
    # float32 tiles of `rows`: the partial one (a mesh's alone), then the
    # chip's rows of the counts and of the scores
    plan = (n_rows * rows + n_rows * tile) * (1 if int8 else 2) \
        + (rows * (dp > 1) + 2 * (rows // dp)) * tile * 4
    if plan <= _TILED_P_BYTES and (int8 or n_users < (1 << 24)):
        return host + ("resident",)
    return host + ("chunked",)


# The user block the count matmul needs to run at the MXU's rate: at
# 131,072 × 100,000 a job's count matmuls took 34.7 s at (K 1,024, G 4),
# 28.0 s at (2,048, 4), 28.3 s at (4,096, 3) and 30.7–31.0 s at (8,192, 3)
# and (8,192, 2), against 35.65 s at (1,024, 1) and a floor of 26.6 s, or
# 27.25 s with the last tile's empty columns (chip runs, PR 34; ledger,
# PR 33).  Past it, bytes serve the program better as tiles counted
# against one densified block: every tile more in a group is a densify of
# the whole primary less.
_BLOCK_ROWS = 2048


def _block_plan(n_rows: int, n_items_p: int, tile: int, n_tiles: int,
                block: int = 0, own_slab: bool = True,
                f32_tiles: int = 2) -> Tuple[int, int, int]:
    """``(user block K, tiles a group G, plan bytes)`` of the user-blocked
    program, by ``_plan``'s accounting against the same ``_TILED_P_BYTES``.
    Counted for ``_cco_chunked_all_tiles`` are the G carried int32 count
    tiles (G × I_p × tile × 4), the float32 tile and the scores made from
    it (``f32_tiles`` × I_p × tile × 4), and the densified block [K, I_p]
    with the other type's slab [K, G × tile] in the count matmul's input
    type three times over (the zero fill, the flat scatter's result, and
    its copy re-laid as a matrix [AOT, PR 31]).  K and G compete for the
    same bytes: every densified byte is paid once a block and GROUP, so a
    job densifies the primary ⌈tiles ÷ G⌉ times an event type, and K is
    the count matmul's contraction.

    The rule: G is the most tiles that leave a block of ``_BLOCK_ROWS``
    (or of all the rows, padded to 128, where they are fewer), at least
    one; K the largest power of two of rows that fits beside them (PR 31:
    a contraction of 13,312 ran 14% slower than 8,192), at least 128, at
    most the padded rows and 2²³ (a block's counts accumulate in float32).
    A ``block`` given is taken as K and only G is derived.  It reads
    shapes, the input type and the budget, nothing else.

    At 131,072 × 100,000, tile 4,096, bf16: K 2,048, G 4 (11.26 GB; five
    tiles would leave 733 rows), 64 blocks × 7 groups a type, the primary
    densified 7 times where a tile a step densified it 25 times; in int8
    K 4,096, G 4.  The TPU compiler's own plan there is (G + 4) tiles with
    the densified block inside the four, 13.2 GB [AOT, PR 34]; the chip's
    peak read (G + 2) tiles, 9.99 GB (chip run, PR 34).

    The basket program is this plan with baskets for rows, no slab of its
    own (the group's is a slice of the chunk) and one float32 tile (the
    convert fuses into the scores): ``own_slab=False, f32_tiles=1``.  At
    65,536 × 102,400, tile 4,096, bf16: K 2,048, G 5 (11.32 GB; six tiles
    would leave 414 rows), a chunk densified 5 times a job, not 25.  The
    TPU compiler holds the group and one float32 tile there, the chunk's
    copies in the tile's place: 10.07 GB, as the chip then read; the plan
    it reports reads a tile more, 11.76 GB [AOT, chip run, PR 36]."""
    in_bytes = 1 if _matmul_dtype() == "int8" else 2
    cap = min(_pad128(n_rows), 1 << 23)

    def tiles_bytes(g: int) -> int:
        return (g + f32_tiles) * n_items_p * tile * 4

    def row_bytes(g: int) -> int:
        return 3 * (n_items_p + own_slab * g * tile) * in_bytes

    def room(g: int) -> int:
        """The rows a group of ``g`` leaves."""
        return (_TILED_P_BYTES - tiles_bytes(g)) // row_bytes(g)

    want = block or min(_BLOCK_ROWS, cap)
    group = max([g for g in range(1, max(n_tiles, 1) + 1)
                 if room(g) >= want] or [1])
    k = block or min(1 << max(room(group), 128).bit_length() - 1, cap)
    return k, group, tiles_bytes(group) + k * row_bytes(group)


def cco_train_indicators(
    p_user: np.ndarray, p_item: np.ndarray,
    others: Sequence[Tuple[str, np.ndarray, np.ndarray, int]],
    n_users: int, n_items_p: int,
    top_k: int = 50,
    llr_threshold: float = 0.0,
    mesh: Optional[Mesh] = None,
    exclude_self_for: Optional[str] = None,
    user_block: int = 0,
    item_tile: int = 4096,
    per_type: Optional[Dict[str, Tuple[int, float]]] = None,
) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """The UR train loop's entry: indicators for every event type against
    one primary, each under the strategy ``_plan`` gives it.

    ``others`` is an ordered list of ``(name, a_user, a_item, n_items_t)``;
    pass the primary's own name/arrays for the self-indicator (detected by
    array identity, which lets the runners skip the second layout).  The
    dense and host-sparse runners lay out and upload the primary once for
    all event types and dispatch asynchronously, so host layout of type
    t+1 overlaps device compute of type t.  The tiled strategies (count
    matrix past the HBM budget) upload and densify the primary once per
    event type and wait for each result before the next.

    ``user_block`` is read by the ``chunked`` strategy alone: 0 (the
    default, ``userBlock`` unset in an ``engine.json``) derives the block
    and the tiles counted against it from the bytes ``_plan``'s budget
    leaves (``_block_plan``); a value is taken as the block, and only the
    group is derived.

    ``per_type`` optionally overrides ``(top_k, llr_threshold)`` for named
    event types (reference UR: per-indicator maxCorrelatorsPerItem/minLLR).

    Returns ``{name: (scores [I_p, top_k], indices [I_p, top_k])}``;
    entries with score == -inf (index -1) are padding: fewer than top_k
    significant correlators.  Every strategy derives the LLR marginals
    from the interactions themselves.
    """
    per_type = per_type or {}
    plans = {nt: _plan(n_users, n_items_p, nt, mesh, item_tile)
             for _, _, _, nt in others}
    runners: Dict[str, object] = {}

    def runner(strategy: str):
        """The primary staged for a runner, once, when a plan first asks."""
        if strategy not in runners:
            staged = (p_user, p_item, n_users, n_items_p)
            if strategy == "host_sparse":
                runners[strategy] = _SparseHostRunner(*staged)
            else:       # user chunks sized for the widest dense event type
                it_pad_max = max([n_items_p] + [
                    _pad128(nt) for nt, plan in plans.items()
                    if "dense" in plan])
                runners[strategy] = _DenseRunner(*staged, it_pad_max, mesh)
        return runners[strategy]

    pending: List[Tuple[str, object]] = []
    results: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    for name, au, ai, n_items_t in others:
        excl = (name == exclude_self_for)
        t_k, t_llr = per_type.get(name, (top_k, llr_threshold))
        for strategy in plans[n_items_t]:
            if strategy in ("host_sparse", "dense"):
                # strict identity only: anything weaker (shape/overlap
                # heuristics) could silently alias two distinct event types
                d = runner(strategy).dispatch(
                    au, ai, n_items_t, t_k, t_llr, excl,
                    self_pair=au is p_user and ai is p_item)
                if d is None:       # host_sparse's budgets: use the device
                    continue
                pending.append((name, d))
            elif strategy == "resident":
                results[name] = _cco_resident(
                    p_user, p_item, au, ai, n_users, n_items_p, n_items_t,
                    t_k, t_llr, item_tile, excl, mesh=mesh)
            else:
                dp = 1 if mesh is None else mesh.shape["dp"]
                block = _block_plan(
                    math.ceil(n_users / dp), n_items_p,
                    *_tiling(n_items_t, item_tile), block=user_block)[0]
                with span("layout") as rec:
                    p = block_interactions(p_user, p_item, n_users, n_items_p,
                                           user_block=block)
                    a = block_interactions(au, ai, n_users, n_items_t,
                                           user_block=block)
                    slots = p.local_u.size + a.local_u.size
                    rec["attrs"] = {
                        "user_blocks": p.n_blocks, "slots": slots,
                        "pad_slots": (slots - int(p.count.sum())
                                      - int(a.count.sum()))}
                results[name] = _cco_chunked(
                    p, a, n_users, top_k=t_k, llr_threshold=t_llr,
                    item_tile=item_tile, mesh=mesh, exclude_self=excl)
            break
    for name, d in pending:
        results[name] = _DenseRunner.collect(d)
    return results


def cco_indicators_coo(
    p_user: np.ndarray, p_item: np.ndarray,
    a_user: np.ndarray, a_item: np.ndarray,
    n_users: int, n_items_p: int, n_items_t: int,
    top_k: int = 50,
    llr_threshold: float = 0.0,
    user_block: int = 0,
    item_tile: int = 4096,
    mesh: Optional[Mesh] = None,
    exclude_self: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """``cco_train_indicators`` for a single event type: ``(scores,
    indices)`` of ``a`` against the primary ``p``.  ``exclude_self=True``
    masks the diagonal (self-similarity) when both are the same event
    type."""
    return cco_train_indicators(
        p_user, p_item, [("a", a_user, a_item, n_items_t)], n_users,
        n_items_p, top_k=top_k, llr_threshold=llr_threshold, mesh=mesh,
        exclude_self_for="a" if exclude_self else None,
        user_block=user_block, item_tile=item_tile)["a"]


@lru_cache(maxsize=8)
def _group_step_sharded_fn(mesh: Mesh, n_total: float, static: tuple):
    """The chunked strategy's step on a mesh, built once for a (mesh,
    population, static arguments) and jitted, so that a job neither
    re-traces it nor hands ``stage_maps`` a new function a call: groups
    of one tile, each summing the primary's counts anew."""
    spec, rep = P("dp"), P()
    static = dict(static)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,) * 6 + (rep,) * 3,
             out_specs=(rep, rep))
    def tile_step_sharded(plu, pit, pcnt, alu, ait, acnt, bs, bi, ts):
        return _cco_group_step(
            plu, pit, pcnt, alu, ait, acnt, n_total,
            bs, bi, jnp.zeros((static["n_items_p"],), jnp.int32), True, ts,
            group=1, axis_name="dp", **static)[:2]

    return tile_step_sharded


def _cco_chunked(
    primary: BlockedInteractions,
    other: BlockedInteractions,
    n_total_users: int,
    top_k: int = 50,
    llr_threshold: float = 0.0,
    item_tile: int = 4096,
    mesh: Optional[Mesh] = None,
    exclude_self: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """The chunked tiled strategy over two blocked layouts: a loop over
    groups of item tiles that never materializes the full count matrix and
    merges a running top-k, the primary re-densified per user block and
    group, marginals accumulated in the same scan.  The layouts' block is
    the program's; the group is what ``_block_plan`` derives beside it
    (``cco_train_indicators`` lays the pairs out in the block the same
    plan gives where ``userBlock`` is unset).  One compiled program on one
    device; on a mesh one sharded step a tile, counts ``psum``'d over
    ``dp``."""
    if n_total_users <= 0:
        raise ValueError(f"n_total_users must be positive, got {n_total_users}")
    if primary.n_blocks != other.n_blocks or primary.user_block != other.user_block:
        raise ValueError("primary/other must be blocked with the same user layout")
    n_items_p, n_items_t = primary.n_items, other.n_items
    tile, n_tiles = _tiling(n_items_t, item_tile)

    topk = topk_impl()

    from predictionio_tpu.ops.pallas_kernels import pallas_mode

    static = dict(block=primary.user_block, n_items_p=n_items_p, tile=tile,
                  top_k=top_k, llr_threshold=float(llr_threshold),
                  pallas=pallas_mode(), exclude_self=exclude_self, topk=topk)
    host_args = (primary.local_u, primary.item, primary.count,
                 other.local_u, other.item, other.count)
    if mesh is None:
        _, group, plan_bytes = _block_plan(
            primary.n_users, n_items_p, tile, n_tiles,
            block=primary.user_block)
        with span("h2d", bytes=sum(a.nbytes for a in host_args)):
            args = tuple(jnp.asarray(a) for a in host_args)
        with span("dispatch", program="_cco_chunked_all_tiles",
                  tiles=n_tiles, user_block=primary.user_block,
                  tile_group=group, plan_bytes=plan_bytes,
                  block_steps=math.ceil(n_tiles / group) * primary.n_blocks,
                  **_topk_attrs(topk, tile, top_k),
                  **_llr_attrs(static["pallas"], n_items_p, tile,
                               exclude_self)):
            best_scores, best_idx = noted(
                _cco_chunked_all_tiles,
                *args, float(n_total_users), n_tiles=n_tiles, group=group,
                **static)
    else:
        dp = mesh.shape["dp"]
        nb = primary.n_blocks
        pad_blocks = (-nb) % dp

        def pad(a):
            if pad_blocks == 0:
                return a
            return np.concatenate([a, np.zeros((pad_blocks, *a.shape[1:]), a.dtype)])

        from predictionio_tpu.parallel.sharding import stage_global

        shard = NamedSharding(mesh, P("dp"))
        with span("h2d", bytes=sum(a.nbytes for a in host_args)):
            args = tuple(stage_global(pad(np.asarray(a)), shard)
                         for a in host_args)

        tile_step_sharded = _group_step_sharded_fn(
            mesh, float(n_total_users), tuple(sorted(static.items())))
        carry_k = _carry_width(top_k, topk)
        best_scores = jnp.full((n_items_p, carry_k), -jnp.inf, jnp.float32)
        best_idx = jnp.zeros((n_items_p, carry_k), jnp.int32)
        with span("dispatch", program="_cco_group_step",
                  **_llr_attrs(static["pallas"], n_items_p, tile,
                               exclude_self)):
            for t in range(n_tiles):
                best_scores, best_idx = noted(
                    tile_step_sharded,
                    *args, best_scores, best_idx, jnp.int32(t * tile),
                )

    return _finalize_topk(best_scores, best_idx, n_items_t, top_k)


# ---------------------------------------------------------------------------
# basket association rules (Complementary Purchase template)
# ---------------------------------------------------------------------------

# The count program of the tiled strategies with baskets for users and the
# items themselves for the other event type (C = BᵀB), and lift under the
# template's three cuts in LLR's place.  Pair counts accumulate as int32:
# exact to 2³¹, and c_ij ≤ n_baskets, which basket_rules guards.  The
# cuts and the lift are float32 products of counts (see _basket_scores).


def session_baskets(user: np.ndarray, item: np.ndarray, time_us: np.ndarray,
                    window_us: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """One user's events, each within ``window_us`` of the one before, are
    one basket: ``(basket id an event, its item, baskets)`` in (user, time)
    order.  A gap of exactly the window stays in the basket."""
    user, time_us = np.asarray(user), np.asarray(time_us, np.int64)
    if not len(user):
        return np.empty(0, np.int32), np.empty(0, np.int32), 0
    order = np.lexsort((time_us, user))
    user, time_us = user[order], time_us[order]
    new = np.ones(len(user), bool)
    new[1:] = (user[1:] != user[:-1]) | (time_us[1:] - time_us[:-1] > window_us)
    basket = (np.cumsum(new) - 1).astype(np.int32)
    return basket, np.asarray(item, np.int32)[order], int(basket[-1]) + 1


def _basket_scores(c, ci_row, ci_col, n, min_support, min_confidence,
                   min_lift):
    """Per-cell rule scoring, one fused elementwise pass: lift =
    c·N / (c_i·c_j) where support c/N, confidence c/c_i and lift pass
    their cuts, else -inf.  Each cut is compared as a product of counts
    (c ≥ minSupport·N, c ≥ minConfidence·c_i, c·N ≥ minLift·c_i·c_j), not
    as a rounded ratio: a product of integers that float32 holds (24
    bits, or a power of two times fewer) is exact, so a rule that sits ON
    a cut (lift exactly 1.0 at N a power of two) is kept as float64 keeps
    it; past 24 bits each side rounds once."""
    pair = ci_row * ci_col
    lift = c * n / jnp.maximum(pair, 1.0)
    ok = ((c > 0) & (c >= min_support * n) & (c >= min_confidence * ci_row)
          & (c * n >= min_lift * pair))
    return jnp.where(ok, lift, -jnp.inf)


@partial(jax.jit, static_argnames=(
    "chunk", "n_tiles", "group", "tile", "top_k", "topk", "mm"))
def _basket_rules_tiled(
    lu, it, cnt, n_baskets, ci,
    chunk: int, n_tiles: int, group: int, tile: int, top_k: int,
    min_support, min_confidence, min_lift, topk: str, mm: str,
):
    """Every item tile of the basket rules in one compiled program: a scan
    over the whole groups of ``group`` adjacent tiles, and the tiles left
    over as one step of their own size (as ``_cco_chunked_all_tiles``).
    A group's counts C [I, group·tile] accumulate over the basket chunks
    on the MXU in one product — each chunk densified ONCE a group from its
    own slots of the chunk-grouped log (``lu``/``it``/``cnt``:
    block_interactions' layout), the group's slab a slice of it — then its
    tiles go one at a time, in tile order, through the scores and the
    running top-k (_merge_topk) as a lone tile does.  I is the catalogue
    padded to whole tiles; ``ci`` [I] float32 is the exact per-item basket
    count from the host."""
    width = n_tiles * tile
    slots = lu.shape[1]
    in_dtype = jnp.int8 if mm == "int8" else jnp.bfloat16

    def group_step(best, start, size: int):
        def body(c_acc, xs):
            blu, bit, bcnt = xs
            with stage("basket.densify"):
                valid = jax.lax.iota(jnp.int32, slots) < bcnt
                B = _densify(blu, bit, valid, chunk, width, in_dtype)
                # the slab as an array of its own: fused into the matmul's
                # operand, the slice ran it 15% slower (chip run, PR 36)
                Bg = jax.lax.optimization_barrier(jax.lax.dynamic_slice(
                    B, (0, start), (chunk, size * tile)))
            with stage("basket.count_matmul"):
                return c_acc + _count_matmul(B, Bg, mm), None

        c, _ = jax.lax.scan(body, jnp.zeros((width, size * tile), jnp.int32),
                            (lu, it, cnt))

        def one_tile(g, best):
            # one tile at a time: a loop, so that the compiler holds one
            # float32 tile, not the group's [AOT, PR 34]
            tile_start = start + g * tile
            with stage("basket.score"):
                c_t = jax.lax.dynamic_slice(c, (0, g * tile), (width, tile))
                ci_col = jax.lax.dynamic_slice(ci, (tile_start,), (tile,))
                scores = _mask_self_pairs(_basket_scores(
                    c_t.astype(jnp.float32), ci[:, None], ci_col[None, :],
                    n_baskets, min_support, min_confidence, min_lift),
                    tile_start)
            return _merge_topk(*best, scores, tile_start, tile, top_k,
                               impl=topk)

        return jax.lax.fori_loop(0, size, one_tile, best)

    whole, rest = divmod(n_tiles, group)
    best = _scan_tiles(          # the whole groups: steps group·tile wide
        lambda bs, bi, start: group_step((bs, bi), start, group),
        width, whole, group * tile, top_k, carry_k=_carry_width(top_k, topk))
    if rest:
        best = group_step(best, jnp.int32(whole * group * tile), rest)
    return best


def basket_rules(
    basket_idx: np.ndarray, item_idx: np.ndarray,
    n_baskets: int, n_items: int,
    top_k: int = 20,
    min_support: float = 0.0,
    min_confidence: float = 0.0,
    min_lift: float = 0.0,
    min_basket_size: int = 1,
    item_tile: int = 4096,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pair rules i → j from (basket, item) pairs with basket ids in
    [0, n_baskets): (lift [I, K], complement ids [I, K], confidence
    [I, K]), -1 ids where no rule passed the cuts.

    A basket of fewer than ``min_basket_size`` distinct items is dropped
    (so is one without any item); N is the number kept.  For i ≠ j over
    the N baskets: support = c_ij / N, confidence = c_ij / c_i, lift =
    confidence / (c_j / N); a rule passes at support ≥ min_support,
    confidence ≥ min_confidence and lift ≥ min_lift, and each item keeps
    its ``top_k`` best by lift.  One device program at every size
    (_basket_rules_tiled): the tile is UR's (``item_tile`` against the
    catalogue), the step's shape — a chunk of K baskets and the G tiles
    counted against one densify of it — is ``_block_plan``'s, from the
    bytes ``_TILED_P_BYTES`` leaves.  Confidence is derived from the kept
    lifts (conf = lift·c_j / N), so no confidence matrix exists anywhere.
    """
    if n_baskets >= (1 << 31):
        raise ValueError(
            f"{n_baskets} baskets would overflow the int32 pair-count "
            "accumulator (exact to 2^31); shard the basket log first")
    k = min(max(top_k, 1), max(n_items, 1))
    with span("layout", events=len(basket_idx)) as rec:
        # distinct (basket, item) pairs: the sizes, the drop and the exact
        # per-item basket counts all come from them
        gb, gi = dedup_pairs(basket_idx, item_idx, n_items)
        sizes = np.bincount(gb, minlength=max(n_baskets, 1))
        kept = sizes >= max(int(min_basket_size), 1)
        n_kept = int(kept.sum())
        pair_kept = kept[gb]
        gb = (np.cumsum(kept) - 1).astype(np.int32)[gb[pair_kept]]
        gi = gi[pair_kept]
        ci = np.bincount(gi, minlength=n_items)
        tile, n_tiles = _tiling(max(n_items, 1), item_tile)
        chunk, group, plan_bytes = _block_plan(
            n_kept, n_tiles * tile, tile, n_tiles, own_slab=False,
            f32_tiles=1)
        n_chunks = max(math.ceil(n_kept / chunk), 1)
        blocks = block_interactions(gb, gi, n_chunks * chunk, n_items,
                                    user_block=chunk)
        ci_pad = np.zeros(n_tiles * tile, np.float32)
        ci_pad[:n_items] = ci
        rec["attrs"].update(baskets=n_kept, baskets_dropped=int(
            (sizes > 0).sum()) - n_kept)
    host_args = (blocks.local_u, blocks.item, blocks.count, ci_pad)
    with span("h2d", bytes=sum(a.nbytes for a in host_args)):
        lu, it, cnt, ci_dev = (jnp.asarray(a) for a in host_args)
    topk = topk_impl()
    with span("dispatch", program="_basket_rules_tiled",
              tiles=n_tiles, chunks=n_chunks, chunk=chunk, tile_group=group,
              plan_bytes=plan_bytes,
              steps=math.ceil(n_tiles / group) * n_chunks,
              **_topk_attrs(topk, tile, k)):
        best_scores, best_idx = noted(
            _basket_rules_tiled,
            lu, it, cnt, jnp.float32(max(n_kept, 1)), ci_dev,
            chunk=chunk, n_tiles=n_tiles, group=group, tile=tile, top_k=k,
            min_support=jnp.float32(min_support),
            min_confidence=jnp.float32(min_confidence),
            min_lift=jnp.float32(min_lift), topk=topk, mm=_matmul_dtype())
    st, si = _finalize_topk(best_scores, best_idx, n_items, k)
    st, si = st[:n_items], si[:n_items].astype(np.int32)
    # conf = lift·c_j/N, from the exact int64 host counts (-inf lifts are
    # zeroed before the multiply so no NaN transient appears)
    conf = np.where(si < 0, 0.0, st) * ci[np.maximum(si, 0)] / max(n_kept, 1)
    return st, si, conf
