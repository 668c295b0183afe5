"""Exact per-row top-k as a bitonic tournament, in pure JAX.

Why this exists: XLA lowers ``lax.top_k`` on TPU to a full variadic sort
of each row.  In the tiled CCO path that sort — top_k(concat(best, tile))
over a [I_p, top_k + 4096] buffer per tile — and the index gather after
it took 16.31 + 4.46 s of a 32.0 s ``ur-ecom-100k.train`` job (ledger,
PR 24: 65% of the job).  The tournament as a Pallas kernel
(``pallas_kernels.tile_topk_desc``) plus ``merge_desc`` took 7.64 + 0.1 s
of the same job while its network ran along the lanes (PR 25), and takes
0.73 + 0.1 s since the kernel runs it across whole vregs, one column a
vreg (14.5 ms a tile, chip run, PR 32: PERF.md section 6).
``approx_max_k`` inside ``lax.scan`` was no escape: it exploded compile
time (>40 min at [100k, 4096]).

This module is the network in pure JAX: the reference the kernel's tests
compare with on any backend, and ``merge_desc``, the carry merge that the
tiled programs run after the kernel.

The tournament does strictly less work than a full sort and lowers to
nothing but elementwise min/max/select chains plus static reshapes, which
XLA fuses onto the VPU with no sort lowering at all:

1. **Block sort** — sort every B-wide block of the row with a bitonic
   network in natural alternating direction (desc, asc, desc, …), where
   ``B = next_pow2(k)``.  All blocks of all rows sort simultaneously:
   each compare-exchange stage is one vectorized min/max over the whole
   [R, W] array.  O(W·log²B) work.
2. **Tournament rounds** — adjacent (desc, asc) block pairs form bitonic
   sequences; one half-cleaner keeps the elementwise max half (exactly
   the top-B multiset of the pair, by the bitonic half-cleaner theorem),
   then log2(B) cleanup stages restore alternating sorted order.  Width
   halves each round: O(W·logB) total.
3. **Carry merge** — the surviving [R, B] desc block merges with the
   running top-B carry (sorted desc) via reverse + half-cleaner +
   cleanup, so a running top-k over tiles (lax.scan carry) never sorts
   more than 2B elements per row per tile.

Everything is shape-static, composes into ``lax.scan`` and ``shard_map``,
and is exact for values (ties may order differently than lax.top_k, which
prefers the lower index; CCO parity tests compare sets at ties).

The reference has no analogue: its cooccurrence top-k is Mahout's JVM
per-row priority queue inside a Spark shuffle (SURVEY.md §2 Universal
Recommender row).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax.numpy as jnp

NEG_INF = float("-inf")


def block_width(k: int) -> int:
    """Tournament block width for a requested top-k: pow2, ≥ k, ≥ 8."""
    return max(8, 1 << max(int(k) - 1, 0).bit_length())


def _cmpex(s, i, d: int, dir_np: np.ndarray):
    """One compare-exchange stage at XOR-distance ``d`` on the last axis.

    ``dir_np`` is a per-group (group = 2d consecutive positions) numpy
    bool: True puts the max in the lower half.  Static per stage, so it
    folds into the compiled program as a constant.
    """
    r, w = s.shape
    g = w // (2 * d)
    s4 = s.reshape(r, g, 2, d)
    i4 = i.reshape(r, g, 2, d)
    ls, us = s4[:, :, 0], s4[:, :, 1]
    li, ui = i4[:, :, 0], i4[:, :, 1]
    l_is_max = ls >= us
    mx_s, mn_s = jnp.maximum(ls, us), jnp.minimum(ls, us)
    mx_i = jnp.where(l_is_max, li, ui)
    mn_i = jnp.where(l_is_max, ui, li)
    dirm = jnp.asarray(dir_np)[None, :, None]
    new_s = jnp.stack(
        [jnp.where(dirm, mx_s, mn_s), jnp.where(dirm, mn_s, mx_s)], axis=2)
    new_i = jnp.stack(
        [jnp.where(dirm, mx_i, mn_i), jnp.where(dirm, mn_i, mx_i)], axis=2)
    return new_s.reshape(r, w), new_i.reshape(r, w)


def _block_sort_alternating(s, i, b: int):
    """Sort every b-wide block of each row, directions alternating
    (block 0 desc, block 1 asc, …) — the natural bitonic pattern, so
    adjacent pairs are ready for a half-cleaner with no reversal."""
    w = s.shape[1]
    kbit = 1
    while (1 << kbit) <= b:
        k = 1 << kbit
        for j in reversed(range(kbit)):
            d = 1 << j
            starts = np.arange(w // (2 * d)) * (2 * d)
            s, i = _cmpex(s, i, d, (starts & k) == 0)
        kbit += 1
    return s, i


def _half_clean_keep_max(s, i, b: int):
    """Drop to the top-b multiset of each adjacent (desc, asc) block pair
    (bitonic half-cleaner), then restore alternating sorted order."""
    r, w = s.shape
    s4 = s.reshape(r, w // (2 * b), 2, b)
    i4 = i.reshape(r, w // (2 * b), 2, b)
    ls, us = s4[:, :, 0], s4[:, :, 1]
    li, ui = i4[:, :, 0], i4[:, :, 1]
    l_is_max = ls >= us
    s = jnp.maximum(ls, us).reshape(r, w // 2)
    i = jnp.where(l_is_max, li, ui).reshape(r, w // 2)
    # each surviving b-block is bitonic; merge-sort it toward the
    # alternating pattern of the halved width
    d = b // 2
    while d >= 1:
        starts = np.arange((w // 2) // (2 * d)) * (2 * d)
        s, i = _cmpex(s, i, d, (starts & b) == 0)
        d //= 2
    return s, i


def sort_topb_desc(scores, idx, b: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-b of each row, sorted descending: [R, W] → [R, b].

    Pads the row width to b·2^r with -inf internally; ``idx`` rides along
    through every exchange.
    """
    r, w = scores.shape
    wp = b
    while wp < w:
        wp *= 2
    if wp != w:
        pad = wp - w
        scores = jnp.concatenate(
            [scores, jnp.full((r, pad), NEG_INF, scores.dtype)], axis=1)
        idx = jnp.concatenate(
            [idx, jnp.full((r, pad), -1, idx.dtype)], axis=1)
    s, i = _block_sort_alternating(scores, idx, b)
    while s.shape[1] > b:
        s, i = _half_clean_keep_max(s, i, b)
    return s, i


def merge_desc(as_, ai, bs, bi) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-b of two sorted-desc [R, b] lists, sorted desc.

    Reverse B (desc → asc) to form a bitonic pair, half-clean, then
    log2(b) cleanup stages with direction fixed desc.
    """
    b = as_.shape[1]
    bs, bi = bs[:, ::-1], bi[:, ::-1]
    a_is_max = as_ >= bs
    s = jnp.maximum(as_, bs)
    i = jnp.where(a_is_max, ai, bi)
    d = b // 2
    while d >= 1:
        starts = np.arange(b // (2 * d)) * (2 * d)
        s, i = _cmpex(s, i, d, np.ones_like(starts, bool))
        d //= 2
    return s, i


def bitonic_topk(
    scores: jnp.ndarray, k: int, idx: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``lax.top_k(scores, k)`` (values exact; tie order may
    differ).  ``idx`` defaults to the column index."""
    r, w = scores.shape
    if idx is None:
        idx = jnp.broadcast_to(
            jnp.arange(w, dtype=jnp.int32)[None, :], (r, w))
    b = block_width(min(k, max(w, 1)))
    s, i = sort_topb_desc(scores, idx, b)
    return s[:, :k], i[:, :k]
