"""Hand-written Pallas TPU kernels for the serving/training hot paths.

The reference has no hand-written kernels at all — its FLOPs run inside
Spark MLlib / Mahout JVM code (SURVEY.md §2: "no C++/Rust/CUDA components in
PredictionIO itself").  On TPU the hot ops are re-expressed so XLA can tile
them onto the MXU; the two below additionally benefit from manual fusion
beyond what XLA does automatically:

- ``masked_score_matmul`` — the `/queries.json` serving hot path: one pass
  computes ``U @ Vᵀ``, adds a per-item bias (business-rule boost /
  popularity blend) and applies the seen-items mask *inside the matmul
  tile*, so the [B, I] score matrix is written to HBM exactly once instead
  of the mask/bias reading it back (3 HBM round-trips → 1).
- ``llr_masked_scores`` — the CCO tile post-pass: Dunning G² over the
  2×2 contingency table + cooccurrence mask + significance threshold,
  fused into one VPU pass over each count tile.

- ``tile_topk_desc`` — exact per-row top-k of a score tile as an in-VMEM
  bitonic tournament (the tiled-CCO merge's per-tile selection wherever
  these kernels run: ``ops.cco.topk_impl``).

Control: ``PIO_PALLAS`` env var — ``auto`` (default: compiled on TPU, off
otherwise), ``1``/``compiled``, ``interpret``, ``0``/``off``.  A kernel is
interpreted ONLY when ``PIO_PALLAS=interpret`` says so (the CPU test suite
sets it per test); compiled mode off-TPU raises instead of silently
interpreting, so a run can never mistake the interpreter for the kernel.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def pallas_mode() -> str:
    """'compiled' | 'interpret' | 'off' for this process."""
    conf = os.environ.get("PIO_PALLAS", "auto").lower()
    if conf in ("0", "off", "false"):
        return "off"
    if conf in ("1", "compiled", "true"):
        return "compiled"
    if conf == "interpret":
        return "interpret"
    return "compiled" if jax.default_backend() == "tpu" else "off"


def pallas_enabled() -> bool:
    return pallas_mode() != "off"


def _interpret() -> bool:
    """True = run the kernel in the Pallas interpreter.  Anything but an
    explicit ``PIO_PALLAS=interpret`` compiles for the TPU — and refuses
    to run where there is none."""
    if pallas_mode() == "interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "Pallas TPU kernels compile only for a TPU backend (this one is "
            f"{jax.default_backend()!r}); set PIO_PALLAS=interpret to run "
            "them in the interpreter, or PIO_PALLAS=off for the XLA twins")
    return False


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """Output spec that varies over the mesh axes its operands vary over:
    inside ``shard_map`` (tiled CCO on several chips) the default
    ``check_vma`` refuses a pallas_call whose outputs do not say; outside
    one the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# fused masked scoring matmul (serving hot path)
# ---------------------------------------------------------------------------


def _score_kernel(u_ref, v_ref, seen_ref, bias_ref, out_ref):
    # MXU tile: [TB, K] @ [TI, K]ᵀ with f32 accumulation.
    s = jax.lax.dot_general(
        u_ref[:], v_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s + bias_ref[:]            # [1, TI] broadcast: business-rule boost
    # VPU: mask seen items in-register — never re-read scores from HBM.
    out_ref[:] = jnp.where(seen_ref[:] > 0, NEG_INF, s)


@functools.partial(jax.jit, static_argnames=("tile_b", "tile_i", "has_bias", "interpret"))
def _masked_score_matmul(
    user_vecs, item_factors, seen_mask, bias,
    tile_b: int, tile_i: int, has_bias: bool, interpret: bool,
):
    """Pad to tile-aligned shapes, run the kernel, slice back — all under one
    jit so the pads fuse into XLA's dataflow instead of eager per-call copies
    (shapes are static per deployment, so this traces once)."""
    b, k = user_vecs.shape
    n_items = item_factors.shape[0]
    bp, ip, kp = _round_up(b, tile_b), _round_up(n_items, tile_i), _round_up(k, 128)

    u, v, seen = user_vecs, item_factors, seen_mask
    if (bp, kp) != (b, k):
        u = jnp.zeros((bp, kp), jnp.float32).at[:b, :k].set(u)
    if (ip, kp) != (n_items, k):
        v = jnp.zeros((ip, kp), jnp.float32).at[:n_items, :k].set(v)
    if (bp, ip) != (b, n_items):
        # padding items arrive pre-masked, so they can never win a top-k
        seen = jnp.ones((bp, ip), jnp.float32).at[:b, :n_items].set(seen)
    bias_row = jnp.zeros((1, ip), jnp.float32)
    if has_bias:
        bias_row = bias_row.at[0, :n_items].set(bias)

    grid = (bp // tile_b, ip // tile_i)
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_i, kp), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_b, tile_i), lambda i, j: (i, j)),
            pl.BlockSpec((1, tile_i), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_i), lambda i, j: (i, j)),
        out_shape=_out_struct((bp, ip), jnp.float32, u, v, seen, bias_row),
        cost_estimate=pl.CostEstimate(
            flops=2 * bp * ip * kp,
            bytes_accessed=4 * (bp * kp + ip * kp + 2 * bp * ip),
            transcendentals=0,
        ),
        interpret=interpret,
    )(u, v, seen, bias_row)
    return out[:b, :n_items]


def masked_score_matmul(
    user_vecs: jnp.ndarray,       # [B, K] f32
    item_factors: jnp.ndarray,    # [I, K] f32
    seen_mask: jnp.ndarray,       # [B, I], >0 where already interacted
    bias: Optional[jnp.ndarray] = None,   # [I] additive per-item boost
    tile_b: int = 128,
    tile_i: int = 512,
) -> jnp.ndarray:
    """Fused ``scores = U @ Vᵀ + bias; scores[seen] = -inf`` as one kernel."""
    b, k = user_vecs.shape
    n_items = item_factors.shape[0]
    tile_b = min(tile_b, _round_up(b, 8))
    tile_i = min(tile_i, _round_up(n_items, 128))
    if bias is None:
        bias_arg = jnp.zeros((0,), jnp.float32)   # placeholder, unused trace-side
    else:
        bias_arg = bias
    return _masked_score_matmul(
        user_vecs, item_factors, seen_mask, bias_arg,
        tile_b, tile_i, bias is not None, _interpret(),
    )


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_batch_fused(
    user_vecs: jnp.ndarray,
    item_factors: jnp.ndarray,
    seen_mask: jnp.ndarray,
    top_k: int,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas-fused variant of ``ops.als.recommend_batch`` (+ optional bias).
    Jitted end to end (static top_k) so serving is one compiled program —
    the top_k fuses with the score kernel's output instead of dispatching
    eagerly per query."""
    scores = masked_score_matmul(user_vecs, item_factors, seen_mask, bias)
    return jax.lax.top_k(scores, top_k)


# ---------------------------------------------------------------------------
# fused LLR + masking over CCO count tiles
# ---------------------------------------------------------------------------


def _llr_kernel(c_ref, row_ref, col_ref, scalars_ref, out_ref):
    from predictionio_tpu.ops.cco import llr_score

    c = c_ref[:]
    row = row_ref[:]               # [TB, 1] primary-item user counts
    col = col_ref[:]               # [1, TI] other-item user counts
    n_total = scalars_ref[0, 0]
    threshold = scalars_ref[0, 1]
    k11 = c
    k12 = row - c
    k21 = col - c
    k22 = n_total - k11 - k12 - k21
    g2 = llr_score(k11, k12, k21, k22)   # determinant-form G², VPU-only
    keep = (c > 0) & (g2 >= threshold)
    out_ref[:] = jnp.where(keep, g2, NEG_INF)


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_c", "interpret"))
def _llr_padded(c, row, col, scalars, tile_r: int, tile_c: int, interpret: bool):
    rp, cp = c.shape
    grid = (rp // tile_r, cp // tile_c)
    return pl.pallas_call(
        _llr_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
            pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j)),
        out_shape=_out_struct((rp, cp), jnp.float32, c, row, col, scalars),
        cost_estimate=pl.CostEstimate(
            flops=30 * rp * cp,
            bytes_accessed=4 * 2 * rp * cp,
            transcendentals=9 * rp * cp,   # the xlogx logs
        ),
        interpret=interpret,
    )(c, row, col, scalars)


def llr_masked_scores(
    counts: jnp.ndarray,       # [R, C] cooccurrence counts
    row_counts: jnp.ndarray,   # [R] users per primary item
    col_counts: jnp.ndarray,   # [C] users per other item
    n_total: float,
    threshold: float = 0.0,
    tile_r: int = 256,
    tile_c: int = 512,
) -> jnp.ndarray:
    """Fused G² scores with zero-cooccurrence + threshold masking (-inf)."""
    r, c = counts.shape
    tile_r = min(tile_r, _round_up(r, 8))
    tile_c = min(tile_c, _round_up(c, 128))
    rp, cp = _round_up(r, tile_r), _round_up(c, tile_c)
    cm = jnp.zeros((rp, cp), jnp.float32).at[:r, :c].set(counts)
    rowm = jnp.zeros((rp, 1), jnp.float32).at[:r, 0].set(row_counts)
    colm = jnp.zeros((1, cp), jnp.float32).at[0, :c].set(col_counts)
    # n_total / threshold may be traced scalars (called inside a jitted step)
    scalars = jnp.stack(
        [jnp.asarray(n_total, jnp.float32), jnp.asarray(threshold, jnp.float32)]
    ).reshape(1, 2)
    out = _llr_padded(cm, rowm, colm, scalars, tile_r, tile_c, _interpret())
    return out[:r, :c]


# ---------------------------------------------------------------------------
# in-VMEM bitonic top-k over score tiles (the tiled-CCO merge bottleneck)
# ---------------------------------------------------------------------------


def _roll_stage(s, i, d: int, kmask: int, w: int):
    """One bitonic compare-exchange stage at XOR-distance ``d``, as lane
    rolls + VPU selects.  Direction: descending where ``col & kmask == 0``
    (the natural alternating pattern).  The cyclic wrap can never pair
    wrong elements because positions whose bit_d is 0 always have i+d in
    range and the rest use i-d.  Ties break toward the lower position so
    (score, idx) pairs move as a permutation — no index duplicated/lost.
    """
    from jax.experimental.pallas import tpu as pltpu

    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    is_lower = (col & d) == 0
    dir_desc = (col & kmask) == 0
    # cyclic roll by w-d ≡ roll by -d (pltpu.roll wants shift ≥ 0)
    ps = jnp.where(is_lower, pltpu.roll(s, w - d, 1), pltpu.roll(s, d, 1))
    pi = jnp.where(is_lower, pltpu.roll(i, w - d, 1), pltpu.roll(i, d, 1))
    self_is_max = (s > ps) | ((s == ps) & is_lower)
    keep_self = (dir_desc == is_lower) == self_is_max
    return jnp.where(keep_self, s, ps), jnp.where(keep_self, i, pi)


def _tournament_topb(s, i, w: int, bk: int):
    """Exact top-``bk`` of each row (sorted descending), INSIDE a Pallas
    kernel: every stage is a VPU select chain over VMEM-resident arrays,
    so the whole network costs ONE HBM read of the tile.  (The same
    network as pure XLA ops materializes every stage to HBM — measured
    19× slower than lax.top_k on CPU; as a kernel it is compute-bound.)

    Schedule (strictly less work than a full bitonic sort):
    1. bitonic-sort every bk-wide block, directions alternating
       (desc, asc, …) — O(log²bk) full-width stages;
    2. tournament rounds: each adjacent (desc, asc) pair is bitonic, so
       an elementwise max of its halves keeps exactly the top-bk multiset
       (half-cleaner theorem); log2(bk) cleanup stages restore the
       alternating order.  Width halves per round, so rounds cost
       O(w·log bk) total.  ~78 → ~36 full-width-equivalent stages at the
       production tile (w=4096, bk=128).
    """
    r = s.shape[0]
    kbit = 1
    while (1 << kbit) <= bk:
        for j in reversed(range(kbit)):
            s, i = _roll_stage(s, i, 1 << j, 1 << kbit, w)
        kbit += 1
    while w > bk:
        g = w // (2 * bk)
        s4 = s.reshape(r, g, 2, bk)
        i4 = i.reshape(r, g, 2, bk)
        ls, us = s4[:, :, 0], s4[:, :, 1]
        li, ui = i4[:, :, 0], i4[:, :, 1]
        l_is_max = ls >= us
        w //= 2
        s = jnp.maximum(ls, us).reshape(r, w)
        i = jnp.where(l_is_max, li, ui).reshape(r, w)
        d = bk // 2
        while d >= 1:
            s, i = _roll_stage(s, i, d, bk, w)
            d //= 2
    return s, i


def _topk_sort_kernel(s_ref, out_s_ref, out_i_ref, *, w: int, b: int, bk: int):
    s = s_ref[:]
    i = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s, i = _tournament_topb(s, i, w, bk)
    out_s_ref[:] = s[:, :b]
    out_i_ref[:] = i[:, :b]


@functools.partial(jax.jit, static_argnames=("b", "block_r", "interpret"))
def _tile_topk_padded(scores, b: int, block_r: int, interpret: bool):
    r, w = scores.shape
    rp = _round_up(r, block_r)
    wp = max(b, 128)
    while wp < w:
        wp *= 2
    if (rp, wp) != (r, w):
        scores = jnp.full((rp, wp), NEG_INF, jnp.float32).at[:r, :w].set(scores)
    grid = (rp // block_r,)
    bk = max(b, 128)   # tournament block ≥ one 128-lane group
    out_s, out_i = pl.pallas_call(
        functools.partial(_topk_sort_kernel, w=wp, b=b, bk=bk),
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, wp), lambda g: (g, 0))],
        out_specs=(
            pl.BlockSpec((block_r, b), lambda g: (g, 0)),
            pl.BlockSpec((block_r, b), lambda g: (g, 0)),
        ),
        out_shape=(
            _out_struct((rp, b), jnp.float32, scores),
            _out_struct((rp, b), jnp.int32, scores),
        ),
        cost_estimate=pl.CostEstimate(
            # block sort log²(bk) full-width stages + tournament ~2·log(bk)
            flops=10 * rp * wp * (bk.bit_length() ** 2 // 2 + bk.bit_length()),
            bytes_accessed=4 * (rp * wp + 2 * rp * b),
            transcendentals=0,
        ),
        interpret=interpret,
    )(scores)
    return out_s[:r], out_i[:r]


def tile_topk_desc(
    scores: jnp.ndarray, b: int, block_r: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-``b`` of each row, sorted descending, as ONE Pallas pass.

    Replaces ``lax.top_k`` in the tiled-CCO running merge, where XLA's
    full variadic row sort and the gather after it took 326 + 89 ms a
    [100000, 4096] tile (ledger, PR 24); this kernel takes 153 ms a tile
    in the same job (chip run, PR 25: PERF.md section 6).
    ``b`` must be a power of two (see ``ops.topk.block_width``); rows pad
    to the block, width pads to the next power of two with -inf (padded
    columns surface with -inf scores, which every caller already filters).

    ``block_r`` is one f32 sublane group: the ~100 unrolled stages keep
    (s, i, partner s, partner i) live across the whole [block_r, W]
    block, and Mosaic's compile time and scoped-VMEM stack both grow with
    it — at [100k, 4096] block_r 8/16/32 compile in 2.5/12/40 s, and 128
    takes ~9 min to then exceed the 16 MiB scoped-VMEM limit (v5e AOT
    compile, PERF.md "Bring-up on TPU v5e").
    """
    return _tile_topk_padded(scores, b, block_r, _interpret())
