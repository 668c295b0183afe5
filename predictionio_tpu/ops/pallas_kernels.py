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
  2×2 contingency table + cooccurrence mask + significance threshold +
  self-pair mask, fused into one VPU pass over each count tile where it
  lies (a tile of a wider group included), scores at their own shape.

- ``tile_topk_desc`` — exact per-row top-k of a score tile as an in-VMEM
  bitonic tournament across whole vregs (the tiled-CCO merge's per-tile
  selection wherever these kernels run: ``ops.cco.topk_impl``).

Control: ``PIO_PALLAS`` env var — ``auto`` (default: compiled on TPU, off
otherwise), ``1``/``compiled``, ``interpret``, ``0``/``off``.  A kernel is
interpreted ONLY when ``PIO_PALLAS=interpret`` says so (the CPU test suite
sets it per test); compiled mode off-TPU raises instead of silently
interpreting, so a run can never mistake the interpreter for the kernel.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

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


def _llr_kernel(block_ref, diag_ref, c_ref, row_ref, col_ref, scalars_ref,
                out_ref, *, mask: bool):
    from predictionio_tpu.ops.cco import llr_score

    c = c_ref[:].astype(jnp.float32)     # the counts' own dtype, in VMEM
    row = row_ref[:].astype(jnp.float32)   # [TB, 1] primary-item user counts
    col = col_ref[:].astype(jnp.float32)   # [1, TI] other-item user counts
    n_total = scalars_ref[0, 0]
    threshold = scalars_ref[0, 1]
    k11 = c
    k12 = row - c
    k21 = col - c
    k22 = n_total - k11 - k12 - k21
    g2 = llr_score(k11, k12, k21, k22)   # determinant-form G², VPU-only
    keep = (c > 0) & (g2 >= threshold)
    out_ref[:] = jnp.where(keep, g2, NEG_INF)
    if mask:
        # cell (a, b) of this block is an item against itself where
        # a - b == d; only the blocks the diagonal crosses pay the compare,
        # on the scores already stored (a value held across two branches
        # ran the kernel 1.3% slower on a TPU v5e)
        tile_r, tile_c = out_ref.shape
        d = diag_ref[0] - (pl.program_id(0) * tile_r
                           - pl.program_id(1) * tile_c)

        @pl.when((d > -tile_c) & (d < tile_r))
        def _():
            a = jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 0)
            b = jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 1)
            out_ref[:] = jnp.where(a - b == d, NEG_INF, out_ref[:])


@functools.partial(jax.jit, static_argnames=(
    "width", "tile_r", "tile_c", "mask", "interpret"))
def _llr_padded(block, diag, c, row, col, scalars, width: int, tile_r: int,
                tile_c: int, mask: bool, interpret: bool):
    """The LLR kernel: scores [R, width] of ``width`` columns of ``c``
    [R, W] from column block ``block[0]`` (``col`` [1, W] alike; ``row``
    is [R, 1]); with ``mask``, -inf too where row − column == ``diag[0]``.
    ``block`` and ``diag`` are prefetched scalars: they reach the index
    maps and the kernel before the grid runs.  "Padded" is the grid's:
    its last row block (and column block) runs past the array, where a
    TPU reads unspecified values and drops the writes; the pass is
    elementwise, so nothing leaks, and no copy is made to fit the
    blocks."""
    from jax.experimental.pallas import tpu as pltpu

    r = c.shape[0]
    cells = r * width
    return pl.pallas_call(
        functools.partial(_llr_kernel, mask=mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(r, tile_r), pl.cdiv(width, tile_c)),
            in_specs=[
                pl.BlockSpec((tile_r, tile_c),
                             lambda i, j, blk, _: (i, j + blk[0])),
                pl.BlockSpec((tile_r, 1), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((1, tile_c), lambda i, j, blk, _: (0, j + blk[0])),
                pl.BlockSpec((1, 2), lambda i, j, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j, *_: (i, j)),
        ),
        out_shape=_out_struct((r, width), jnp.float32, block, diag, c, row,
                              col, scalars),
        cost_estimate=pl.CostEstimate(
            flops=30 * cells,
            bytes_accessed=(c.dtype.itemsize + 4) * cells,
            transcendentals=9 * cells,   # the xlogx logs
        ),
        interpret=interpret,
    )(block, diag, c, row, col, scalars)


def llr_blocks(rows: int, width: int) -> Tuple[int, int]:
    """``(tile_r, tile_c)`` of the LLR kernel over ``rows`` × ``width``
    scores: 256 rows, and the widest of 512, 256, 128 columns that divides
    ``width`` (a tile read in its group starts on a whole block), else
    ``width`` in whole 128-wide columns, at most 512."""
    tile_r = min(256, _round_up(rows, 8))
    tile_c = next((t for t in (512, 256, 128) if width % t == 0),
                  min(512, _round_up(width, 128)))
    return tile_r, tile_c


def llr_masked_scores(
    counts: jnp.ndarray,       # [R, W] cooccurrence counts, any number type
    row_counts: jnp.ndarray,   # [R] users per primary item
    col_counts: jnp.ndarray,   # [W] users per other item
    n_total: float,
    threshold: float = 0.0,
    col_start=None,            # with width: read [R, width] from this
    width: Optional[int] = None,   # column, a multiple of width (traced)
    diagonal=None,             # -inf where row − column == diagonal (traced)
) -> jnp.ndarray:
    """Fused G² scores with zero-cooccurrence + threshold masking (-inf),
    [R, width] (``width`` W unless a window is asked for), and with
    ``diagonal`` the self-pairs masked too, in the same pass.  The kernel
    reads ``counts`` where it lies and converts it to float32 in VMEM:
    neither a padded copy nor a sliced or converted tile is made."""
    r, w = counts.shape
    window = width is not None and width != w
    width = w if width is None else width
    tile_r, tile_c = llr_blocks(r, width)
    if window and width % tile_c:     # a window no 128-wide block divides
        counts = jax.lax.dynamic_slice_in_dim(counts, col_start, width, 1)
        col_counts = jax.lax.dynamic_slice_in_dim(col_counts, col_start, width)
        window = False
    block = (jax.lax.div(jnp.asarray(col_start, jnp.int32), jnp.int32(tile_c))
             if window else jnp.int32(0))
    diag = jnp.asarray(0 if diagonal is None else diagonal, jnp.int32)
    # n_total / threshold may be traced scalars (called inside a jitted step)
    scalars = jnp.stack(
        [jnp.asarray(n_total, jnp.float32), jnp.asarray(threshold, jnp.float32)]
    ).reshape(1, 2)
    return _llr_padded(block.reshape(1), diag.reshape(1), counts,
                       row_counts.reshape(r, 1),
                       col_counts.reshape(1, -1), scalars, width, tile_r,
                       tile_c, diagonal is not None, _interpret())


# ---------------------------------------------------------------------------
# in-VMEM bitonic top-k over score tiles (the tiled-CCO merge's selection)
# ---------------------------------------------------------------------------

_SUBLANES = 8        # a float32 vreg: 8 sublanes x 128 lanes
_LANES = 128
_TOPK_ROWS = _SUBLANES * _LANES    # rows a grid step: one vreg a column
_TOPK_GROUP = _LANES               # columns a grid step, where b allows


class TopkPlan(NamedTuple):
    """The selection network for one ``(w, b)``, as data (``topk_plan``)."""
    block: int          # slabs a sorted block: the carry b, never wider
    group: int          # slabs (columns) a chunk, one grid step's
    chunks: int         # chunks a row: w pads to chunks * group columns
    chunk: tuple        # stages: a chunk's slabs -> one block, sorted asc
    merge: tuple        # stages: carry (desc) + that block -> carry (desc)
    slab_stages: float  # full-width equivalents of whole-vreg exchanges
    lane_stages: float  # ... of stages that move data inside a vreg: none


def topk_plan(w: int, b: int) -> TopkPlan:
    """The stage list of the exact top-``b`` network over ``w`` columns: a
    pure function of the two static shapes, read by the kernel, by its
    cost estimate, by the ``dispatch`` spans and by the tests' numpy
    executor.

    Layout.  The kernel turns a [1024, group] piece of the tile so that a
    *slab* is ONE column over 1024 rows (a whole vreg: the rows on its
    sublanes and lanes).  A row's elements then lie along the slab index
    alone, every index bit of the network is a slab bit, and every stage
    is an elementwise exchange between whole vregs in a direction that is
    static per slab: no rotation, no iota, no mask.

    Stages (``j`` indexes the current list of slabs):
    ``("cx", d, k, flip)``  compare-exchange slabs ``j`` and ``j | d`` for
        every ``j & d == 0``; the max goes to ``j`` where ``j & k == 0``
        (``k`` 0: everywhere) and to ``j | d`` elsewhere; ``flip`` swaps
        the two.
    ``("fold", b)``  halve the list: of each pair of adjacent ``b``-slab
        blocks, sorted in opposite directions, keep the elementwise max:
        the pair's top ``b`` as a bitonic block (half-cleaner theorem).

    ``chunk`` bitonic-sorts every block of ``b`` slabs, directions
    alternating (log2(b)·(log2(b)+1)/2 stages), then folds and cleans up
    (log2(b) stages) until one block is left, ascending; ``merge`` folds
    it into the descending carry.  Chunks merge one after the other, so a
    row's need not number a power of two.  At (4096, 64) that is 28
    full-width stages and at (4096, 8) 10, where the network along the
    lanes ran 36 at either (PR 25).
    """
    if b < 1 or b & (b - 1):
        raise ValueError(f"top-k block must be a power of two, got {b}")
    kb = b.bit_length() - 1
    group = max(b, _TOPK_GROUP)
    chunk = [("cx", 1 << j, 1 << kbit, True)
             for kbit in range(1, kb + 1) for j in reversed(range(kbit))]
    n, stages = group, kb * (kb + 1) / 2
    while n > b:
        chunk += [("fold", b)]
        chunk += [("cx", b >> t, b, True) for t in range(1, kb + 1)]
        n //= 2
        stages += (1 + kb) * n / group
    merge = (("fold", b),
             *(("cx", b >> t, 0, False) for t in range(1, kb + 1)))
    stages += (1 + kb) * b / group
    return TopkPlan(b, group, pl.cdiv(w, group), tuple(chunk), merge,
                    round(stages, 3), 0.0)


def _run_stages(s, i, stages):
    """Apply ``stages`` (see ``topk_plan``) to the stacked slabs ``s``
    [n * 8, 128] and their columns ``i``: slab ``j`` is rows 8j..8j+7, so
    a stage is a reshape of the leading dimension (whole vregs, no data
    moves) and five elementwise operations.  Ties go toward the lower
    slab, so (score, column) pairs are permuted and halved, never
    duplicated."""
    lanes = s.shape[1]
    for op, *arg in stages:
        n = s.shape[0] // _SUBLANES
        if op == "fold":
            shape = (n // (2 * arg[0]), 2, arg[0] * _SUBLANES, lanes)
            s, i = s.reshape(shape), i.reshape(shape)
            ge = s[:, 0] >= s[:, 1]
            s = jnp.maximum(s[:, 0], s[:, 1])
            i = jnp.where(ge, i[:, 0], i[:, 1])
        else:
            d, k, flip = arg
            one_way = k == 0 or k >= n
            # [.., bit k of j, .., bit d of j, the rows of d slabs, lanes]
            shape = ((1, 1, n // (2 * d)) if one_way
                     else (n // (2 * k), 2, k // (2 * d)))
            shape += (2, d * _SUBLANES, lanes)
            s, i = s.reshape(shape), i.reshape(shape)
            lo_s, hi_s = s[:, :, :, 0], s[:, :, :, 1]
            lo_i, hi_i = i[:, :, :, 0], i[:, :, :, 1]
            ge = lo_s >= hi_s
            mx_s, mn_s = jnp.maximum(lo_s, hi_s), jnp.minimum(lo_s, hi_s)
            mx_i, mn_i = jnp.where(ge, lo_i, hi_i), jnp.where(ge, hi_i, lo_i)
            if flip:
                mx_s, mn_s, mx_i, mn_i = mn_s, mx_s, mn_i, mx_i

            def by_k(p, q):     # p where bit k of j is 0, q where it is 1
                if one_way:
                    return p
                return jnp.concatenate([p[:, :1], q[:, 1:]], axis=1)

            s = jnp.stack([by_k(mx_s, mn_s), by_k(mn_s, mx_s)], axis=3)
            i = jnp.stack([by_k(mx_i, mn_i), by_k(mn_i, mx_i)], axis=3)
        s = s.reshape(-1, lanes)
        i = i.reshape(-1, lanes)
    return s, i


def _topk_slab_kernel(x_ref, out_s_ref, out_i_ref, acc_s_ref, acc_i_ref, *,
                      plan: TopkPlan):
    c = pl.program_id(1)
    # [1024 rows, group columns] -> slab j = column j, row 128a + r at
    # (sublane a, lane r): 128-wide transposes put the rows on the lanes,
    # swapping the two leading dimensions then gathers a column's eight
    # row groups into one vreg.
    t = jnp.stack([x_ref[a * _LANES:(a + 1) * _LANES, :].T
                   for a in range(_SUBLANES)])          # [a, column, r]
    s = jnp.transpose(t, (1, 0, 2)).reshape(plan.group * _SUBLANES, _LANES)
    i = c * plan.group + jax.lax.broadcasted_iota(     # slab j's column
        jnp.int32, (plan.group, _SUBLANES, _LANES), 0).reshape(s.shape)
    s, i = _run_stages(s, i, plan.chunk)

    @pl.when(c == 0)
    def _():
        acc_s_ref[...] = jnp.full(acc_s_ref.shape, NEG_INF, jnp.float32)
        acc_i_ref[...] = jnp.zeros(acc_i_ref.shape, jnp.int32)

    s, i = _run_stages(jnp.concatenate([acc_s_ref[...], s]),
                       jnp.concatenate([acc_i_ref[...], i]), plan.merge)
    acc_s_ref[...] = s
    acc_i_ref[...] = i

    @pl.when(c == plan.chunks - 1)
    def _():
        out_s_ref[...] = s.reshape(out_s_ref.shape)
        out_i_ref[...] = i.reshape(out_i_ref.shape)


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def _tile_topk_padded(scores, b: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    r, w = scores.shape
    plan = topk_plan(w, b)
    # rows past the last whole block are the grid's own edge; columns pad
    # to whole chunks with -inf (no copy at the production tile)
    rp, wp = max(r, _TOPK_ROWS), plan.chunks * plan.group
    if (rp, wp) != (r, w):
        scores = jnp.full((rp, wp), NEG_INF, jnp.float32).at[:r, :w].set(scores)
    # rank t of row 1024g + 128a + l lands at out[t, 8g + a, l]
    out_block = pl.BlockSpec((b, _SUBLANES, _LANES), lambda g, c: (0, g, 0))
    out_rows = pl.cdiv(rp, _LANES)
    out_s, out_i = pl.pallas_call(
        functools.partial(_topk_slab_kernel, plan=plan),
        grid=(pl.cdiv(rp, _TOPK_ROWS), plan.chunks),
        in_specs=[pl.BlockSpec((_TOPK_ROWS, plan.group), lambda g, c: (g, c))],
        out_specs=(out_block, out_block),
        out_shape=(
            _out_struct((b, out_rows, _LANES), jnp.float32, scores),
            _out_struct((b, out_rows, _LANES), jnp.int32, scores),
        ),
        scratch_shapes=[
            pltpu.VMEM((b * _SUBLANES, _LANES), jnp.float32),
            pltpu.VMEM((b * _SUBLANES, _LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            # 2.5 VPU operations an element a full-width stage
            flops=int(2.5 * rp * wp * (plan.slab_stages + plan.lane_stages)),
            bytes_accessed=4 * (rp * wp + 2 * rp * b),
            transcendentals=0,
        ),
        interpret=interpret,
    )(scores)
    return out_s.reshape(b, -1)[:, :r].T, out_i.reshape(b, -1)[:, :r].T


def tile_topk_desc(scores: jnp.ndarray, b: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-``b`` of each row, sorted descending, as ONE Pallas pass.

    Replaces ``lax.top_k`` in the tiled-CCO running merge, where XLA's
    full variadic row sort and the gather after it took 326 + 89 ms a
    [100000, 4096] tile (ledger, PR 24).  The network (``topk_plan``)
    runs across whole vregs, one column a vreg, and sorts no wider than
    ``b``: 14.5 ms a tile at ``b`` 64 and 4.7 ms a [102400, 4096] tile at
    ``b`` 8, of which 3.3 ms are the tile's read and its turn to slabs
    (chip run, PR 32: PERF.md section 6); along the lanes, 128 wide, it
    took 153 ms at either (PR 25).

    ``b`` must be a power of two (see ``ops.topk.block_width``).  Width
    pads to whole chunks of ``max(b, 128)`` columns with -inf; padded
    columns, and the places of a row with fewer than ``b`` finite scores,
    surface with -inf scores, which every caller already filters (their
    columns mean nothing).  Ties keep any of the tied columns, each once.
    A grid step is 1024 rows by one chunk; Mosaic compiles the kernel in
    2.3 s at [100000, 4096], ``b`` 64 [AOT, PR 32].
    """
    return _tile_topk_padded(scores, b, _interpret())
