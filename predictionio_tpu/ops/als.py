"""Alternating Least Squares matrix factorization, TPU-first.

Replaces the reference Recommendation template's call into Spark MLlib
``ALS.train`` (template repo's ALSAlgorithm.scala; MLlib implements block
ALS over a users×products grid of RDD partitions — SURVEY.md §2).

TPU design (not a translation of MLlib's shuffle pattern):

- Interactions are COO triples ``(user, item, rating)``, dictionary-encoded.
- The mesh's ``dp`` axis owns both sides: user ``u`` lives on shard
  ``u % dp``, item ``i`` on shard ``i % dp``.  The host prepares TWO padded
  layouts of the same events — grouped by user shard and by item shard —
  so each half-step is pure local compute after one ``all_gather`` of the
  opposite factor block (the collective rides ICI; this replaces MLlib's
  shuffle of in/out-link blocks).
- Each half-step forms per-entity normal equations from ONE packed row a
  rating — the upper triangle of y yᵀ and r·y, K(K+3)/2 floats (65 at rank
  10: one 128-lane row) — summed by ONE ``segment_sum`` over the ratings,
  and solves the K×K systems with a batched Cholesky.  No per-rating K×K
  block exists: the TPU pads one to [16, 128] (20× its size), which was 8.2
  GB a half-step at a million ratings and 57% of a train (PERF.md, PR 27).
  The ratings-per-row counts behind the ridge depend on the layout alone
  and are reduced once, before the sweeps.  No data-dependent shapes, one
  compiled program for the whole training run (`lax.fori_loop` over sweeps).

Memory: the packed rows are [E, K(K+3)/2] f32 per shard (lane-padded to a
multiple of 128), the reduced systems [rows_per_shard, K, K]; events are
padded to the max per-shard count. f32 throughout the solves (K ≤ a few
hundred) and in the 0/1 matmuls that lay the packed row out (HIGHEST:
exact); gathers/matmuls stay f32 in storage; what precision a TPU multiplies
the serving matmuls at is the compiler's choice, so parity with MLlib's f64
holds on CPU only (on a v5e the Pallas scoring kernel's products are
bf16-rounded: max abs error 0.085 on one query's 100k scores, where the XLA
path it replaces stayed at 3e-6 — PERF.md "Bring-up on TPU v5e").
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs.spans import span
from predictionio_tpu.utils.device import PLAN_BYTES, noted, stage


@dataclasses.dataclass
class ALSData:
    """Host-prepared dual-layout interaction data for a mesh of size dp.

    Layout invariant: global entity ``e`` maps to (shard ``e % dp``, local row
    ``e // dp``); factor blocks are stored as [dp * rows, K] arrays whose
    flat index is ``shard * rows + local_row``.
    """

    dp: int
    n_users: int
    n_items: int
    user_rows: int   # padded users per shard
    item_rows: int   # padded items per shard
    # by-user layout: [dp, E_u]
    u_user_local: np.ndarray   # local user row on the owning shard
    u_item_flat: np.ndarray    # flat index into item factor blocks
    u_rating: np.ndarray
    u_mask: np.ndarray         # f32 validity mask
    # by-item layout: [dp, E_i]
    i_item_local: np.ndarray
    i_user_flat: np.ndarray
    i_rating: np.ndarray
    i_mask: np.ndarray


def _group_by_shard(
    owner: np.ndarray, other_flat: np.ndarray, rating: np.ndarray, dp: int, pad_multiple: int = 8
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket events by ``owner % dp``; pad buckets to a common length."""
    shard = owner % dp
    order = np.argsort(shard, kind="stable")
    owner_s, other_s, rating_s, shard_s = owner[order], other_flat[order], rating[order], shard[order]
    counts = np.bincount(shard_s, minlength=dp)
    width = max(int(counts.max()) if len(owner) else 1, 1)
    width = ((width + pad_multiple - 1) // pad_multiple) * pad_multiple
    local = np.zeros((dp, width), np.int32)
    other = np.zeros((dp, width), np.int32)
    rat = np.zeros((dp, width), np.float32)
    mask = np.zeros((dp, width), np.float32)
    start = 0
    for s in range(dp):
        c = int(counts[s])
        sl = slice(start, start + c)
        local[s, :c] = owner_s[sl] // dp
        other[s, :c] = other_s[sl]
        rat[s, :c] = rating_s[sl]
        mask[s, :c] = 1.0
        start += c
    return local, other, rat, mask


def prepare_als_data(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    dp: int,
) -> ALSData:
    with span("layout"):
        user_idx = np.asarray(user_idx, np.int32)
        item_idx = np.asarray(item_idx, np.int32)
        rating = np.asarray(rating, np.float32)
        user_rows = max(math.ceil(n_users / dp), 1)
        item_rows = max(math.ceil(n_items / dp), 1)
        # flat index of the OTHER side's factor row: shard * rows + local_row
        item_flat = (item_idx % dp) * item_rows + item_idx // dp
        user_flat = (user_idx % dp) * user_rows + user_idx // dp
        uu, ui, ur, um = _group_by_shard(user_idx, item_flat, rating, dp)
        ii, iu, ir, im = _group_by_shard(item_idx, user_flat, rating, dp)
    return ALSData(
        dp=dp, n_users=n_users, n_items=n_items,
        user_rows=user_rows, item_rows=item_rows,
        u_user_local=uu, u_item_flat=ui, u_rating=ur, u_mask=um,
        i_item_local=ii, i_user_flat=iu, i_rating=ir, i_mask=im,
    )


def _packed_width(k: int) -> int:
    """Width of a rating's packed row: the triangle of y yᵀ, then r·y."""
    return k * (k + 1) // 2 + k


@functools.lru_cache(maxsize=None)
def _pack_selectors(k: int) -> Tuple[np.ndarray, np.ndarray]:
    """0/1 matrices ([K+1, W], [K, W]) that lay ``[yw | rhs_w]`` and ``y``
    out along the packed row: column (p, q), p ≤ q, of the triangle takes
    ``yw[p]`` and ``y[q]``; column j of the tail takes ``rhs_w`` and ``y[j]``."""
    p, q = np.triu_indices(k)
    col = np.arange(_packed_width(k))
    sel_l = np.zeros((k + 1, len(col)), np.float32)
    sel_r = np.zeros((k, len(col)), np.float32)
    sel_l[np.concatenate([p, np.full(k, k)]), col] = 1.0
    sel_r[np.concatenate([q, np.arange(k)]), col] = 1.0
    return sel_l, sel_r


def _packed_rows(yw: jnp.ndarray, y: jnp.ndarray, rhs_w: jnp.ndarray) -> jnp.ndarray:
    """One flat row a rating, [E, W]: ``yw[p]·y[q]`` for p ≤ q (row by row of
    the upper triangle), then ``rhs_w·y``.  Summed over a row's ratings it
    holds that row's whole normal equations; at rank 10 it is 65 floats, one
    128-lane row, where a K×K block pads to [16, 128].

    The two operands are spread over the W columns by 0/1 matmuls (exact at
    HIGHEST: each column copies one float32): XLA:TPU fuses the product into
    the second matmul's output, where W lane slices concatenated cost a pass
    over [E, 128] each (my chip runs, PR 27: 2.9 against ~32 ms a half-step)."""
    sel_l, sel_r = _pack_selectors(y.shape[-1])
    with stage("als.rhs"):
        left = jnp.concatenate([yw, rhs_w[:, None]], axis=1)
    hi = jax.lax.Precision.HIGHEST
    return jnp.dot(left, sel_l, precision=hi) * jnp.dot(y, sel_r, precision=hi)


def _unpack_rows(red: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[rows, W] sums of packed rows → (A [rows, K, K] symmetric, b [rows, K])."""
    t = k * (k + 1) // 2
    at = np.zeros((k, k), np.int32)
    at[np.triu_indices(k)] = np.arange(t, dtype=np.int32)
    at = np.maximum(at, at.T)
    return red[:, at], red[:, t:]


def _row_ridge(local_idx: jnp.ndarray, mask: jnp.ndarray, rows: int, reg) -> jnp.ndarray:
    """λ·n_e ridge (MLlib's ALS-WR weighting) + ε guard for empty rows.  It
    depends on the layout alone: computed once, before the sweeps."""
    with stage("als.normal_eq"):
        n_e = jax.ops.segment_sum(mask, local_idx, num_segments=rows)
        return reg * jnp.maximum(n_e, 1.0) + 1e-6


def _solve_rows(z, local_idx, lam, k: int, gram, block: int) -> jnp.ndarray:
    """The half-step's one segment reduction, over the packed rows, then the
    K×K solves.  XLA:TPU's scatter-add of [E, W] rows costs 6.8 ns a rating
    on a v5e, 8.7 where runs of ratings share a row, and more again with
    ``indices_are_sorted``; a blockwise one-hot matmul over rows sorted by
    segment gained 5% of the program for a device sort that compiles for
    15–27 s, and was not kept (both measured on a v5e).

    The systems are solved ``block`` rows at a time (``_solve_plan``'s,
    dividing ``lam``'s rows) in a ``lax.map``: where one block holds every
    row, XLA drops the one-trip loop and the program is the unblocked one."""
    with stage("als.normal_eq"):
        red = jax.ops.segment_sum(z, local_idx, num_segments=lam.shape[0])
    n = lam.shape[0] // block
    return jax.lax.map(
        lambda a: _solve_block(*a, k, gram),
        (red.reshape(n, block, -1), lam.reshape(n, block))).reshape(-1, k)


def _solve_block(red, lam, k: int, gram=None) -> jnp.ndarray:
    """Rows' systems from their summed packed rows [rows, W], solved:
    unpack, the Gram and the ridge added, then the batched Cholesky."""
    with stage("als.normal_eq"):
        A, b = _unpack_rows(red, k)
        if gram is not None:
            A = A + gram
        A = A + lam[:, None, None] * jnp.eye(k, dtype=A.dtype)
    with stage("als.solve"):
        cho = jax.scipy.linalg.cho_factor(A)
        return jax.scipy.linalg.cho_solve(cho, b[..., None])[..., 0]  # [rows, K]


# the bytes a program's plan may take (a name of this module's, which the
# tests shrink)
_PLAN_BYTES = PLAN_BYTES


def _tiled_bytes(rows: int, cols: int) -> int:
    """A float32 [rows, cols] in the TPU's (8, 128) tiles of its two minor
    dimensions: a K×K system at rank 10 takes [16, 128], 8 KB."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def _solve_plan(rows_u: int, rows_i: int, k: int) -> Tuple[int, int]:
    """``(user block, item block)``: the rows whose systems one step of a
    side's solve holds, all of the side's where they fit in one.

    Counted as the TPU compiler lays the solve out for a v5e: a row of a
    block holds its K×K system and that system's Cholesky factor, padded
    ``_tiled_bytes(K, K)`` each (16 KB a row at rank 10, where the floats
    are 800 B); beside the block stay, for every row of either side, its
    summed packed row, that sum's copy laid out in blocks and its factor
    row, lane-padded.  (The event slots' packed rows are dead by then: the
    solve reuses their bytes.)  The block is the most rows that fit what
    ``_PLAN_BYTES`` leaves; a side with more rows is cut into the fewest
    blocks that fit, evenly, each rounded up to whole 8-row tiles.  At 1.4M
    users and 235k items, rank 10: the users in 3 blocks of 469,200 and the
    items in one, where one block of all the users asked the compiler for
    21.64 GB.  It reads shapes alone."""
    held = 3 * _tiled_bytes(rows_u + rows_i, _packed_width(k))
    most = max((_PLAN_BYTES - held) // (2 * _tiled_bytes(k, k)) // 8 * 8, 8)

    def block(rows: int) -> int:
        if rows <= most:
            return rows
        per_block = -(-rows // -(-rows // most))      # ⌈rows ÷ blocks⌉
        return -(-per_block // 8) * 8

    return block(rows_u), block(rows_i)


def _blocked_rows(rows: int, block: int) -> int:
    """The rows a side's systems are reduced into: whole blocks."""
    return -(-rows // block) * block


def _half_step(
    other_full: jnp.ndarray,   # [dp*other_rows, K] gathered opposite factors
    local_idx: jnp.ndarray,    # [E] rows to solve for (this shard)
    other_flat: jnp.ndarray,   # [E] flat gather index into other_full
    rating: jnp.ndarray,       # [E]
    mask: jnp.ndarray,         # [E]
    lam: jnp.ndarray,          # [rows] ridge of each row (_row_ridge)
    block: int,                # rows a solve block (_solve_plan)
) -> jnp.ndarray:
    """Solve per-row normal equations (YtY + λ n_e I) x = Ytr on one shard."""
    with stage("als.gather"):
        y = other_full[other_flat] * mask[:, None]            # [E, K]
    with stage("als.normal_eq"):
        z = _packed_rows(y, y, rating)
    return _solve_rows(z, local_idx, lam, y.shape[-1], None, block)


def _half_step_implicit(
    other_full: jnp.ndarray,   # [dp*other_rows, K] gathered opposite factors
    gram: jnp.ndarray,         # [K, K] = other_fullᵀ other_full (YᵀY term)
    local_idx: jnp.ndarray,    # [E]
    other_flat: jnp.ndarray,   # [E]
    rating: jnp.ndarray,       # [E] raw counts/strengths r ≥ 0
    mask: jnp.ndarray,         # [E]
    lam: jnp.ndarray,          # [rows]
    alpha: jnp.ndarray,
    block: int,                # rows a solve block (_solve_plan)
) -> jnp.ndarray:
    """Implicit-feedback half-step (Hu/Koren/Volinsky; MLlib trainImplicit).

    Preference p = 1 for every observed event, confidence c = 1 + α·r.
    Per-row system: (YᵀY + Yᵀ(C−I)Y + λ·n_e·I) x = Yᵀ C p — the dense YᵀY
    is the precomputed ``gram`` (``_gram``, once a half-step), and only the
    observed events contribute the (c−1)-weighted correction.
    """
    with stage("als.gather"):
        y = other_full[other_flat] * mask[:, None]            # [E, K]
    with stage("als.normal_eq"):
        c1 = alpha * rating * mask                            # c − 1, 0 on padding
        z = _packed_rows(c1[:, None] * y, y, 1.0 + c1)
    return _solve_rows(z, local_idx, lam, y.shape[-1], gram, block)


# rows of one partial Gram (``_gram``)
_GRAM_ROWS = 2048


def _gram(other_full: jnp.ndarray) -> jnp.ndarray:
    """YᵀY [K, K] of the implicit half-step, at HIGHEST, summed in two
    levels: the Gram of every ``_GRAM_ROWS`` rows, then their sum.

    At default precision a TPU multiplies in one bfloat16 pass (~1e-3 off
    float32's Gram).  At HIGHEST, one contraction over a whole table still
    loses float32: on a v5e its largest error was 4.1e-5 of the largest
    entry over 1.4M rows (1.9e-5 over 235k; numpy's float32 1.6e-6), and
    every row's system carries it (the cell's reference reads it: PERF.md
    section 2).  Summed in two levels it was 1.3e-7.  The barrier keeps XLA
    from folding the sum of the partial Grams back into one contraction."""
    n, k = other_full.shape
    with stage("als.normal_eq"):
        o = jnp.pad(other_full, ((0, -n % _GRAM_ROWS), (0, 0)))
        o = o.reshape(-1, _GRAM_ROWS, k)
        part = jnp.einsum("cnk,cnl->ckl", o, o,
                          precision=jax.lax.Precision.HIGHEST)
        return jax.lax.optimization_barrier(part).sum(0)


def _sweeps(x0, y0, iters, reg, alpha, u_side, i_side, implicit: bool,
            gather_full, blocks):
    """``iters`` ALS sweeps over the factor rows this program solves (``x0``
    [rows_u, K], ``y0`` [rows_i, K]) and their events (``u_side``,
    ``i_side``: local row, opposite flat index, rating, mask, each [E]).
    ``gather_full`` turns its rows of one side into that side's whole table.
    ``blocks`` are the rows of a solve block of each side (``_solve_plan``):
    a side's systems are reduced into ⌈rows ÷ block⌉ · block rows, and the
    rows past its own are dropped (they have no events: b = 0, solved to
    0)."""
    # the counts depend on the layout alone: once, not in each half-step
    lam_u = _row_ridge(u_side[0], u_side[3],
                       _blocked_rows(x0.shape[0], blocks[0]), reg)
    lam_i = _row_ridge(i_side[0], i_side[3],
                       _blocked_rows(y0.shape[0], blocks[1]), reg)

    def half(other_full, side, lam, block, rows):
        if implicit:
            out = _half_step_implicit(other_full, _gram(other_full), *side,
                                      lam, alpha, block)
        else:
            out = _half_step(other_full, *side, lam, block)
        return out[:rows]

    def sweep(_, carry):
        x, y = carry
        x = half(gather_full(y), u_side, lam_u, blocks[0], x0.shape[0])
        y = half(gather_full(x), i_side, lam_i, blocks[1], y0.shape[0])
        return (x, y)

    return jax.lax.fori_loop(0, iters, sweep, (x0, y0))


def _as_one_shard(local, other_flat, rating, mask, rows: int):
    """A [dp, E] layout as ONE shard of dp·rows rows: shard s's local row r
    is row s·rows + r, its flat index in the factor blocks."""
    with stage("als.gather"):
        offset = jnp.arange(local.shape[0], dtype=local.dtype)[:, None] * rows
        return tuple(a.reshape(-1)
                     for a in (local + offset, other_flat, rating, mask))


@functools.partial(jax.jit, static_argnames=("implicit", "blocks"))
def _als_run_single(
    x0, y0, iters, reg, alpha,
    uu, ui, ur, um, ii, iu, ir, im,
    *, implicit: bool = False, blocks: Tuple[int, int],
):
    """Single-program ALS sweeps: every shard's rows and events on one device.

    Module-level jit with DYNAMIC iteration count, reg, and alpha: one
    compiled program per data/factor shape/mode serves every (iterations,
    reg, alpha) setting — retraining and hyperparameter grids never
    recompile.
    """
    k = y0.shape[-1]
    x, y = _sweeps(
        x0.reshape(-1, k), y0.reshape(-1, k), iters, reg, alpha,
        _as_one_shard(uu, ui, ur, um, x0.shape[1]),
        _as_one_shard(ii, iu, ir, im, y0.shape[1]),
        implicit, gather_full=lambda f: f, blocks=blocks)
    return x.reshape(x0.shape), y.reshape(y0.shape)


@functools.lru_cache(maxsize=8)
def _als_sharded_fn(mesh: Mesh, implicit: bool, blocks: Tuple[int, int]):
    """Build (and cache per mesh/mode) the shard_map'd ALS runner."""

    def per_shard(x0_, y0_, iters, reg, alpha, *sides):
        # Every array here is this shard's block: factors [1, rows, K],
        # events [1, E].  all_gather pulls the opposite side's blocks over
        # ICI — the only communication in a sweep.  The implicit Gram is
        # computed from the gathered full matrix (replicated K×K work,
        # negligible next to the solves).
        mine = [a[0] for a in sides]
        x, y = _sweeps(
            x0_[0], y0_[0], iters, reg, alpha, mine[:4], mine[4:], implicit,
            gather_full=lambda f: jax.lax.all_gather(f, "dp", tiled=True),
            blocks=blocks)
        return x[None], y[None]

    spec, rep = P("dp"), P()
    return jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec, spec, rep, rep, rep) + (spec,) * 8,
        out_specs=(spec, spec),
    ))


def _als_run_sharded(mesh, implicit, blocks, x0, y0, iters, reg, alpha,
                     *args):
    return noted(_als_sharded_fn(mesh, implicit, blocks), x0, y0, iters, reg,
                 alpha, *args)


def als_train(
    data: ALSData,
    k: int,
    reg: float,
    iterations: int,
    mesh: Optional[Mesh] = None,
    seed: int = 7,
    checkpoint=None,
    checkpoint_every: int = 0,
    implicit: bool = False,
    alpha: float = 1.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Run ALS sweeps; returns (X [n_users, K], Y [n_items, K]) on host.

    With a mesh, factors live block-sharded over ``dp`` and each half-step
    all-gathers the opposite blocks (ICI); without, the same program runs on
    one device with dp=1.

    ``implicit=True`` switches to implicit-feedback ALS (Hu/Koren/Volinsky,
    the MLlib ``ALS.trainImplicit`` the reference e-commerce and
    similar-product templates call): ratings become confidences
    c = 1 + ``alpha``·r over binary preferences, and each half-step adds the
    dense YᵀY Gram term.

    ``checkpoint`` (a utils.checkpoint.CheckpointStore) + ``checkpoint_every``
    snapshot the factor blocks every N sweeps and resume from the newest
    snapshot — sweeps already completed by a failed run are not repeated.
    """
    if checkpoint is not None and checkpoint_every > 0:
        return _als_train_checkpointed(
            data, k, reg, iterations, mesh, seed, checkpoint, checkpoint_every,
            implicit=implicit, alpha=alpha,
        )
    with span("dispatch", program="_als_init"):
        x0, y0 = _als_init(data, k, seed)
    x, y = _als_sweeps(data, x0, y0, iterations, reg, mesh,
                       implicit=implicit, alpha=alpha)
    return _als_deinterleave(data, x, y, k)


def _als_init(data: ALSData, k: int, seed: int):
    # A dozen eager operations, not one jitted program: under one jit XLA
    # folds the two constant factors of the normal draw into one, and the
    # factors start a last bit away from where they started before (so the
    # trained model moves, within its limits: chip run, PR 35).  They run in
    # microseconds and are the unstaged remainder of a trace.
    key = jax.random.PRNGKey(seed)
    y0 = jax.random.normal(key, (data.dp, data.item_rows, k), jnp.float32) * 0.1
    # zero the padding rows (shard s, local r holds item r*dp + s): real rows
    # never read them in the explicit path, but the implicit path's Gram
    # (YᵀY over the full gathered block) must not see init noise there —
    # and they then stay exactly 0 (their normal equations have b = 0).
    item_id = (
        jnp.arange(data.item_rows, dtype=jnp.int32)[None, :] * data.dp
        + jnp.arange(data.dp, dtype=jnp.int32)[:, None]
    )
    y0 = y0 * (item_id < data.n_items)[..., None]
    x0 = jnp.zeros((data.dp, data.user_rows, k), jnp.float32)
    return x0, y0


def _als_device_args(data: ALSData):
    host = (data.u_user_local, data.u_item_flat, data.u_rating, data.u_mask,
            data.i_item_local, data.i_user_flat, data.i_rating, data.i_mask)
    with span("h2d", bytes=sum(a.nbytes for a in host)):
        return tuple(jnp.asarray(a) for a in host)


def _als_sweeps(data: ALSData, x0, y0, n_sweeps: int, reg: float, mesh, args=None,
                implicit: bool = False, alpha: float = 1.0):
    if args is None:
        args = _als_device_args(data)
    # which program shape ran: the packed row's width and the event slots a
    # shard of each layout (the reduction always engages; this is its witness)
    # and the solve's blocks: the rows of the largest, and how many a call
    # solves (each side's a half-step, every sweep)
    k, sides = x0.shape[-1], (data.user_rows, data.item_rows)
    blocks = _solve_plan(*sides, k)
    shape = dict(packed_width=_packed_width(k),
                 events=int(data.u_mask.shape[1]), implicit=implicit,
                 solve_block=max(blocks),
                 solve_blocks=n_sweeps * sum(
                     -(-r // b) for b, r in zip(blocks, sides)))
    if mesh is None:
        with span("dispatch", program="_als_run_single", **shape):
            return noted(
                _als_run_single,
                x0, y0, jnp.int32(n_sweeps), jnp.float32(reg),
                jnp.float32(alpha),
                *args, implicit=implicit, blocks=blocks,
            )
    if mesh.shape.get("dp", 1) != data.dp:
        raise ValueError(
            f"ALSData prepared for dp={data.dp}, mesh has dp={mesh.shape.get('dp')}")
    sharding = NamedSharding(mesh, P("dp"))
    from predictionio_tpu.parallel.sharding import stage_global

    with span("h2d", bytes=x0.nbytes + y0.nbytes):
        x0 = stage_global(np.asarray(x0), sharding)
        y0 = stage_global(np.asarray(y0), sharding)
    with span("dispatch", program="_als_run_sharded", **shape):
        return _als_run_sharded(
            mesh, implicit, blocks, x0, y0, jnp.int32(n_sweeps),
            jnp.float32(reg),
            jnp.float32(alpha), *args,
        )


def _als_deinterleave(data: ALSData, x, y, k: int):
    # De-interleave [dp, rows, K] back to global [n, K]: global e = shard + dp*row.
    def host(a):
        # multi-process meshes: gather before fetching (np.asarray can only
        # read fully-addressable arrays)
        if hasattr(a, "is_fully_addressable") and not a.is_fully_addressable:
            from jax.experimental import multihost_utils

            a = multihost_utils.process_allgather(a, tiled=True)
        return np.asarray(a)

    with span("device_wait", bytes=x.nbytes + y.nbytes):
        x, y = host(x), host(y)
    x = x.transpose(1, 0, 2).reshape(-1, k)[: data.n_users]
    y_arr = y.transpose(1, 0, 2).reshape(-1, k)[: data.n_items]
    return x, y_arr


def als_fingerprint(data: ALSData, k: int, reg: float, seed: int,
                    implicit: bool = False, alpha: float = 1.0) -> str:
    """Identifies a training run well enough to reject foreign snapshots:
    hyperparams + data layout + a cheap content signature."""
    n_events = int(data.u_mask.sum())
    sig = int(np.int64(data.u_rating.sum() * 1000)) if n_events else 0
    mode = f"-imp{alpha}" if implicit else ""
    return (
        f"k{k}-dp{data.dp}-u{data.n_users}x{data.user_rows}"
        f"-i{data.n_items}x{data.item_rows}-e{n_events}-r{reg}-s{seed}-h{sig}{mode}"
    )


def _als_train_checkpointed(
    data: ALSData, k: int, reg: float, iterations: int, mesh,
    seed: int, checkpoint, checkpoint_every: int,
    implicit: bool = False, alpha: float = 1.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Chunked sweeps with snapshot/resume (see als_train docstring)."""
    from predictionio_tpu.utils.checkpoint import maybe_inject

    fingerprint = als_fingerprint(data, k, reg, seed, implicit, alpha)
    done = 0
    x = y = None
    latest = checkpoint.latest()
    if latest is not None:
        step, state = latest
        # resume ONLY a snapshot of this exact run with sweeps still to do;
        # anything else (other dataset/params, or already >= iterations) is
        # stale — start fresh rather than return foreign/over-trained factors
        if state.get("fingerprint") == fingerprint and step < iterations:
            done = step
            x = jnp.asarray(state["x"])
            y = jnp.asarray(state["y"])
    if x is None:
        with span("dispatch", program="_als_init"):
            x, y = _als_init(data, k, seed)
    args = _als_device_args(data)  # one host->device upload for all chunks
    while done < iterations:
        n = min(checkpoint_every, iterations - done)
        x, y = _als_sweeps(data, x, y, n, reg, mesh, args=args,
                           implicit=implicit, alpha=alpha)
        done += n
        maybe_inject("als.sweep")  # rehearse mid-training failure in tests
        checkpoint.save(done, {
            "x": np.asarray(x), "y": np.asarray(y), "fingerprint": fingerprint,
        })
    return _als_deinterleave(data, x, y, k)


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_scores(
    user_vec: jnp.ndarray,        # [K]
    item_factors: jnp.ndarray,    # [n_items, K]
    seen_mask: jnp.ndarray,       # [n_items] 1.0 where already interacted
    top_k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-K item scores for one user; seen items pushed to -inf."""
    scores = item_factors @ user_vec
    scores = jnp.where(seen_mask > 0, -jnp.inf, scores)
    return jax.lax.top_k(scores, top_k)


def check_f32_id_range(n_items: int) -> None:
    """The stacked-readback serving paths pack item indices as f32, which
    is exact only below 2**24.  Callers invoke this with the static catalog
    size at trace time (shapes are static under jit, so every new catalog
    shape passes through here exactly once) — violating catalogs fail
    loudly instead of silently serving corrupted item ids."""
    if n_items >= 1 << 24:
        raise ValueError(
            f"catalog of {n_items} items exceeds the 2**24 exact-int range "
            "of the f32-packed top-k serving path; shard the catalog across "
            "devices or split the app")


def _stack_topk(scores: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Pack (scores, idx) as one [2, k] f32 array so serving does ONE
    device→host readback per query: each separate fetch is its own
    device sync.  Item indices are exact in f32 up to 2^24 — enforced at
    trace time by check_f32_id_range."""
    return jnp.stack([scores, idx.astype(jnp.float32)])


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_scores_excl(
    user_vec: jnp.ndarray,        # [K]
    item_factors: jnp.ndarray,    # [n_items, K] — device-resident
    excl_idx: jnp.ndarray,        # [W] item ids to exclude, -1 padding
    top_k: int,
) -> jnp.ndarray:                 # [2, top_k]: scores row, item-id row
    """Top-K scores with an exclusion LIST instead of a dense mask.

    The serving path stages ``item_factors`` to device once at model load;
    per query only the K-vector and a small padded id list transfer, so the
    full [n_items] mask (400 KB at 100k items) never crosses PCIe.
    """
    check_f32_id_range(item_factors.shape[0])
    scores = item_factors @ user_vec
    valid = excl_idx >= 0
    scores = scores.at[jnp.where(valid, excl_idx, 0)].min(
        jnp.where(valid, -jnp.inf, jnp.inf))
    return _stack_topk(*jax.lax.top_k(scores, top_k))


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_scores_rules(
    user_vec: jnp.ndarray,        # [K]
    item_factors: jnp.ndarray,    # [n_items, K] — device-resident
    cat_masks: jnp.ndarray,       # [C, n_items] bool — device-resident at warm()
    cat_ids: jnp.ndarray,         # [Wc] category ids to OR, -1 padding
    white_idx: jnp.ndarray,       # [Ww] whitelist item ids, -1 padding
    excl_idx: jnp.ndarray,        # [We] excluded item ids, -1 padding
    top_k: int,
) -> jnp.ndarray:                 # [2, top_k]: scores row, item-id row
    """Top-K with e-commerce business rules, fully device-final.

    Category masks live on device (staged once per model load); a query
    ships only three small padded id lists, and only the top-K crosses back
    — at no point does an [n_items] vector transfer per query (the
    reference template does this filtering in the ES/driver JVM instead).
    Empty cat_ids/white_idx (all -1) mean "no constraint of that kind".
    """
    return _rules_topk(item_factors @ user_vec, cat_masks,
                       cat_ids, white_idx, excl_idx, top_k)


def _rules_topk(scores, cat_masks, cat_ids, white_idx, excl_idx, top_k: int):
    """Shared traced epilogue: category/whitelist allow-masks, exclusion
    list, and the stacked [2, top_k] result (see recommend_scores_rules)."""
    n_items = scores.shape[0]
    check_f32_id_range(n_items)
    cat_valid = cat_ids >= 0
    sel = cat_masks[jnp.where(cat_valid, cat_ids, 0)] & cat_valid[:, None]
    allow_cat = jnp.where(cat_valid.any(), sel.any(axis=0), True)
    white_valid = white_idx >= 0
    white_mask = jnp.zeros((n_items,), bool).at[
        jnp.where(white_valid, white_idx, 0)].max(white_valid)
    allow_white = jnp.where(white_valid.any(), white_mask, True)
    scores = jnp.where(allow_cat & allow_white, scores, -jnp.inf)
    excl_valid = excl_idx >= 0
    scores = scores.at[jnp.where(excl_valid, excl_idx, 0)].min(
        jnp.where(excl_valid, -jnp.inf, jnp.inf))
    return _stack_topk(*jax.lax.top_k(scores, top_k))


@functools.partial(jax.jit, static_argnames=("top_k",))
def scores_rules_topk(
    scores: jnp.ndarray,          # [n_items] precomputed device scores
    cat_masks: jnp.ndarray,       # [C, n_items] bool — device-resident
    cat_ids: jnp.ndarray,         # [Wc] -1-padded
    white_idx: jnp.ndarray,       # [Ww] -1-padded
    excl_idx: jnp.ndarray,        # [We] -1-padded
    top_k: int,
) -> jnp.ndarray:                 # [2, top_k]
    """Business-rule mask + top-k over an already-computed score vector
    (e.g. indicator-table similarity) — same contract as
    recommend_scores_rules without the factor matmul."""
    return _rules_topk(scores, cat_masks, cat_ids, white_idx, excl_idx, top_k)


def pad_id_rows(rows, min_width: int = 16) -> "np.ndarray":
    """-1-padded [B, W] id matrix with W pow2-bucketed (the 2-D sibling of
    pad_ids) — the shared scaffold for every serve_batch_predict."""
    w = bucket_width(max((len(r) for r in rows), default=1), min_width)
    out = np.full((len(rows), w), -1, np.int32)
    for r, ids in enumerate(rows):
        out[r, : len(ids)] = ids
    return out


@jax.jit
def indicator_scatter_scores(idx: jnp.ndarray, llr: jnp.ndarray,
                             q_ids: jnp.ndarray) -> jnp.ndarray:
    """score[j] = Σ_{q ∈ query items} Σ_k 1[idx[q,k] = j] · llr[q,k] —
    a gather of the query rows + one scatter-add, all on device.  Shared
    indicator-table serving (similar-product, complementary-purchase)."""
    qv = q_ids >= 0
    safe = jnp.where(qv, q_ids, 0)
    rows = idx[safe]                              # [Wq, C]
    vals = llr[safe] * qv[:, None]
    valid = rows >= 0
    return jnp.zeros((idx.shape[0],), jnp.float32).at[
        jnp.where(valid, rows, 0)].add(jnp.where(valid, vals, 0.0))


@jax.jit
def indicator_scatter_scores_batch(idx: jnp.ndarray, llr: jnp.ndarray,
                                   q_ids: jnp.ndarray) -> jnp.ndarray:
    """Batched indicator_scatter_scores: [B, Wq] query rows →
    [B, n_items] scores in one gather + scatter-add (all-(-1) rows
    score 0 everywhere)."""
    b = q_ids.shape[0]
    qv = q_ids >= 0
    safe = jnp.where(qv, q_ids, 0)
    rows = idx[safe]                              # [B, Wq, C]
    vals = llr[safe] * qv[:, :, None]
    valid = rows >= 0
    out_rows = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[:, None, None], rows.shape)
    return jnp.zeros((b, idx.shape[0]), jnp.float32).at[
        out_rows, jnp.where(valid, rows, 0)
    ].add(jnp.where(valid, vals, 0.0))


def _rules_topk_batch(scores, cat_masks, cat_ids, white_idx, excl_idx,
                      top_k: int):
    """Batched _rules_topk: per-row rule id lists over [B, n_items]
    scores → stacked [B, 2, top_k].  One device program serves a whole
    serving micro-batch (see create_server._MicroBatcher)."""
    b, n_items = scores.shape
    check_f32_id_range(n_items)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    cat_valid = cat_ids >= 0                              # [B, Wc]
    sel = (cat_masks[jnp.where(cat_valid, cat_ids, 0)]    # [B, Wc, I]
           & cat_valid[:, :, None])
    allow_cat = jnp.where(cat_valid.any(axis=1, keepdims=True),
                          sel.any(axis=1), True)          # [B, I]
    white_valid = white_idx >= 0                          # [B, Ww]
    white_mask = jnp.zeros((b, n_items), bool).at[
        rows, jnp.where(white_valid, white_idx, 0)].max(white_valid)
    allow_white = jnp.where(white_valid.any(axis=1, keepdims=True),
                            white_mask, True)
    scores = jnp.where(allow_cat & allow_white, scores, -jnp.inf)
    excl_valid = excl_idx >= 0
    scores = scores.at[rows, jnp.where(excl_valid, excl_idx, 0)].min(
        jnp.where(excl_valid, -jnp.inf, jnp.inf))
    st, si = jax.lax.top_k(scores, top_k)
    return jnp.stack([st, si.astype(jnp.float32)], axis=1)


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_batch_rules(
    user_vecs: jnp.ndarray,       # [B, K]
    item_factors: jnp.ndarray,    # [n_items, K] — device-resident
    cat_masks: jnp.ndarray,       # [C, n_items] bool — device-resident
    cat_ids: jnp.ndarray,         # [B, Wc] -1-padded
    white_idx: jnp.ndarray,       # [B, Ww] -1-padded
    excl_idx: jnp.ndarray,        # [B, We] -1-padded
    top_k: int,
) -> jnp.ndarray:                 # [B, 2, top_k]
    """Batched recommend_scores_rules: B queries' rules + top-ks in one
    program, one readback."""
    return _rules_topk_batch(user_vecs @ item_factors.T, cat_masks,
                             cat_ids, white_idx, excl_idx, top_k)


@functools.partial(jax.jit, static_argnames=("top_k",))
def scores_rules_topk_batch(
    scores: jnp.ndarray,          # [B, n_items] precomputed device scores
    cat_masks: jnp.ndarray,       # [C, n_items] bool — device-resident
    cat_ids: jnp.ndarray,         # [B, Wc] -1-padded
    white_idx: jnp.ndarray,       # [B, Ww] -1-padded
    excl_idx: jnp.ndarray,        # [B, We] -1-padded
    top_k: int,
) -> jnp.ndarray:                 # [B, 2, top_k]
    """Batched scores_rules_topk (indicator-table similarity serving)."""
    return _rules_topk_batch(scores, cat_masks, cat_ids, white_idx,
                             excl_idx, top_k)


@functools.partial(jax.jit, static_argnames=("top_k",))
def recommend_batch_excl(
    user_vecs: jnp.ndarray,       # [B, K]
    item_factors: jnp.ndarray,    # [n_items, K]
    excl_idx: jnp.ndarray,        # [B, W] per-row exclusions, -1 padding
    top_k: int,
) -> jnp.ndarray:                 # [B, 2, top_k]: scores row, item-id row
    check_f32_id_range(item_factors.shape[0])
    scores = user_vecs @ item_factors.T
    valid = excl_idx >= 0
    b = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
    scores = scores.at[b, jnp.where(valid, excl_idx, 0)].min(
        jnp.where(valid, -jnp.inf, jnp.inf))
    st, si = jax.lax.top_k(scores, top_k)
    return jnp.stack([st, si.astype(jnp.float32)], axis=1)


def bucket_width(n: int, min_width: int = 16) -> int:
    """Smallest power-of-two ≥ n (and ≥ min_width) — the ONE shape-bucketing
    rule for serving (SURVEY §7 hard part (d)): distinct history/exclusion
    lengths and top-k values collapse to a handful of compiled programs."""
    return max(min_width, 1 << max(0, (int(n) - 1).bit_length()))


def pad_ids(ids, min_width: int = 16) -> "np.ndarray":
    """Pad an id list to a bucketed width with -1 (see bucket_width)."""
    n = len(ids)
    out = np.full(bucket_width(n, min_width), -1, np.int32)
    if n:
        out[:n] = np.asarray(ids, np.int32)
    return out


@functools.partial(jax.jit, static_argnames=("top_k",))
def _recommend_batch_xla(user_vecs, item_factors, seen_mask, top_k):
    scores = user_vecs @ item_factors.T
    scores = jnp.where(seen_mask > 0, -jnp.inf, scores)
    return jax.lax.top_k(scores, top_k)


@functools.lru_cache(maxsize=4)
def _recommend_route(mode: str):
    """Scoring implementation per PIO_PALLAS mode — caching by mode keeps
    the per-query cost to one env read (no import), while still honoring
    runtime toggling of the env var (tests flip it)."""
    from predictionio_tpu.ops.pallas_kernels import recommend_batch_fused

    return _recommend_batch_xla if mode == "off" else recommend_batch_fused


def recommend_batch(
    user_vecs: jnp.ndarray,       # [B, K]
    item_factors: jnp.ndarray,    # [n_items, K]
    seen_mask: jnp.ndarray,       # [B, n_items]
    top_k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched top-K scoring; routes to the fused Pallas kernel when enabled
    — one HBM pass for matmul+mask, jitted end to end either way."""
    from predictionio_tpu.ops.pallas_kernels import pallas_mode

    return _recommend_route(pallas_mode())(user_vecs, item_factors, seen_mask, top_k)
