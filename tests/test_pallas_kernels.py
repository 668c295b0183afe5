"""Pallas kernels vs their pure-XLA references (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PIO_PALLAS", "interpret")


def test_masked_score_matmul_matches_xla():
    from predictionio_tpu.ops.pallas_kernels import masked_score_matmul

    rng = np.random.default_rng(0)
    b, k, n_items = 5, 12, 300   # deliberately unaligned shapes
    u = rng.normal(size=(b, k)).astype(np.float32)
    v = rng.normal(size=(n_items, k)).astype(np.float32)
    seen = (rng.random((b, n_items)) < 0.1).astype(np.float32)
    bias = rng.normal(size=n_items).astype(np.float32)

    got = np.asarray(masked_score_matmul(jnp.asarray(u), jnp.asarray(v), jnp.asarray(seen), jnp.asarray(bias)))
    want = u @ v.T + bias[None, :]
    want = np.where(seen > 0, -np.inf, want)
    assert got.shape == (b, n_items)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_recommend_batch_fused_matches_unfused(monkeypatch):
    from predictionio_tpu.ops.als import recommend_batch
    from predictionio_tpu.ops.pallas_kernels import recommend_batch_fused

    rng = np.random.default_rng(1)
    b, k, n_items = 4, 16, 257
    u = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_items, k)), jnp.float32)
    seen = jnp.asarray((rng.random((b, n_items)) < 0.2), jnp.float32)

    monkeypatch.setenv("PIO_PALLAS", "0")       # pure-XLA reference path
    s1, i1 = recommend_batch(u, v, seen, 10)
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    s2, i2 = recommend_batch_fused(u, v, seen, 10)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def _llr_case(r, w, dtype, seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 20, size=(r, w))
    row = counts.sum(1) + rng.integers(0, 50, r)     # row marginal ≥ cooccurrence
    col = counts.sum(0) + rng.integers(0, 50, w)
    return (counts.astype(dtype), row.astype(dtype), col.astype(dtype),
            float(row.sum() + 1000))


def _llr_want(counts, row, col, n_total, thr, diagonal):
    from predictionio_tpu.ops.cco import llr_score

    c, row, col = (np.asarray(x, np.float32) for x in (counts, row, col))
    k12 = row[:, None] - c
    k21 = col[None, :] - c
    k22 = n_total - c - k12 - k21
    want = np.asarray(llr_score(*map(jnp.asarray, (c, k12, k21, k22))))
    want = np.where((c > 0) & (want >= thr), want, -np.inf)
    if diagonal is not None:
        rows, cols = np.indices(want.shape)
        want = np.where(rows - cols == diagonal, -np.inf, want)
    return want


@pytest.mark.parametrize("r,w,dtype,window,diagonal", [
    # rows and columns no multiple of the blocks: both edge blocks partial
    (37, 190, np.float32, None, None),
    (300, 700, np.float32, None, None),
    (300, 700, np.int32, None, None),
    # the self-pair mask, row − column == diagonal: tile_start 300 less a
    # row_offset of 100 (it crosses the row block boundary at 256), and
    # tile_start 0 less a row_offset of 400 (the column boundary at 512)
    (300, 700, np.float32, None, 200),
    (300, 700, np.int32, None, -400),
    # a tile read out of a wider int32 group at offset g, masked or not
    (300, 3 * 512, np.int32, (1, 512), None),
    (300, 3 * 512, np.int32, (2, 512), 1024 - 900),
    (300, 4 * 256, np.float32, (3, 256), -100),
    # a window no 128-wide block divides: sliced out first
    (37, 3 * 200, np.int32, (1, 200), -30),
])
def test_llr_masked_scores_matches_reference(r, w, dtype, window, diagonal):
    """The kernel reads the counts where they lie (no pad, no slice, no
    conversion outside it): every score equals to the bit what the same
    kernel gives on a float32 copy padded to whole blocks, with the mask
    put on after; -inf lies where the XLA twin puts it, and the finite
    scores equal the twin's and `llr_score`'s to 4e-6 relative, at the
    edge blocks and across the self-pair diagonal.  (The twin and the
    interpreted kernel are two CPU compilations of one elementwise chain:
    up to 2.3e-6 apart at some shapes, as the padded copy's scores are.)"""
    from predictionio_tpu.ops.cco import _llr_mask_scores, _mask_self_pairs
    from predictionio_tpu.ops.pallas_kernels import (
        llr_blocks, llr_masked_scores)

    counts, row, col, n_total = _llr_case(r, w, dtype, r + w)
    thr = 2.0
    g, width = window or (0, w)
    args = (jnp.asarray(counts), jnp.asarray(row), jnp.asarray(col),
            n_total, thr)
    kw = dict(col_start=g * width, width=width, diagonal=diagonal)
    got = np.asarray(llr_masked_scores(*args, **kw))
    twin = np.asarray(_llr_mask_scores(*args, "off", **kw))
    cols = slice(g * width, (g + 1) * width)
    want = _llr_want(counts[:, cols], row, col[cols], n_total, thr, diagonal)

    # the copy the kernel took before it read the tile where it lies
    tile_r, tile_c = llr_blocks(r, width)
    rp, cp = -(-r // tile_r) * tile_r, -(-width // tile_c) * tile_c
    padded = np.zeros((rp, cp), np.float32)
    padded[:r, :width] = counts[:, cols]
    rowp, colp = np.zeros(rp, np.float32), np.zeros(cp, np.float32)
    rowp[:r], colp[:width] = row, col[cols]
    assert llr_blocks(rp, cp) == (tile_r, tile_c)
    before = llr_masked_scores(*map(jnp.asarray, (padded, rowp, colp)),
                               n_total, thr)
    before = np.asarray(_mask_self_pairs(before[:r, :width], diagonal))

    assert got.shape == (r, width)
    if diagonal is not None:      # the diagonal crosses the scores
        assert (np.subtract.outer(np.arange(r), np.arange(width))
                == diagonal).any()
    np.testing.assert_array_equal(got, before)
    finite = np.isfinite(twin)
    assert (np.isfinite(got) == finite).all()
    assert (np.isfinite(want) == finite).all()
    assert 0 < finite.sum() < finite.size
    np.testing.assert_allclose(got[finite], twin[finite], rtol=4e-6)
    np.testing.assert_allclose(got[finite], want[finite], rtol=4e-6)


def _outside_kernels(jaxpr) -> set:
    """Primitive names of ``jaxpr`` and the jaxprs nested in it, those
    inside a ``pallas_call`` left out."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found |= _outside_kernels(sub)
    return found


@pytest.mark.parametrize("shape,dtype,window", [
    ((100_000, 4096), jnp.float32, None),         # a resident tile
    ((100_000, 4 * 4096), jnp.int32, 4096),       # a tile of a blocked group
])
def test_llr_masked_scores_copies_nothing(shape, dtype, window):
    """At the cells' unaligned 100,000 rows the traced LLR is the kernel
    alone: no pad, slice, dynamic_slice, scatter or convert around it."""
    import jax

    from predictionio_tpu.ops.pallas_kernels import llr_masked_scores

    def llr(c, row, col, start, diagonal):
        return llr_masked_scores(c, row, col, 1e6, 0.0, col_start=start,
                                 width=window, diagonal=diagonal)

    traced = jax.make_jaxpr(llr)(
        jax.ShapeDtypeStruct(shape, dtype),
        jax.ShapeDtypeStruct(shape[:1], dtype),
        jax.ShapeDtypeStruct(shape[1:], dtype),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    found = _outside_kernels(traced.jaxpr)
    assert "pallas_call" in found
    copies = {"pad", "slice", "dynamic_slice", "scatter", "scatter-add",
              "dynamic_update_slice", "convert_element_type"}
    assert not copies & found, copies & found
    (out,) = traced.out_avals
    assert out.shape == (shape[0], window or shape[1])


def test_cco_indicators_pallas_matches_xla(monkeypatch):
    from predictionio_tpu.ops.cco import cco_indicators_coo

    rng = np.random.default_rng(3)
    n_users, n_ip, n_it = 60, 25, 40
    pu = rng.integers(0, n_users, 400)
    pi = rng.integers(0, n_ip, 400)
    ou = rng.integers(0, n_users, 800)
    oi = rng.integers(0, n_it, 800)

    def run():
        return cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it, top_k=5,
                                  llr_threshold=1.0, user_block=16,
                                  item_tile=16)

    monkeypatch.setenv("PIO_PALLAS", "0")
    s1, i1 = run()
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    s2, i2 = run()

    finite = np.isfinite(s1)
    assert (np.isfinite(s2) == finite).all()
    np.testing.assert_allclose(s1[finite], s2[finite], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(i1, i2)


def test_pallas_mode_env(monkeypatch):
    from predictionio_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("PIO_PALLAS", "0")
    assert pk.pallas_mode() == "off" and not pk.pallas_enabled()
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    assert pk.pallas_mode() == "interpret" and pk.pallas_enabled()
    monkeypatch.setenv("PIO_PALLAS", "compiled")
    assert pk.pallas_mode() == "compiled"
