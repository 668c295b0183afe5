"""Bitonic tournament top-k (ops/topk.py) and its Pallas tile kernel
(pallas_kernels.tile_topk_desc) vs lax.top_k, plus the tiled-CCO merge
parity under PIO_CCO_TOPK=pallas."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _check_topk(x, s, i, k):
    """Values must match lax.top_k exactly; indices must be a valid
    (possibly tie-reordered) selection."""
    ref_s, _ = jax.lax.top_k(jnp.asarray(x), k)
    sv, iv = np.asarray(s), np.asarray(i)
    np.testing.assert_allclose(sv[:, :k], np.asarray(ref_s))
    for r in range(x.shape[0]):
        fin = np.isfinite(sv[r, :k])
        assert (x[r][iv[r, :k][fin]] == sv[r, :k][fin]).all()
        assert len(set(iv[r, :k][fin].tolist())) == fin.sum()


def test_bitonic_topk_matches_lax():
    from predictionio_tpu.ops.topk import bitonic_topk

    rng = np.random.default_rng(0)
    for (r, w, k) in [(7, 100, 10), (9, 161, 20), (5, 8, 3), (4, 64, 64),
                      (3, 5, 9), (2, 1, 1)]:
        x = rng.standard_normal((r, w)).astype(np.float32)
        x[x < -1.0] = -np.inf           # padding-like rows
        x[0, : min(w, 5)] = 1.5         # ties
        k_eff = min(k, w)
        s, i = bitonic_topk(jnp.asarray(x), k_eff)
        _check_topk(x, s, i, k_eff)


def test_running_merge_across_tiles_matches_global_topk():
    from predictionio_tpu.ops.topk import block_width, merge_desc, sort_topb_desc

    rng = np.random.default_rng(1)
    r, t, n_tiles, k = 9, 64, 4, 12
    b = block_width(k)
    x = rng.standard_normal((r, t * n_tiles)).astype(np.float32)
    x[x < 0.5] = -np.inf
    bs = jnp.full((r, b), -np.inf)
    bi = jnp.zeros((r, b), jnp.int32)
    for tt in range(n_tiles):
        tile = jnp.asarray(x[:, tt * t:(tt + 1) * t])
        idx = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None, :] + tt * t, tile.shape)
        ts, ti = sort_topb_desc(tile, idx, b)
        bs, bi = merge_desc(bs, bi, ts, ti)
    _check_topk(x, bs, bi, b)


def test_pallas_tile_topk_desc_matches_lax(monkeypatch):
    from predictionio_tpu.ops.pallas_kernels import tile_topk_desc

    monkeypatch.setenv("PIO_PALLAS", "interpret")
    rng = np.random.default_rng(2)
    for (r, w, b) in [(9, 300, 64), (3, 64, 128), (5, 520, 16)]:
        x = rng.standard_normal((r, w)).astype(np.float32)
        x[x < 0] = -np.inf
        x[0, : min(5, w)] = 2.0
        s, i = tile_topk_desc(jnp.asarray(x), b)
        _check_topk(x, s, i, min(b, w))


def test_pallas_kernels_never_interpret_silently(monkeypatch):
    """Off-TPU a kernel runs only when PIO_PALLAS=interpret asks for the
    interpreter; any other setting raises instead of quietly
    interpreting (a CPU run must not pass for a kernel run)."""
    from predictionio_tpu.ops.pallas_kernels import (
        llr_masked_scores, tile_topk_desc)

    x = jnp.zeros((8, 128), jnp.float32)
    for conf in (None, "compiled"):
        if conf is None:
            monkeypatch.delenv("PIO_PALLAS", raising=False)
        else:
            monkeypatch.setenv("PIO_PALLAS", conf)
        with pytest.raises(RuntimeError, match="PIO_PALLAS=interpret"):
            tile_topk_desc(x, 8)
        with pytest.raises(RuntimeError, match="PIO_PALLAS=interpret"):
            llr_masked_scores(x, jnp.ones(8), jnp.ones(128), 10.0)


@pytest.mark.parametrize("strategy", ["resident", "chunked", "dense"])
def test_cco_topk_pallas_matches_lax(monkeypatch, strategy):
    """dense ≡ tiled parity contract extended to the merge impl: the CCO
    indicator tables are identical under PIO_CCO_TOPK=lax and =pallas on
    every device strategy (kernels in interpret mode on CPU)."""
    from predictionio_tpu.ops import cco as cco_ops

    monkeypatch.setenv("PIO_PALLAS", "interpret")
    rng = np.random.default_rng(3)
    n_users, n_ip, n_it = 80, 30, 47
    pu = rng.integers(0, n_users, 500)
    pi = rng.integers(0, n_ip, 500)
    ou = rng.integers(0, n_users, 900)
    oi = rng.integers(0, n_it, 900)

    if strategy == "dense":
        monkeypatch.setenv("PIO_CCO_DENSE", "1")
    else:
        monkeypatch.setenv("PIO_CCO_DENSE", "0")
        if strategy == "chunked":
            monkeypatch.setattr(cco_ops, "_TILED_P_BYTES", 0)

    def run():
        return cco_ops.cco_indicators_coo(
            pu, pi, ou, oi, n_users, n_ip, n_it,
            top_k=7, llr_threshold=0.5, user_block=32, item_tile=16)

    monkeypatch.setenv("PIO_CCO_TOPK", "lax")
    s1, i1 = run()
    monkeypatch.setenv("PIO_CCO_TOPK", "pallas")
    s2, i2 = run()

    finite = np.isfinite(s1)
    assert (np.isfinite(s2) == finite).all()
    np.testing.assert_allclose(s1[finite], s2[finite], rtol=1e-5, atol=1e-5)
    # ids equal wherever scores have no exact ties at the cut
    np.testing.assert_allclose(
        np.sort(s1, axis=1), np.sort(s2, axis=1), rtol=1e-5, atol=1e-5)


def test_topk_impl_env(monkeypatch):
    from predictionio_tpu.ops.cco import _carry_width, topk_impl

    monkeypatch.setenv("PIO_CCO_TOPK", "pallas")
    assert topk_impl() == "pallas"
    monkeypatch.setenv("PIO_CCO_TOPK", "lax")
    assert topk_impl() == "lax"
    monkeypatch.delenv("PIO_CCO_TOPK", raising=False)
    assert topk_impl() == "lax"    # auto stays lax until S3 measures both
    assert _carry_width(50, "pallas") == 64
    assert _carry_width(50, "lax") == 50
    assert _carry_width(3, "pallas") == 8
