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


def test_llr_masked_scores_matches_reference():
    from predictionio_tpu.ops.cco import llr_score
    from predictionio_tpu.ops.pallas_kernels import llr_masked_scores

    rng = np.random.default_rng(2)
    r, c = 37, 190
    counts = rng.integers(0, 20, size=(r, c)).astype(np.float32)
    row = counts.sum(1) + rng.integers(0, 50, r)     # row marginal ≥ cooccurrence
    col = counts.sum(0) + rng.integers(0, 50, c)
    n_total = float(row.sum() + 1000)
    thr = 2.0

    got = np.asarray(
        llr_masked_scores(jnp.asarray(counts), jnp.asarray(row.astype(np.float32)),
                          jnp.asarray(col.astype(np.float32)), n_total, thr)
    )
    k11 = counts
    k12 = row[:, None] - counts
    k21 = col[None, :] - counts
    k22 = n_total - k11 - k12 - k21
    want = np.asarray(llr_score(jnp.asarray(k11), jnp.asarray(k12), jnp.asarray(k21), jnp.asarray(k22)))
    want = np.where((counts > 0) & (want >= thr), want, -np.inf)

    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-4, atol=1e-4)


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
