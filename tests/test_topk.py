"""Bitonic tournament top-k (ops/topk.py) and its Pallas tile kernel
(pallas_kernels.tile_topk_desc) vs lax.top_k, plus the tiled-CCO merge:
its per-tile selection follows PIO_PALLAS, and kernels and XLA twins keep
the same indicators."""

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


@pytest.mark.parametrize("rows", [16, 13])
@pytest.mark.parametrize("w", [128, 200, 520, 4096])
@pytest.mark.parametrize("b", [8, 16, 64, 128])
def test_pallas_tile_topk_desc_matches_lax(monkeypatch, b, w, rows):
    """The slab tournament against lax.top_k at every carry, over one,
    two, five and 32 column chunks (the last partly padding), row counts
    that are and are not whole sublane groups, and the rows a network
    gets wrong first: nothing finite, fewer finite scores than ``b``, a
    run of equal scores across the cut, every score equal, and equal
    scores in columns (slabs) of different chunks."""
    from predictionio_tpu.ops.pallas_kernels import tile_topk_desc

    monkeypatch.setenv("PIO_PALLAS", "interpret")
    rng = np.random.default_rng(2)
    x = rng.standard_normal((rows, w)).astype(np.float32)
    x[x < 0] = -np.inf
    x[0] = -np.inf
    x[1] = -np.inf
    x[1, rng.choice(w, 3, replace=False)] = [0.5, 0.25, 0.5]
    k = min(b, w)
    x[2] = rng.random(w).astype(np.float32)             # all below 1
    spread = rng.permutation(w)[:k + 4]
    x[2, spread[:k - 3]] = 2.0 + np.arange(k - 3)       # k-3 above the run
    x[2, spread[k - 3:]] = 1.5                          # 7 equal, 3 kept
    x[3] = 0.75
    x[4, [0, w // 2, w - 1]] = 9.0
    s, i = tile_topk_desc(jnp.asarray(x), b)
    assert s.shape == i.shape == (rows, b)
    _check_topk(x, s, i, k)


def _np_stages(s, i, stages):
    """``topk_plan``'s stages on numpy arrays [slabs, rows]: the stage
    list's meaning, written without the kernel's reshapes."""
    for op, *arg in stages:
        n = len(s)
        if op == "fold":
            s4, i4 = (a.reshape(n // (2 * arg[0]), 2, arg[0], -1) for a in (s, i))
            ge = s4[:, 0] >= s4[:, 1]
            s = np.where(ge, s4[:, 0], s4[:, 1]).reshape(n // 2, -1)
            i = np.where(ge, i4[:, 0], i4[:, 1]).reshape(n // 2, -1)
            continue
        d, k, flip = arg
        lo = np.array([j for j in range(n) if not j & d])
        hi = lo | d
        max_to_lo = (((lo & k) == 0) != flip)[:, None]
        swap = np.where(max_to_lo, s[lo] < s[hi], s[lo] > s[hi])
        s[lo], s[hi] = np.where(swap, s[hi], s[lo]), np.where(swap, s[lo], s[hi])
        i[lo], i[hi] = np.where(swap, i[hi], i[lo]), np.where(swap, i[lo], i[hi])
    return s, i


@pytest.mark.parametrize("w,b,stages", [(4096, 64, 28.0), (4096, 8, 10.0),
                                        (128, 8, 10.0), (256, 128, 36.0),
                                        (300, 16, 15.0)])
def test_topk_plan_sorts_and_selects_in_numpy(w, b, stages):
    """The planner alone, no kernel: its stage list, run by ``_np_stages``
    over a row's columns one chunk after the other, leaves exactly the
    top ``b`` sorted descending, each with its own column; and it says
    what it costs: no stage inside a vreg, and fewer full-width stages
    than the 36 of the network along the lanes wherever the carry is
    narrower than 128."""
    from predictionio_tpu.ops.pallas_kernels import topk_plan

    plan = topk_plan(w, b)
    assert (plan.block, plan.group) == (b, max(b, 128))
    assert plan.chunks * plan.group >= w > (plan.chunks - 1) * plan.group
    assert (plan.slab_stages, plan.lane_stages) == (stages, 0.0)
    assert b == 128 or plan.slab_stages + plan.lane_stages < 36
    rows = 40
    rng = np.random.default_rng(w + b)
    x = np.full((rows, plan.chunks * plan.group), -np.inf, np.float32)
    x[:, :w] = rng.integers(0, 3 * b, (rows, w))        # ties everywhere
    x[0, :w] = rng.permutation(w)                       # and none
    acc_s = np.full((b, rows), -np.inf, np.float32)
    acc_i = np.zeros((b, rows), np.int64)
    for c in range(plan.chunks):
        cols = np.arange(c * plan.group, (c + 1) * plan.group)
        s, i = _np_stages(x[:, cols].T.copy(),
                          np.repeat(cols[:, None], rows, 1), plan.chunk)
        assert s.shape == (b, rows) and (np.diff(s, axis=0) >= 0).all()
        acc_s, acc_i = _np_stages(np.concatenate([acc_s, s]),
                                  np.concatenate([acc_i, i]), plan.merge)
    np.testing.assert_array_equal(acc_s.T, -np.sort(-x, axis=1)[:, :b])
    fin = np.isfinite(acc_s.T)
    assert (np.take_along_axis(x, acc_i.T, axis=1)[fin] == acc_s.T[fin]).all()
    for r in range(rows):
        assert len(set(acc_i.T[r][fin[r]].tolist())) == fin[r].sum()


def test_topk_plan_refuses_a_block_that_is_no_power_of_two():
    from predictionio_tpu.ops.pallas_kernels import topk_plan

    with pytest.raises(ValueError, match="power of two"):
        topk_plan(4096, 50)


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


def _dispatch_attrs(collector):
    return {s["attrs"]["program"]: s["attrs"] for s in collector.spans()
            if s["name"] == "dispatch"}


@pytest.mark.parametrize("strategy", ["resident", "chunked", "dense"])
def test_cco_topk_pallas_matches_lax(monkeypatch, strategy):
    """dense ≡ tiled parity contract extended to the kernels: the CCO
    indicator tables are identical under PIO_PALLAS=off (XLA twins, the
    lax merge) and =interpret (Pallas LLR + tournament merge) on every
    device strategy, and the tiled programs' dispatch span says which
    merge ran."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco as cco_ops

    rng = np.random.default_rng(3)
    n_users, n_ip, n_it = 80, 30, 47
    pu = rng.integers(0, n_users, 500)
    pi = rng.integers(0, n_ip, 500)
    ou = rng.integers(0, n_users, 900)
    oi = rng.integers(0, n_it, 900)

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    if strategy == "dense":
        monkeypatch.setenv("PIO_CCO_DENSE", "1")
    else:
        monkeypatch.setenv("PIO_CCO_DENSE", "0")
        if strategy == "chunked":
            monkeypatch.setattr(cco_ops, "_TILED_P_BYTES", 0)

    def run(pallas):
        monkeypatch.setenv("PIO_PALLAS", pallas)
        with SpanCollector().activate() as spans:
            out = cco_ops.cco_indicators_coo(
                pu, pi, ou, oi, n_users, n_ip, n_it,
                top_k=7, llr_threshold=0.5, user_block=32, item_tile=16)
        return (*out, _dispatch_attrs(spans))

    s1, i1, d1 = run("off")
    s2, i2, d2 = run("interpret")

    finite = np.isfinite(s1)
    assert (np.isfinite(s2) == finite).all()
    np.testing.assert_allclose(s1[finite], s2[finite], rtol=1e-5, atol=1e-5)
    # ids equal wherever scores have no exact ties at the cut
    np.testing.assert_allclose(
        np.sort(s1, axis=1), np.sort(s2, axis=1), rtol=1e-5, atol=1e-5)
    if strategy == "dense":
        # the whole-row top-k stays lax.top_k on every backend
        assert "topk" not in d2["_llr_topk_dense"]
    else:
        program = f"_cco_{strategy}_all_tiles"
        assert d1[program]["topk"] == "lax"
        assert d2[program]["topk"] == "pallas"


@pytest.mark.parametrize("program", ["_cco_resident_all_tiles",
                                     "_cco_chunked_all_tiles",
                                     "_basket_rules_tiled"])
def test_tiled_dispatch_spans_say_which_network_ran(monkeypatch, program):
    """A journal says which selection network a job ran: under the
    tournament the tiled programs' ``dispatch`` span carries the block
    (the carry, not 128) and the planner's stage counts for the (tile,
    carry) the kernel is traced with; under lax.top_k none of them."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco as cco_ops
    from predictionio_tpu.ops.pallas_kernels import topk_plan

    rng = np.random.default_rng(4)
    n_users, n_items, top_k, tile = 60, 40, 5, 16
    u = rng.integers(0, n_users, 400)
    it = rng.integers(0, n_items, 400)
    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    if program == "_cco_chunked_all_tiles":
        monkeypatch.setattr(cco_ops, "_TILED_P_BYTES", 0)

    def attrs(pallas):
        monkeypatch.setenv("PIO_PALLAS", pallas)
        with SpanCollector().activate() as spans:
            if program == "_basket_rules_tiled":
                cco_ops.basket_rules(u, it, n_users, n_items, top_k=top_k,
                                     item_tile=tile)
            else:
                cco_ops.cco_indicators_coo(
                    u, it, u, it, n_users, n_items, n_items, top_k=top_k,
                    user_block=32, item_tile=tile)
        return _dispatch_attrs(spans)[program]

    keys = {"topk_block", "topk_slab_stages", "topk_lane_stages"}
    off = attrs("off")
    assert off["topk"] == "lax" and not keys & set(off)
    on = attrs("interpret")
    plan = topk_plan(tile, 8)
    assert on["topk"] == "pallas" and on["topk_block"] == 8
    assert on["topk_slab_stages"] == plan.slab_stages == 10.0
    assert on["topk_lane_stages"] == plan.lane_stages == 0.0


@pytest.mark.parametrize("exclude_self", [True, False])
@pytest.mark.parametrize("program", ["_cco_resident_all_tiles",
                                     "_cco_chunked_all_tiles"])
def test_tiled_dispatch_spans_say_which_llr_ran(monkeypatch, program,
                                                exclude_self):
    """A journal says which LLR form a job ran: under the kernel the
    block, the rows of its partial last block and whether the kernel
    masks the self-pairs; under the XLA twin only where the mask is."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco as cco_ops

    rng = np.random.default_rng(6)
    n_users, n_items, tile = 60, 300, 64
    u = rng.integers(0, n_users, 900)
    it = rng.integers(0, n_items, 900)
    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    if program == "_cco_chunked_all_tiles":
        monkeypatch.setattr(cco_ops, "_TILED_P_BYTES", 0)

    def attrs(pallas):
        monkeypatch.setenv("PIO_PALLAS", pallas)
        with SpanCollector().activate() as spans:
            cco_ops.cco_indicators_coo(
                u, it, u, it, n_users, n_items, n_items, top_k=5,
                exclude_self=exclude_self, user_block=32, item_tile=tile)
        return _dispatch_attrs(spans)[program]

    off = attrs("off")
    assert off["llr_mask"] == ("xla" if exclude_self else "none")
    assert "llr_block" not in off and "llr_edge_rows" not in off
    on = attrs("interpret")
    # 300 rows: a block of 256 and one of 44; a 64-wide tile in one block
    assert on["llr_block"] == "256x128" and on["llr_edge_rows"] == 44
    assert on["llr_mask"] == ("kernel" if exclude_self else "none")


def test_topk_impl_follows_pallas_mode(monkeypatch):
    """No knob of its own: the merge's selection is the Pallas tournament
    exactly where Pallas kernels run."""
    from predictionio_tpu.ops.cco import _carry_width, topk_impl

    monkeypatch.setenv("PIO_PALLAS", "off")
    assert topk_impl() == "lax"
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    assert topk_impl() == "pallas"
    monkeypatch.delenv("PIO_PALLAS", raising=False)
    assert topk_impl() == "lax"    # auto on a CPU backend: no kernels
    assert _carry_width(50, "pallas") == 64
    assert _carry_width(50, "lax") == 50
    assert _carry_width(3, "pallas") == 8


def _primitives(jaxpr) -> set:
    """Names of every primitive in ``jaxpr`` and the jaxprs nested in its
    equations' parameters (pjit, scan, ...)."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found |= _primitives(sub)
    return found


@pytest.mark.parametrize("topk", ["pallas", "lax"])
def test_resident_program_selects_with_one_impl_only(monkeypatch, topk):
    """The traced tile program holds the Pallas selection and no top_k
    sort, or the reverse: an edit cannot put the tile-wide sort back under
    the tournament (or lose the kernel) unnoticed.  The LLR runs as its
    XLA twin here, so the selection's is the only pallas_call."""
    from functools import partial

    from predictionio_tpu.ops import cco as cco_ops

    monkeypatch.setenv("PIO_PALLAS", "interpret")
    n_rows, n_ip, e = 128, 24, 60
    traced = jax.make_jaxpr(partial(
        cco_ops._cco_resident_all_tiles, n_tiles=3, tile=16, top_k=5,
        llr_threshold=0.0, exclude_self=True, pallas="off", mm="bf16",
        topk=topk))(
        jnp.zeros((n_rows, n_ip), jnp.bfloat16), jnp.zeros((n_ip,), jnp.int32),
        jnp.zeros((e,), jnp.int32), jnp.zeros((e,), jnp.int32),
        jnp.ones((e,), bool), 100.0)
    found = _primitives(traced.jaxpr)
    assert "scan" in found          # the walk reached the tile loop's body
    assert ("pallas_call" in found) == (topk == "pallas")
    assert ("top_k" in found) == (topk == "lax")


def test_merge_ties_at_the_cut_over_a_partial_last_tile(monkeypatch):
    """Target items in groups of identical columns, a group's members
    spread over all three tiles, so a row's cut at top_k 50 falls inside
    a run of exactly equal scores (7 or 8 to a group, so in most rows);
    the last tile is partial (150 = 2 x 64 + 22).  The tournament merge (carry 64) keeps, per row, the
    same multiset of scores as lax.top_k, only items that exist, and
    -1 / -inf elsewhere."""
    from predictionio_tpu.ops import cco as cco_ops

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    rng = np.random.default_rng(5)
    n_users, n_ip, n_it, groups, top_k = 96, 20, 150, 21, 50
    pu, pi = np.nonzero(rng.random((n_users, n_ip)) < 0.3)
    pattern = rng.random((n_users, groups)) < 0.35
    ou, oi = np.nonzero(pattern[:, np.arange(n_it) % groups])
    assert cco_ops._plan(n_users, n_ip, n_it, None, 64) == ("resident",)

    def run(pallas):
        monkeypatch.setenv("PIO_PALLAS", pallas)
        return cco_ops.cco_indicators_coo(
            pu, pi, ou, oi, n_users, n_ip, n_it, top_k=top_k, user_block=32,
            item_tile=64)

    s_lax, _ = run("off")
    s_pal, i_pal = run("interpret")
    assert s_pal.shape == i_pal.shape == (n_ip, top_k)
    kept = np.isfinite(s_pal)
    # ties at the cut are really there, in rows that fill all 50 places
    full = kept.all(axis=1)
    assert full.sum() >= n_ip // 2
    tied_at_cut = (s_lax[full] == s_lax[full, -1:]).sum(axis=1) >= 2
    assert tied_at_cut.sum() >= n_ip // 2
    np.testing.assert_allclose(np.sort(s_pal, axis=1), np.sort(s_lax, axis=1),
                               rtol=1e-5, atol=1e-5)
    assert (i_pal[kept] >= 0).all() and (i_pal[kept] < n_it).all()
    assert (i_pal[~kept] == -1).all() and (s_pal[~kept] == -np.inf).all()
    for r in range(n_ip):           # no item kept twice
        assert len(set(i_pal[r][kept[r]].tolist())) == kept[r].sum()
