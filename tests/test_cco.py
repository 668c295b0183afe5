"""CCO op tests: cooccurrence counts, LLR correctness vs a naive reference,
tile streaming, and mesh parity."""

import numpy as np
import pytest

from predictionio_tpu.ops.cco import (
    block_interactions,
    cco_indicators_coo,
    dedup_pairs,
    llr_score,
)
from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh


def naive_llr(k11, k12, k21, k22):
    def xlogx(x):
        return x * np.log(x) if x > 0 else 0.0

    def ent(*ks):
        return xlogx(sum(ks)) - sum(xlogx(k) for k in ks)

    return max(2.0 * (ent(k11 + k12, k21 + k22) + 0 - 0 + ent(k11 + k21, k12 + k22) - ent(k11, k12, k21, k22)), 0.0)


def naive_cco(pu, pi, ou, oi, n_users, n_ip, n_it):
    P = np.zeros((n_users, n_ip))
    A = np.zeros((n_users, n_it))
    P[pu, pi] = 1
    A[ou, oi] = 1
    C = P.T @ A
    row = P.sum(0)
    col = A.sum(0)
    llr = np.zeros_like(C)
    for i in range(n_ip):
        for j in range(n_it):
            k11 = C[i, j]
            k12 = row[i] - k11
            k21 = col[j] - k11
            k22 = n_users - k11 - k12 - k21
            llr[i, j] = naive_llr(k11, k12, k21, k22) if k11 > 0 else -np.inf
    return C, llr


def random_interactions(n_users, n_items, n_events, seed):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, n_events).astype(np.int32)
    i = rng.integers(0, n_items, n_events).astype(np.int32)
    return u, i


def test_llr_matches_naive_formula():
    import jax.numpy as jnp

    cases = [(10, 5, 3, 100), (1, 0, 0, 50), (7, 7, 7, 7), (0, 3, 4, 10)]
    for k in cases:
        got = float(llr_score(*map(jnp.float32, k)))
        want = naive_llr(*k)
        assert abs(got - want) < 1e-3, (k, got, want)


# how each device program is reached on the CPU: by what the rule is given
PROGRAMS = {"dense": {"PIO_CCO_DENSE": "1"},
            "resident": {"PIO_CCO_DENSE": "0"},
            "chunked": {"PIO_CCO_DENSE": "0", "_TILED_P_BYTES": 0}}


def _take_program(monkeypatch, program):
    from predictionio_tpu.ops import cco as cco_mod

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    for key, value in PROGRAMS[program].items():
        if key.startswith("PIO_"):
            monkeypatch.setenv(key, value)
        else:
            monkeypatch.setattr(cco_mod, key, value)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("user_block,item_tile", [(64, 64), (16, 8), (1024, 4096)])
def test_cco_matches_naive(monkeypatch, user_block, item_tile, program):
    """Every device program keeps the naive table's cells with its scores:
    users not a multiple of the block (50 = 3 x 16 + 2), items not a
    multiple of the tile (15 = 8 + 7)."""
    _take_program(monkeypatch, program)
    n_users, n_ip, n_it = 50, 20, 15
    pu, pi = random_interactions(n_users, n_ip, 300, 1)
    ou, oi = random_interactions(n_users, n_it, 400, 2)
    # dedup for the naive side
    C, llr = naive_cco(pu, pi, ou, oi, n_users, n_ip, n_it)

    p = block_interactions(*dedup_pairs(pu, pi, n_ip), n_users, n_ip,
                           user_block=user_block)
    # distinct-user counts from dedup'd blocked data
    assert np.array_equal(np.bincount(p.item[p.mask], minlength=n_ip),
                          _dense(pu, pi, n_users, n_ip).sum(0))

    scores, idx = cco_indicators_coo(
        pu, pi, ou, oi, n_users, n_ip, n_it, top_k=n_it,
        user_block=user_block, item_tile=item_tile)
    for i in range(n_ip):
        got = {int(j): float(s) for s, j in zip(scores[i], idx[i]) if j >= 0}
        want = {j: llr[i, j] for j in range(n_it) if np.isfinite(llr[i, j]) and llr[i, j] >= 0}
        assert set(got) == set(want), (i, got, want)
        for j, s in got.items():
            assert abs(s - want[j]) < 1e-2, (i, j, s, want[j])


def _dense(u, i, n_users, n_items):
    M = np.zeros((n_users, n_items))
    M[u, i] = 1
    return M


def test_cco_top_k_and_threshold():
    n_users, n_ip, n_it = 40, 10, 12
    pu, pi = random_interactions(n_users, n_ip, 200, 3)
    ou, oi = random_interactions(n_users, n_it, 250, 4)
    scores, idx = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                     top_k=3)
    assert scores.shape == (n_ip, 3)
    # scores sorted descending per row
    finite = np.where(np.isfinite(scores), scores, -1e30)
    assert (np.diff(finite, axis=1) <= 1e-6).all()
    # high threshold kills everything
    s2, i2 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=3, llr_threshold=1e9)
    assert (i2 == -1).all()


def test_cco_exclude_self():
    n_users, n_items = 30, 8
    u, i = random_interactions(n_users, n_items, 150, 5)
    scores, idx = cco_indicators_coo(u, i, u, i, n_users, n_items, n_items,
                                     top_k=4, exclude_self=True)
    for row in range(n_items):
        assert row not in idx[row][idx[row] >= 0]


# what a mesh takes: the dense strategy at these sizes when nothing is
# pinned, the chunked tiled strategy where no chip's share of the primary
# stays resident, the sharded resident program (tests/test_cco_sharded.py
# holds it to the reference) where it does
MESH_PROGRAMS = ["auto", "chunked", "resident"]


def _take_mesh_program(monkeypatch, program):
    if program != "auto":
        _take_program(monkeypatch, program)


def _mesh_strategy(n_users, n_ip, n_it, mesh, item_tile=4096):
    from predictionio_tpu.ops.cco import _plan

    return _plan(n_users, n_ip, n_it, mesh, item_tile)[-1]


@pytest.mark.parametrize("program", MESH_PROGRAMS)
def test_cco_mesh_matches_single(monkeypatch, program):
    _take_mesh_program(monkeypatch, program)
    n_users, n_ip, n_it = 64, 12, 10
    pu, pi = random_interactions(n_users, n_ip, 300, 6)
    ou, oi = random_interactions(n_users, n_it, 300, 7)
    s1, i1 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5, user_block=8)
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    assert _mesh_strategy(n_users, n_ip, n_it, mesh) == (
        "dense" if program == "auto" else program)
    s8, i8 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5, user_block=8, mesh=mesh)
    assert np.allclose(np.where(np.isfinite(s1), s1, -1), np.where(np.isfinite(s8), s8, -1), atol=1e-3)
    assert (i1 == i8).all()


@pytest.mark.parametrize("program", ["chunked", "resident"])
@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_tiled_mesh_matches_single(monkeypatch, kernels, program):
    """The tiled strategies sharded over dp — what `pio train` takes by
    default on a several-chip host once the catalog outgrows the dense
    budget: the resident program with the count tiles reduce-scattered
    where a chip's share of the primary fits, one sharded step a tile
    with the whole tile `psum`'d where it does not — equal one device;
    also with the Pallas LLR and top-k kernels traced INSIDE the
    shard_map (interpreted here), which is how they run on TPUs."""
    _take_program(monkeypatch, program)
    if kernels == "pallas":
        monkeypatch.setenv("PIO_PALLAS", "interpret")
    n_users, n_ip, n_it = 64, 12, 10
    pu, pi = random_interactions(n_users, n_ip, 300, 6)
    ou, oi = random_interactions(n_users, n_it, 300, 7)
    s1, i1 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5, user_block=8, item_tile=4)
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    assert _mesh_strategy(n_users, n_ip, n_it, mesh, 4) == program
    s8, i8 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5, user_block=8, item_tile=4, mesh=mesh)
    np.testing.assert_allclose(s1, s8, rtol=1e-5)
    for r in range(n_ip):   # tie order aside, the same correlators
        assert set(i1[r][s1[r] > -np.inf]) == set(i8[r][s8[r] > -np.inf])


def test_dense_matches_tiled(monkeypatch):
    """The dense user-chunked path and the tiled fallback agree exactly."""
    n_users, n_ip, n_it = 60, 12, 17
    pu, pi = random_interactions(n_users, n_ip, 300, 11)
    ou, oi = random_interactions(n_users, n_it, 500, 12)

    def run():
        return cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                  top_k=6, user_block=16, item_tile=8)

    _take_program(monkeypatch, "dense")
    sd, idd = run()
    _take_program(monkeypatch, "resident")
    st, idt = run()
    np.testing.assert_allclose(sd, st, rtol=1e-5)
    # indices may tie-break differently only where scores tie; require
    # identical index sets per row for non-padding entries
    for r in range(n_ip):
        assert set(idd[r][sd[r] > -np.inf]) == set(idt[r][st[r] > -np.inf])


def test_dense_mesh_matches_single(monkeypatch):
    import jax

    monkeypatch.setenv("PIO_CCO_DENSE", "1")
    n_users, n_ip, n_it = 64, 10, 10
    pu, pi = random_interactions(n_users, n_ip, 240, 21)
    ou, oi = random_interactions(n_users, n_it, 400, 22)
    s1, i1 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5)
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    s8, i8 = cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it,
                                top_k=5, mesh=mesh)
    np.testing.assert_allclose(s1, s8, rtol=1e-5, atol=1e-5)


def test_dense_exclude_self_and_topk_overflow(monkeypatch):
    _take_program(monkeypatch, "dense")
    n_users, n_items = 40, 6
    u, i = random_interactions(n_users, n_items, 200, 31)
    # top_k wider than the (padded) item space still returns [I, top_k]
    scores, idx = cco_indicators_coo(u, i, u, i, n_users, n_items, n_items,
                                     top_k=300, exclude_self=True)
    assert scores.shape == (n_items, 300) and idx.shape == (n_items, 300)
    for r in range(n_items):
        assert r not in set(idx[r][idx[r] >= 0])


def test_dense_matches_tiled_exclude_self(monkeypatch):
    """Both strategies mask self-pairs BEFORE top-k: full top_k correlators
    per row and identical scores either way."""
    n_users, n_items = 60, 14
    u, i = random_interactions(n_users, n_items, 400, 41)

    def run():
        return cco_indicators_coo(u, i, u, i, n_users, n_items, n_items,
                                  top_k=5, user_block=16, item_tile=8,
                                  exclude_self=True)

    _take_program(monkeypatch, "dense")
    sd, idd = run()
    _take_program(monkeypatch, "resident")
    st, idt = run()
    np.testing.assert_allclose(sd, st, rtol=1e-5)
    for r in range(n_items):
        assert r not in set(idd[r][idd[r] >= 0])
        assert r not in set(idt[r][idt[r] >= 0])
        assert set(idd[r][sd[r] > -np.inf]) == set(idt[r][st[r] > -np.inf])


def test_duplicates_collapse_without_host_dedup(monkeypatch):
    """Raw pairs with heavy duplication give the same indicators as
    pre-dedup'd pairs on BOTH device strategies — the scatter-max densify
    is the dedup, and marginals derive from it on device."""
    n_users, n_ip, n_it = 40, 9, 11
    pu, pi = random_interactions(n_users, n_ip, 500, 51)  # ~500 raw, many dups
    ou, oi = random_interactions(n_users, n_it, 700, 52)
    pu_d, pi_d = dedup_pairs(pu, pi, n_ip)
    ou_d, oi_d = dedup_pairs(ou, oi, n_it)
    for program in ("dense", "resident"):
        _take_program(monkeypatch, program)
        s_raw, i_raw = cco_indicators_coo(
            pu, pi, ou, oi, n_users, n_ip, n_it, top_k=4, item_tile=8)
        s_ded, i_ded = cco_indicators_coo(
            pu_d, pi_d, ou_d, oi_d, n_users, n_ip, n_it, top_k=4, item_tile=8)
        np.testing.assert_allclose(s_raw, s_ded, rtol=1e-5)
        for r in range(n_ip):
            assert set(i_raw[r][s_raw[r] > -np.inf]) == set(i_ded[r][s_ded[r] > -np.inf])


def test_cco_train_indicators_matches_per_call(monkeypatch):
    """The staged multi-event-type entry returns exactly what independent
    cco_indicators_coo calls return (self + cross)."""
    from predictionio_tpu.ops.cco import cco_train_indicators

    monkeypatch.setenv("PIO_CCO_DENSE", "1")
    n_users, n_ip, n_view = 50, 12, 18
    pu, pi = random_interactions(n_users, n_ip, 300, 61)
    vu, vi = random_interactions(n_users, n_view, 600, 62)
    out = cco_train_indicators(
        pu, pi,
        [("buy", pu, pi, n_ip), ("view", vu, vi, n_view)],
        n_users, n_ip, top_k=5, exclude_self_for="buy")
    s_self, i_self = cco_indicators_coo(
        pu, pi, pu, pi, n_users, n_ip, n_ip, top_k=5, exclude_self=True)
    s_cross, i_cross = cco_indicators_coo(
        pu, pi, vu, vi, n_users, n_ip, n_view, top_k=5)
    np.testing.assert_allclose(out["buy"][0], s_self, rtol=1e-5)
    np.testing.assert_allclose(out["view"][0], s_cross, rtol=1e-5)
    for r in range(n_ip):
        assert r not in set(out["buy"][1][r][out["buy"][1][r] >= 0])
        assert set(out["view"][1][r][out["view"][0][r] > -np.inf]) == set(
            i_cross[r][s_cross[r] > -np.inf])


def test_cco_train_indicators_tiled_fallback(monkeypatch):
    """Event types too big for the dense budget route through the tiled
    path inside the same call, with identical semantics."""
    from predictionio_tpu.ops.cco import cco_train_indicators

    n_users, n_ip, n_view = 30, 8, 10
    pu, pi = random_interactions(n_users, n_ip, 200, 71)
    vu, vi = random_interactions(n_users, n_view, 300, 72)
    _take_program(monkeypatch, "dense")
    dense = cco_train_indicators(
        pu, pi, [("buy", pu, pi, n_ip), ("view", vu, vi, n_view)],
        n_users, n_ip, top_k=4, exclude_self_for="buy")
    _take_program(monkeypatch, "resident")
    tiled = cco_train_indicators(
        pu, pi, [("buy", pu, pi, n_ip), ("view", vu, vi, n_view)],
        n_users, n_ip, top_k=4, exclude_self_for="buy", item_tile=8, user_block=8)
    for name in ("buy", "view"):
        np.testing.assert_allclose(dense[name][0], tiled[name][0], rtol=1e-4)


@pytest.mark.parametrize("program", ["dense", "chunked", "resident"])
def test_cco_train_indicators_mesh(monkeypatch, program):
    from predictionio_tpu.ops.cco import cco_train_indicators

    if program == "dense":     # as before the tiled cases: one switch
        monkeypatch.setenv("PIO_CCO_DENSE", "1")
    else:
        _take_program(monkeypatch, program)
    n_users, n_ip, n_view = 64, 10, 12
    pu, pi = random_interactions(n_users, n_ip, 250, 81)
    vu, vi = random_interactions(n_users, n_view, 400, 82)
    single = cco_train_indicators(
        pu, pi, [("buy", pu, pi, n_ip), ("view", vu, vi, n_view)],
        n_users, n_ip, top_k=5, exclude_self_for="buy")
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    assert _mesh_strategy(n_users, n_ip, n_view, mesh) == program
    sharded = cco_train_indicators(
        pu, pi, [("buy", pu, pi, n_ip), ("view", vu, vi, n_view)],
        n_users, n_ip, top_k=5, exclude_self_for="buy", mesh=mesh)
    for name in ("buy", "view"):
        np.testing.assert_allclose(single[name][0], sharded[name][0],
                                   rtol=1e-5, atol=1e-5)


def test_block_interactions_stream_matches_batch():
    """The streaming host-staging layout yields identical indicators to the
    one-shot layout (same data, batched arbitrarily)."""
    from predictionio_tpu.ops.cco import (
        _cco_chunked, block_interactions_stream)

    n_users, n_items = 48, 12
    u, i = random_interactions(n_users, n_items, 400, 91)
    whole = block_interactions(u, i, n_users, n_items, user_block=16)
    streamed = block_interactions_stream(
        ((u[s:s + 37], i[s:s + 37]) for s in range(0, 400, 37)),
        n_users, n_items, user_block=16)
    s1, i1 = _cco_chunked(whole, whole, n_users, top_k=5,
                          item_tile=8, exclude_self=True)
    s2, i2 = _cco_chunked(streamed, streamed, n_users, top_k=5,
                          item_tile=8, exclude_self=True)
    np.testing.assert_allclose(s1, s2, rtol=1e-5)
    for r in range(n_items):
        assert set(i1[r][s1[r] > -np.inf]) == set(i2[r][s2[r] > -np.inf])


@pytest.mark.parametrize("n_users,n_ip,n_it,user_block,item_tile", [
    (70, 14, 19, 16, 8),
    (131, 37, 45, 32, 16),     # a last block of 3 users, a last tile of 13
])
def test_resident_tiled_matches_chunked_tiled(monkeypatch, n_users, n_ip, n_it,
                                              user_block, item_tile):
    """The P-resident tiled strategy (primary densified once, reused per
    tile), the chunked tiled path (re-densified per user block and tile)
    and the dense path return the same kept cells with the same scores."""
    from predictionio_tpu.ops import cco as cco_mod

    pu, pi = random_interactions(n_users, n_ip, 400, 101)
    ou, oi = random_interactions(n_users, n_it, 600, 102)

    def run():
        return cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it, top_k=5,
                                  item_tile=item_tile, user_block=user_block)

    _take_program(monkeypatch, "dense")
    sd, idd = run()
    _take_program(monkeypatch, "resident")
    plan = (n_users, n_ip, n_it, None, item_tile)
    assert cco_mod._plan(*plan) == ("resident",)      # P easily fits
    sr, idr = run()
    # the chunked tiled path: no budget for a resident primary
    _take_program(monkeypatch, "chunked")
    assert cco_mod._plan(*plan) == ("chunked",)
    st, idt = run()
    np.testing.assert_allclose(sd, sr, rtol=1e-4)
    np.testing.assert_allclose(sr, st, rtol=1e-4)
    for r in range(n_ip):   # tie order aside, the same correlators
        kept = set(idd[r][sd[r] > -np.inf])
        assert kept == set(idr[r][sr[r] > -np.inf])
        assert kept == set(idt[r][st[r] > -np.inf])


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_only_the_chunked_program_blocks_by_user(monkeypatch, program):
    """The strategy is planned before any layout: the pairs are blocked by
    user once, inside the chunked strategy, and nowhere else."""
    from predictionio_tpu.obs.spans import SpanCollector

    _take_program(monkeypatch, program)
    n_users, n_ip, n_it = 70, 14, 19
    pu, pi = random_interactions(n_users, n_ip, 400, 121)
    ou, oi = random_interactions(n_users, n_it, 600, 122)
    with SpanCollector().activate() as collector:
        cco_indicators_coo(pu, pi, ou, oi, n_users, n_ip, n_it, top_k=5,
                           user_block=16, item_tile=8)
    spans = collector.spans()
    assert any(s["name"] == "layout" for s in spans)
    blocked = [s["attrs"] for s in spans if s["name"] == "layout"
               and "user_blocks" in s.get("attrs", {})]
    assert len(blocked) == (program == "chunked")
    if blocked:
        assert blocked[0]["user_blocks"] == 5       # 70 users in blocks of 16


def test_resident_tiled_self_pair(monkeypatch):
    n_users, n_items = 50, 12
    u, i = random_interactions(n_users, n_items, 300, 111)
    _take_program(monkeypatch, "resident")
    s1, i1 = cco_indicators_coo(u, i, u, i, n_users, n_items, n_items,
                                top_k=4, item_tile=8, exclude_self=True)
    _take_program(monkeypatch, "chunked")
    s2, i2 = cco_indicators_coo(u, i, u, i, n_users, n_items, n_items,
                                top_k=4, item_tile=8, exclude_self=True)
    np.testing.assert_allclose(s1, s2, rtol=1e-4)
    for r in range(n_items):
        assert r not in set(i1[r][i1[r] >= 0])

def test_sparse_host_matches_dense_and_tiled(monkeypatch):
    """The host sparse-count strategy (CPU-backend cross-join + bincount)
    is bit-identical to the device dense path — same integer counts, same
    device LLR/top-k tail — and set-identical to tiled under ties."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_ip, n_it = 70, 13, 19
    pu, pi = random_interactions(n_users, n_ip, 350, 51)
    ou, oi = random_interactions(n_users, n_it, 600, 52)

    def run():
        return cco_ops.cco_indicators_coo(
            pu, pi, ou, oi, n_users, n_ip, n_it,
            top_k=6, llr_threshold=0.3, item_tile=8)

    monkeypatch.setenv("PIO_CCO_SPARSE", "1")
    ss, si = run()
    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "1")
    ds, di = run()
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    ts, ti = run()
    np.testing.assert_array_equal(ss, ds)      # same counts, same tail: exact
    np.testing.assert_array_equal(si, di)
    np.testing.assert_allclose(ss, ts, rtol=1e-5)
    for r in range(n_ip):
        assert set(si[r][ss[r] > -np.inf]) == set(ti[r][ts[r] > -np.inf])

    # over-budget expansion bails to the device path with identical output
    monkeypatch.setenv("PIO_CCO_SPARSE", "1")
    monkeypatch.delenv("PIO_CCO_DENSE", raising=False)
    monkeypatch.setattr(cco_ops, "_SPARSE_PAIR_BUDGET", 0)
    bs, bi_ = run()
    np.testing.assert_array_equal(bs, ds)
    np.testing.assert_array_equal(bi_, di)


def test_sparse_host_self_pair_and_train_indicators(monkeypatch):
    """cco_train_indicators on the sparse path: self-pair reuses the
    primary CSR, exclude_self masks the diagonal, multi-type results match
    the device dense runner exactly."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_items = 50, 11
    pu, pi = random_interactions(n_users, n_items, 260, 61)
    vu, vi = random_interactions(n_users, n_items, 500, 62)
    others = [("buy", pu, pi, n_items), ("view", vu, vi, n_items)]

    monkeypatch.setenv("PIO_CCO_SPARSE", "1")
    r_sparse = cco_ops.cco_train_indicators(
        pu, pi, others, n_users, n_items, top_k=4, exclude_self_for="buy")
    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    r_dense = cco_ops.cco_train_indicators(
        pu, pi, others, n_users, n_items, top_k=4, exclude_self_for="buy")
    for name in ("buy", "view"):
        np.testing.assert_array_equal(r_sparse[name][0], r_dense[name][0])
        np.testing.assert_array_equal(r_sparse[name][1], r_dense[name][1])
    for r in range(n_items):
        idx = r_sparse["buy"][1][r]
        assert r not in set(idx[idx >= 0])


def test_sparse_host_tail_matches_device_tail():
    """The sparse host LLR/top-k tail (scores only nonzero cells, lexsort
    top-k) must be bit-identical to the dense device tail at both forced
    settings, including the COO fast path and exclude_self."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_items = 300, 64
    u, i = random_interactions(n_users, n_items, 900, 71)

    def run(tail):
        r = cco_ops._SparseHostRunner(u, i, n_users, n_items)
        d = r.dispatch(u, i, n_items, 5, 1.0, True, self_pair=True, tail=tail)
        return cco_ops._DenseRunner.collect(d)

    ds, di = run("device")
    hs, hi = run("host")
    np.testing.assert_array_equal(hs, ds)
    np.testing.assert_array_equal(hi, di)
    # the density rule picks SOME tail here; result must match either way
    as_, ai_ = run(None)
    np.testing.assert_array_equal(as_, ds)
    np.testing.assert_array_equal(ai_, di)
    # rows with fewer than top_k surviving cells pad with -inf / -1
    assert ((hi == -1) == (hs == -np.inf)).all()


def test_sparse_counts_coo_touched_path():
    """want_coo on a matrix ABOVE the bincount-branch gate must collect
    the touched cells from the unique-branch chunks — and they must equal
    a direct flatnonzero scan of the dense result."""
    from predictionio_tpu.ops import cco as cco_ops

    # 4200 x 4100 = 17.2M cells > _SPARSE_BINCOUNT_CELLS (16.8M)
    n_users, n_ip, n_it = 500, 4200, 4100
    assert n_ip * n_it > cco_ops._SPARSE_BINCOUNT_CELLS
    pu, pi = random_interactions(n_users, n_ip, 3000, 81)
    au, ai = random_interactions(n_users, n_it, 4000, 82)
    p = cco_ops._SparseHostCSR(pu, pi, n_ip, n_users)
    a = cco_ops._SparseHostCSR(au, ai, n_it, n_users)
    C, flat = cco_ops._sparse_counts(p, a, want_coo=True)
    np.testing.assert_array_equal(flat, np.flatnonzero(C))
    assert len(flat) > 0
    # and the host tail built from that COO matches the device tail
    s_host, i_host = cco_ops._llr_topk_sparse_host(
        C, p.col_counts, a.col_counts, float(n_users), 0.0, 6, False,
        flat=flat)
    import jax.numpy as jnp
    from predictionio_tpu.ops.pallas_kernels import pallas_mode
    s_dev, i_dev = cco_ops._llr_topk_dense(
        jnp.asarray(C), jnp.asarray(p.col_counts), jnp.asarray(a.col_counts),
        float(n_users), 0.0, top_k=6, exclude_self=False,
        pallas=pallas_mode())
    s_dev, i_dev = cco_ops._finalize_topk(s_dev, i_dev, n_it)
    np.testing.assert_array_equal(s_host, s_dev)
    np.testing.assert_array_equal(i_host, i_dev)


def test_sparse_counts_coo_bincount_downgrade():
    """A bincount-branch chunk loses cell identities, so want_coo must
    fall back to the flatnonzero scan — exercised with a small matrix
    and a dense chunk (chunk * 8 >= cells), where the bincount branch
    actually fires."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_items = 40, 50         # 2500 cells << bincount gate
    pu, pi = random_interactions(n_users, n_items, 700, 91)
    p = cco_ops._SparseHostCSR(pu, pi, n_items, n_users)
    total = cco_ops._cross_join_pairs(p, p)
    assert total * 8 >= n_items * n_items, "need a dense chunk for the test"
    C, flat = cco_ops._sparse_counts(p, p, want_coo=True)
    np.testing.assert_array_equal(flat, np.flatnonzero(C))
    assert len(flat) > 0


def test_pure_coo_counts_match_dense():
    """_sparse_counts_coo (no dense matrix anywhere) must reproduce the
    dense host counts cell for cell, across the chunked merge."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_ip, n_it = 400, 300, 250
    pu, pi = random_interactions(n_users, n_ip, 5000, 101)
    au, ai = random_interactions(n_users, n_it, 6000, 102)
    p = cco_ops._SparseHostCSR(pu, pi, n_ip, n_users)
    a = cco_ops._SparseHostCSR(au, ai, n_it, n_users)
    cells, counts = cco_ops._sparse_counts_coo(p, a)
    C_ref = cco_ops._sparse_counts(p, a)
    C = np.zeros((n_ip, n_it), np.int32)
    C[cells // n_it, cells % n_it] = counts
    np.testing.assert_array_equal(C, C_ref)
    assert np.all(np.diff(cells) > 0)


def test_pure_coo_counts_chunked_merge():
    """The end-of-scan merge across expansion chunks (argsort +
    segment-sum) must aggregate duplicate cells exactly — forced by
    shrinking the chunk budget so every user lands in its own chunk."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_items = 200, 60
    pu, pi = random_interactions(n_users, n_items, 3000, 103)
    p = cco_ops._SparseHostCSR(pu, pi, n_items, n_users)
    saved = cco_ops._SPARSE_CHUNK_PAIRS
    try:
        cco_ops._SPARSE_CHUNK_PAIRS = 16   # many tiny chunks
        cells, counts = cco_ops._sparse_counts_coo(p, p)
    finally:
        cco_ops._SPARSE_CHUNK_PAIRS = saved
    C_ref = cco_ops._sparse_counts(p, p)
    C = np.zeros((n_items, n_items), np.int32)
    C[cells // n_items, cells % n_items] = counts
    np.testing.assert_array_equal(C, C_ref)


def test_huge_catalog_coo_dispatch_matches_dense():
    """When the dense host count matrix is over budget the runner must
    take the pure-COO dispatch (counts + row-scoped sparse tail, no
    [I_p, I_t] array anywhere) and return bit-identical results —
    forced by shrinking _SPARSE_C_BYTES under the same shape."""
    from predictionio_tpu.ops import cco as cco_ops

    n_users, n_items = 300, 120
    u, i = random_interactions(n_users, n_items, 2500, 104)

    def run():
        r = cco_ops._SparseHostRunner(u, i, n_users, n_items)
        d = r.dispatch(u, i, n_items, 6, 0.5, True, self_pair=True,
                       tail="host")
        assert d is not None
        return cco_ops._DenseRunner.collect(d)

    s_ref, i_ref = run()
    saved = cco_ops._SPARSE_C_BYTES
    try:
        cco_ops._SPARSE_C_BYTES = 1024    # dense C "cannot exist"
        s_coo, i_coo = run()
    finally:
        cco_ops._SPARSE_C_BYTES = saved
    np.testing.assert_array_equal(s_ref, s_coo)
    np.testing.assert_array_equal(i_ref, i_coo)


def test_llr_topk_sparse_rows_matches_host_tail_slices():
    """The fold engine's row-scoped sparse tail must equal the TRAINING
    host tail's rows at an arbitrary row subset — same ``_llr_cells``
    compiled program, so bit-identity is structural — including
    self-pair masking at the subset's GLOBAL row ids.  (The host tail's
    own parity with the device tail is pinned separately on real count
    data; two DIFFERENT XLA compilations of the same elementwise chain
    can disagree by 1 ULP on adversarial inputs, so this test compares
    within the one program the fold actually shares with training.)"""
    from predictionio_tpu.ops import cco as cco_ops

    rng = np.random.default_rng(105)
    n_p, n_t, n_users = 90, 70, 500
    C = (rng.random((n_p, n_t)) < 0.1).astype(np.int32) * \
        rng.integers(1, 9, (n_p, n_t)).astype(np.int32)
    rc = C.sum(axis=1).astype(np.int64) + rng.integers(0, 5, n_p)
    cc = C.sum(axis=0).astype(np.int64) + rng.integers(0, 5, n_t)
    # full-matrix host tail with the diagonal masked, as training runs it
    s_host, i_host = cco_ops._llr_topk_sparse_host(
        C, rc, cc, float(n_users), 0.25, top_k=5, exclude_self=True)
    rows = np.asarray(sorted(rng.choice(n_p, 17, replace=False)), np.int64)
    sub = C[rows]
    lr, lc = np.nonzero(sub)
    s_sp, i_sp = cco_ops._llr_topk_sparse_rows(
        lr, lc, sub[lr, lc], rc[rows], cc, float(n_users), 0.25,
        top_k=5, n_rows=len(rows), n_cols=n_t, self_cols=rows)
    np.testing.assert_array_equal(s_sp, s_host[rows])
    np.testing.assert_array_equal(i_sp, i_host[rows])
