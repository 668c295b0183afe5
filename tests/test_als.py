"""ALS op tests: reconstruction quality and single-device vs 8-device mesh
parity (the reference tests MLlib ALS only via its template integration; here
the op itself is tested — SURVEY.md §4 maps SharedSparkContext local[*] to the
virtual CPU mesh)."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSData,
    als_train,
    prepare_als_data,
    recommend_batch,
    recommend_scores,
)
from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh


def synthetic_ratings(n_users=40, n_items=30, k_true=4, density=0.5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_users, k_true))
    Y = rng.normal(size=(n_items, k_true))
    R = X @ Y.T
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    return u.astype(np.int32), i.astype(np.int32), R[u, i].astype(np.float32), R, mask


def rmse_on_observed(X, Y, R, mask):
    pred = X @ Y.T
    return float(np.sqrt(np.mean((pred[mask] - R[mask]) ** 2)))


def test_prepare_als_data_layout():
    u = np.array([0, 1, 2, 3, 4, 0], np.int32)
    i = np.array([0, 0, 1, 1, 2, 2], np.int32)
    r = np.ones(6, np.float32)
    d = prepare_als_data(u, i, r, n_users=5, n_items=3, dp=2)
    assert d.user_rows == 3 and d.item_rows == 2
    assert d.u_user_local.shape[0] == 2
    # user 3 -> shard 1, local row 1
    assert d.u_mask.sum() == 6
    # flat item index targets shard*item_rows + row
    assert d.u_item_flat.max() < 2 * d.item_rows


def test_als_reconstructs_ratings_single_device():
    u, i, r, R, mask = synthetic_ratings()
    data = prepare_als_data(u, i, r, 40, 30, dp=1)
    X, Y = als_train(data, k=8, reg=0.01, iterations=12)
    assert X.shape == (40, 8) and Y.shape == (30, 8)
    assert rmse_on_observed(X, Y, R, mask) < 0.15


def test_als_mesh_matches_single_device():
    u, i, r, R, mask = synthetic_ratings(n_users=33, n_items=17)
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    data8 = prepare_als_data(u, i, r, 33, 17, dp=8)
    X8, Y8 = als_train(data8, k=6, reg=0.05, iterations=8, mesh=mesh)
    data1 = prepare_als_data(u, i, r, 33, 17, dp=1)
    X1, Y1 = als_train(data1, k=6, reg=0.05, iterations=8)
    # Factors are not identical (different init partitioning) but the
    # reconstruction they produce must match closely.
    r1 = rmse_on_observed(X1, Y1, R, mask)
    r8 = rmse_on_observed(X8, Y8, R, mask)
    assert abs(r1 - r8) < 0.05
    assert r8 < 0.2


def test_recommend_topk_masks_seen():
    Y = np.eye(4, dtype=np.float32)  # items = axis vectors
    x = np.array([0.9, 0.5, 0.1, 0.0], np.float32)
    seen = np.array([1.0, 0, 0, 0], np.float32)  # best item already seen
    scores, idx = recommend_scores(x, Y, seen, top_k=2)
    assert idx.tolist() == [1, 2]
    bscores, bidx = recommend_batch(x[None], Y, seen[None], top_k=2)
    assert bidx[0].tolist() == [1, 2]


def test_als_empty_rows_are_stable():
    # users/items with no events must not produce NaNs
    u = np.array([0, 0], np.int32)
    i = np.array([0, 1], np.int32)
    r = np.array([1.0, 2.0], np.float32)
    data = prepare_als_data(u, i, r, n_users=5, n_items=4, dp=2)
    X, Y = als_train(data, k=3, reg=0.1, iterations=3)
    assert np.isfinite(X).all() and np.isfinite(Y).all()


# -- implicit-feedback ALS (Hu/Koren; MLlib trainImplicit analogue) ----------


def _implicit_numpy_reference(R, y_init, k, reg, alpha, iters):
    """Direct f64 solve of the implicit normal equations, per row."""
    n_users, n_items = R.shape
    Yn = y_init.astype(np.float64).copy()
    Xn = np.zeros((n_users, k))
    C1 = alpha * R
    P = (R > 0).astype(np.float64)
    for _ in range(iters):
        G = Yn.T @ Yn
        for u in range(n_users):
            n_e = (R[u] > 0).sum()
            A = G + (Yn * C1[u][:, None]).T @ Yn + (reg * max(n_e, 1) + 1e-6) * np.eye(k)
            Xn[u] = np.linalg.solve(A, ((1 + C1[u]) * P[u]) @ Yn)
        G = Xn.T @ Xn
        for i in range(n_items):
            n_e = (R[:, i] > 0).sum()
            A = G + (Xn * C1[:, i][:, None]).T @ Xn + (reg * max(n_e, 1) + 1e-6) * np.eye(k)
            Yn[i] = np.linalg.solve(A, ((1 + C1[:, i]) * P[:, i]) @ Xn)
    return Xn, Yn


def implicit_counts(n_users=30, n_items=20, seed=0):
    rng = np.random.default_rng(seed)
    R = np.zeros((n_users, n_items), np.float32)
    for _ in range(200):
        R[rng.integers(n_users), rng.integers(n_items)] += rng.integers(1, 5)
    u, i = np.nonzero(R)
    return u.astype(np.int32), i.astype(np.int32), R[u, i].astype(np.float32), R


def test_implicit_als_matches_direct_solve():
    from predictionio_tpu.ops import als as als_ops

    u, i, r, R = implicit_counts()
    k, reg, alpha, iters = 4, 0.05, 2.0, 6
    data = prepare_als_data(u, i, r, *R.shape, dp=1)
    X, Y = als_train(data, k=k, reg=reg, iterations=iters, seed=7,
                     implicit=True, alpha=alpha)
    _, y0 = als_ops._als_init(data, k, 7)
    y_init = np.asarray(y0).reshape(-1, k)[: R.shape[1]]
    Xn, Yn = _implicit_numpy_reference(R, y_init, k, reg, alpha, iters)
    pj, pn = X @ Y.T, Xn @ Yn.T
    rel = np.abs(pj - pn).max() / np.abs(pn).max()
    assert rel < 5e-3, f"implicit ALS deviates from direct solve: {rel}"
    # preference recovery: observed cells outrank unobserved on average
    assert pj[R > 0].mean() > 2 * pj[R == 0].mean()


def test_implicit_als_mesh_matches_single_device():
    # Init partitioning differs per dp (as in the explicit mesh test), so
    # compare the preference structure the factorizations recover, not the
    # raw factors.
    u, i, r, R = implicit_counts(seed=3)
    k, reg, alpha, iters = 4, 0.05, 1.5, 8
    d1 = prepare_als_data(u, i, r, *R.shape, dp=1)
    X1, Y1 = als_train(d1, k=k, reg=reg, iterations=iters, seed=7,
                       implicit=True, alpha=alpha)
    mesh = create_mesh(MeshSpec(dp=8, mp=1))
    d8 = prepare_als_data(u, i, r, *R.shape, dp=8)
    X8, Y8 = als_train(d8, k=k, reg=reg, iterations=iters, seed=7, mesh=mesh,
                       implicit=True, alpha=alpha)
    p1, p8 = X1 @ Y1.T, X8 @ Y8.T

    def separation(p):
        return float(p[R > 0].mean() - p[R == 0].mean())

    s1, s8 = separation(p1), separation(p8)
    assert s1 > 0 and s8 > 0
    assert abs(s1 - s8) / max(s1, s8) < 0.15, (s1, s8)


# -- the half-step itself: one packed row a rating, one reduction -------------


def _half_step_case(k, seed=0):
    """A shard's events over 7 rows with row 3 empty and two padded slots
    (mask 0) that still carry an index and a rating, as no layout pads them:
    the mask alone must keep them out."""
    rng = np.random.default_rng(seed)
    rows, n_other, e = 7, 9, 48
    local = rng.integers(0, rows - 1, e)
    local = np.where(local >= 3, local + 1, local).astype(np.int32)
    other = rng.integers(0, n_other, e).astype(np.int32)
    rating = rng.integers(1, 6, e).astype(np.float32)
    mask = np.ones(e, np.float32)
    mask[[5, 17]] = 0.0
    factors = rng.normal(size=(n_other, k)).astype(np.float32)
    return rows, local, other, rating, mask, factors


def _half_step_float64(rows, local, other, rating, mask, factors, reg, alpha):
    """Each row's normal equations solved alone, in float64.  ``alpha`` None
    is the explicit system, else the implicit one."""
    f = factors.astype(np.float64)
    k = f.shape[1]
    out = np.zeros((rows, k))
    for r in range(rows):
        sel = (local == r) & (mask > 0)
        Y, rr = f[other[sel]], rating[sel].astype(np.float64)
        ridge = (reg * max(int(sel.sum()), 1) + 1e-6) * np.eye(k)
        if alpha is None:
            A, b = Y.T @ Y + ridge, Y.T @ rr
        else:
            c1 = alpha * rr
            A = f.T @ f + (Y * c1[:, None]).T @ Y + ridge
            b = Y.T @ (1.0 + c1)
        out[r] = np.linalg.solve(A, b)
    return out


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
@pytest.mark.parametrize("k", [1, 10, 14, 15, 32])
def test_half_step_matches_float64_row_solves(k, implicit):
    """W = K(K+3)/2 is 2, 65, 119 (under 128 lanes), 135 (over) and 560."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as als_ops

    rows, local, other, rating, mask, factors = _half_step_case(k)
    reg, alpha = 0.1, 1.5
    assert als_ops._packed_width(k) == k * (k + 3) // 2
    lam = als_ops._row_ridge(jnp.asarray(local), jnp.asarray(mask), rows, reg)
    if implicit:
        got = als_ops._half_step_implicit(
            jnp.asarray(factors), jnp.asarray(factors.T @ factors), local,
            other, rating, mask, lam, jnp.float32(alpha), rows)
    else:
        got = als_ops._half_step(jnp.asarray(factors), local, other, rating,
                                 mask, lam, rows)
    want = _half_step_float64(rows, local, other, rating, mask, factors, reg,
                              alpha if implicit else None)
    got = np.asarray(got, np.float64)
    assert got.shape == (rows, k)
    if not implicit:
        assert np.all(got[3] == 0.0)        # an empty row solves to exactly 0
    # float32's level: 5e-8 (K = 1) to 3e-5 (K = 32, implicit: a Gram of 9
    # rows at rank 32), digit for digit what the [E, K, K] form read here
    gap = np.abs(got - want).max() / np.abs(want).max()
    assert gap < 1e-4, gap


def _walk(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _walk(j)


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_program_has_no_event_by_rank_squared_array_and_counts_once(implicit):
    """What keeps the [E, K, K] outer products from coming back: in the jaxpr
    of the whole program nothing is as large as E·K·K, a sweep's body holds
    one segment reduction a half-step, over packed rows, and the constant
    ratings-per-row counts are reduced before the loop, not in it."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as als_ops

    k = 10
    u, i, r, _, _ = synthetic_ratings(n_users=40, n_items=30, density=0.4)
    data = prepare_als_data(u, i, r, 40, 30, dp=1)
    e = data.u_mask.shape[1]
    x0, y0 = als_ops._als_init(data, k, 0)
    program = functools.partial(
        als_ops._als_run_single, implicit=implicit,
        blocks=als_ops._solve_plan(data.user_rows, data.item_rows, k))
    jaxpr = jax.make_jaxpr(program)(
        x0, y0, jnp.int32(3), jnp.float32(0.1), jnp.float32(1.0),
        *als_ops._als_device_args(data)).jaxpr
    eqns = list(_walk(jaxpr))

    largest = max(int(np.prod(v.aval.shape)) for q in eqns for v in q.outvars
                  if hasattr(v.aval, "shape"))
    assert largest == e * als_ops._packed_width(k)      # the packed rows
    assert largest < e * k * k

    (loop,) = [q for q in eqns if q.primitive.name == "while"]
    body = list(_walk(loop.params["body_jaxpr"].jaxpr))
    in_body = {id(q) for q in body}

    def reductions(some):
        # (operand shape, updates shape) of each scatter-add
        return [(q.invars[0].aval.shape, q.invars[2].aval.shape)
                for q in some if q.primitive.name == "scatter-add"]

    w = als_ops._packed_width(k)
    assert sorted(reductions(body)) == sorted(
        [((data.user_rows, w), (e, w)), ((data.item_rows, w), (e, w))])
    # the counts: one reduction of each layout's mask, outside the loop
    outside = reductions(q for q in eqns if id(q) not in in_body)
    assert sorted(outside) == sorted(
        [((data.user_rows,), (e,)), ((data.item_rows,), (e,))])


@pytest.mark.parametrize("dp", [1, 8], ids=["single", "sharded"])
def test_dispatch_span_says_which_program_shape_ran(dp, tmp_path):
    from predictionio_tpu.obs import spans as obs_spans
    from predictionio_tpu.ops import als as als_ops

    u, i, r, _, _ = synthetic_ratings(n_users=33, n_items=17)
    data = prepare_als_data(u, i, r, 33, 17, dp=dp)
    mesh = create_mesh(MeshSpec(dp=dp, mp=1)) if dp > 1 else None
    with obs_spans.SpanJournal(tmp_path / "als.jsonl").activate():
        als_train(data, k=6, reg=0.05, iterations=2, mesh=mesh)
    (dispatch,) = [s for s in obs_spans.recent_runs()[-1]
                   if s["name"] == "dispatch"
                   and s["attrs"]["program"].startswith("_als_run_")]
    assert dispatch["attrs"]["program"] == (
        "_als_run_sharded" if dp > 1 else "_als_run_single")
    assert dispatch["attrs"]["packed_width"] == als_ops._packed_width(6) == 27
    assert dispatch["attrs"]["events"] == data.u_mask.shape[1]
    # one block a side, each sweep
    assert dispatch["attrs"]["solve_blocks"] == 2 * 2
    assert dispatch["attrs"]["solve_block"] == max(data.user_rows,
                                                   data.item_rows)


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_one_device_runs_a_sharded_layout_as_the_mesh_does(implicit):
    """The same dp = 8 layout and start on one device (shard s's row r as row
    s·rows + r of one shard) and on the mesh: the same factors."""
    u, i, r, _, _ = synthetic_ratings(n_users=33, n_items=17)
    if implicit:
        r = np.abs(r)
    data = prepare_als_data(u, i, r, 33, 17, dp=8)
    kw = dict(k=5, reg=0.05, iterations=4, implicit=implicit, alpha=1.5)
    x1, y1 = als_train(data, **kw)
    x8, y8 = als_train(data, mesh=create_mesh(MeshSpec(dp=8, mp=1)), **kw)
    assert np.abs(x1 - x8).max() < 1e-4 * np.abs(x8).max()
    assert np.abs(y1 - y8).max() < 1e-4 * np.abs(y8).max()


@pytest.mark.parametrize("rows", [1, 2048, 5000])
def test_gram_sums_partial_grams_to_the_float64_gram(rows):
    """`_gram` pads the table to whole partials of `_GRAM_ROWS` rows: the
    padding adds nothing, and the sum is YᵀY to float32's rounding."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as als_ops

    y = np.random.default_rng(rows).normal(size=(rows, 10)).astype(np.float32)
    want = y.astype(np.float64).T @ y.astype(np.float64)
    got = np.asarray(als_ops._gram(jnp.asarray(y)), np.float64)
    assert got.shape == (10, 10)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


def test_solve_plan_blocks_a_side_only_where_its_systems_do_not_fit():
    """The plan at the cells' shapes: MovieLens-1M's 9,746 rows solve in
    one block each side (a one-trip `lax.map`); 1.4M visitors do
    not, and are cut into three even blocks of whole 8-row tiles beside
    one block of 235k items.  A padded K×K system at rank 10 is [16, 128]
    floats."""
    from predictionio_tpu.ops import als as als_ops

    assert als_ops._tiled_bytes(10, 10) == 8192
    assert als_ops._solve_plan(6040, 3706, 10) == (6040, 3706)
    block, item_block = als_ops._solve_plan(1_407_580, 235_061, 10)
    assert item_block == 235_061 and block % 8 == 0
    assert als_ops._blocked_rows(1_407_580, block) // block == 3
    assert 0 <= als_ops._blocked_rows(1_407_580, block) - 1_407_580 < 3 * 8


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_blocked_solve_equals_the_one_block_solve(implicit, monkeypatch, tmp_path):
    """The budget shrunk until the users take three blocks and the items
    two: the same factors as one block a side, to float32's rounding, and
    the dispatch span counts the blocks the program solved."""
    from predictionio_tpu.obs import spans as obs_spans
    from predictionio_tpu.ops import als as als_ops

    u, i, r, _, _ = synthetic_ratings(n_users=40, n_items=30)
    if implicit:
        r = np.abs(r)
    data = prepare_als_data(u, i, r, 40, 30, dp=1)
    kw = dict(k=6, reg=0.05, iterations=4, implicit=implicit, alpha=1.5)
    assert als_ops._solve_plan(40, 30, 6) == (40, 30)
    x1, y1 = als_train(data, **kw)
    # what the plan holds beside a block at this shape, and 16 rows' systems
    held = 3 * als_ops._tiled_bytes(40 + 30, als_ops._packed_width(6))
    monkeypatch.setattr(als_ops, "_PLAN_BYTES",
                        held + 16 * 2 * als_ops._tiled_bytes(6, 6))
    blocks = als_ops._solve_plan(40, 30, 6)
    assert blocks == (16, 16)
    with obs_spans.SpanJournal(tmp_path / "als.jsonl").activate():
        xb, yb = als_train(data, **kw)
    (dispatch,) = [s for s in obs_spans.recent_runs()[-1]
                   if s["name"] == "dispatch"
                   and s["attrs"]["program"] == "_als_run_single"]
    assert dispatch["attrs"]["solve_block"] == 16
    assert dispatch["attrs"]["solve_blocks"] == 4 * (3 + 2)
    assert dispatch["attrs"]["implicit"] is implicit
    assert np.abs(xb - x1).max() <= 1e-5 * np.abs(x1).max()
    assert np.abs(yb - y1).max() <= 1e-5 * np.abs(y1).max()
