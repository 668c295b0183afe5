"""Plain reference of explicit-feedback ALS (the Recommendation template's
model), as MLlib's ALS-WR states it: alternate, for `iterations` sweeps,

    x_u = (sum_i y_i y_i^T + lambda * n_u * I)^-1  sum_i r_ui y_i     (users)
    y_i = (sum_u x_u x_u^T + lambda * n_i * I)^-1  sum_u r_ui x_u     (items)

over the ratings of u (of i), from item factors drawn 0.1 * N(0, 1) and
user factors 0.  numpy and scipy.sparse in float64 on the host: the normal
matrices of all rows are one sparse-times-dense product, so 20 sweeps at
MovieLens-1M take seconds.  (The program adds 1e-6 to the ridge against
empty rows; the reference does not: no row is empty.)

The start is part of the configuration: `jax.random.normal(PRNGKey(seed),
(1, n_items, rank)) * 0.1`, laid out by the program's item rows.  The
reference draws the same numbers itself and reads from the persisted model
only what is compared (the two factor tables) and the two dictionaries
that say which row is which id.

`round_to` is the control: every factor table rounded to that type as it
is stored and as it is used (bfloat16, the step below the float32 the
program's factors state).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _store(a, round_to):
    return a if round_to is None else a.astype(round_to).astype(np.float64)


def _half(other, S, R, n_e, reg: float, round_to):
    """Solve every row's normal equations against `other`."""
    k = other.shape[1]
    other = _store(other, round_to)
    outer = (other[:, :, None] * other[:, None, :]).reshape(len(other), k * k)
    A = (S @ _store(outer, round_to)).reshape(-1, k, k)
    b = R @ other
    A = A + (reg * np.maximum(n_e, 1.0))[:, None, None] * np.eye(k)
    return _store(np.linalg.solve(A, b[..., None])[..., 0], round_to)


def factorize(users, items, ratings, n_users: int, n_items: int, y0,
              reg: float, iterations: int, round_to=None) -> tuple:
    S = sp.csr_matrix((np.ones(len(users)), (users, items)),
                      shape=(n_users, n_items))
    R = sp.csr_matrix((ratings.astype(np.float64), (users, items)),
                      shape=(n_users, n_items))
    St, Rt = S.T.tocsr(), R.T.tocsr()
    n_u = np.asarray(S.sum(1)).ravel()
    n_i = np.asarray(S.sum(0)).ravel()
    y = np.asarray(y0, np.float64)
    x = np.zeros((n_users, y.shape[1]))
    for _ in range(iterations):
        x = _half(y, S, R, n_u, reg, round_to)
        y = _half(x, St, Rt, n_i, reg, round_to)
    return x, y


def start(seed: int, n_items: int, rank: int) -> np.ndarray:
    import jax

    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (1, n_items, rank), "float32"))[0] * 0.1


def _ids(strings) -> np.ndarray:
    return np.array([int(s[1:]) for s in strings], np.int64)


def compare(x_prog, y_prog, user_ids, item_ids, data: dict, rank: int,
            reg: float, iterations: int, seed: int, round_to=None,
            control: bool = False) -> dict:
    """`x_prog[r]` is the factor of user `user_ids[r]`, `y_prog[r]` of item
    `item_ids[r]`.  Compared over every rating: the predictions (gauge
    free: a rotation of the factors leaves them alone) and the fit."""
    b = data["blocks"][0]
    users, items, ratings = b["users"], b["items"], b["ratings"]
    nu, ni = data["n_users"], data["n_items"]
    if sorted(user_ids.tolist()) != list(range(nu)) or sorted(
            item_ids.tolist()) != list(range(ni)):
        return {"pred_gap_rms": 1e30, "rmse_gap": 1e30}
    y0 = np.empty((ni, rank))
    y0[item_ids] = start(seed, ni, rank)       # row r starts as row r drew
    xr, yr = factorize(users, items, ratings, nu, ni, y0, reg, iterations)
    if control:       # the reference, lower precision, in the program's place
        x_prog, y_prog = factorize(users, items, ratings, nu, ni, y0, reg,
                                   iterations, round_to)
        user_ids, item_ids = np.arange(nu), np.arange(ni)
    xp = np.empty((nu, rank))
    xp[user_ids] = np.asarray(x_prog, np.float64)
    yp = np.empty((ni, rank))
    yp[item_ids] = np.asarray(y_prog, np.float64)
    p_ref = np.einsum("ek,ek->e", xr[users], yr[items])
    p_prog = np.einsum("ek,ek->e", xp[users], yp[items])
    rmse_ref = np.sqrt(np.mean((p_ref - ratings) ** 2))
    rmse_prog = np.sqrt(np.mean((p_prog - ratings) ** 2))
    return {
        "pred_gap_rms": float(np.sqrt(np.mean((p_prog - p_ref) ** 2))
                              / np.sqrt(np.mean(p_ref ** 2))),
        "rmse_gap": float(abs(rmse_prog - rmse_ref) / rmse_ref),
        "rmse_ref": float(rmse_ref), "rmse_prog": float(rmse_prog)}


def check(model, data: dict, variant: dict, limits: dict, seed: int) -> list:
    params = variant["algorithms"][0]["params"]
    got = compare(model.user_factors, model.item_factors,
                  _ids(model.user_dict.strings()),
                  _ids(model.item_dict.strings()), data, int(params["rank"]),
                  float(params["lambda"]), int(params["numIterations"]),
                  int(params["seed"]))
    return [{"name": k, "value": got[k], "limit": limits[k],
             "ok": got[k] <= limits[k]} for k in limits]


def _zeroed(y: np.ndarray, item: int) -> np.ndarray:
    """One answer altered: a copy of the item factors with one item's zeroed."""
    y = np.array(y)
    y[item] = 0.0
    return y


def alter(model, seed: int) -> None:
    """The fault "one answer altered where it is produced", on the model as
    it is about to be persisted: one item's factors zeroed, the item drawn by
    the seed (a model holds no counts to find the most rated item by)."""
    model.item_factors = _zeroed(model.item_factors,
                                 seed % len(model.item_factors))


def readings(config: dict, data: dict, seed: int, half) -> dict:
    """What `compare` reads with, in the program's place: the reference
    itself, the control (bfloat16), half of the ratings left out
    (`half(data)`), and the most rated item's factors zeroed.  `control.py`
    prints them."""
    import ml_dtypes

    a = config["engine"]["algorithms"][0]["params"]
    rank, reg, sweeps = int(a["rank"]), float(a["lambda"]), int(
        a["numIterations"])
    first = seed % (2 ** 31 - 1)
    nu, ni = data["n_users"], data["n_items"]
    ids = (np.arange(nu), np.arange(ni))
    b = data["blocks"][0]
    y0 = start(first, ni, rank)

    def held(x, y):
        got = compare(x, y, *ids, data, rank, reg, sweeps, first)
        return {k: got[k] for k in ("pred_gap_rms", "rmse_gap")}

    x, y = factorize(b["users"], b["items"], b["ratings"], nu, ni, y0,
                     reg, sweeps)
    out = {"reference": held(x, y)}
    out["control_bfloat16"] = held(*factorize(
        b["users"], b["items"], b["ratings"], nu, ni, y0, reg, sweeps,
        ml_dtypes.bfloat16))
    h = half(data)["blocks"][0]
    out["fault_half_left_out"] = held(*factorize(
        h["users"], h["items"], h["ratings"], nu, ni, y0, reg, sweeps))
    out["fault_answer_altered"] = held(x, _zeroed(y, int(np.argmax(
        np.bincount(b["items"])))))
    return out
