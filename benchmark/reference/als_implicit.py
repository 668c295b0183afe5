"""Plain reference of implicit-feedback ALS (Hu, Koren, Volinsky 2008; MLlib
`ALS.trainImplicit`), as the E-Commerce Recommendation engine of this repo
trains it: events of a visitor on an item fold into one cell of strength
r = the sum of the events' weights (view 1, buy 4: the engine's
`eventWeights`), confidence c = 1 + alpha * r over preference 1, and for
`iterations` sweeps

    x_u = (Y^T Y + sum_i (c_ui - 1) y_i y_i^T + ridge_u I)^-1  sum_i c_ui y_i
    y_i = (X^T X + sum_u (c_ui - 1) x_u x_u^T + ridge_i I)^-1  sum_u c_ui x_u

over the cells of u (of i), ridge = lambda * n + 1e-6 with n the row's
cells (the program's ALS-WR weighting and its guard), from item factors
drawn 0.1 * N(0, 1) and user factors 0.  numpy and scipy.sparse in float64
on the host: the sums over cells are one sparse-times-dense product a
half-step, and the 10 x 10 systems are solved by a Cholesky written over
rows (each entry of every row's system one vector), in blocks of rows.

The start is the configuration's: `jax.random.normal(PRNGKey(seed), (1,
n_items, rank)) * 0.1`, laid out by the program's item rows.  From the
persisted model only the two factor tables and the two dictionaries that
say which row is which id are read.

Two numbers are compared.  `pred_gap_rms`: the predictions over the
observed cells against the reference's sweeps from the start.
`half_step_gap`: the persisted item factors against one item half-step
from the persisted user factors; the program's last half-step is exactly
that, so it reads float32's rounding, and a Gram or a factor table held
one precision lower fails it.  Both are root-mean-square gaps relative to
the reference's root mean square: the factors of items and visitors seen
only with each other shrink by a constant factor every sweep, to float32's
underflow within 20 sweeps at RetailRocket's counts, and no row's own
relative gap means anything there.

`round_to` is the control: every factor table rounded to that type as it
is stored and as it is used (bfloat16, the step below the float32 the
program's factors state).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

BIG = 1e30
WEIGHTS = {"buy": 4.0}          # the engine's eventWeights; others weigh 1
ALPHA = 1.0                     # the engine's alpha: trainImplicit's 1.0
GUARD = 1e-6                    # the program's ridge guard
_BLOCK = 1 << 14                # rows a block of the host's solve


def _store(a, round_to):
    return a if round_to is None else a.astype(round_to).astype(np.float64)


def cells(data: dict, weights: dict) -> tuple:
    """(users, items, strength) of every cell, summed over its events."""
    ni = data["n_items"]
    key = np.concatenate([b["users"].astype(np.int64) * ni + b["items"]
                          for b in data["blocks"]])
    w = np.concatenate([np.full(len(b["users"]),
                                float(weights.get(b["event"], 1.0)))
                        for b in data["blocks"]])
    uniq, inv = np.unique(key, return_inverse=True)
    return uniq // ni, uniq % ni, np.bincount(inv, weights=w)


def _triangle(a: np.ndarray, pool=None) -> np.ndarray:
    """[n, k(k+1)/2]: a_p a_q of each row for p <= q, row by row of the
    upper triangle (numpy's `triu_indices` order), in blocks of rows."""
    n, k = a.shape
    out = np.empty((n, k * (k + 1) // 2))

    def rows(lo):
        at, part = 0, a[lo:lo + _BLOCK]
        for p in range(k):
            np.multiply(part[:, p:p + 1], part[:, p:],
                        out=out[lo:lo + _BLOCK, at:at + k - p])
            at += k - p

    list((pool.map if pool else map)(rows, range(0, n, _BLOCK)))
    return out


def _cholesky_solve(s, b, gram, lam, k: int) -> np.ndarray:
    """Rows' systems (Gram + the triangle `s` [k(k+1)/2, n] + lam I) x = b
    [k, n], by a Cholesky over rows: each entry of a system [k, k, n] is
    one vector of the rows, factored in place column by column (its lower
    triangle becomes L), then the two triangular solves.  Returns x [n, k]."""
    tri = np.zeros((k, k), np.int64)
    tri[np.triu_indices(k)] = np.arange(k * (k + 1) // 2)
    L = s[np.maximum(tri, tri.T)] + gram[:, :, None]
    L[np.arange(k), np.arange(k)] += lam
    for j in range(k):
        L[j, j] = np.sqrt(L[j, j])
        L[j + 1:, j] /= L[j, j]
        for i in range(j + 1, k):
            L[i:, i] -= L[i:, j] * L[i, j]
    x = b.copy()
    for i in range(k):                # L z = b
        x[i] /= L[i, i]
        x[i + 1:] -= L[i + 1:, i] * x[i]
    for i in reversed(range(k)):      # L^T x = z
        x[i] /= L[i, i]
        x[:i] -= L[i, :i] * x[i]
    return x.T


def _half(other, blocks, ridge, round_to=None, pool=None):
    """Solve every row's system against `other`.  `blocks` are (C1, C) row
    blocks of the sparse [rows, other rows] matrices of c - 1 and c over
    the cells; the blocks are solved on `pool`'s threads."""
    k = other.shape[1]
    other = _store(other, round_to)
    outer = _store(_triangle(other, pool), round_to)
    gram = other.T @ other

    def solve(at):
        lo, (C1, C) = at
        return _cholesky_solve(np.ascontiguousarray((C1 @ outer).T),
                               np.ascontiguousarray((C @ other).T), gram,
                               ridge[lo:lo + C.shape[0]], k)

    starts = np.cumsum([0] + [C.shape[0] for _, C in blocks[:-1]])
    parts = list((pool.map if pool else map)(solve, zip(starts, blocks)))
    return _store(np.concatenate(parts), round_to)


def _row_blocks(*matrices) -> list:
    """The CSR matrices' rows cut into blocks of `_BLOCK`, side by side."""
    n = matrices[0].shape[0]
    return [tuple(m[lo:lo + _BLOCK] for m in matrices)
            for lo in range(0, n, _BLOCK)]


def _threads():
    """The host's cores for the blocks: numpy and scipy's sparse products
    let go of the interpreter's lock while they work."""
    return ThreadPoolExecutor(min(8, os.cpu_count() or 1))


class Problem:
    """A configuration's cells as the sparse matrices of both half-steps."""

    def __init__(self, data: dict, params: dict):
        self.nu, self.ni = data["n_users"], data["n_items"]
        self.k = int(params["rank"])
        self.reg = float(params["lambda"])
        self.sweeps = int(params["numIterations"])
        self.seed = int(params["seed"])
        alpha = float(params.get("alpha", ALPHA))
        self.users, self.items, r = cells(
            data, params.get("eventWeights", WEIGHTS))
        shape = (self.nu, self.ni)
        c1 = sp.csr_matrix((alpha * r, (self.users, self.items)), shape)
        c = c1 + sp.csr_matrix((np.ones(len(r)), (self.users, self.items)),
                               shape)
        self.by_user = _row_blocks(c1, c)
        self.by_item = _row_blocks(c1.T.tocsr(), c.T.tocsr())
        n_u = np.bincount(self.users, minlength=self.nu)
        n_i = np.bincount(self.items, minlength=self.ni)
        self.ridge_u = self.reg * np.maximum(n_u, 1.0) + GUARD
        self.ridge_i = self.reg * np.maximum(n_i, 1.0) + GUARD

    def item_half(self, x, round_to=None, pool=None):
        return _half(x, self.by_item, self.ridge_i, round_to, pool)

    def factorize(self, y0, round_to=None) -> tuple:
        y = _store(np.asarray(y0, np.float64), round_to)
        x = np.zeros((self.nu, self.k))
        with _threads() as pool:
            for _ in range(self.sweeps):
                x = _half(y, self.by_user, self.ridge_u, round_to, pool)
                y = self.item_half(x, round_to, pool)
        return x, y

    def compare(self, x, y, xr, yr) -> dict:
        """`x`, `y` in the reference's rows against the reference's sweeps
        (`xr`, `yr`) and against one item half-step from `x`."""
        p_ref = np.einsum("ek,ek->e", xr[self.users], yr[self.items])
        p = np.einsum("ek,ek->e", x[self.users], y[self.items])
        with _threads() as pool:
            half = self.item_half(x, pool=pool)
        return {"pred_gap_rms": _rms_gap(p, p_ref),
                "half_step_gap": _rms_gap(y, half)}


def _rms_gap(got, want) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def start(seed: int, n_items: int, rank: int) -> np.ndarray:
    import jax

    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (1, n_items, rank), "float32"))[0] * 0.1


def _ids(strings) -> np.ndarray:
    return np.array([int(s[1:]) for s in strings], np.int64)


def compare(x_prog, y_prog, user_ids, item_ids, data: dict,
            params: dict) -> dict:
    """`x_prog[r]` is the factor of user `user_ids[r]`, `y_prog[r]` of item
    `item_ids[r]`; the start is drawn in the program's item rows."""
    prob = Problem(data, params)
    if sorted(user_ids.tolist()) != list(range(prob.nu)) or sorted(
            item_ids.tolist()) != list(range(prob.ni)):
        return {"pred_gap_rms": BIG, "half_step_gap": BIG}
    y0 = np.empty((prob.ni, prob.k))
    y0[item_ids] = start(prob.seed, prob.ni, prob.k)
    xr, yr = prob.factorize(y0)
    x = np.empty((prob.nu, prob.k))
    x[user_ids] = np.asarray(x_prog, np.float64)
    y = np.empty((prob.ni, prob.k))
    y[item_ids] = np.asarray(y_prog, np.float64)
    return prob.compare(x, y, xr, yr)


def check(model, data: dict, variant: dict, limits: dict, seed: int) -> list:
    got = compare(model.user_factors, model.item_factors,
                  _ids(model.user_dict.strings()),
                  _ids(model.item_dict.strings()), data,
                  variant["algorithms"][0]["params"])
    return [{"name": k, "value": got[k], "limit": limits[k],
             "ok": got[k] <= limits[k]} for k in limits]


def _zeroed(y: np.ndarray, item: int) -> np.ndarray:
    """One answer altered: a copy of the item factors with one item's zeroed."""
    y = np.array(y)
    y[item] = 0.0
    return y


def _largest(y: np.ndarray) -> int:
    return int(np.argmax((np.asarray(y, np.float64) ** 2).sum(1)))


def alter(model, seed: int) -> None:
    """The fault "one answer altered where it is produced", on the model as
    it is about to be persisted: the factors of the item with the largest
    zeroed (an item drawn at random is more likely than not one whose
    factors have shrunk to nothing, and zeroing it alters no answer)."""
    model.item_factors = _zeroed(model.item_factors,
                                 _largest(model.item_factors))


def readings(config: dict, data: dict, seed: int, half) -> dict:
    """What `compare` reads with, in the program's place: the reference
    itself, the control (bfloat16), half of the events left out
    (`half(data)`), and the largest item factors zeroed, as `alter` zeroes
    them.  `control.py` prints them."""
    import ml_dtypes

    params = dict(config["engine"]["algorithms"][0]["params"],
                  seed=seed % (2 ** 31 - 1))
    prob = Problem(data, params)
    y0 = start(prob.seed, prob.ni, prob.k)
    xr, yr = prob.factorize(y0)
    out = {"reference": prob.compare(xr, yr, xr, yr)}
    out["control_bfloat16"] = prob.compare(
        *prob.factorize(y0, ml_dtypes.bfloat16), xr, yr)
    out["fault_half_left_out"] = prob.compare(
        *Problem(half(data), params).factorize(y0), xr, yr)
    out["fault_answer_altered"] = prob.compare(
        xr, _zeroed(yr, _largest(yr)), xr, yr)
    return out
