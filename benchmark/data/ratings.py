"""Seeded explicit ratings at a published data set's counts.

MovieLens itself cannot be fetched here, so the ratings are drawn: distinct
(user, item) pairs, every user and every item present, user activity
log-normal with a floor (MovieLens keeps users with 20 ratings or more) and
item popularity Zipf-like, values 1..5 from a low-rank taste model plus
noise, so that the factorisation has something to find.  The exponents are
`assumed` in the configuration's file.
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int) -> dict:
    nu, ni, n = (int(params[k]) for k in ("n_users", "n_items", "n_ratings"))
    floor = int(params["min_ratings_per_user"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xA1]))
    if n < max(nu * floor, ni) or n > nu * ni // 2:
        raise ValueError("n_ratings does not fit the floor or the matrix")
    # how many ratings each user gives: log-normal above the floor, scaled
    # to the total exactly
    w = rng.lognormal(0.0, float(params["user_sigma"]), nu)
    extra = n - nu * floor
    per_user = floor + np.floor(w / w.sum() * extra).astype(np.int64)
    per_user = np.minimum(per_user, ni // 2)
    short = n - int(per_user.sum())
    while short > 0:   # hand out the rounding remainder (and any cap spill)
        room = np.flatnonzero(per_user < ni // 2)
        take = rng.choice(room, size=min(short, len(room)), replace=False)
        per_user[take] += 1
        short = n - int(per_user.sum())
    pop = 1.0 / np.arange(1, ni + 1) ** float(params["item_zipf"])
    pop = pop[rng.permutation(ni)]
    pop /= pop.sum()
    # distinct items per user by the Gumbel top-k trick, in blocks of users
    users = np.repeat(np.arange(nu), per_user)
    items = np.empty(n, np.int64)
    logp = np.log(pop)
    at = 0
    for lo in range(0, nu, 512):
        hi = min(lo + 512, nu)
        g = logp[None, :] + rng.gumbel(size=(hi - lo, ni))
        kmax = int(per_user[lo:hi].max())
        top = np.argpartition(-g, kmax - 1, axis=1)[:, :kmax]
        # order the kept block by score so that a prefix is a valid draw
        top = np.take_along_axis(
            top, np.argsort(-np.take_along_axis(g, top, 1), axis=1), 1)
        for r, k in enumerate(per_user[lo:hi]):
            items[at:at + k] = top[r, :k]
            at += k
    # every item rated once at least: give an unrated item the place of a
    # rating of an item that has several
    counts = np.bincount(items, minlength=ni)
    for missing in np.flatnonzero(counts == 0):
        while True:
            j = int(rng.integers(0, n))
            if counts[items[j]] > 1 and not np.any(
                    items[users == users[j]] == missing):
                counts[items[j]] -= 1
                items[j] = missing
                counts[missing] = 1
                break
    rank = int(params["taste_rank"])
    pu = rng.normal(size=(nu, rank)) / np.sqrt(rank)
    qi = rng.normal(size=(ni, rank))
    raw = 3.6 + 1.1 * np.einsum("ek,ek->e", pu[users], qi[items]) \
        + 0.6 * rng.normal(size=n)
    ratings = np.clip(np.rint(raw), 1, 5).astype(np.float32)
    order = rng.permutation(n)
    return {"n_users": nu, "n_items": ni, "blocks": [{
        "event": "rate", "users": users[order], "items": items[order],
        "ratings": ratings[order]}]}
