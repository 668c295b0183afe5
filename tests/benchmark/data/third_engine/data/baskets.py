"""Seeded shopping baskets with their times (a throw-away test's generator).

Each shopper comes `sessions` times and buys `basket_size` distinct items a
visit, Zipf-like by item: the buys of one visit lie `within_s` seconds apart,
a shopper's visits `between_min` minutes apart, the shoppers' first visits
`shopper_h` hours apart.  A data source that groups one shopper's buys by a
window between the two gaps forms exactly `n_baskets` baskets; one that gets
no times forms `n_users`.  `"times": false` leaves the times out (the control
of the test that the times arrive).
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int) -> dict:
    nu, ni = int(params["n_users"]), int(params["n_items"])
    sessions, size = int(params["sessions"]), int(params["basket_size"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xB5]))
    n_baskets = nu * sessions
    pop = np.log(1.0 / np.arange(1, ni + 1) ** float(params["item_zipf"]))
    # distinct items a basket by the Gumbel top-k trick; the first baskets
    # take the catalogue in order, so that every item is bought
    picks = np.argsort(-(pop + rng.gumbel(size=(n_baskets, ni))), 1)[:, :size]
    cover = np.arange(-(-ni // size) * size).reshape(-1, size) % ni
    picks[: len(cover)] = cover
    baskets = np.repeat(np.arange(n_baskets), size)
    users = baskets // sessions
    us = 1_000_000
    times = (users * int(float(params["shopper_h"]) * 3600 * us)
             + (baskets % sessions) * int(float(params["between_min"]) * 60 * us)
             + np.tile(np.arange(size), n_baskets)
             * int(float(params["within_s"]) * us))
    order = rng.permutation(len(baskets))
    block = {"event": "buy", "users": users[order].astype(np.int64),
             "items": picks.ravel()[order].astype(np.int64),
             "baskets": baskets[order].astype(np.int64)}
    if params.get("times", True):
        block["times"] = times[order].astype(np.int64)
    return {"n_users": nu, "n_items": ni, "n_baskets": n_baskets,
            "blocks": [block]}
