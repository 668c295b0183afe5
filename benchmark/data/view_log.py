"""Seeded view/buy log of a shop at a published data set's counts.

RetailRocket's e-commerce log cannot be fetched here, so it is drawn to its
counts: every visitor and every item appears, a visitor's cells (distinct
items it viewed) are drawn without repeats by item popularity, repeat views
of a cell aggregate into its count, and buys fall on viewed cells.  The
number of distinct cells is a parameter, the same for every seed, so that
every seed gives the program one shape.  The shapes (visitor activity
log-normal above one cell, item popularity 1/rank^s, repeat views and buys
by a log-normal weight of the cell) are `assumed` in the configuration's
file.

Blocks: `view` (one event a view) and `buy` (one event a buy), each in a
random order.
"""

from __future__ import annotations

import numpy as np


def _spread(rng, total: int, weights: np.ndarray) -> np.ndarray:
    """`total` draws over len(weights) slots in proportion to the weights;
    how many each slot got."""
    cdf = np.cumsum(weights / weights.sum())
    at = np.searchsorted(cdf, rng.random(total) * cdf[-1], side="right")
    return np.bincount(np.minimum(at, len(weights) - 1),
                       minlength=len(weights))


def generate(params: dict, seed: int) -> dict:
    nv, ni, n_views, n_buys, n_cells = (int(params[k]) for k in (
        "n_visitors", "n_items", "n_views", "n_buys", "n_cells"))
    if not (max(nv, ni) <= n_cells <= n_views and n_buys <= n_cells):
        raise ValueError("the counts admit no log: need visitors and items "
                         "<= cells <= views, buys <= cells")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xEC0]))
    # cells a visitor has: one, and the rest in proportion to its activity
    activity = rng.lognormal(0.0, float(params["visitor_sigma"]), nv)
    per_visitor = 1 + _spread(rng, n_cells - nv, activity)
    if per_visitor.max() > ni:
        raise ValueError("a visitor has more cells than there are items")
    users = np.repeat(np.arange(nv, dtype=np.int64), per_visitor)
    # items: every item once, at cells drawn at random, the others by
    # popularity; a visitor's repeats of an item are drawn again until
    # none is left (an item so kept keeps its other cell)
    pop = 1.0 / np.arange(1, ni + 1) ** float(params["item_zipf"])
    cdf = np.cumsum(pop[rng.permutation(ni)])

    def by_popularity(n: int) -> np.ndarray:
        return np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1],
                                          side="right"), ni - 1)

    items = by_popularity(n_cells)
    items[rng.permutation(n_cells)[:ni]] = rng.permutation(ni)
    while True:
        key = users * ni + items
        order = np.argsort(key, kind="stable")
        again = order[1:][key[order[1:]] == key[order[:-1]]]
        if not len(again):
            break
        items[again] = by_popularity(len(again))
    # views of a cell: one, and the repeats by a weight of the cell
    repeat = rng.lognormal(0.0, float(params["repeat_sigma"]), n_cells)
    views = 1 + _spread(rng, n_views - n_cells, repeat)
    # buys: distinct cells, the more viewed the likelier (Gumbel top-k)
    key = np.log(views) + rng.gumbel(size=n_cells)
    bought = np.argpartition(-key, n_buys - 1)[:n_buys]
    bought = bought[rng.permutation(n_buys)]
    shuffle = rng.permutation(n_views)
    return {"n_users": nv, "n_items": ni, "n_cells": n_cells, "blocks": [
        {"event": "view", "users": np.repeat(users, views)[shuffle],
         "items": np.repeat(items, views)[shuffle]},
        {"event": "buy", "users": users[bought], "items": items[bought]}]}
