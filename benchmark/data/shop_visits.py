"""Seeded shoppers' visits to an e-commerce catalogue, with their times: the
order log a Complementary Purchase deployment trains on.

`n_kept` visits buy two or more distinct items (2 + a geometric number, at
most `max_items`; `mean_items` exactly, so every seed makes `n_kept` *
`mean_items` + `n_single` events) and `n_single` visits buy one; a data
source that groups one shopper's buys by a window between `within_s` and
`between_s` forms exactly these visits, and one that drops baskets of fewer
than two items keeps `n_kept`.  The items of a visit: the first is a
Zipf(`zipf`) draw; each later one is, with probability one half, one of the
first item's `complements` (drawn uniformly from the catalogue by the seed, so
that rules above a shop's cuts exist), else a Zipf draw; repeats are drawn
again, so a visit's items are distinct.  The catalogue is `n_items` wide in
every seed: each item is bought at least once, the one-item visits (which no
rule reads) taking the first of a permutation of the catalogue and Zipf-drawn
later items of the larger visits the rest, as `commerce.py` covers its own.

Times: a shopper's buys within a visit lie 1..`within_s` seconds apart, the
last buy of a visit and the first of the shopper's next `between_s` seconds
or more.  `baskets` holds each event's visit number and is not written to the
store: a reference reads it only to say whether the program formed the
generator's baskets.  `floors` passes through: what a reference may hold the
size of its own table to.
"""

from __future__ import annotations

import numpy as np

US = 1_000_000


def _first_distinct(cand: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """bool [rows, W]: the first `sizes[r]` distinct values of row r, in
    stream order (what drawing again on a repeat gives)."""
    rows, width = cand.shape
    key = (np.arange(rows)[:, None] * (int(cand.max()) + 1) + cand).ravel()
    order = np.argsort(key, kind="stable")
    repeat = np.zeros(rows * width, bool)
    repeat[order[1:]] = key[order[1:]] == key[order[:-1]]
    fresh = ~repeat.reshape(rows, width)
    return fresh & (np.cumsum(fresh, 1) <= sizes[:, None])


def generate(params: dict, seed: int) -> dict:
    ni, nu = int(params["n_items"]), int(params["n_users"])
    n_kept, n_single = int(params["n_kept"]), int(params["n_single"])
    most, a = int(params["max_items"]), float(params["zipf"])
    n_comp = int(params["complements"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xB7]))
    complements = rng.integers(0, ni, (ni, n_comp))
    sizes = np.minimum(
        1 + rng.geometric(1.0 / (float(params["mean_items"]) - 1), n_kept),
        most)
    # the same number of events in every seed (a cell's rate counts them):
    # visits drawn by the seed give or take one item until the mean is exact
    short = int(n_kept * float(params["mean_items"])) - int(sizes.sum())
    able = np.flatnonzero(sizes < most if short > 0 else sizes > 2)
    sizes[rng.permutation(able)[:abs(short)]] += np.sign(short)
    # a stream of candidates a visit, the first a Zipf draw; long enough
    # that `most` distinct items are there (the head of a Zipf repeats)
    width = 8 * most
    zipf = rng.zipf(a, (n_kept, width)) % ni
    comp = complements[zipf[:, :1], rng.integers(0, n_comp, (n_kept, width))]
    from_zipf = rng.random((n_kept, width)) < 0.5
    from_zipf[:, 0] = True
    cand = np.where(from_zipf, zipf, comp)
    take = _first_distinct(cand, sizes)
    if (take.sum(1) != sizes).any():
        raise ValueError("a visit's candidates ran out of distinct items")
    visit, slot = np.nonzero(take)
    items = cand[visit, slot]
    # the catalogue covered: the one-item visits take the first of a
    # permutation, Zipf-drawn later items of the larger visits the rest
    cover = rng.permutation(ni)
    single_items = np.resize(cover, n_single)
    rest = cover[n_single:]
    free = np.flatnonzero(from_zipf[visit, slot] & (slot > 0))
    if len(rest) > len(free):
        raise ValueError(f"{len(rest)} items left to cover, {len(free)} "
                         "Zipf-drawn slots to put them in")
    at = rng.permutation(free)[:len(rest)]
    items[at] = rest
    for _ in range(64):      # a covering item its visit already holds: swap
        key = visit * ni + items
        order = np.argsort(key, kind="stable")
        twice = np.zeros(len(key), bool)
        twice[order[1:]] = key[order[1:]] == key[order[:-1]]
        clash = np.flatnonzero(twice | np.isin(key, key[twice]))
        clash = np.intersect1d(clash, at)
        if not len(clash):
            break
        other = rng.permutation(np.setdiff1d(at, clash))[:max(len(clash), 2)]
        ring = np.concatenate([clash, other])
        items[ring] = np.roll(items[ring], 1)
    else:
        raise ValueError("could not place the covering items distinctly")
    # visits to shoppers (each shopper comes at least once), then times
    n_visits = n_kept + n_single
    visit = np.concatenate([visit, n_kept + np.arange(n_single)])
    items = np.concatenate([items, single_items])
    if n_visits < nu:
        raise ValueError(f"{n_visits} visits cannot cover {nu} shoppers")
    shopper = np.concatenate([rng.permutation(nu),
                              rng.integers(0, nu, n_visits - nu)])
    shopper = shopper[rng.permutation(n_visits)]
    within, between = int(params["within_s"]), int(params["between_s"])
    by_shopper = np.argsort(shopper, kind="stable")
    nth = np.empty(n_visits, np.int64)     # a visit's number for its shopper
    s = shopper[by_shopper]
    first = np.concatenate([[0], np.flatnonzero(s[1:] != s[:-1]) + 1])
    nth[by_shopper] = np.arange(n_visits) - np.repeat(
        first, np.diff(np.concatenate([first, [n_visits]])))
    start = (nth * (most * within + between + 86_400)
             + rng.integers(0, 86_400, n_visits)) * US
    lag = rng.integers(1, within + 1, len(visit)) * US
    position = np.arange(len(visit)) - np.searchsorted(visit, visit)
    lag[position == 0] = 0
    ends = np.cumsum(lag)
    times = start[visit] + ends - ends[np.searchsorted(visit, visit)]
    order = rng.permutation(len(visit))
    block = {"event": "buy", "users": shopper[visit][order].astype(np.int64),
             "items": items[order].astype(np.int64),
             "times": times[order].astype(np.int64),
             "baskets": visit[order].astype(np.int64)}
    return {"n_users": nu, "n_items": ni, "n_baskets": n_kept,
            "n_single": n_single, "floors": params.get("floors", {}),
            "blocks": [block]}
