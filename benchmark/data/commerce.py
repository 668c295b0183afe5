"""Seeded buy/view events of an e-commerce catalogue.

A copy of the generator the repo already had three times (`bench.py`
`bench_http.commerce_events`, `chip_smoke.make_events`): users uniform,
every item bought once so that the catalogue IS `n_items` wide, the rest of
the buys Zipf(1.3) and the views Zipf(1.2), folded onto the catalogue.  One
change: the views cover the catalogue too, so that every seed gives the
trainer the same shapes (the number of distinct viewed items decides the
number of item tiles, and a seed that moved it would compile a new program
and do other work).
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int) -> dict:
    """`{"n_users", "n_items", "blocks": [{"event", "users", "items"}]}` —
    integer ids; the harness writes them as `u<id>` / `i<id>`."""
    nu, ni = int(params["n_users"]), int(params["n_items"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0]))
    blocks = []
    for name, n, a in (("buy", int(params["n_buy"]), float(params["zipf_buy"])),
                       ("view", int(params["n_view"]), float(params["zipf_view"]))):
        if n < ni:
            raise ValueError(f"{name}: {n} events cannot cover {ni} items")
        users = rng.integers(0, nu, n)
        # every user has one event of each type at least: n_users is the LLR
        # population and the height of the resident matrix
        users[rng.permutation(n)[:nu]] = np.arange(nu)
        items = np.concatenate([rng.permutation(ni), rng.zipf(a, n - ni) % ni])
        order = rng.permutation(n)
        blocks.append({"event": name, "users": users[order].astype(np.int64),
                       "items": items[order].astype(np.int64)})
    return {"n_users": nu, "n_items": ni, "blocks": blocks}
