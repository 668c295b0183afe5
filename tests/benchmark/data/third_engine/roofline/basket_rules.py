"""Least work of one basket-rules job (a throw-away test's roofline): the
square count BᵀB over N baskets x I items, 2·N·I² operations, and one read of
the densified baskets and one write of the int32 counts."""

from __future__ import annotations


def work(config: dict) -> dict:
    p = config["data"]["params"]
    n = int(p["n_users"]) * int(p["sessions"])
    i = int(p["n_items"])
    return {"flops": 2.0 * n * i * i, "bytes": 2.0 * n * i + 4.0 * i * i}
