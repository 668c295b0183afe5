"""Operations and bytes one Universal Recommender train job needs, from the
configuration's shapes: what the work is, not how the program does it.

For each event type against the primary, the count matrix C = P^T A takes
2 * users * items_primary * items_type operations on 0/1 matrices (bf16 on
the MXU: the chip's bf16 peak is the right ceiling), then one elementwise
G^2 over C and a top-k.  Bytes: the densified primary read once per item
tile, each tile's densified slab written and read, and the count tile
written and read once (float32) before the scores reduce it to top-k.
"""

from __future__ import annotations

import math


def work(config: dict) -> dict:
    p = config["data"]["params"]
    users = math.ceil(int(p["n_users"]) / 128) * 128
    items = int(p["n_items"])
    tile = int(config["engine"]["algorithms"][0]["params"]["itemTile"])
    types = len(config["engine"]["datasource"]["params"]["eventNames"])
    tiles = math.ceil(items / tile)
    flops = types * 2.0 * users * items * items
    per_tile = (users * items * 2          # resident primary, bf16
                + 2 * users * tile * 2     # the tile's slab, written and read
                + 2 * items * tile * 4)    # the count tile, written and read
    return {"flops": flops, "bytes": float(types * tiles * per_tile),
            "calls": types * tiles}
