"""Operations and bytes one Complementary Purchase train job needs, from the
configuration's shapes: what the work is, not how the program does it.

The pair counts are C = B^T B over N kept baskets x I items: 2 * N * I * I
operations on 0/1 matrices (bf16 on the MXU: the chip's bf16 peak is the
right ceiling), then one elementwise pass of ratios and cuts over C and a
top-k.  Bytes: the densified baskets read once per item tile, each tile's
slab written and read, and the count tile written and read once (float32)
before the scores reduce it to top-k.  The tile is the algorithm's `itemTile`
where the engine variant sets one, else the program's 4,096.
"""

from __future__ import annotations

import math


def work(config: dict) -> dict:
    p = config["data"]["params"]
    baskets, items = int(p["n_kept"]), int(p["n_items"])
    tile = int(config["engine"]["algorithms"][0]["params"].get(
        "itemTile", 4096))
    tiles = math.ceil(items / tile)
    per_tile = (baskets * items * 2        # the densified baskets, bf16
                + 2 * baskets * tile * 2   # the tile's slab, written and read
                + 2 * items * tile * 4)    # the count tile, written and read
    return {"flops": 2.0 * baskets * items * items,
            "bytes": float(tiles * per_tile), "calls": tiles}
