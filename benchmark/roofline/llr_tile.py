"""Operations and bytes of the LLR pass over one job's count tiles: each
tile `[items_primary, tile]` float32 read once and its scores written once.
The four logarithms a cell takes are counted as 40 operations; on a chip
with 240 operations to the byte the pass is bound by bytes.  It describes
the work, so it reads the same for the Pallas kernel and for XLA's twin.
"""

from __future__ import annotations

import math


def work(config: dict) -> dict:
    p = config["data"]["params"]
    items = int(p["n_items"])
    tile = int(config["engine"]["algorithms"][0]["params"]["itemTile"])
    types = len(config["engine"]["datasource"]["params"]["eventNames"])
    calls = types * math.ceil(items / tile)
    cells = float(calls) * items * tile
    return {"flops": 40.0 * cells, "bytes": 8.0 * cells, "calls": calls}
