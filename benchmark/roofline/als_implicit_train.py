"""Operations and bytes one implicit ALS train job needs, from the
configuration's shapes (the E-Commerce Recommendation engine's cells).
Per sweep, both half-steps: per cell one rank x rank outer product
accumulated and one right-hand side (2 k^2 + 2 k, twice); the two Grams
Y^T Y and X^T X (2 n k^2 each, over the other side's rows); per solved
row a Cholesky factorisation (k^3 / 3) and two triangular solves (2 k^2).
Bytes: per cell and half-step one pass over the layout (two ids, a
strength and a mask, 16 B) and the gathered factor row (4 k); each Gram
reads its factor table once, and both tables are written once.
"""

from __future__ import annotations


def work(config: dict) -> dict:
    p = config["data"]["params"]
    a = config["engine"]["algorithms"][0]["params"]
    k, sweeps = int(a["rank"]), int(a["numIterations"])
    c, nu, ni = int(p["n_cells"]), int(p["n_visitors"]), int(p["n_items"])
    per_sweep_flops = 2 * c * (2 * k * k + 2 * k) + 2 * (nu + ni) * k * k \
        + (nu + ni) * (k ** 3 / 3 + 2 * k * k)
    per_sweep_bytes = 2 * c * (16 + 4 * k) + 2 * (nu + ni) * 4 * k
    return {"flops": float(sweeps * per_sweep_flops),
            "bytes": float(sweeps * per_sweep_bytes), "calls": 2 * sweeps}
