"""Operations and bytes one explicit ALS train job needs, from the
configuration's shapes.  Per half-step and rating: one rank x rank outer
product accumulated (2 k^2) and one right-hand side (2 k); per solved row a
Cholesky factorisation (k^3 / 3) and two triangular solves (2 k^2).  Bytes:
one pass over the ratings (two ids and a value, 12 B) and the gathered
factor row (4 k) per rating, and both factor tables written once.
"""

from __future__ import annotations


def work(config: dict) -> dict:
    p = config["data"]["params"]
    a = config["engine"]["algorithms"][0]["params"]
    k, sweeps = int(a["rank"]), int(a["numIterations"])
    e, nu, ni = int(p["n_ratings"]), int(p["n_users"]), int(p["n_items"])
    per_sweep_flops = 2 * e * (2 * k * k + 2 * k) \
        + (nu + ni) * (k ** 3 / 3 + 2 * k * k)
    per_sweep_bytes = 2 * e * (12 + 4 * k) + (nu + ni) * 4 * k
    return {"flops": float(sweeps * per_sweep_flops),
            "bytes": float(sweeps * per_sweep_bytes), "calls": 2 * sweeps}
