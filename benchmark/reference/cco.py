"""Plain reference of correlated cross-occurrence (the Universal
Recommender's model): counts -> Dunning's G^2 -> the k best per row.

For a primary event type p and an event type t, over N users:
`C[i, j]` = users with p on item i and t on item j (each user counted once),
`r[i]`, `c[j]` the users of i under p and of j under t, and the score of a
cell with C > 0 is G^2 of the 2x2 table (C, r-C, c-C, N-r-c+C),
`2 * sum k * ln(k * N / (row * col))` (see `g2`).  Each primary item keeps its `top_k`
highest cells at or above the threshold, without itself where t is p.

numpy and scipy.sparse in float64, on the host: the tables are sparse (the
products of one user's items), so the whole table of every row is checked
in seconds and nothing is put on the chip.  It imports nothing of the
program, and reads from the persisted model only what is compared (the two
tables) and the two dictionaries that say which row is which item.

`dtype` is the precision G^2 is computed in: float64 for the reference,
`ml_dtypes.bfloat16` for the control (the step below the float32 the
program's LLR states).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

BIG = 1e30          # stands for "not there at all" in a JSON line


def g2(k11, k12, k21, k22, dtype=np.float64):
    """Dunning's G^2 of 2x2 tables, every step in `dtype`.

    `2 * sum k_ij * ln(k_ij * N / (r_i * c_j))`, with each logarithm written
    as `log1p((k_ij * N - r_i * c_j) / (r_i * c_j))`: for a 2x2 table the
    numerator is plus or minus the determinant D = k11 k22 - k12 k21, so no
    term is the small difference of two large ones.  (In a low precision
    the plain form loses everything; this one is what a lower-precision
    LLR would sensibly compute, so the control reads no worse than it has
    to.)"""
    k = [np.asarray(a).astype(dtype) for a in (k11, k12, k21, k22)]
    r = [k[0] + k[1], k[2] + k[3]]
    c = [k[0] + k[2], k[1] + k[3]]
    d = k[0] * k[3] - k[1] * k[2]
    zero, one = np.asarray(0, dtype), np.asarray(1, dtype)
    eps = one
    while one + eps / 2 != one:      # the type's own epsilon
        eps = eps / 2
    floor = -one + eps               # k N / (r c) > 0: keep log1p off its pole
    total = np.zeros(k[0].shape, dtype)
    for kij, ri, cj, sign in ((k[0], r[0], c[0], 1), (k[1], r[0], c[1], -1),
                              (k[2], r[1], c[0], -1), (k[3], r[1], c[1], 1)):
        safe = np.where(kij > 0, ri * cj, one)
        arg = np.maximum(np.asarray(sign, dtype) * d / safe, floor)
        total = total + np.where(kij > 0, kij * np.log1p(arg), zero)
    return np.maximum(np.asarray(2, dtype) * total, zero).astype(np.float64)


def _binary(users, items, n_users: int, n_items: int):
    m = sp.csr_matrix((np.ones(len(users), np.int64), (users, items)),
                      shape=(n_users, n_items))
    m.sum_duplicates()
    m.data[:] = 1
    return m


def indicators(primary: dict, other: dict, n_users: int, n_items: int,
               top_k: int, threshold: float, exclude_self: bool,
               dtype=np.float64) -> dict:
    """The cells of one event type against the primary, by generator ids:
    `keys` (row * n_items + col, ascending), `scores`, and `top` [n_items,
    top_k], each row's best scores in falling order, -inf where it has
    fewer."""
    P = _binary(primary["users"], primary["items"], n_users, n_items)
    A = P if other is primary else _binary(other["users"], other["items"],
                                           n_users, n_items)
    C = (P.T @ A).tocsr()
    C.sort_indices()
    rows = np.repeat(np.arange(n_items), np.diff(C.indptr))
    cols = C.indices.astype(np.int64)
    k11 = C.data.astype(np.float64)
    r = np.asarray(P.sum(0)).ravel().astype(np.float64)[rows]
    c = np.asarray(A.sum(0)).ravel().astype(np.float64)[cols]
    scores = g2(k11, r - k11, c - k11, n_users - r - c + k11, dtype)
    keep = scores >= threshold
    if exclude_self:
        keep &= rows != cols
    rows, cols, scores = rows[keep], cols[keep], scores[keep]
    order = np.lexsort((-scores, rows))
    first = np.searchsorted(rows[order], np.arange(n_items))
    rank = np.arange(len(order)) - first[rows[order]]
    best = rank < top_k
    at = (rows[order][best], rank[best])
    top = np.full((n_items, top_k), -np.inf)
    top[at] = scores[order][best]
    top_cols = np.full((n_items, top_k), -1, np.int64)
    top_cols[at] = cols[order][best]
    return {"keys": rows * n_items + cols, "scores": scores, "top": top,
            "top_cols": top_cols}


def _ids(strings) -> np.ndarray:
    return np.array([int(s[1:]) for s in strings], np.int64)


def _gap(a, b):
    """|a - b| against max(|b|, 1); a cell that one side lacks reads BIG."""
    both = np.isfinite(a) & np.isfinite(b)
    neither = ~np.isfinite(a) & ~np.isfinite(b)
    out = np.full(a.shape, BIG)
    out[both] = np.abs(a[both] - b[both]) / np.maximum(np.abs(b[both]), 1.0)
    out[neither] = 0.0
    return out


def compare(tables: dict, row_ids, col_ids: dict, data: dict, top_k: int,
            threshold: float, primary_name: str, dtype=np.float64) -> dict:
    """`tables[name] = (idx [rows, K] with -1 padding, llr [rows, K])` as
    the program persisted them, `row_ids` / `col_ids[name]` the generator
    id of each row / column code.  The two numbers, worst over the event
    types: how far a kept score lies from the reference's score of the same
    cell, and how far the reference's scores of the kept cells, in falling
    order, lie from the reference's own best."""
    blocks = {b["event"]: b for b in data["blocks"]}
    n_users, n_items = data["n_users"], data["n_items"]
    score_gap = topk_gap = 0.0
    if len(row_ids) != n_items or sorted(row_ids.tolist()) != list(
            range(n_items)):
        return {"score_gap_max": BIG, "topk_gap_max": BIG}
    for name, (idx, llr) in tables.items():
        ref = indicators(blocks[primary_name], blocks[name], n_users, n_items,
                         top_k, threshold, name == primary_name, dtype)
        idx = np.asarray(idx)
        kept = idx >= 0
        cols = col_ids[name][np.where(kept, idx, 0)]
        keys = row_ids[:, None] * n_items + cols
        pos = np.minimum(np.searchsorted(ref["keys"], keys),
                         len(ref["keys"]) - 1)
        found = kept & (ref["keys"][pos] == keys)
        at = np.where(found, ref["scores"][pos], -np.inf)
        mine = np.where(kept, np.asarray(llr, np.float64), -np.inf)
        score_gap = max(score_gap, float(_gap(mine, at)[kept].max(initial=0)))
        width = ref["top"].shape[1]
        if at.shape[1] < width:
            at = np.pad(at, ((0, 0), (0, width - at.shape[1])),
                        constant_values=-np.inf)
        ordered = -np.sort(-at, axis=1)
        extra = np.isfinite(ordered[:, width:]).any()   # kept more than top_k
        topk_gap = max(topk_gap, BIG if extra else float(
            _gap(ordered[:, :width], ref["top"][row_ids]).max()))
    return {"score_gap_max": score_gap, "topk_gap_max": topk_gap}


def check(model, data: dict, variant: dict, limits: dict, seed: int) -> list:
    params = variant["algorithms"][0]["params"]
    names = variant["datasource"]["params"]["eventNames"]
    got = compare(
        {n: (model.indicator_idx[n], model.indicator_llr[n])
         for n in model.indicator_idx},
        _ids(model.item_dict.strings()),
        {n: _ids(d.strings()) for n, d in model.event_item_dicts.items()},
        data, int(params["maxCorrelatorsPerItem"]),
        float(params.get("minLlr", 0.0)), names[0])
    missing = [n for n in names if n not in model.indicator_idx]
    return [{"name": k, "value": BIG if missing else v, "limit": limits[k],
             "ok": not missing and v <= limits[k]} for k, v in got.items()]


def control_tables(data: dict, top_k: int, threshold: float,
                   primary_name: str, dtype) -> tuple:
    """The reference in `dtype`, in the shape the program persists: what
    `compare` is given when the control stands in the program's place."""
    blocks = {b["event"]: b for b in data["blocks"]}
    n = data["n_items"]
    tables = {}
    for name in blocks:
        ref = indicators(blocks[primary_name], blocks[name], data["n_users"],
                         n, top_k, threshold, name == primary_name, dtype)
        tables[name] = (ref["top_cols"],
                        np.where(ref["top_cols"] >= 0, ref["top"], 0.0))
    ids = np.arange(n, dtype=np.int64)
    return tables, ids, {name: ids for name in blocks}


def _moved(idx: np.ndarray, seed: int, n_items: int) -> np.ndarray:
    """One answer altered: a copy of a table's kept columns with the first
    kept cell of one row, drawn by the seed among the rows that keep two or
    more, moved to another column."""
    rows = np.flatnonzero((idx >= 0).sum(1) >= 2)
    row = int(rows[seed % min(97, len(rows))])
    idx = idx.copy()
    idx[row, 0] = (idx[row, 0] + 1 + row) % n_items
    return idx


def alter(model, seed: int) -> None:
    """The fault "one answer altered where it is produced", on the model as
    it is about to be persisted: one kept cell of the primary table moved."""
    model.indicator_idx[model.primary_event] = _moved(
        model.indicator_idx[model.primary_event], seed, len(model.item_dict))


def readings(config: dict, data: dict, seed: int, half) -> dict:
    """What `compare` reads with, in the program's place: the reference
    itself, the control (bfloat16), half of the events left out
    (`half(data)`), and one kept cell moved.  `control.py` prints them."""
    import ml_dtypes

    algo = config["engine"]["algorithms"][0]["params"]
    k, thr = int(algo["maxCorrelatorsPerItem"]), float(algo.get("minLlr", 0))
    primary = config["engine"]["datasource"]["params"]["eventNames"][0]

    def held(tables, rows, cols):
        return compare(tables, rows, cols, data, k, thr, primary)

    tables, rows, cols = control_tables(data, k, thr, primary, np.float64)
    out = {"reference": held(tables, rows, cols)}
    out["control_bfloat16"] = held(*control_tables(
        data, k, thr, primary, ml_dtypes.bfloat16))
    out["fault_half_left_out"] = held(*control_tables(
        half(data), k, thr, primary, np.float64))
    idx, llr = tables[primary]
    tables[primary] = (_moved(idx, seed, data["n_items"]), llr)
    out["fault_answer_altered"] = held(tables, rows, cols)
    return out
