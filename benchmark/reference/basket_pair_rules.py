"""Plain reference of the Complementary Purchase template's pair rules
(`maxRuleLength` 2): baskets from the events' times -> pair counts -> support,
confidence, lift under the three cuts -> each item's best rules by lift.

A basket is one shopper's `buy` events, each within `basketWindow` seconds of
the one before (a gap of exactly the window stays inside); a basket of fewer
than `minBasketSize` distinct items is dropped; N is the number kept.  For
items i != j, c_ij the baskets that hold both and c_i those that hold i:
support = c_ij / N, confidence(i -> j) = c_ij / c_i, lift = confidence /
(c_j / N).  A rule is kept at support >= `minSupport`, confidence >=
`minConfidence` and lift >= `minLift`; each condition item keeps its
`maxNumRulesPerCond` best by lift (equal lifts: the lower item first).

numpy and scipy.sparse in float64, on the host: the count matrix is sparse
(the pairs inside one basket), so every row of the catalogue is checked in
seconds.  It imports nothing of the program, forms the baskets itself from
`times` (the generator's `baskets` key is not read; its `n_baskets` only by
`baskets_gap`), and reads from the persisted model the two tables and the
dictionary that says which row is which item.

A rule ON a cut does not decide `correct`.  lift = c N / (c_i c_j) is exactly
1.0 for some integer counts, and the program's float32 may land on the other
side of `minLift` from float64 (so may a user's cut that no float holds).  A
cell whose float64 support, confidence or lift lies within `EDGE` (1e-6, a
few float32 roundings) of its cut, and clears the other cuts, is on the
edge: the reference takes it as kept if the program kept it and as cut if
not.  Every other cell is held exactly: a kept rule the reference cuts, or a
cut rule the reference keeps, reads `BIG`.

`dtype` is the precision the three ratios are computed in: float64 for the
reference, `ml_dtypes.bfloat16` for the control (the step below the float32
the program states).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

BIG = 1e30          # stands for "not there at all" in a JSON line
EDGE = 1e-6         # relative distance from a cut inside which a rule is free
US = 1_000_000


def params_of(engine: dict) -> dict:
    """The template's algorithm parameters, from the engine variant."""
    p = engine["algorithms"][0]["params"]
    return {"window_us": int(float(p["basketWindow"]) * US),
            "min_size": int(p.get("minBasketSize", 1)),
            "cuts": (float(p.get("minSupport", 0)),
                     float(p.get("minConfidence", 0)),
                     float(p.get("minLift", 0))),
            "k": int(p["maxNumRulesPerCond"])}


def baskets(block: dict, n_items: int, window_us: int, min_size: int):
    """The kept baskets as a 0/1 matrix [N, n_items], formed from the times."""
    users, items = np.asarray(block["users"]), np.asarray(block["items"])
    times = np.asarray(block["times"], np.int64)
    if not len(users):
        return sp.csr_matrix((0, n_items), dtype=np.int64)
    order = np.lexsort((times, users))
    u, t = users[order], times[order]
    new = np.ones(len(u), bool)
    new[1:] = (u[1:] != u[:-1]) | (t[1:] - t[:-1] > window_us)
    rows = np.cumsum(new) - 1
    B = sp.csr_matrix((np.ones(len(u), np.int64), (rows, items[order])),
                      shape=(int(rows[-1]) + 1, n_items))
    B.sum_duplicates()
    B.data[:] = 1
    return B[np.diff(B.indptr) >= max(min_size, 1)]


def cells(B, cuts: tuple, dtype=np.float64) -> dict:
    """Every pair i != j that shares a basket, by `key` = i * n_items + j
    (ascending): its `lift`, and where it stands against the cuts: `kept`
    (passes all three, in `dtype`), and, in float64 only, `edge`."""
    n_items = B.shape[1]
    C = (B.T @ B).tocsr()
    C.sort_indices()
    ci = C.diagonal().astype(np.float64)
    rows = np.repeat(np.arange(n_items), np.diff(C.indptr))
    cols = C.indices.astype(np.int64)
    off = rows != cols
    rows, cols, c = rows[off], cols[off], C.data[off].astype(np.float64)
    n = dtype(max(B.shape[0], 1))
    c_, ci_, cj_ = c.astype(dtype), ci[rows].astype(dtype), ci[cols].astype(dtype)
    support, confidence = c_ / n, c_ / ci_
    lift = confidence / (cj_ / n)
    values = (support, confidence, lift)
    kept = np.ones(len(c), bool)
    for v, cut in zip(values, cuts):
        kept &= v >= dtype(cut)
    out = {"key": rows * n_items + cols, "rows": rows, "cols": cols, "c": c,
           "ci": ci, "lift": lift.astype(np.float64), "kept": kept,
           "n": int(B.shape[0])}
    if dtype is np.float64:
        clear = np.ones(len(c), bool)       # passes every cut with room
        out_ = np.zeros(len(c), bool)       # fails some cut with room
        for v, cut in zip(values, cuts):
            clear &= v >= cut * (1 + EDGE)
            out_ |= v < cut * (1 - EDGE)
        out["edge"] = ~clear & ~out_
    return out


def best(rows, cols, lift, n_items: int, k: int):
    """(cols [n_items, k] with -1, lifts [n_items, k] with -inf): each
    row's k highest lifts, falling; equal lifts by the lower column."""
    order = np.lexsort((cols, -lift, rows))
    r = rows[order]
    rank = np.arange(len(r)) - np.searchsorted(r, r)
    top = rank < k
    at = (r[top], rank[top])
    top_cols = np.full((n_items, k), -1, np.int64)
    top_lift = np.full((n_items, k), -np.inf)
    top_cols[at], top_lift[at] = cols[order][top], lift[order][top]
    return top_cols, top_lift


def table(block: dict, n_items: int, p: dict, dtype=np.float64):
    """The reference in the shape the program persists: (idx, lift, ids)."""
    ref = cells(baskets(block, n_items, p["window_us"], p["min_size"]),
                p["cuts"], dtype)
    keep = ref["kept"]
    idx, lift = best(ref["rows"][keep], ref["cols"][keep], ref["lift"][keep],
                     n_items, p["k"])
    return idx, lift, np.arange(n_items, dtype=np.int64)


def _gap(a, b):
    """|a - b| against max(|b|, 1); a rule that one side lacks reads BIG."""
    both = np.isfinite(a) & np.isfinite(b)
    neither = ~np.isfinite(a) & ~np.isfinite(b)
    out = np.full(a.shape, BIG)
    out[both] = np.abs(a[both] - b[both]) / np.maximum(np.abs(b[both]), 1.0)
    out[neither] = 0.0
    return out


def compare(idx, lift, ids, data: dict, p: dict) -> dict:
    """`idx` [rows, K] (-1 padding) and `lift` [rows, K] as the program
    persisted them, `ids` the generator's item of each row and column code.

    lift_gap_max: worst |kept lift - reference's lift of that rule|;
    topk_gap_max: the reference's lifts of the kept rules, falling, against
    the reference's own k best (a missing or extra rule reads BIG);
    baskets_gap: |N the kept lifts imply - N the reference formed| + |that -
    the generator's|; rules / condition_items: what the reference keeps,
    short of the floors the generator passes on."""
    n_items, k = data["n_items"], p["k"]
    floors = data.get("floors", {})
    ref = cells(baskets(data["blocks"][0], n_items, p["window_us"],
                        p["min_size"]), p["cuts"])
    n_rules = int(ref["kept"].sum())
    n_cond = len(np.unique(ref["rows"][ref["kept"]]))
    got = {"rules_short": max(0, int(floors.get("rules", 0)) - n_rules),
           "condition_items_short": max(
               0, int(floors.get("condition_items", 0)) - n_cond)}
    ids = np.asarray(ids, np.int64)
    if len(ids) != n_items or sorted(ids.tolist()) != list(range(n_items)):
        return {**got, "lift_gap_max": BIG, "topk_gap_max": BIG,
                "baskets_gap": BIG}
    idx = np.asarray(idx)
    held = idx >= 0
    rows = np.broadcast_to(ids[:, None], idx.shape)
    cols = ids[np.where(held, idx, 0)]
    keys = rows * n_items + cols
    if not len(ref["key"]):                 # no two items share a basket
        return {**got, "lift_gap_max": BIG if held.any() else 0.0,
                "topk_gap_max": BIG if held.any() else 0.0,
                "baskets_gap": BIG}
    pos = np.minimum(np.searchsorted(ref["key"], keys), len(ref["key"]) - 1)
    found = held & (ref["key"][pos] == keys)
    # an edge rule is the program's to decide; any other the reference's
    taken = np.zeros(len(ref["key"]), bool)
    taken[pos[found]] = True
    accept = ref["kept"] & ~ref["edge"] | ref["edge"] & taken
    at = np.where(found & accept[pos], ref["lift"][pos], -np.inf)
    mine = np.where(held, np.asarray(lift, np.float64), -np.inf)
    got["lift_gap_max"] = float(_gap(mine, at)[held].max(initial=0.0))
    _, ref_top = best(ref["rows"][accept], ref["cols"][accept],
                      ref["lift"][accept], n_items, k)
    if at.shape[1] < k:
        at = np.pad(at, ((0, 0), (0, k - at.shape[1])),
                    constant_values=-np.inf)
    ordered = -np.sort(-at, axis=1)
    extra = np.isfinite(ordered[:, k:]).any()        # kept more than k
    got["topk_gap_max"] = BIG if extra else float(
        _gap(ordered[:, :k], ref_top[ids]).max(initial=0.0))
    # lift = c N / (c_i c_j): the N each kept lift implies
    sound = found & np.isfinite(mine)
    implied = (mine[sound] * ref["ci"][rows[sound]] * ref["ci"][cols[sound]]
               / ref["c"][pos[sound]])
    got["baskets_gap"] = float(
        abs(round(float(np.median(implied))) - ref["n"])
        + abs(ref["n"] - int(data["n_baskets"]))) if len(implied) else BIG
    return got


def _ids(strings) -> np.ndarray:
    return np.array([int(s[1:]) for s in strings], np.int64)


def check(model, data: dict, variant: dict, limits: dict, seed: int) -> list:
    got = compare(model.comp_idx, model.comp_lift,
                  _ids(model.item_dict.strings()), data, params_of(variant))
    return [{"name": k, "value": got[k], "limit": limits[k],
             "ok": got[k] <= limits[k]} for k in limits]


def _doubled(idx, lift, seed: int):
    """One answer altered: a copy of the lifts with the first kept rule of
    one row, drawn by the seed among the rows that keep a rule, doubled."""
    rows = np.flatnonzero(np.asarray(idx)[:, 0] >= 0)
    lift = np.array(lift)
    lift[int(rows[seed % min(97, len(rows))]), 0] *= 2
    return lift


def alter(model, seed: int) -> None:
    """The fault "one answer altered where it is produced", on the model as
    it is about to be persisted."""
    model.comp_lift = _doubled(model.comp_idx, model.comp_lift, seed)


def readings(config: dict, data: dict, seed: int, half) -> dict:
    """What `compare` reads with, in the program's place: the reference
    itself, the control (bfloat16 ratios), half of the events left out
    (`half(data)`), and one kept lift doubled.  `control.py` prints them."""
    import ml_dtypes

    p = params_of(config["engine"])
    block, ni = data["blocks"][0], data["n_items"]
    idx, lift, ids = table(block, ni, p)
    return {name: compare(*t, data, p) for name, t in (
        ("reference", (idx, lift, ids)),
        ("control_bfloat16", table(block, ni, p, ml_dtypes.bfloat16)),
        ("fault_half_left_out", table(half(data)["blocks"][0], ni, p)),
        ("fault_answer_altered", (idx, _doubled(idx, lift, seed), ids)))}
