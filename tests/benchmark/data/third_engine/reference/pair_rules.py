"""Plain reference of pair rules (a throw-away test's): over N baskets,
support c_ij / N, confidence c_ij / c_i, lift confidence / (c_j / N); each
item keeps its `k` best rules by lift.  numpy, float64; `dtype` is the
precision of the ratios (the control: bfloat16 under the program's float32)."""
import numpy as np

BIG = 1e30


def lifts(block, n_items, cuts, dtype=np.float64):
    """[I, I] lifts of i -> j (-inf: cut by (support, confidence), or
    i = j), and the pair counts; a basket is a distinct `baskets` value."""
    rows = np.unique(block["baskets"], return_inverse=True)[1]
    B = np.zeros((rows.max() + 1, n_items))
    B[rows, block["items"]] = 1
    counts = B.T @ B
    c, ci, n = counts.astype(dtype), np.diag(counts).astype(dtype), dtype(len(B))
    conf = c / np.maximum(ci[:, None], dtype(1))
    lift = conf / np.maximum(ci[None, :] / n, dtype(1e-9))
    ok = (c / n >= cuts[0]) & (conf >= cuts[1]) & (c > 0)
    ok &= ~np.eye(n_items, dtype=bool)
    return np.where(ok, lift.astype(np.float64), -np.inf), counts


def table(block, n_items, k, cuts, dtype=np.float64):
    """The reference in the shape the program persists: (idx, lift, ids)."""
    lift = lifts(block, n_items, cuts, dtype)[0]
    idx = np.argsort(-lift, 1, kind="stable")[:, :k]
    top = np.take_along_axis(lift, idx, 1)
    return np.where(np.isfinite(top), idx, -1), top, np.arange(n_items)


def _gap(a, b):
    """|a - b| against max(|b|, 1); a rule that one side lacks reads BIG."""
    both, one = np.isfinite(a) & np.isfinite(b), np.isfinite(a) != np.isfinite(b)
    a, b = np.where(both, a, 0), np.where(both, b, 0)
    return np.where(one, BIG, np.abs(a - b) / np.maximum(np.abs(b), 1))


def compare(idx, lift, ids, data, k, cuts) -> dict:
    """Worst gap of a kept rule's lift from the reference's lift of that
    rule; of the reference's lifts of the kept rules, in falling order, from
    the reference's own k best; and |baskets the lifts imply - generator's|."""
    ref, counts = lifts(data["blocks"][0], data["n_items"], cuts)
    if sorted(ids.tolist()) != list(range(data["n_items"])):
        return {"lift_gap_max": BIG, "topk_gap_max": BIG, "baskets_gap": BIG}
    idx, kept = np.asarray(idx), np.asarray(idx) >= 0
    rows, cols = np.broadcast_to(ids[:, None], idx.shape), ids[idx * kept]
    at = np.where(kept, ref[rows, cols], -np.inf)
    mine = np.where(kept, np.asarray(lift, np.float64), -np.inf)
    c, ci = counts[rows, cols], np.diag(counts)
    n = (mine * ci[rows] * ci[cols] / np.maximum(c, 1))[kept & (c > 0)]
    return {"lift_gap_max": float(_gap(mine, at).max()),
            "topk_gap_max": float(_gap(-np.sort(-at, 1)[:, :k],
                                       -np.sort(-ref, 1)[ids, :k]).max()),
            "baskets_gap": float(abs(round(np.median(n)) - data["n_baskets"])
                                 if len(n) else BIG)}     # lift = c N / ci cj


def _params(engine):
    p = engine["algorithms"][0]["params"]
    return int(p["maxRulesPerItem"]), (p["minSupport"], p["minConfidence"])


def check(model, data, variant, limits, seed) -> list:
    ids = np.array([int(s[1:]) for s in model.item_dict.strings()], np.int64)
    got = compare(model.comp_idx, model.comp_lift, ids, data, *_params(variant))
    return [{"name": k, "value": got[k], "limit": limits[k],
             "ok": got[k] <= limits[k]} for k in limits]


def _doubled(idx, lift):
    """One answer altered: the first kept rule's lift doubled."""
    lift = np.array(lift)
    lift[np.nonzero(np.asarray(idx) >= 0)[0][0], 0] *= 2
    return lift


def alter(model, seed) -> None:
    model.comp_lift = _doubled(model.comp_idx, model.comp_lift)


def readings(config, data, seed, half) -> dict:
    import ml_dtypes

    k, cuts = _params(config["engine"])
    block, ni = data["blocks"][0], data["n_items"]
    idx, lift, ids = table(block, ni, k, cuts)
    return {name: compare(*t, data, k, cuts) for name, t in (
        ("reference", (idx, lift, ids)),
        ("control_bfloat16", table(block, ni, k, cuts, ml_dtypes.bfloat16)),
        ("fault_half_left_out", table(half(data)["blocks"][0], ni, k, cuts)),
        ("fault_answer_altered", (idx, _doubled(idx, lift), ids)))}
