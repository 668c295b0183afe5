"""The P-resident tiled CCO program with the users sharded over `dp`
(`_densify_sharded`, `_cco_sharded_all_tiles`): against the one-device
program it is the same scan as, against the benchmark's plain reference
(`benchmark/reference/cco.py`, numpy/scipy float64), the shares against the
whole, the rule that selects it, what it writes on its spans, and the
engine trained through it with `meshDp` stated.  The suite's CPU backend
shows eight devices."""

import importlib.util
import json
from functools import partial
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mesh(dp):
    import jax

    return create_mesh(MeshSpec(dp=dp, mp=1), devices=jax.devices()[:dp])


def _events(n_users, n_items, n_buy, n_view, seed):
    """Skewed pairs with duplicates: no `dp x 128` divides 301 or 300."""
    rng = np.random.default_rng(seed)

    def draw(n):
        return (rng.integers(0, n_users, n).astype(np.int32),
                np.minimum(rng.zipf(1.3, n) - 1, n_items - 1).astype(np.int32))

    return draw(n_buy), draw(n_view)


def _resident(monkeypatch, kernels):
    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    monkeypatch.setenv("PIO_PALLAS", "interpret" if kernels == "pallas"
                       else "off")


@pytest.mark.parametrize("kernels", ["lax", "pallas"])
@pytest.mark.parametrize("dp", [2, 4, 8])
def test_sharded_program_is_the_one_device_program(monkeypatch, dp, kernels):
    """Buy against itself without the diagonal and view at a top-k of its
    own, 301 users over `dp` chips (the last range is short) and 300 items
    in tiles of 64 (the last holds 44): the same kept cells with the same
    float32 scores as one device gives (the counts are the same integers
    and the LLR the same operations), and both within float32 rounding of
    the float64 reference."""
    from predictionio_tpu.ops import cco

    _resident(monkeypatch, kernels)
    n_users, n_items, top_k = 301, 300, 7
    (bu, bi), (vu, vi) = _events(n_users, n_items, 2500, 4000, dp)
    others = [("buy", bu, bi, n_items), ("view", vu, vi, n_items)]
    kw = dict(top_k=top_k, exclude_self_for="buy", item_tile=64,
              per_type={"view": (4, 0.5)})
    mesh = _mesh(dp)
    assert cco._plan(n_users, n_items, n_items, mesh, 64) == ("resident",)
    one = cco.cco_train_indicators(bu, bi, others, n_users, n_items, **kw)
    got = cco.cco_train_indicators(bu, bi, others, n_users, n_items,
                                   mesh=mesh, **kw)
    for name, k in (("buy", top_k), ("view", 4)):
        s1, i1 = one[name]
        s, i = got[name]
        assert s.shape == i.shape == (n_items, k)
        np.testing.assert_array_equal(s, s1)
        for r in range(n_items):        # tie order aside, the same cells
            assert set(i[r][i[r] >= 0]) == set(i1[r][i1[r] >= 0]), (name, r)
        if name == "buy":
            assert not (i == np.arange(n_items)[:, None]).any()

    ref = _bench_module("reference", "cco")
    data = {"n_users": n_users, "n_items": n_items, "blocks": [
        {"event": "buy", "users": bu, "items": bi},
        {"event": "view", "users": vu, "items": vi}]}
    ids = np.arange(n_items)
    for name, k, llr in (("buy", top_k, 0.0), ("view", 4, 0.5)):
        s, i = got[name]
        gaps = ref.compare({name: (i, np.where(i >= 0, s, 0.0))}, ids,
                           {name: ids}, data, k, llr, "buy")
        assert gaps["score_gap_max"] < 1e-4 and gaps["topk_gap_max"] < 1e-4, (
            name, gaps)


@pytest.mark.parametrize("dp", [2, 4, 8])
def test_the_shares_add_up_to_the_whole_count_tile(monkeypatch, dp):
    """What ties a chip's share to the whole: the `dp` slabs of the primary
    are the one-device matrix cut by user range, the partial count tiles
    the chips compute from their slabs sum to the one-device count tile
    exactly, and so do the marginals (the program's own reduce-scattered
    row counts, and the tile's column counts)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.ops import cco

    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    n_users, n_items, tile, start = 301, 300, 64, 128
    (bu, bi), (vu, vi) = _events(n_users, n_items, 2500, 4000, 40 + dp)
    mesh = _mesh(dp)
    users_chip = -(-n_users // dp)
    n_rows, rows = cco._pad128(users_chip), cco._pad_items(n_items, dp)
    assert rows % (dp * 128) == 0 and rows >= n_items
    by_user = NamedSharding(mesh, P("dp"))
    p = cco._stage_chunked(bu, bi, users_chip, dp, by_user, by_chip=True)
    a = cco._stage_chunked(vu, vi, users_chip, dp, by_user, by_chip=True)
    Pm, rc = cco._densify_sharded(p.local_u, p.item, p.count, mesh=mesh,
                                  n_rows=n_rows, n_cols=rows)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"),) * 4,
             out_specs=(P("dp"), P("dp")))
    def partials(Pm, a_lu, a_it, a_cnt):
        local = a_it[0] - start
        in_tile = (cco._valid_slots(a_cnt, a_lu.shape[1])
                   & (local >= 0) & (local < tile))
        A_t = cco._densify_global(a_lu[0], jnp.where(in_tile, local, 0),
                                  in_tile, n_rows, tile)
        return (cco._count_matmul(Pm, A_t, "bf16")[None],
                cco._col_count(A_t)[None])

    c_parts, cc_parts = partials(Pm, a.local_u, a.item, a.count)
    assert c_parts.shape == (dp, rows, tile)

    whole_rows = cco._pad128(n_users)
    ones = jnp.ones(len(bu), bool)
    P1 = cco._densify_global(jnp.asarray(bu), jnp.asarray(bi), ones,
                             whole_rows, n_items)
    in_tile = (vi >= start) & (vi < start + tile)
    A1 = cco._densify_global(jnp.asarray(vu), jnp.asarray(
        np.where(in_tile, vi - start, 0)), jnp.asarray(in_tile), whole_rows,
        tile)
    c1 = np.asarray(cco._count_matmul(P1, A1, "bf16"))
    assert c1.max() > 1                        # counts, not a 0/1 matrix
    np.testing.assert_array_equal(np.asarray(c_parts).sum(0)[:n_items], c1)
    assert not np.asarray(c_parts)[:, n_items:].any()      # padding rows
    np.testing.assert_array_equal(np.asarray(cc_parts).sum(0),
                                  np.asarray(cco._col_count(A1)))
    np.testing.assert_array_equal(np.asarray(rc)[:n_items],
                                  np.asarray(cco._col_count(P1)))
    slabs = np.asarray(Pm.astype(jnp.float32)).reshape(dp, n_rows, rows)
    whole = np.asarray(P1.astype(jnp.float32))
    for d in range(dp):
        lo, hi = d * users_chip, min((d + 1) * users_chip, n_users)
        np.testing.assert_array_equal(slabs[d, :hi - lo, :n_items],
                                      whole[lo:hi])
        assert not slabs[d, hi - lo:].any()


def test_the_rule_on_a_mesh_counts_one_chips_share(monkeypatch):
    """131,072 x 100,000 is resident on four chips (each holds 32,768
    users' slab: 9.31 GB of the 12 GB budget) and on eight, not on two and
    not on one; shrinking the budget under a chip's share sends a mesh to
    `chunked`; and `mesh=None` answers as it did before meshes were asked."""
    from predictionio_tpu.ops import cco

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    monkeypatch.delenv("PIO_CCO_DENSE", raising=False)
    users, items, tile = 131072, 100000, 4096

    def strategy(mesh, n_users=users):
        return cco._plan(n_users, items, items, mesh, tile)[-1]

    assert [strategy(m) for m in (None, _mesh(2), _mesh(4), _mesh(8))] == [
        "chunked", "chunked", "resident", "resident"]
    rows = cco._pad_items(items, 4)
    assert rows == 100352
    share = (32768 * rows + 32768 * tile) * 2 \
        + (rows + 2 * rows // 4) * tile * 4
    assert share == pytest.approx(9.31e9, rel=0.002)
    monkeypatch.setattr(cco, "_TILED_P_BYTES", share)
    assert strategy(_mesh(4)) == "resident"
    assert strategy(_mesh(4), users + 1) == "chunked"    # a 129th row a chip
    monkeypatch.setattr(cco, "_TILED_P_BYTES", share - 1)
    assert strategy(_mesh(4)) == "chunked"
    # one device: the plan PR 25 measured, to the byte
    one = 32768 * items * 2 + 32768 * tile * 2 + 2 * items * tile * 4
    monkeypatch.setattr(cco, "_TILED_P_BYTES", one)
    assert strategy(None, 32768) == "resident"
    monkeypatch.setattr(cco, "_TILED_P_BYTES", one - 1)
    assert strategy(None, 32768) == "chunked"
    assert cco._pad_items(items, 1) == items


# -- the engine, trained through the sharded program ------------------------

CONFIG = json.loads((BENCH / "configs" / "ur-ecom-100k-u131k-dp4.json"
                     ).read_text())
SHAPE = dict(n_users=899, n_items=700, n_buy=5000, n_view=9000,
             zipf_buy=1.3, zipf_view=1.2)
TILE, TOP_K = 256, 10


def _variant(app, **params):
    variant = json.loads(json.dumps(CONFIG["engine"]).replace("$app", app))
    variant["algorithms"][0]["params"].update(
        maxCorrelatorsPerItem=TOP_K, itemTile=TILE, **params)
    return variant


def _train(storage, app, seed, **params):
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.storage import App
    from predictionio_tpu.workflow import create_workflow

    data = _bench_module("data", "commerce").generate(SHAPE, seed)
    app_id = storage.apps.insert(App(0, app))
    wire = _bench_module("drivers", "train_jobs").wire_events
    for block in data["blocks"]:
        for r in storage.l_events.insert_json_batch(list(wire(block)),
                                                    app_id):
            assert r["status"] == 201
    variant = _variant(app, **params)
    _, engine, ep = create_workflow.engine_from_variant(variant)
    with SpanCollector().activate() as collector:
        models = engine.train(ep)
    return data, variant, models, collector.spans()


def test_the_configuration_states_its_mesh():
    assert CONFIG["engine"]["algorithms"][0]["params"]["meshDp"] == 4
    assert CONFIG["reduced"] == []


@pytest.mark.parametrize("seed", [5, 4000000009])
def test_engine_with_mesh_dp_4_takes_the_sharded_program(
        mem_storage, monkeypatch, seed):
    """`ur-ecom-100k-u131k-dp4`'s engine.json at a small top-k and tile on
    the suite's eight CPU devices: `meshDp` 4 takes the first four (it
    raised "mesh 4x1 != 8 devices" before), both event types run the two
    sharded programs, the spans say what was laid out, handed over and
    exchanged, and both persisted tables hold against the reference by the
    configuration's limits."""
    _resident(monkeypatch, "pallas")
    data, variant, models, spans = _train(mem_storage, "dp4", seed)
    users, items = SHAPE["n_users"], SHAPE["n_items"]

    dispatched = [s["attrs"] for s in spans if s["name"] == "dispatch"]
    assert [d["program"] for d in dispatched] == [
        "_densify_sharded", "_cco_sharded_all_tiles"] * 2
    rows = 1024                       # 700 items in whole 128-row tiles x 4
    tiles = -(-items // TILE)
    for d in dispatched[1::2]:
        assert d["dp"] == 4 and d["tiles"] == tiles == 3
        assert d["rows_per_chip"] == rows // 4 and d["topk"] == "pallas"
        # three quarters of each float32 partial tile leave the chip
        assert d["exchange_mb"] == tiles * (rows * 3 // 4) * TILE * 4 / 1e6
        # a chip's 256 rows are whole blocks of the LLR kernel
        assert d["llr_block"] == "256x256" and d["llr_edge_rows"] == 0
    assert [d["llr_mask"] for d in dispatched[1::2]] == ["kernel", "none"]
    laid = [s["attrs"] for s in spans if s["name"] == "layout"
            and "dp" in s.get("attrs", {})]
    # buy against itself stages its pairs once; view stages the primary
    # again (the tiled strategies stage it once an event type) and itself
    staged = (SHAPE["n_buy"], SHAPE["n_buy"], SHAPE["n_view"])
    assert len(laid) == 3
    for attrs, n in zip(laid, staged):
        assert attrs["dp"] == 4 and attrs["users_per_chip"] == 225
        assert n / 4 <= attrs["events_max_chip"] < n / 4 * 1.25
        width = -(-attrs["events_max_chip"] // 8) * 8
        assert attrs["pad_events"] == 4 * width - n
    h2d = [s["attrs"]["bytes"] for s in spans if s["name"] == "h2d"]
    assert h2d == [4 * (a["pad_events"] + n) * 2 + 16
                   for a, n in zip(laid, staged)]
    assert sum(s["name"] == "device_wait" for s in spans) == 2

    limits = CONFIG["reference"]["limits"]
    checks = _bench_module("reference", "cco").check(
        models[0], data, variant, limits, seed)
    assert {c["name"] for c in checks} == set(limits)
    for c in checks:
        assert c["ok"], checks


def test_mesh_dp_above_the_devices_raises(mem_storage, monkeypatch):
    _resident(monkeypatch, "lax")
    with pytest.raises(ValueError, match="mesh 16x1 != 8 devices"):
        _train(mem_storage, "dp16", 5, meshDp=16)
