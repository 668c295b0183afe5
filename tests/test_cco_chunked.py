"""The user-blocked tiled CCO program (`_cco_chunked_all_tiles`): the rule
that selects it, what it writes on its spans, and the engine trained
through it against the benchmark's plain reference
(`benchmark/reference/cco.py`, numpy/scipy float64)."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
UR_CONFIGS = {name: json.loads((BENCH / "configs" / f"{name}.json").read_text())
              for name in ("ur-ecom-100k", "ur-ecom-100k-u131k")}


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("mm", ["bf16", "int8"])
@pytest.mark.parametrize("config,resident", [("ur-ecom-100k", True),
                                             ("ur-ecom-100k-u131k", False)])
def test_rule_puts_each_ur_configuration_on_its_side(monkeypatch, config,
                                                     resident, mm):
    """32,768 x 100,000 keeps the densified primary resident, 131,072 x
    100,000 does not, whichever input type the count matmul takes."""
    from predictionio_tpu.ops import cco

    monkeypatch.setenv("PIO_CCO_MM_DTYPE", mm)
    p = UR_CONFIGS[config]["data"]["params"]
    tile = UR_CONFIGS[config]["engine"]["algorithms"][0]["params"]["itemTile"]
    assert cco._plan(p["n_users"], p["n_items"], p["n_items"], None, tile)[-1] \
        == ("resident" if resident else "chunked")


def test_rule_counts_the_plan_the_compiler_makes(monkeypatch):
    """Primary + the tile's slab + the float32 count tile + its scores:
    10.10 GB at the resident cell's shape, inside three quarters of a
    16 GB chip; the budget is the only number the rule is held to."""
    from predictionio_tpu.ops import cco

    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    users, items, tile = 32768, 100000, 4096
    plan = users * items * 2 + users * tile * 2 + 2 * items * tile * 4
    assert plan == pytest.approx(10.10e9, rel=0.002)
    assert cco._TILED_P_BYTES == 0.75 * 16e9
    monkeypatch.setattr(cco, "_TILED_P_BYTES", plan)

    def device_strategy(n_users):
        return cco._plan(n_users, items, items, None, tile)[-1]

    assert device_strategy(users) == "resident"
    monkeypatch.setattr(cco, "_TILED_P_BYTES", plan - 1)
    assert device_strategy(users) == "chunked"
    # the padded user rows are what is planned, not the users
    monkeypatch.setattr(cco, "_TILED_P_BYTES", plan)
    assert device_strategy(users - 100) == "resident"
    assert device_strategy(users + 1) == "chunked"


def test_blocks_carry_a_count_and_no_mask_array():
    """A block's valid slots are its first `count`; the nearly empty last
    block and an empty one pad with zeros."""
    from predictionio_tpu.ops.cco import (
        block_interactions, block_interactions_stream)

    user = np.array([0, 1, 1, 5, 9, 9, 9, 40], np.int32)
    item = np.array([3, 4, 4, 2, 7, 8, 9, 1], np.int32)
    for b in (block_interactions(user, item, 41, 12, user_block=8),
              block_interactions_stream([(user, item)], 41, 12, user_block=8)):
        assert b.n_blocks == 6 and b.count.dtype == np.int32
        assert b.count.tolist() == [4, 3, 0, 0, 0, 1]
        assert b.local_u.shape == b.item.shape == b.mask.shape == (6, 8)
        assert b.mask.sum(axis=1).tolist() == b.count.tolist()
        assert b.mask[:, 0].tolist() == [True, True, False, False, False, True]
        assert b.local_u[5, 0] == 0 and b.item[5, 0] == 1    # user 40 = 5*8+0
        assert not b.item[~b.mask].any() and not b.local_u[~b.mask].any()


# -- the step's shape: the user block and the tiles counted against it -------

U131K = (131072, 100000, 4096, 25)        # users, items, tile, tiles
GB = 10**9


def _plan_bytes(k, g, items, tile, in_bytes):
    """The accounting the derivation is held to, restated: G carried int32
    count tiles, the float32 tile and its scores, and the densified block
    with the group's slab three times over."""
    return (g + 2) * items * tile * 4 + 3 * k * (items + g * tile) * in_bytes


@pytest.mark.parametrize("mm,shape,budget,block,want", [
    # the blocked cell: four tiles against a block of 2,048 (11.26 GB);
    # five would leave 733 rows, and 4,096 rows leave three tiles
    ("bf16", U131K, 12 * GB, 0, (2048, 4)),
    ("int8", U131K, 12 * GB, 0, (4096, 4)),
    # an engine.json's userBlock is the block, and only the group is derived
    ("bf16", U131K, 12 * GB, 1024, (1024, 4)),
    ("bf16", U131K, 12 * GB, 4096, (4096, 3)),
    ("bf16", U131K, 12 * GB, 8192, (8192, 2)),
    ("int8", U131K, 12 * GB, 8192, (8192, 3)),
    # the resident cell's shape, were it ever sent here
    ("bf16", (32768, 100000, 4096, 25), 12 * GB, 0, (2048, 4)),
    ("int8", (32768, 100000, 4096, 25), 12 * GB, 0, (4096, 4)),
    # no room for two tiles: one, and the largest power of two that fits
    ("bf16", U131K, 7 * GB, 0, (2048, 1)),
    ("bf16", U131K, 5 * GB, 0, (128, 1)),
    # not even one: a group of one tile and one 128-row block all the same
    ("bf16", U131K, 1 * GB, 0, (128, 1)),
    # the suite's sizes: no block past the padded users, no group past the
    # tiles
    ("bf16", (899, 700, 256, 3), 12 * GB, 0, (1024, 3)),
    ("bf16", (70, 14, 8, 3), 12 * GB, 0, (128, 3)),
    ("bf16", (300, 90, 32, 5), 12 * GB, 64, (64, 5)),
])
def test_the_steps_shape_comes_from_the_bytes_the_plan_leaves(
        monkeypatch, mm, shape, budget, block, want):
    """`_block_plan` reads shapes, the input type and `_TILED_P_BYTES`: the
    most tiles a group can hold beside a block of `_BLOCK_ROWS` (or of the
    block given, or of every row), then the deepest power-of-two block
    beside them."""
    from predictionio_tpu.ops import cco

    monkeypatch.setenv("PIO_CCO_MM_DTYPE", mm)
    monkeypatch.setattr(cco, "_TILED_P_BYTES", budget)
    users, items, tile, tiles = shape
    k, g, plan_bytes = cco._block_plan(users, items, tile, tiles, block=block)
    assert (k, g) == want
    assert 1 <= g <= tiles and k <= max(block, cco._pad128(users))
    in_bytes = 1 if mm == "int8" else 2
    assert plan_bytes == _plan_bytes(k, g, items, tile, in_bytes)
    # only the smallest step there is may pass the budget
    assert plan_bytes <= budget or (k, g) == (block or 128, 1)
    if not block and k < cco._pad128(users):
        assert k & (k - 1) == 0         # the contraction is a power of two
        # neither a deeper block nor, at this depth, one more tile fits
        assert _plan_bytes(2 * k, g, items, tile, in_bytes) > budget
    if g < tiles and plan_bytes <= budget:
        assert _plan_bytes(block or min(k, cco._BLOCK_ROWS), g + 1, items,
                           tile, in_bytes) > budget


CP = (65536, 25 * 4096, 4096, 25)         # baskets, padded items, tile, tiles


@pytest.mark.parametrize("mm,shape,budget,f32_tiles,want", [
    # the basket cell: five tiles against a chunk of 2,048 (11.32 GB; six
    # would leave 414 rows), each chunk densified 5 times a job, not 25
    ("bf16", CP, 12 * GB, 1, (2048, 5)),
    # a step that held a float32 tile more would count one tile fewer
    ("bf16", CP, 12 * GB, 2, (2048, 4)),
    ("int8", CP, 12 * GB, 1, (4096, 5)),
    # one tile a step is the program as it was: its chunk of 8,192
    ("bf16", CP[:3] + (1,), 12 * GB, 1, (8192, 1)),
    # a chip half the size: one tile, and the deepest chunk beside it
    ("bf16", CP, 6 * GB, 1, (4096, 1)),
    # a shop of few baskets is one chunk of all of them, padded to 128
    ("bf16", (900,) + CP[1:], 12 * GB, 1, (1024, 5)),
    ("bf16", (5, 3, 3, 1), 12 * GB, 1, (128, 1)),
])
def test_the_basket_plan_is_the_same_derivation_without_a_slab(
        monkeypatch, mm, shape, budget, f32_tiles, want):
    """The basket program's step is `_block_plan`'s with no slab of its own
    (the group's is a slice of the chunk) and one float32 tile: G carried
    int32 tiles, the float32 tile, the densified chunk three times."""
    from predictionio_tpu.ops import cco

    monkeypatch.setenv("PIO_CCO_MM_DTYPE", mm)
    monkeypatch.setattr(cco, "_TILED_P_BYTES", budget)
    rows, width, tile, tiles = shape
    k, g, plan_bytes = cco._block_plan(rows, width, tile, tiles,
                                       own_slab=False, f32_tiles=f32_tiles)
    assert (k, g) == want
    in_bytes = 1 if mm == "int8" else 2
    assert plan_bytes == ((g + f32_tiles) * width * tile * 4
                          + 3 * k * width * in_bytes) <= budget


GROUPED = dict(n_users=1100, n_ip=90, n_it=150, tile=32, top_k=7)   # 5 tiles


@pytest.mark.parametrize("block,group,exclude_self", [
    (128, 2, False),    # 2 whole groups and a last one of 1; a last block of 76
    (128, 3, False),    # 1 whole group and a last one of 2
    (128, 5, False),    # every tile against one densify of each block
    (64, 2, True),      # 3 tiles, the diagonal masked in a group's second too
    (0, 2, False),      # the block derived: the power of two the bytes leave
])
def test_grouped_program_equals_the_ungrouped_one_to_the_bit(
        monkeypatch, block, group, exclude_self):
    """Several item tiles counted against one densified block give the
    counts one tile at a time gives, so scores and indices are equal to
    the bit: a ragged last group, a ragged last user block, `exclude_self`
    and an explicit `userBlock`, the budget alone deciding the group."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_CCO_DENSE", "0")
    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    monkeypatch.setattr(cco, "_BLOCK_ROWS", 256)    # a chip's 4,096, in small
    n_users, n_ip, n_it, tile, top_k = GROUPED.values()
    rng = np.random.default_rng(340 + group)
    pu, pi = rng.integers(0, n_users, 2500), rng.integers(0, n_ip, 2500)
    if exclude_self:
        n_it, (au, ai) = n_ip, (pu, pi)
    else:
        au, ai = rng.integers(0, n_users, 4000), rng.integers(0, n_it, 4000)
    n_tiles = -(-n_it // tile)

    def run(budget, user_block):
        monkeypatch.setattr(cco, "_TILED_P_BYTES", budget)
        with SpanCollector().activate() as collector:
            out = cco.cco_indicators_coo(
                pu, pi, au, ai, n_users, n_ip, n_it, top_k=top_k,
                user_block=user_block, item_tile=tile,
                exclude_self=exclude_self)
        (attrs,) = [s["attrs"] for s in collector.spans()
                    if s["name"] == "dispatch"]
        return out, attrs

    k = block or 256
    (s1, i1), one = run(0, k)
    assert (one["user_block"], one["tile_group"]) == (k, 1)
    assert one["block_steps"] == n_tiles * -(-n_users // k)
    (sg, ig), grouped = run(_plan_bytes(k, group, n_ip, tile, 2), block)
    assert grouped["program"] == "_cco_chunked_all_tiles"
    assert (grouped["user_block"], grouped["tile_group"]) == (k, group)
    assert grouped["plan_bytes"] == _plan_bytes(k, group, n_ip, tile, 2)
    assert grouped["tiles"] == n_tiles
    assert grouped["block_steps"] == -(-n_tiles // group) * -(-n_users // k)
    assert np.array_equal(s1, sg) and np.array_equal(i1, ig)
    assert (i1 >= 0).sum() > n_ip       # and there is something to compare


# -- the engine, trained through the chunked program, against the reference ---

SHAPE = dict(n_users=1923, n_items=700, n_buy=5000, n_view=9000,
             zipf_buy=1.3, zipf_view=1.2)
BLOCK, TILE, TOP_K = 128, 256, 10


def _variant(app):
    """ur-ecom-100k-u131k's engine.json at a small top-k, tile and block,
    on one device (the suite's CPU backend shows eight)."""
    variant = json.loads(json.dumps(UR_CONFIGS["ur-ecom-100k-u131k"]["engine"]
                                    ).replace("$app", app))
    variant["algorithms"][0]["params"].update(
        maxCorrelatorsPerItem=TOP_K, itemTile=TILE, userBlock=BLOCK, meshDp=1)
    return variant


@pytest.mark.parametrize("seed", [3, 4000000007])
def test_engine_through_the_chunked_program_agrees_with_the_reference(
        mem_storage, monkeypatch, seed):
    """1,923 users in blocks of 128 (the last holds 3), 700 items in tiles
    of 256 (the last holds 188) counted two to a group (the last group
    holds one), buy and view: `Engine.train` from the engine variant, the
    rule sending both event types to `_cco_chunked_all_tiles`, and every
    row of both persisted tables held against `benchmark/reference/cco.py`
    by the configuration's limits."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco
    from predictionio_tpu.storage import App
    from predictionio_tpu.workflow import create_workflow

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    # a chip three-thousandth the size: the rule, not a switch, decides
    monkeypatch.setattr(cco, "_TILED_P_BYTES", cco._TILED_P_BYTES // 3_000)
    monkeypatch.setattr(cco, "_DENSE_C_BYTES", cco._DENSE_C_BYTES // 100_000)
    users, items = SHAPE["n_users"], SHAPE["n_items"]
    assert cco._plan(users, items, items, None, TILE) == ("chunked",)
    # a shop that still fits
    assert cco._plan(64, 60, 60, None, 32) == ("resident",)
    # two tiles beside the engine's block of 128 fit this chip, three do not
    plan = cco._block_plan(users, items, TILE, 3, block=BLOCK)
    assert plan[:2] == (BLOCK, 2) and plan[2] <= cco._TILED_P_BYTES

    data = _bench_module("data", "commerce").generate(SHAPE, seed)
    app_id = mem_storage.apps.insert(App(0, "chunked"))
    wire = _bench_module("drivers", "train_jobs").wire_events
    for block in data["blocks"]:
        for r in mem_storage.l_events.insert_json_batch(list(wire(block)),
                                                        app_id):
            assert r["status"] == 201
    variant = _variant("chunked")
    _, engine, params = create_workflow.engine_from_variant(variant)
    with SpanCollector().activate() as collector:
        models = engine.train(params)

    spans = collector.spans()
    dispatched = [s["attrs"] for s in spans if s["name"] == "dispatch"]
    assert [d["program"] for d in dispatched] == ["_cco_chunked_all_tiles"] * 2
    n_blocks, n_tiles = -(-users // BLOCK), -(-items // TILE)
    for d in dispatched:
        assert d["tiles"] == n_tiles == 3 and d["topk"] == "pallas"
        assert (d["user_block"], d["tile_group"]) == (BLOCK, 2)
        assert d["plan_bytes"] == plan[2]
        # densify + count steps: groups x blocks, a whole group and one of 1
        assert d["block_steps"] == 2 * n_blocks == 32
        # the LLR kernel reads each tile in its group: 700 rows = two
        # blocks of 256 and one of 188
        assert d["llr_block"] == "256x256" and d["llr_edge_rows"] == 188
    assert [d["llr_mask"] for d in dispatched] == ["kernel", "none"]
    laid = [s["attrs"] for s in spans if s["name"] == "layout"
            and "user_blocks" in s.get("attrs", {})]
    assert len(laid) == 2
    events = {"buy": SHAPE["n_buy"], "view": SHAPE["n_view"]}
    for attrs, other in zip(laid, ("buy", "view")):
        assert attrs["user_blocks"] == n_blocks
        assert attrs["slots"] % n_blocks == 0
        assert attrs["slots"] - attrs["pad_slots"] == (
            events["buy"] + events[other])
        assert 0 < attrs["pad_slots"] < attrs["slots"]
    h2d = [s["attrs"]["bytes"] for s in spans if s["name"] == "h2d"]
    assert h2d == [4 * (2 * a["slots"] + 2 * n_blocks) for a in laid]

    limits = UR_CONFIGS["ur-ecom-100k-u131k"]["reference"]["limits"]
    checks = _bench_module("reference", "cco").check(
        models[0], data, variant, limits, seed)
    assert {c["name"] for c in checks} == set(limits)
    for c in checks:
        assert c["ok"], checks
