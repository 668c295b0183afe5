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


# -- the engine, trained through the chunked program, against the reference ---

SHAPE = dict(n_users=899, n_items=700, n_buy=5000, n_view=9000,
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
    """899 users in blocks of 128 (the last holds 3), 700 items in tiles of
    256 (the last holds 188), buy and view: `Engine.train` from the engine
    variant, the rule sending both event types to
    `_cco_chunked_all_tiles`, and every row of both persisted tables held
    against `benchmark/reference/cco.py` by the configuration's limits."""
    from predictionio_tpu.obs.spans import SpanCollector
    from predictionio_tpu.ops import cco
    from predictionio_tpu.storage import App
    from predictionio_tpu.workflow import create_workflow

    monkeypatch.setenv("PIO_CCO_SPARSE", "0")
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    # a chip a hundred-thousandth the size: the rule, not a switch, decides
    monkeypatch.setattr(cco, "_TILED_P_BYTES", cco._TILED_P_BYTES // 100_000)
    monkeypatch.setattr(cco, "_DENSE_C_BYTES", cco._DENSE_C_BYTES // 100_000)
    users, items = SHAPE["n_users"], SHAPE["n_items"]
    assert cco._plan(users, items, items, None, TILE) == ("chunked",)
    # a shop that still fits
    assert cco._plan(64, 60, 60, None, 32) == ("resident",)

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
        assert d["tiles"] == n_tiles and d["topk"] == "pallas"
        assert d["block_steps"] == n_tiles * n_blocks == 24
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
