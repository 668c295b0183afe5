"""The selection and LLR kernels compiled for a described TPU v5e at the
shapes the benchmark's cells run, with no chip: Mosaic refuses here what it
would refuse there (a layout it cannot turn, too much VMEM), which the
interpreter never sees.  Nothing runs, so nothing here is a speed.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time; see the on-chip-measurement
guide), and every test of this kind lives in this one file."""

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,width,b", [
    (100_000, 4096, 64),    # ur-ecom-100k, ur-ecom-100k-u131k: a tile, top 50
    (102_400, 4096, 8),     # cp-ecom-100k: a tile, top 5
    (1_000, 200, 16),       # fewer rows than a block, a padded width
])
def test_tile_topk_compiles_for_a_v5e(one_chip, rows, width, b):
    from predictionio_tpu.ops import pallas_kernels as pk

    scores = jax.ShapeDtypeStruct((rows, width), jnp.float32,
                                  sharding=one_chip)
    compiled = pk._tile_topk_padded.lower(scores, b, False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert [o.shape for o in jax.eval_shape(
        lambda s: pk._tile_topk_padded(s, b, True), scores)] == [(rows, b)] * 2


@pytest.mark.parametrize("rows,width,group,dtype", [
    (100_000, 4096, 1, jnp.float32),   # ur-ecom-100k: a resident tile
    (100_000, 4096, 4, jnp.int32),     # u131k: a tile of the carried group
    (25_088, 4096, 1, jnp.float32),    # the four-chip cell: a chip's rows
    # fewer rows than a block; a window no 128-wide block divides
    (37, 200, 3, jnp.int32),
])
def test_llr_compiles_for_a_v5e(one_chip, monkeypatch, rows, width, group,
                                dtype):
    """The LLR kernel at the cells' shapes, masked: the program is the
    custom call on the counts as they lie, the tile read in its group, and
    the scores at their own shape; no pad, slice or conversion of the
    tile around it."""
    import re

    from predictionio_tpu.ops import cco, pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def of(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def llr(c, row, col, start, diagonal):
        return cco._llr_mask_scores(c, row, col, 1e6, 0.0, "compiled",
                                    col_start=start, width=width,
                                    diagonal=diagonal)

    scalar = of((), jnp.int32)
    compiled = jax.jit(llr).lower(
        of((rows, group * width), dtype), of((rows,), dtype),
        of((group * width,), dtype), scalar, scalar).compile()
    text = compiled.as_text()
    assert re.search(rf"%_llr_padded[.0-9]* = f32\[{rows},{width}\]", text)
    aligned = width % 128 == 0
    tile_shaped = rf"\[{rows},{width}\]\S* (pad|slice|dynamic-slice|convert)\("
    assert (re.search(tile_shaped, text) is None) == aligned
    assert "f32[100096" not in text


def _held_bytes(compiled) -> int:
    """The TPU compiler's memory plan of one program: arguments,
    temporaries and outputs."""
    memory = compiled.memory_analysis()
    return (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes)


def test_blocked_cco_program_fits_a_v5e(one_chip, monkeypatch):
    """The user-blocked program at ur-ecom-100k-u131k's shape, under the
    step `_block_plan` derives for it: the TPU compiler's own memory plan
    stays inside the 14 GB the cell is held to.  (It plans more than the
    derivation counts and than the chip then holds: PERF.md section 4.
    A Python loop over a group's tiles in place of the `fori_loop` made
    it 18 GB.)"""
    from predictionio_tpu.ops import cco, pallas_kernels as pk

    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    users, items, tile, tiles = 131072, 100000, 4096, 25
    block, group, plan_bytes = cco._block_plan(users, items, tile, tiles)
    assert plan_bytes <= cco._TILED_P_BYTES
    n_blocks = users // block

    def slots(events):      # a block's share of the events, and some
        return jax.ShapeDtypeStruct((n_blocks, events // n_blocks // 8 * 9),
                                    jnp.int32, sharding=one_chip)

    count = jax.ShapeDtypeStruct((n_blocks,), jnp.int32, sharding=one_chip)
    buy, view = slots(1_600_000), slots(3_200_000)
    compiled = cco._cco_chunked_all_tiles.lower(
        buy, buy, count, view, view, count, float(users), n_tiles=tiles,
        group=group, block=block, n_items_p=items, tile=tile, top_k=50,
        llr_threshold=0.0, pallas="compiled", exclude_self=False,
        topk="pallas").compile()
    assert _held_bytes(compiled) < 14e9
    assert "tpu_custom_call" in compiled.as_text()


def test_basket_program_fits_a_v5e(one_chip, monkeypatch):
    """The basket program at cp-ecom-100k's shape, under the step
    `_block_plan` derives for it (a chunk of 2,048, five tiles a group):
    the TPU compiler's memory plan stays inside 14 GB.  Its buffers are the
    carried group and ONE float32 tile, the densified chunk's copies in
    the tile's place while the chunks are counted (10.07 GB); the plan it
    reports reads a tile more (11.76 GB) [AOT, PR 36]."""
    from predictionio_tpu.ops import cco, pallas_kernels as pk

    monkeypatch.delenv("PIO_CCO_MM_DTYPE", raising=False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    baskets, tile, tiles = 65536, 4096, 25      # 100,000 items in 25 tiles
    width = tiles * tile
    chunk, group, plan_bytes = cco._block_plan(
        baskets, width, tile, tiles, own_slab=False, f32_tiles=1)
    assert (chunk, group) == (2048, 5) and plan_bytes <= cco._TILED_P_BYTES
    n_chunks = baskets // chunk

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # a chunk's share of the 394,000 pairs of the baskets kept, and some
    log = of((n_chunks, 394_000 // n_chunks // 8 * 9), jnp.int32)
    scalar = of((), jnp.float32)
    compiled = cco._basket_rules_tiled.lower(
        log, log, of((n_chunks,), jnp.int32), scalar, of((width,), jnp.float32),
        chunk=chunk, n_tiles=tiles, group=group, tile=tile, top_k=5,
        min_support=scalar, min_confidence=scalar, min_lift=scalar,
        topk="pallas", mm="bf16").compile()
    assert _held_bytes(compiled) < 14e9
    assert "tpu_custom_call" in compiled.as_text()


def test_implicit_als_program_fits_a_v5e(one_chip):
    """`_als_run_single(implicit=True)` at ecom-retailrocket's shape (1.4M
    visitors, 235k items, 2M cells, rank 10), under the solve blocks
    `_solve_plan` derives: the TPU compiler's plan stays inside the 15.75
    GB a v5e gives a program, where one block of every visitor's systems
    asked the compiler for 21.64 GB.  And the implicit Gram is multiplied
    at HIGHEST, not in one bfloat16 pass, in partial Grams that XLA has not
    folded back into one contraction over the whole table."""
    import re

    from predictionio_tpu.ops import als

    users, items, cells, k = 1_407_580, 235_061, 2_000_000, 10
    blocks = als._solve_plan(users, items, k)
    assert blocks[0] < users and blocks[1] == items

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    side = [of((1, cells), jnp.int32), of((1, cells), jnp.int32),
            of((1, cells), jnp.float32), of((1, cells), jnp.float32)]
    scalar = of((), jnp.float32)
    compiled = als._als_run_single.lower(
        of((1, users, k), jnp.float32), of((1, items, k), jnp.float32),
        of((), jnp.int32), scalar, scalar, *side, *side, implicit=True,
        blocks=blocks).compile()
    assert _held_bytes(compiled) < 15.75e9
    # the partial Grams of every _GRAM_ROWS rows, one contraction a half-step
    partials = -(-users // als._GRAM_ROWS), -(-items // als._GRAM_ROWS)
    grams = [ln for ln in compiled.as_text().splitlines()
             if re.search(r"= f32\[(%d|%d),10,10\]\S* (convolution|dot)\("
                          % partials, ln)]
    assert len(grams) == 2
    assert all("operand_precision={highest,highest}" in ln for ln in grams)
