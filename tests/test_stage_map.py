"""The stage map: what the program notes of the programs it dispatches
(``utils.device.note_dispatch``), and what ``stage_maps()`` reads out of
their compiled modules: which stage of the ops (``utils.device.stage``)
each instruction a trace shows belongs to."""

import gc
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.utils import device


@pytest.fixture()
def fresh(monkeypatch):
    """A table of this test's own: the process's is every test's."""
    monkeypatch.setattr(device, "_noted", {})
    return device._noted


@jax.jit
def _toy(x, n):
    def body(_, acc):
        with device.stage("toy.scale"):
            y = acc * n
        with device.stage("toy.product"):
            return y @ x.T @ x
    return jnp.tanh(jax.lax.fori_loop(0, 3, body, x))


def _random_pairs(n_users, n_items, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, n).astype(np.int32),
            rng.integers(0, n_items, n).astype(np.int32))


def test_two_scopes_in_a_loop_and_an_unstaged_rest(fresh):
    x = jnp.ones((8, 8))
    device.noted(_toy, x, 0.5)
    got = device.stage_maps()["jit__toy"]
    assert set(got["stages"].values()) == {"toy.scale", "toy.product"}
    # the loop and the tanh after it are no stage's
    assert any(name.startswith("while") for name in got["unstaged"])
    assert got["instructions"] == len(got["stages"]) + len(got["unstaged"])
    assert not set(got["stages"]) & set(got["unstaged"])


def test_the_note_keeps_no_array_and_one_entry_a_signature(fresh):
    x = jnp.ones((8, 8))
    alive = weakref.ref(x)
    device.noted(_toy, x, 0.5)
    device.noted(_toy, jnp.zeros((8, 8)), 0.25)   # a float's value is no part
    assert len(fresh) == 1
    device.noted(_toy, jnp.ones((16, 8)), 0.5)    # another shape is
    assert len(fresh) == 2
    del x
    gc.collect()
    assert alive() is None
    for fn, args, kwargs, _ in fresh.values():
        assert all(isinstance(a, jax.ShapeDtypeStruct) for a in args)


def test_an_instruction_two_signatures_stage_differently_has_none(
        fresh, monkeypatch):
    maps = iter([("jit_p", {"fusion.1": "a.x", "fusion.2": "a.x", "copy": None}),
                 ("jit_p", {"fusion.1": "a.y", "fusion.2": "a.x", "copy": None,
                            "fusion.3": None})])
    monkeypatch.setattr(device, "_compiled_text", lambda *a: "")
    monkeypatch.setattr(device, "parse_stage_map", lambda *a: next(maps))
    fresh["one"], fresh["two"] = [_toy, (), {}, None], [_toy, (), {}, None]
    assert device.stage_maps() == {"jit_p": {
        "stages": {"fusion.2": "a.x"},
        "unstaged": ["copy", "fusion.1", "fusion.3"], "instructions": 4}}


HLO = """HloModule jit_f, is_scheduled=true

%region_0.1 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1)
}

%fused_inner (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %convert.5 = f32[8]{0} convert(%p.1)
  ROOT %mul.3 = f32[8]{0} multiply(%convert.5, %convert.5), metadata={op_name="jit(f)/while/body/cco.llr/mul"}
}

%fused_outer (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %fusion.7 = f32[8]{0} fusion(%p.2), kind=kLoop, calls=%fused_inner
}

ENTRY %main.1 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %constant.2 = f32[] constant(0)
  %broadcast.4 = f32[8]{0} broadcast(%constant.2), dimensions={}
  %fusion.80 = f32[8]{0} fusion(%broadcast.4, %x.1), kind=kCustom, calls=%fused_scatter, metadata={op_name="jit(f)/cco.densify_block/scatter-max"}
  %reshape.6 = f32[8]{0} reshape(%fusion.80)
  %copy.4 = f32[8]{0} copy(%reshape.6), metadata={op_name="jit(f)/while/body/closed_call"}
  %fusion.83 = (f32[8]{0}, f32[8]{0}) fusion(%reshape.6), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(f)/cco.densify_block/cco.count_matmul/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_outer
  %add.3 = f32[8]{0} add(%fusion.8, %x.1), metadata={op_name="jit(f)/while/body/add"}
  ROOT %reduce.2 = f32[] reduce(%add.3, %constant.2), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(f)/reduce_sum"}
}
"""


def test_the_three_rules_on_a_module_written_by_hand():
    stages = {"cco.llr", "cco.densify_block", "cco.count_matmul"}
    module, found = device.parse_stage_map(HLO, stages)
    assert module == "jit_f"
    assert found == {
        # the innermost stage of its own op_name
        "fusion.80": "cco.densify_block", "fusion.83": "cco.count_matmul",
        # a fusion without one: what it calls, through the fusion nested there
        "fusion.8": "cco.llr",
        # the compiler's own, no op_name: the zero fill by who reads it, the
        # re-laying copy by what it reads
        "broadcast.4": "cco.densify_block", "reshape.6": "cco.densify_block",
        # so is a copy, whatever its op_name says, if that names no stage
        "copy.4": "cco.densify_block",
        # an op_name with no stage in it: none
        "add.3": None, "reduce.2": None}


def test_a_resident_cco_job_maps_every_stage_it_declares(fresh, monkeypatch):
    from predictionio_tpu.ops import cco

    monkeypatch.delenv("PIO_PALLAS", raising=False)
    pu, pi = _random_pairs(300, 90, 2000, 1)
    au, ai = _random_pairs(300, 150, 3000, 2)
    cco._cco_resident(pu, pi, au, ai, 300, 90, 150, 7, 0.0, 64, False)
    maps = device.stage_maps()
    assert set(maps["jit__densify_global"]["stages"].values()) == {
        "cco.densify_primary"}
    assert set(maps["jit__primary_counts"]["stages"].values()) == {
        "cco.densify_primary"}
    assert set(maps["jit__cco_resident_all_tiles"]["stages"].values()) == {
        "cco.densify_tile", "cco.count_matmul", "cco.llr", "cco.topk_merge",
        "cco.topk_gather"}


def test_a_sharded_cco_job_maps_every_stage_and_the_exchange(fresh, monkeypatch):
    from predictionio_tpu.ops import cco
    from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh

    monkeypatch.delenv("PIO_PALLAS", raising=False)
    mesh = create_mesh(MeshSpec(dp=4, mp=1), devices=jax.devices()[:4])
    pu, pi = _random_pairs(300, 90, 2000, 8)
    au, ai = _random_pairs(300, 150, 3000, 9)
    cco._cco_resident(pu, pi, au, ai, 300, 90, 150, 7, 0.0, 64, False,
                      mesh=mesh)
    maps = device.stage_maps()
    assert set(maps["jit__densify_sharded"]["stages"].values()) == {
        "cco.densify_primary", "cco.exchange"}
    assert set(maps["jit__cco_sharded_all_tiles"]["stages"].values()) == {
        "cco.densify_tile", "cco.count_matmul", "cco.exchange", "cco.llr",
        "cco.topk_merge", "cco.topk_gather"}


def test_a_blocked_cco_job_maps_every_stage_it_declares(fresh, monkeypatch):
    from predictionio_tpu.ops import cco

    # the kernels interpreted: XLA's CPU fusion of the LLR's plain twin
    # into the merge's concatenate would be named by its root, the merge
    monkeypatch.setenv("PIO_PALLAS", "interpret")
    pu, pi = _random_pairs(300, 90, 2000, 3)
    au, ai = _random_pairs(300, 150, 3000, 4)
    p = cco.block_interactions(*cco.dedup_pairs(pu, pi, 90), 300, 90,
                               user_block=128)
    a = cco.block_interactions(*cco.dedup_pairs(au, ai, 150), 300, 150,
                               user_block=128)
    cco._cco_chunked(p, a, 300, top_k=7, item_tile=64)
    got = device.stage_maps()["jit__cco_chunked_all_tiles"]
    assert set(got["stages"].values()) == {
        "cco.densify_block", "cco.count_matmul", "cco.llr", "cco.topk_merge"}


def test_a_basket_job_maps_every_stage_it_declares(fresh, monkeypatch):
    from predictionio_tpu.ops import cco

    monkeypatch.delenv("PIO_PALLAS", raising=False)
    b, i = _random_pairs(500, 40, 4000, 5)
    cco.basket_rules(b, i, 500, 40, top_k=5, item_tile=16)
    got = device.stage_maps()["jit__basket_rules_tiled"]
    assert set(got["stages"].values()) == {
        "basket.densify", "basket.count_matmul", "basket.score",
        "cco.topk_merge", "cco.topk_gather"}


def test_an_als_job_maps_every_stage_it_declares(fresh):
    from predictionio_tpu.ops import als

    rng = np.random.default_rng(6)
    u, i = _random_pairs(40, 30, 500, 6)
    data = als.prepare_als_data(u, i, rng.random(500).astype(np.float32) * 4 + 1,
                                40, 30, dp=1)
    als.als_train(data, k=4, reg=0.1, iterations=2)
    maps = device.stage_maps()
    assert set(maps) == {"jit__als_run_single"}   # the init stays eager
    assert set(maps["jit__als_run_single"]["stages"].values()) >= {
        "als.gather", "als.normal_eq", "als.solve"}


def test_profile_to_leaves_the_map_beside_the_trace(fresh, tmp_path):
    from predictionio_tpu.utils.tracing import profile_to

    with profile_to(str(tmp_path)):
        device.noted(_toy, jnp.ones((8, 8)), 0.5).block_until_ready()
    got = json.loads((tmp_path / "stages.json").read_text())
    assert set(got["jit__toy"]["stages"].values()) == {"toy.scale",
                                                       "toy.product"}
    assert list(tmp_path.glob("**/*.xplane.pb"))


def test_no_job_lowers_or_compiles_for_the_map(fresh, tmp_path):
    """Two jobs of one shape after a warm-up: the second adds no compile;
    ``stage_maps()`` alone then adds to the tally, and opens no ``compile``
    span, not even under a journal that is active."""
    from predictionio_tpu.obs import spans
    from predictionio_tpu.ops import als

    device.watch_compiles()
    u, i = _random_pairs(50, 35, 700, 7)
    r = np.random.default_rng(7).random(700).astype(np.float32) * 4 + 1
    data = als.prepare_als_data(u, i, r, 50, 35, dp=1)

    def job(path):
        journal = spans.SpanJournal(path)
        with journal.activate():
            with spans.span("train"):
                als.als_train(data, k=3, reg=0.1, iterations=2)
        return journal.spans()

    def compiles(run):
        return [s for s in run if s["name"] == "compile"]

    job(tmp_path / "warm.jsonl")
    before = device.compile_stats()["programs"]
    first, second = job(tmp_path / "a.jsonl"), job(tmp_path / "b.jsonl")
    assert device.compile_stats()["programs"] == before
    assert not compiles(first) and not compiles(second)
    assert len(fresh) == 1          # the sweeps' program, once
    journal = spans.SpanJournal(tmp_path / "reader.jsonl")
    with journal.activate():
        with spans.span("reading_a_trace"):
            maps = device.stage_maps()
    assert device.compile_stats()["programs"] >= before + 1
    assert not compiles(journal.spans())
    assert "jit__als_run_single" in maps
    # read once a process: a second reading compiles nothing
    after = device.compile_stats()["programs"]
    assert device.stage_maps() == maps
    assert device.compile_stats()["programs"] == after


@pytest.mark.parametrize("stats,want", [
    # the TPU runtime's two books: live buffers and the programs' scratch
    ({"peak_bytes_in_use": 130, "peak_bytes_reserved": 9840}, 9970),
    ({"peak_bytes_in_use": 7}, 7),
    ({}, None), (None, None),          # the CPU backend keeps no such book
])
def test_peak_memory_is_in_use_plus_reserved(monkeypatch, stats, want):
    """What the journal's root attr says is what ``benchmark/run.py``
    reports as ``memory_peak_bytes``."""
    import types

    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(memory_stats=lambda: stats)] * 2)
    assert device.peak_memory_bytes() == [want, want]
