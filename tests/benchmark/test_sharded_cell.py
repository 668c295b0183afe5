"""The four-chip cell `ur-ecom-100k-u131k-dp4.train`: its three metrics in
a traced rehearsal on four CPU devices, its roofline module against
`cco_train`, the reader of collective operations on lines as a TPU trace
and a CPU trace print them, and that the parent's program (no sharded
program, no `exchange_mb`) gives these readers nothing and no error."""

import json

import pytest

from bench_helpers import (BENCH, ROOT, load_harness, rehearsal_config,
                           rehearsal_result, run_cell)

H = load_harness()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ur-ecom-100k-u131k-dp4.train"
OWN = {"cco_sharded_program_ms", "cco_exchange_ms", "cco_exchange_mb_per_job"}
COMMON = {"host_lead_in_s", "host_tail_s", "train_mfu_pct",
          "device_idle_pct.train", "store_read_s", "host_layout_s", "h2d_s",
          "h2d_mb_per_job", "dispatch_s", "device_wait_s", "model_build_s",
          "persist_s", "train_unattributed_s"}


def test_the_cell_asks_for_four_chips_and_reports_sixteen_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "train-jobs"
    mine = {m["name"] for m in H.metrics_of(MANIFEST, CELL, "per_layer")}
    assert mine == OWN | COMMON
    for m in MANIFEST["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL]
    # a chip's kernel sees a quarter of what llr_tile counts for a tile
    assert "llr_roofline" not in mine and "cco_program_ms" not in mine


def test_the_configuration_is_the_one_chip_cells_store_with_a_mesh():
    one = json.loads((BENCH / "configs" / "ur-ecom-100k-u131k.json"
                      ).read_text())
    four = json.loads((BENCH / "configs" / "ur-ecom-100k-u131k-dp4.json"
                       ).read_text())
    assert four["data"] == one["data"]            # the same seed, one store
    assert four["reference"]["module"] == one["reference"]["module"]
    assert four["reference"]["limits"] == one["reference"]["limits"]
    params = dict(four["engine"]["algorithms"][0]["params"])
    assert params.pop("meshDp") == 4
    assert params == one["engine"]["algorithms"][0]["params"]
    assert four["rehearsal"]["env"]["XLA_FLAGS"].endswith("device_count=4")
    assert "_plan" in four["rehearsal"]["note"]
    assert "_resident_p_ok" not in json.dumps(four)


def test_the_roofline_is_one_chips_share_of_cco_train():
    """`train_mfu_pct` divides by one chip's peaks, so its module divides
    the job's work by `meshDp`: 2 x 2 x 131,072 x 100,000^2 / 4 operations
    = 6.65 s at 197 TFLOP/s, where `cco_train` gives 26.6 s."""
    config = H.find_cell(MANIFEST, CELL)[1]
    assert config["roofline"] == "cco_train_sharded"
    share = H.load_module("roofline", "cco_train_sharded").work(config)
    whole = H.load_module("roofline", "cco_train").work(config)
    assert share["chips"] == 4 and share["calls"] == whole["calls"] == 50
    assert share["flops"] == whole["flops"] / 4 == 131072 * 1e10
    assert share["bytes"] == whole["bytes"] / 4
    peaks = H.peaks_for("TPU v5 lite")
    assert H.least_seconds("cco_train_sharded", config, peaks) == \
        pytest.approx(6.653, rel=1e-3)
    eight = json.loads(json.dumps(config))
    eight["engine"]["algorithms"][0]["params"]["meshDp"] = 8
    assert H.load_module("roofline", "cco_train_sharded").work(
        eight)["flops"] == whole["flops"] / 8
    del eight["engine"]["algorithms"][0]["params"]["meshDp"]
    with pytest.raises(KeyError):       # a share of nothing stated: refused
        H.load_module("roofline", "cco_train_sharded").work(eight)


TPU_OPS = {
    "reduce_scatter.16": {"seconds": 1.5, "detail":
        "%reduce_scatter.16 = f32[25088,4096]{1,0:T(8,128)} reduce-scatter("
        "%fusion.33), channel_id=1, replica_groups={{0,1,2,3}}"},
    "psum_invariant.16": {"seconds": 0.25, "detail":
        "%psum_invariant.16 = f32[1,4096]{1,0:T(1,128)} all-reduce(%bitcast"
        ".109), channel_id=1"},
    "all-reduce-start.2": {"seconds": 0.125, "detail":
        "%all-reduce-start.2 = s32[100352]{0} all-reduce-start(%x.1)"},
    "fusion.36 (kCustom)": {"seconds": 0.5, "detail":
        "%fusion.36 = f32[25088,4096]{1,0} fusion(%fusion.35), kind=kCustom,"
        " calls=%all-reduce-scatter.clone.clone"},
    # takes a collective's RESULT as an operand: not a collective
    "_llr_padded.5 (tpu_custom_call)": {"seconds": 9.0, "detail":
        "%_llr_padded.5 = f32[25088,4096]{1,0} custom-call(%reduce_scatter"
        ".16, %scatter.32), custom_call_target=\"tpu_custom_call\""},
    "fusion.30 (kOutput)": {"seconds": 99.0, "detail":
        "%fusion.30 = f32[100352,4096]{1,0} fusion(%p.1, %p.2), kind=kOutput"},
}


def test_collective_operations_are_taken_by_name_or_opcode_never_by_operand():
    reader = H.load_module("readers", "trace_ops_ms")
    args = json.loads((BENCH / "metrics" / "cco_exchange_ms.json"
                       ).read_text())["args"]
    facts = {"reduced": {"ops": TPU_OPS}, "jobs": 2}
    # reduce-scatter + all-reduce + the asynchronous start + the fused
    # form (named by what it calls), over two jobs
    assert reader.read(args, facts) == pytest.approx(1e3 * 2.375 / 2)
    assert reader.opcode(TPU_OPS["reduce_scatter.16"]["detail"]) == \
        "reduce-scatter"
    assert reader.opcode(TPU_OPS["fusion.30 (kOutput)"]["detail"]) == "fusion"
    # as a chip trace prints them: operands with their types and tilings
    assert reader.opcode(
        "%reduce_scatter.16 = f32[25088,4096]{1,0:T(8,128)} reduce-scatter("
        "f32[100352,4096]{1,0:T(8,128)} %fusion.33), channel_id=1") == \
        "reduce-scatter"
    assert reader.opcode(
        "%_tile_topk_padded.5 = (f32[64,196,128]{2,1,0:T(8,128)S(1)}, s32[64,"
        "196,128]{2,1,0:T(8,128)S(1)}) custom-call(f32[25088,4096]{1,0:T(8,"
        "128)} %_llr_padded.5)") == "custom-call"
    assert reader.opcode("reduce_scatter.11") == ""     # a CPU trace's name
    cpu = {"reduced": {"ops": {
        "reduce_scatter.11": {"seconds": 0.5, "detail": "reduce_scatter.11"},
        "psum.11": {"seconds": 0.25, "detail": "psum.11"},
        "dot_general.7": {"seconds": 3.0, "detail": "dot_general.7"}}},
        "jobs": 3}
    assert reader.read(args, cpu) == pytest.approx(250.0)
    # one chip, or the parent's loop of steps without a scatter: nothing
    none = {"reduced": {"ops": {k: v for k, v in TPU_OPS.items()
                                if k.startswith(("_llr", "fusion.30"))}},
            "jobs": 2}
    assert reader.read(args, none) is None
    assert reader.read(args, {**facts, "jobs": 0}) is None


def test_traced_rehearsal_reports_the_three_metrics_of_the_sharded_program():
    """On four CPU devices the rehearsal takes `_densify_sharded` and
    `_cco_sharded_all_tiles`; a CPU trace names the collectives too
    (`reduce_scatter.N`, `psum.N`), so all three read something."""
    code, out, err = run_cell(CELL, "--trace", "1", "--rehearsal",
                              seed=2147484033)
    assert code == 0, err[-3000:]
    got = rehearsal_result(out)
    assert got["correct"] is True and got["device"]["count"] == 4
    m = {k: v["value"] for k, v in got["metrics"].items()}
    assert OWN <= set(m) and "train_mfu_pct" not in m   # no peaks on a CPU
    assert 0 < m["cco_exchange_ms"] < m["cco_sharded_program_ms"]
    assert m["cco_sharded_program_ms"] < 1e3 * m["device_wait_s"] * 1.5
    # 600 items in whole 128-row tiles x 4 chips = 1,024 rows, 3 tiles of
    # 256 an event type, two types: three quarters of every float32 tile
    config = rehearsal_config(H, CELL)
    assert config["engine"]["algorithms"][0]["params"]["meshDp"] == 4
    assert m["cco_exchange_mb_per_job"] == pytest.approx(
        2 * 3 * 768 * 256 * 4 / 1e6)


def test_a_program_without_the_sharded_program_gives_the_readers_nothing():
    """What the parent commit gives on this cell: other programs, no
    `exchange_mb` on any span, no collective this reader knows by name."""
    facts = {"jobs": 1, "reduced": {
        "programs": {"jit__cco_tile_step": {"count": 50, "seconds": 9.0}},
        "ops": {"fusion.1 (kLoop)": {"seconds": 1.0, "detail":
                                      "%fusion.1 = f32[8] fusion(%p)"}}}}
    for name in ("cco_sharded_program_ms", "cco_exchange_ms"):
        spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert H.load_module("readers", spec["reader"]).read(
            spec["args"], facts) is None
