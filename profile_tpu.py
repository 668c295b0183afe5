"""Stage-by-stage CCO profiler for the bench shapes (run on the real chip).

Measures, blocking on each stage's result:
  1. host layout (_stage_chunked, no dedup)
  2. H2D upload bytes/time
  3. device counts: int8 vs bf16 matmul, self-pair reuse on/off
  4. scatter-densify alone vs matmul alone (isolates the scatter cost)
  5. LLR+topk
  6. full cco_train_indicators (the headline path)

Usage: python profile_tpu.py [--events N] [--items I] [--users U]
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np


def sync(x):
    import jax

    return jax.block_until_ready(x)


# Peak bf16 matmul rate per chip, keyed by jax's device_kind.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A device
# that is not in the table is an error, not a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def t(label, fn, n=3):
    fn()
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    print(f"{label:48s} {best * 1e3:9.1f} ms")
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=100_000)
    ap.add_argument("--items", type=int, default=8_192)
    ap.add_argument("--buy", type=int, default=1_000_000)
    ap.add_argument("--view", type=int, default=3_000_000)
    args = ap.parse_args()

    from predictionio_tpu.utils.config import enable_compilation_cache

    enable_compilation_cache()

    import jax
    import jax.numpy as jnp

    from bench import synth_commerce
    from predictionio_tpu.ops import cco

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_TFLOPS:
        raise SystemExit(
            f"no peak tabulated for device kind {kind!r} (known: "
            f"{sorted(PEAK_BF16_TFLOPS)}); this profiler reports shares of "
            "a chip's peak and runs on the chip only")
    print(f"device: {jax.devices()[0]} ({kind})")
    n_users, n_items = args.users, args.items
    buy_u, buy_i, view_u, view_i = synth_commerce(n_users, n_items, args.buy, args.view)
    total = args.buy + args.view

    it_pad = n_items
    chunk = cco._dense_chunk_users(n_items, it_pad, n_users)
    n_chunks = -(-n_users // chunk)
    print(f"chunk={chunk} n_chunks={n_chunks} mm={cco._matmul_dtype()}")

    # 1. host layout
    t("host layout buy (1M, no dedup)", lambda: cco._stage_chunked(
        buy_u, buy_i, chunk, n_chunks))
    t("host layout view (3M, no dedup)", lambda: cco._stage_chunked(
        view_u, view_i, chunk, n_chunks))

    p = cco._stage_chunked(buy_u, buy_i, chunk, n_chunks)
    a = cco._stage_chunked(view_u, view_i, chunk, n_chunks)
    sync((p.local_u, a.local_u))
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in (p.local_u, p.item, a.local_u, a.item))
    print(f"staged {nbytes / 1e6:.1f} MB")

    # 2. upload
    def upload():
        q = cco._stage_chunked(view_u, view_i, chunk, n_chunks)
        sync((q.local_u, q.item))
    t("layout+upload view (3M)", upload)

    # 3. counts: int8 vs bf16, self vs cross
    for mm in ("int8", "bf16"):
        for self_pair, label in ((False, "cross"), (True, "self")):
            def counts(mm=mm, sp=self_pair):
                out = cco._cco_counts_dense(
                    p.local_u, p.item, p.count, a.local_u, a.item, a.count,
                    chunk=chunk, n_items_p=n_items, it_pad=it_pad,
                    self_pair=sp, mm=mm)
                sync(out)
            t(f"counts {label} mm={mm}", counts)

    # 4. isolate scatter vs matmul
    in_dtype = jnp.int8

    @jax.jit
    def scatter_only(lu, it, cnt):
        def body(c, xs):
            l, i, n = xs
            valid = jax.lax.iota(jnp.int32, l.shape[0]) < n
            m = jnp.zeros((chunk, n_items), in_dtype).at[l, i].max(
                valid.astype(in_dtype))
            return c + m.sum(dtype=jnp.int32), None
        out, _ = jax.lax.scan(body, jnp.int32(0), (lu, it, cnt))
        return out

    t_sc_view = t("scatter-densify only (view 3M)", lambda: sync(
        scatter_only(a.local_u, a.item, a.count)))
    t_sc_buy = t("scatter-densify only (buy 1M)", lambda: sync(
        scatter_only(p.local_u, p.item, p.count)))

    P8 = jnp.zeros((chunk, n_items), jnp.int8)

    @jax.jit
    def mm_only(P):
        def body(c, _):
            return c + jax.lax.dot_general(
                P, P, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32), None
        out, _ = jax.lax.scan(body, jnp.zeros((n_items, n_items), jnp.int32),
                              None, length=n_chunks)
        return out
    t(f"matmul only int8 ({n_chunks}x)", lambda: sync(mm_only(P8)))
    Pb = jnp.zeros((chunk, n_items), jnp.bfloat16)

    @jax.jit
    def mm_only_bf(P):
        def body(c, _):
            return c + jax.lax.dot_general(
                P, P, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), None
        out, _ = jax.lax.scan(body, jnp.zeros((n_items, n_items), jnp.float32),
                              None, length=n_chunks)
        return out
    t_mm_bf = t(f"matmul only bf16 ({n_chunks}x)", lambda: sync(mm_only_bf(Pb)))

    # 5. LLR+topk
    C, rc, cc = cco._cco_counts_dense(
        p.local_u, p.item, p.count, a.local_u, a.item, a.count,
        chunk=chunk, n_items_p=n_items, it_pad=it_pad, self_pair=False,
        mm=cco._matmul_dtype())
    sync((C, rc, cc))
    t_llr = float("inf")
    for pl in ("off", "compiled"):
        t_llr = min(t_llr, t(
            f"LLR+topk pallas={pl}", lambda pl=pl: sync(cco._llr_topk_dense(
                C, rc, cc, float(n_users), 0.0, top_k=50,
                exclude_self=False, pallas=pl))))

    # 6. the headline path
    def full():
        cco.cco_train_indicators(
            buy_u, buy_i,
            [("buy", buy_u, buy_i, n_items), ("view", view_u, view_i, n_items)],
            n_users, n_items, top_k=50, exclude_self_for="buy")
    wall = t("FULL cco_train_indicators (bench path)", full)
    print(f"=> {total / wall:,.0f} events/s  "
          f"(vs_baseline {total / wall / 200_000:.2f}, target >= 20)")

    # 7. lax vs pallas tiled merge, each alone, at the tiled path's
    # production shape: [100k rows, 4096-wide tiles].
    from predictionio_tpu.ops.pallas_kernels import tile_topk_desc
    from predictionio_tpu.ops.topk import block_width, merge_desc

    rows, tile_w, k = min(n_users, 100_000), 4096, 50
    b = block_width(k)
    rng = np.random.default_rng(0)
    tile_scores = jnp.asarray(
        rng.standard_normal((rows, tile_w)).astype(np.float32))
    sync(tile_scores)

    @jax.jit
    def merge_lax(bs, bi, ts):
        idx = jnp.broadcast_to(
            jnp.arange(tile_w, dtype=jnp.int32)[None, :], ts.shape)
        s, pos = jax.lax.top_k(jnp.concatenate([bs, ts], axis=1), k)
        ai = jnp.concatenate([bi, idx], axis=1)
        return s, jnp.take_along_axis(ai, pos, axis=1)

    @jax.jit
    def merge_pallas(bs, bi, ts):
        s, i = tile_topk_desc(ts, b)
        return merge_desc(bs, bi, s, i)

    bs_l = jnp.full((rows, k), -jnp.inf); bi_l = jnp.zeros((rows, k), jnp.int32)
    bs_p = jnp.full((rows, b), -jnp.inf); bi_p = jnp.zeros((rows, b), jnp.int32)
    tl = t(f"tile merge LAX      [{rows}, {tile_w}]", lambda: sync(
        merge_lax(bs_l, bi_l, tile_scores)))
    # compile the kernel separately first so its compile time is visible
    t0 = time.perf_counter()
    out = merge_pallas(bs_p, bi_p, tile_scores)
    sync(out)
    print(f"  pallas merge compile+first-run: {time.perf_counter()-t0:.1f}s")
    tp = t(f"tile merge PALLAS   [{rows}, {tile_w}]", lambda: sync(
        merge_pallas(bs_p, bi_p, tile_scores)))
    print(f"=> merge speedup {tl / tp:.2f}x")

    # 8. MFU / roofline for the headline kernel: achieved TFLOP/s of the
    # count-matmul stage, % of the chip's peak, and the top non-matmul
    # consumers — "beat the baseline" says
    # nothing about how much single-chip headroom is left.
    flops = 2.0 * n_chunks * chunk * n_items * n_items   # one A^T·A sweep
    tflops = flops / t_mm_bf / 1e12
    peak = PEAK_BF16_TFLOPS[kind]
    print("\n--- roofline (count-matmul stage) ---")
    print(f"count matmul: {flops / 1e12:.2f} TFLOP in {t_mm_bf * 1e3:.0f} ms"
          f" = {tflops:.1f} TFLOP/s achieved (bf16 {chunk}x{n_items} A^T.A"
          f" x{n_chunks})")
    print(f"  = {100 * tflops / peak:.1f}% of the {kind} bf16 peak"
          f" ({peak:.0f} TFLOP/s)")
    t_sc = t_sc_buy + t_sc_view
    pct = (lambda x: 100.0 * x / wall) if wall else (lambda x: 0.0)
    print("top non-matmul consumers (vs FULL wall"
          f" {wall * 1e3:.0f} ms):")
    for label, v in sorted(
            (("scatter-densify (buy+view)", t_sc),
             ("LLR + top-k epilogue", t_llr)), key=lambda kv: -kv[1]):
        print(f"  {label:32s} {v * 1e3:8.0f} ms  ({pct(v):4.1f}%)")
    print("next lever: whichever of the above dominates — scatter rides "
          "the VPU (fuse into the matmul via Pallas if it leads); the "
          "top-k epilogue is the tiled-merge kernel's territory "
          "(see section 7 verdict above)")


if __name__ == "__main__":
    main()
