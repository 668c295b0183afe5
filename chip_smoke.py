#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the README quick-start path once, through the entry points a user
would call, at the full width of one model the repo supports — Universal
Recommender, 20,000 users x 100,000 items, 400,000 `buy` + 800,000 `view`
events generated from a seed, maxCorrelatorsPerItem 50:

    pio status -> pio app new -> pio import -> pio train -> pio train again
    (the second process must find the first one's compile cache) ->
    pio deploy + POST /queries.json + GET /stop (device scorer, device tail,
    micro-batcher) -> the same queries against a second deploy pinned to the
    host twins -> every Pallas kernel against its XLA twin at its production
    shape -> a look at the persisted model.

This parent process never imports jax: each phase is a child process that
owns the chip in turn.  Every claim it checks is one the program printed
itself (`pio status`, the train run's span journal, the server's `GET /`).

It exits non-zero — reasons on the last lines, and no result line — when
any phase fails, when JAX finds no TPU, or when run outside the repo.  On
success the last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--rehearsal` is the explicit CPU debugging mode (tiny shape,
JAX_PLATFORMS=cpu, Pallas kernels interpreted, device placements forced):
same phases, same checks, output labelled a rehearsal, and never the
`ok` line above.  It is a flag, not a fallback.

`--mesh` (a host with several chips) adds a train pinned to ONE device and
checks that the default train — which shards over every chip — produced
the same indicator tables.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
DEADLINE_S = 1150.0          # the contract allows 1200 s, compilation included
FULL = dict(n_users=20_000, n_items=100_000, n_buy=400_000, n_view=800_000,
            top_k=50, item_tile=4096)
TINY = dict(n_users=300, n_items=700, n_buy=4_000, n_view=8_000,
            top_k=10, item_tile=256)
APP = "smoke"
FACTORY = ("predictionio_tpu.models.universal_recommender."
           "UniversalRecommenderEngine")
# Host and device serving twins must agree on the items; scores may differ
# by float32 summation order (they are sums of <= 100 indicator weights).
SCORE_RTOL = 1e-5


class Failed(Exception):
    pass


class Smoke:
    def __init__(self, args):
        self.rehearsal = args.rehearsal
        self.mesh = args.mesh
        self.shape = TINY if args.rehearsal else FULL
        # how the kernels must have run: never as something else unasked
        self.pallas = "interpret" if args.rehearsal else "compiled"
        self.work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
        self.keep = args.keep
        self.t0 = time.monotonic()
        self.report: dict = {}
        self.procs: list = []
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        env["PIO_FS_BASEDIR"] = str(self.work / "store")
        for k in [k for k in env if k.startswith("PIO_STORAGE_")]:
            del env[k]
        if self.rehearsal:
            # the same placements a TPU resolves to by itself, forced, so
            # the rehearsal walks the code the chip run will
            env.update({
                "JAX_PLATFORMS": "cpu", "PIO_PALLAS": "interpret",
                "PIO_CCO_SPARSE": "0", "PIO_CCO_DENSE": "0",
                "PIO_UR_SERVE_SCORER": "device", "PIO_UR_SERVE_TAIL": "device",
                "PIO_SERVE_BATCH": "on",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            })
        self.env = env

    # -- plumbing ------------------------------------------------------------

    def left(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:6.1f}s] {msg}", flush=True)

    def run(self, name: str, argv: list, env=None, timeout=None) -> str:
        """One child process to completion; its stdout on success."""
        log = self.work / f"{name}.log"
        timeout = min(timeout or 900.0, max(self.left(), 1.0))
        t = time.monotonic()
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, *argv], env=env or self.env, cwd=str(REPO),
                stdout=subprocess.PIPE, stderr=f, text=True)
            self.procs.append(proc)
            try:
                out, _ = proc.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
                raise Failed(f"{name}: no end after {timeout:.0f}s\n"
                             + _tail(log))
        self.report.setdefault("seconds", {})[name] = round(
            time.monotonic() - t, 1)
        if proc.returncode != 0:
            raise Failed(f"{name}: exit code {proc.returncode}\n{out[-2000:]}"
                         + _tail(log))
        return out

    def pio(self, name: str, *argv: str, env=None, timeout=None) -> str:
        return self.run(name, ["-m", "predictionio_tpu.cli.main", *argv],
                        env=env, timeout=timeout)

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        if self.keep:
            self.say(f"kept {self.work}")
        else:
            shutil.rmtree(self.work, ignore_errors=True)

    # -- phases --------------------------------------------------------------

    def status(self) -> dict:
        out = self.pio("status", "status", timeout=300)
        line = next((l for l in out.splitlines() if "jax devices:" in l), "")
        try:   # "  jax devices: 1 (tpu, TPU v5 lite)"
            count, rest = line.split(":", 1)[1].strip().split(" ", 1)
            platform, kind = rest.strip("()").split(", ", 1)
            device = {"platform": platform, "kind": kind, "count": int(count)}
        except ValueError:
            raise Failed(f"pio status printed no device line:\n{out}")
        cache = next((l.split(":", 1)[1].strip() for l in out.splitlines()
                      if "compile cache:" in l), "")
        self.report["device"] = device
        self.report["compile_cache_dir"] = cache
        self.say(f"pio status: {device}, compile cache {cache}")
        want = "cpu" if self.rehearsal else "tpu"
        if device["platform"] != want:
            raise Failed(f"JAX found {device['platform']!r}, not {want!r}: "
                         "this check runs on the chip"
                         + ("" if self.rehearsal else
                            " (--rehearsal is the CPU debugging mode)"))
        if self.mesh and device["count"] < 2:
            raise Failed("--mesh needs several devices, JAX found one")
        return device

    def make_events(self) -> dict:
        """Seeded commerce events: every item bought at least once (so
        the catalog IS n_items wide), the rest Zipf-popular."""
        s = self.shape
        rng = np.random.default_rng(3)
        nu, ni, nb, nv = s["n_users"], s["n_items"], s["n_buy"], s["n_view"]
        bu = rng.integers(0, nu, nb)
        bi = np.concatenate([np.arange(min(ni, nb)),
                             rng.zipf(1.3, max(nb - ni, 0)) % ni])
        vu = rng.integers(0, nu, nv)
        vi = rng.zipf(1.2, nv) % ni
        path = self.work / "events.jsonl"
        line = ('{"event": "%s", "entityType": "user", "entityId": "u%d", '
                '"targetEntityType": "item", "targetEntityId": "i%d", '
                '"eventTime": "2026-01-01T00:00:00+00:00"}\n')
        with open(path, "w") as f:
            f.writelines(line % ("buy", u, i) for u, i in zip(bu, bi))
            f.writelines(line % ("view", u, i) for u, i in zip(vu, vi))
        buyers = list(dict.fromkeys(int(u) for u in bu[ni:]))  # repeat buyers
        self.report["shape"] = dict(s, events=nb + nv)
        return {"path": path, "buyers": buyers or [int(bu[0])]}

    def engine_json(self, engine_id: str, **algo) -> str:
        s = self.shape
        doc = {
            "id": engine_id, "engineFactory": FACTORY,
            "datasource": {"params": {"appName": APP,
                                      "eventNames": ["buy", "view"]}},
            # appName in the ALGORITHM params too: without it serving
            # finds no user history and answers popularity backfill
            "algorithms": [{"name": "ur", "params": {
                "appName": APP, "maxCorrelatorsPerItem": s["top_k"],
                "itemTile": s["item_tile"], **algo}}],
        }
        path = self.work / f"{engine_id}.json"
        path.write_text(json.dumps(doc))
        return str(path)

    def train(self, name: str, engine_json: str) -> dict:
        out = self.pio(name, "train", "--engine-json", engine_json,
                       timeout=900)
        if "Training completed" not in out:
            raise Failed(f"{name}: no COMPLETED instance:\n{out[-1000:]}")
        instance = out.rsplit("Engine instance id:", 1)[1].strip()
        journal = self.work / "store" / "spans" / f"{instance}.jsonl"
        spans = [json.loads(l) for l in journal.read_text().splitlines()]
        root = next(s for s in spans if s["name"] == "train")
        a = root["attrs"]
        got = {"instance": instance, "device": a["device"],
               "pallas": a["pallas"], "compile": a["compile"],
               "peak_memory_bytes": a["peak_memory_bytes"],
               "train_span_s": round(root["duration_s"], 1)}
        self.report[name] = got
        self.say(f"{name}: {got['train_span_s']}s on {a['device']}, pallas="
                 f"{a['pallas']}, compile {a['compile']}, peak bytes "
                 f"{a['peak_memory_bytes']}")
        if a["device"] != self.report["device"]:
            raise Failed(f"{name} ran on {a['device']}, pio status said "
                         f"{self.report['device']}")
        if a["pallas"] != self.pallas:
            raise Failed(f"{name}: pallas mode {a['pallas']!r}, not "
                         f"{self.pallas!r} — the LLR ran as something else "
                         "than the kernel")
        return got

    def check_cache(self, first: dict, second: dict) -> None:
        """The first train leaves its programs in the compile cache, the
        second process finds them.  (The first is cold unless the machine
        came with a filled cache: its own hits and writes say which.)"""
        cache = first["compile"]["cacheDir"]
        if not cache or not os.path.isdir(cache) or not os.listdir(cache):
            raise Failed(f"compile cache {cache!r} missing or empty after "
                         "the first train")
        if cache != second["compile"]["cacheDir"]:
            raise Failed("the two trains used different compile caches")
        c1, c2 = first["compile"], second["compile"]
        if c1["cacheWrites"] + c1["cacheHits"] < 1:
            raise Failed("the first train neither wrote to nor read from "
                         f"the compile cache {cache}")
        if c2["cacheHits"] < 1:
            raise Failed("the second train process hit nothing in the "
                         f"compile cache {cache}")
        self.report["compile_cache"] = {
            "dir": cache, "entries": len(os.listdir(cache)),
            "first_process": {k: c1[k] for k in (
                "programs", "seconds", "cacheHits", "cacheWrites")},
            "second_process": {k: c2[k] for k in (
                "programs", "seconds", "cacheHits", "cacheWrites")}}
        self.say(f"compile cache: {self.report['compile_cache']}")

    def serve(self, name: str, engine_json: str, queries: list, burst: list,
              pins: dict, want: dict) -> dict:
        """Deploy, answer every query, stop.  `queries` go one at a time,
        `burst` from eight threads at once (the micro-batcher's case)."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        log = self.work / f"{name}.log"
        t_start = time.monotonic()
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
                 "--engine-json", engine_json, "--ip", "127.0.0.1",
                 "--port", str(port)],
                env={**self.env, **pins}, cwd=str(REPO), stdout=f, stderr=f)
        self.procs.append(proc)
        try:
            info = None
            while info is None:
                if proc.poll() is not None:
                    raise Failed(f"{name}: deploy exited with code "
                                 f"{proc.returncode}\n" + _tail(log))
                if self.left() <= 0 or time.monotonic() - t_start > 600:
                    raise Failed(f"{name}: server never came up\n" + _tail(log))
                try:
                    info = _get(base + "/")
                except (urllib.error.URLError, OSError, ValueError):
                    time.sleep(0.5)
            up_s = time.monotonic() - t_start
            got = {k: info[k] for k in ("device", "scorer", "tail", "batcher")}
            self.say(f"{name}: up after {up_s:.1f}s, GET / says {got}")
            if got != {"device": self.report["device"], **want}:
                raise Failed(f"{name}: resolved to {got}, expected "
                             f"{dict(want, device=self.report['device'])}")
            answers, lat = {}, []
            for q in queries:
                t = time.monotonic()
                answers[q["user"]] = _post(base + "/queries.json", q)
                lat.append(time.monotonic() - t)
            first = _get(base + "/")["compile"]
            errors: list = []

            def worker(part):
                try:
                    for q in part:
                        answers[q["user"]] = _post(base + "/queries.json", q)
                except Exception as e:   # surfaced after join
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(burst[w::8],))
                       for w in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=max(self.left(), 1.0))
            if errors or any(t.is_alive() for t in threads):
                raise Failed(f"{name}: concurrent queries failed: {errors}")
            end = _get(base + "/")
            out = {"up_s": round(up_s, 1), **got,
                   "first_query_s": round(lat[0], 3),
                   "first_query_compile": first,
                   "later_query_ms_median": round(
                       1e3 * float(np.median(lat[1:])), 2),
                   "compile": end["compile"], "queries": end["queryCount"]}
            self.report[name] = out
            try:
                _get(base + "/stop")
            except (urllib.error.URLError, OSError, ValueError):
                pass   # the server may close the socket while stopping
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                raise Failed(f"{name}: still running 30s after GET /stop")
            return answers
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    def check_answers(self, device: dict, host: dict, hist_users: list,
                      cold_users: list) -> None:
        backfill = {(s["item"], s["score"])
                    for u in cold_users for s in device[u]["itemScores"]}
        ties = 0
        for u in hist_users + cold_users:
            d, h = device[u]["itemScores"], host[u]["itemScores"]
            if not d:
                raise Failed(f"user {u}: empty itemScores")
            if u in hist_users and not any(
                    s["score"] >= 1.0 and (s["item"], s["score"])
                    not in backfill for s in d):
                raise Failed(f"user {u} has history but was answered from "
                             f"the popularity backfill: {d[:3]}")
            ds, hs = [s["score"] for s in d], [s["score"] for s in h]
            if len(d) != len(h) or not np.allclose(
                    ds, hs, rtol=SCORE_RTOL, atol=0.0):
                raise Failed(f"user {u}: device and host tails disagree on "
                             f"scores:\n  device {d}\n  host   {h}")
            if [s["item"] for s in d] != [s["item"] for s in h]:
                # equal scores may order differently; a tie cut at the end
                # of the list may keep different members — nothing else
                cut = ds[-1]
                above_d = {s["item"] for s in d if s["score"] > cut}
                above_h = {s["item"] for s in h if s["score"] > cut}
                if above_d != above_h:
                    raise Failed(f"user {u}: device and host tails return "
                                 f"different items:\n  device {d}\n  host   {h}")
                ties += 1
        self.report["parity"] = {
            "users": len(hist_users) + len(cold_users),
            "identical_lists": len(hist_users) + len(cold_users) - ties,
            "differ_only_within_ties": ties, "score_rtol": SCORE_RTOL}
        self.say(f"device vs host twins: {self.report['parity']}")

    def child_json(self, name: str, phase: str, *extra: str, env=None) -> dict:
        out = self.run(name, [str(REPO / "chip_smoke.py"), "--phase", phase,
                              *(["--rehearsal"] if self.rehearsal else []),
                              *extra], env=env, timeout=600)
        return json.loads(out.strip().splitlines()[-1])

    def main(self) -> dict:
        device = self.status()
        ev = self.make_events()
        self.say(f"generated {self.report['shape']}")
        self.pio("app_new", "app", "new", APP)
        out = self.pio("import", "import", "--app-name", APP,
                       "--input", str(ev["path"]), timeout=600)
        self.say(out.strip().splitlines()[-1])
        ej = self.engine_json("smoke-ur")
        first = self.train("train_first", ej)
        second = self.train("train_second", ej)
        self.check_cache(first, second)

        hist = [f"u{u}" for u in ev["buyers"][:28]]
        cold_users = ["nobody-1", "nobody-2"]
        seq = [{"user": u, "num": 10} for u in hist[:12] + cold_users]
        burst = [{"user": u, "num": 10} for u in hist[12:]]
        dev_ans = self.serve(
            "serve_device", ej, seq, burst, {},
            {"scorer": "device", "tail": "device", "batcher": True})
        host_ans = self.serve(
            "serve_host", ej, seq + burst, [],
            {"PIO_UR_SERVE_SCORER": "host", "PIO_UR_SERVE_TAIL": "host"},
            {"scorer": "host", "tail": "host", "batcher": True})
        self.check_answers(dev_ans, host_ans, hist, cold_users)

        kernels = self.child_json("kernels", "kernels")
        self.report["kernels"] = kernels
        for k, v in kernels.items():
            self.say(f"kernels: {k}: {v}")
        for b, c in kernels["tile_topk_desc"]["carries"].items():
            self.say(f"kernels: tile_topk_desc carry {b}: {c['ms']} ms a "
                     f"tile (lax.top_k {c['twin_ms']} ms)")
        if kernels["device"] != device or kernels["pallas"] != self.pallas:
            raise Failed(f"the kernel phase ran on {kernels['device']} in "
                         f"pallas mode {kernels['pallas']!r}")
        bad = [k for k in ("llr_masked_scores", "masked_score_matmul",
                           "tile_topk_desc") if not kernels[k]["ok"]]
        if bad:
            raise Failed(f"kernels differ from their XLA twins: {bad}")

        cpu = {**self.env, "JAX_PLATFORMS": "cpu"}   # never needs the chip
        engines = ["smoke-ur"]
        if self.mesh:
            self.train("train_one_device",
                       self.engine_json("smoke-ur-dp1", meshDp=1))
            engines.append("smoke-ur-dp1")
        model = self.child_json("inspect", "inspect", *engines, env=cpu)
        self.report["model"] = model
        self.say(f"persisted model: {model}")
        if model["n_items"] != self.shape["n_items"]:
            raise Failed(f"the model has {model['n_items']} primary items, "
                         f"not {self.shape['n_items']}")
        if self.mesh:
            # (the CPU backend of a rehearsal keeps no memory statistics)
            if not self.rehearsal and not all(first["peak_memory_bytes"]):
                raise Failed("a device of the mesh held nothing: "
                             f"{first['peak_memory_bytes']}")
            if model["mismatched_rows"]:
                raise Failed(f"{device['count']}-device and one-device "
                             f"indicator tables differ: {model}")
        return device


# -- children (these import jax; the parent above never does) ---------------


def phase_kernels(rehearsal: bool) -> dict:
    """Each kernel of ops/pallas_kernels.py once, compiled, at its
    production shape, against its XLA twin on the same device."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import cco
    from predictionio_tpu.ops import pallas_kernels as pk
    from predictionio_tpu.models.common import host_topk_desc
    from predictionio_tpu.utils.config import enable_compilation_cache
    from predictionio_tpu.utils.device import device_info, peak_memory_bytes

    enable_compilation_cache()
    rows, width, n_items, rank = (
        (600, 256, 700, 16) if rehearsal else (100_000, 4096, 100_000, 32))
    mode = pk.pallas_mode()
    rng = np.random.default_rng(0)
    out: dict = {"device": device_info(), "pallas": mode}

    def timed(fn, *a):
        """(result, seconds of the first call — compile included —, best
        milliseconds of three more)"""
        secs = []
        for _ in range(4):
            t = time.perf_counter()
            r = jax.block_until_ready(fn(*a))
            secs.append(time.perf_counter() - t)
        return r, round(secs[0], 2), round(min(secs[1:]) * 1e3, 2)

    # 1. LLR over one count tile of the tiled CCO path
    c = rng.poisson(0.05, (rows, width)).astype(np.float32)
    rc = c.sum(1) + rng.integers(0, 50, rows).astype(np.float32)
    cc = c.sum(0) + rng.integers(0, 50, width).astype(np.float32)
    n_total = float(max(rc.max(), cc.max())) * 2.0
    c, rc, cc = map(jnp.asarray, (c, rc, cc))
    llr = jax.jit(lambda c, r, k, p: cco._llr_mask_scores(
        c, r, k, n_total, 0.5, p), static_argnums=3)
    got, first, ms = timed(llr, c, rc, cc, mode)
    want, _, ms_twin = timed(llr, c, rc, cc, "off")
    got, want = np.asarray(got), np.asarray(want)
    fin = np.isfinite(want)
    same_mask = bool((np.isfinite(got) == fin).all())
    err = float(np.max(np.abs(got[fin] - want[fin])
                       / np.maximum(np.abs(want[fin]), 1.0), initial=0.0))
    # same f32 elementwise chain, different compilers: a few ULP of
    # log1p; a cell AT the threshold may mask differently, none did
    out["llr_masked_scores"] = {
        "shape": [rows, width], "first_call_s": first, "ms": ms,
        "twin_ms": ms_twin, "same_mask": same_mask, "max_rel_err": err,
        "tolerance": 1e-4, "ok": same_mask and err <= 1e-4}

    # 2. the ALS serving scorer, one query (tile_b = 8)
    u = rng.normal(size=(1, rank)).astype(np.float32)
    v = rng.normal(size=(n_items, rank)).astype(np.float32)
    seen = (rng.random((1, n_items)) < 0.01).astype(np.float32)
    bias = rng.normal(size=n_items).astype(np.float32)
    got, first, ms = timed(jax.jit(pk.masked_score_matmul),
                           *map(jnp.asarray, (u, v, seen, bias)))
    twin = jax.jit(lambda u, v, s, b: jnp.where(s > 0, -jnp.inf, u @ v.T + b))
    want, _, ms_twin = timed(twin, *map(jnp.asarray, (u, v, seen, bias)))
    got, want = np.asarray(got), np.asarray(want)
    exact = np.where(seen > 0, -np.inf,
                     u.astype(np.float64) @ v.T.astype(np.float64) + bias)
    # f32 matmuls on a TPU run at DEFAULT precision (bf16 passes), in the
    # XLA twin at least; so both are held to bf16 rounding of each product
    # against the float64 answer, not to each other
    bound = 2.0 ** -7 * (np.abs(u) @ np.abs(v.T)) + 1e-5
    fin = np.isfinite(exact)
    ok = bool((np.isfinite(got) == fin).all()
              and (np.abs(got - exact)[fin] <= bound[fin]).all())
    out["masked_score_matmul"] = {
        "shape": [1, n_items, rank], "first_call_s": first, "ms": ms,
        "twin_ms": ms_twin,
        "max_abs_err_vs_f64": float(np.abs(got - exact)[fin].max()),
        "twin_max_abs_err_vs_f64": float(np.abs(want - exact)[fin].max()),
        "tolerance": "2^-7 * sum|u_k v_k| per score (bf16 products)",
        "ok": ok}

    # 3. the tile top-k of the tiled-CCO merge (selection: values exact),
    # at both carries the benchmark's cells run: 64 (the UR cells' top 50)
    # and 8 (cp-ecom-100k's top 8)
    x = rng.standard_normal((rows, width)).astype(np.float32)
    x[x < -1.0] = -np.inf
    xj = jnp.asarray(x)
    carries = {}
    for b in (64, 8):
        (gs, gi), first, ms = timed(
            jax.jit(lambda s: pk.tile_topk_desc(s, b)), xj)
        (ws, _), _, ms_twin = timed(jax.jit(lambda s: jax.lax.top_k(s, b)), xj)
        gs, gi, ws = np.asarray(gs), np.asarray(gi), np.asarray(ws)
        fin = np.isfinite(gs)
        picked = np.take_along_axis(x, np.clip(gi, 0, width - 1), axis=1)
        carries[b] = {
            "first_call_s": first, "ms": ms, "twin_ms": ms_twin,
            "ok": bool(np.array_equal(gs, ws) and (picked[fin] == gs[fin]).all()
                       and all(len(set(r[f])) == f.sum()
                               for r, f in zip(gi[:2000], fin[:2000])))}
    out["tile_topk_desc"] = {
        "shape": [rows, width], "carries": carries,
        "tolerance": "values bit-equal to lax.top_k",
        "ok": all(c["ok"] for c in carries.values())}

    # the serve tails' premise: lax.top_k breaks ties toward the lower
    # index on this backend, as models/common.host_topk_desc does on host
    ties = np.repeat(np.arange(40, dtype=np.float32)[::-1], 50)
    rng.shuffle(ties)
    _, ti = jax.lax.top_k(jnp.asarray(ties), 120)
    _, hi = host_topk_desc(ties, 120)
    out["lax_topk_tie_order_matches_host"] = bool(
        np.array_equal(np.asarray(ti), hi))
    out["peak_memory_bytes"] = peak_memory_bytes()
    return out


def phase_inspect(engine_ids: list) -> dict:
    """The persisted model(s), read back on the CPU: sizes, and with two
    engines whether their indicator tables agree (as SETS per row where
    scores tie at the cut, values to float32 rounding)."""
    from predictionio_tpu.workflow.core_workflow import load_latest_models

    models = [load_latest_models(e)[1][0] for e in engine_ids]
    m = models[0]
    out = {"n_items": len(m.item_dict), "n_users": len(m.user_dict),
           "indicators": {
               name: {"shape": list(idx.shape),
                      "rows_with_correlators": int((idx >= 0).any(1).sum()),
                      "correlators": int((idx >= 0).sum())}
               for name, idx in m.indicator_idx.items()}}
    if len(models) == 2:
        bad = 0
        for name, idx_a in m.indicator_idx.items():
            idx_b = models[1].indicator_idx[name]
            llr_a, llr_b = m.indicator_llr[name], models[1].indicator_llr[name]
            for r in np.flatnonzero((idx_a != idx_b).any(1)):
                # same scores in the same order, and the same members
                # everywhere above the lowest kept score
                cut = llr_a[r][idx_a[r] >= 0].min(initial=np.inf)
                same = np.allclose(llr_a[r], llr_b[r], rtol=1e-5) and (
                    set(idx_a[r][llr_a[r] > cut]) == set(idx_b[r][llr_b[r] > cut]))
                bad += not same
            bad += int(not np.allclose(llr_a, llr_b, rtol=1e-5))
        out["compared_with"] = engine_ids[1]
        out["mismatched_rows"] = bad
    return out


# -- helpers -----------------------------------------------------------------


def _tail(path: Path, n: int = 3000) -> str:
    try:
        return f"--- end of {path.name} ---\n" + path.read_text()[-n:]
    except OSError:
        return ""


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise Failed(f"POST {body} -> HTTP {e.code}: {e.read()[:500]!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU debugging mode: tiny shape, interpreted kernels")
    ap.add_argument("--mesh", action="store_true",
                    help="several chips: also compare with a one-device train")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (store, logs)")
    ap.add_argument("--phase", choices=["kernels", "inspect"],
                    help=argparse.SUPPRESS)   # internal: a child of this script
    ap.add_argument("engines", nargs="*", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not (REPO / "predictionio_tpu" / "cli" / "main.py").is_file():
        print(f"chip_smoke.py: {REPO} holds no predictionio_tpu package — "
              "this script drives the repo it is part of", file=sys.stderr)
        return 1
    if args.phase:
        sys.path.insert(0, str(REPO))
        out = (phase_kernels(args.rehearsal) if args.phase == "kernels"
               else phase_inspect(args.engines))
        print(json.dumps(out))
        return 0

    smoke = Smoke(args)
    label = "REHEARSAL (CPU, interpreted kernels) " if args.rehearsal else ""
    try:
        device = smoke.main()
    except Failed as e:
        print(json.dumps(smoke.report, indent=1))
        print(f"\n{label}chip_smoke FAILED after "
              f"{time.monotonic() - smoke.t0:.0f}s:\n{e}")
        return 1
    finally:
        smoke.close()
    smoke.report["total_s"] = round(time.monotonic() - smoke.t0, 1)
    smoke.report["rehearsal"] = args.rehearsal
    smoke.report["claim"] = None
    print(json.dumps(smoke.report, indent=1))
    if args.rehearsal:
        print(f"{label}passed in {smoke.report['total_s']}s — this says "
              "nothing about the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
