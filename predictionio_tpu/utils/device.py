"""What this process runs on, as JAX itself reports it.

The entry points print this — ``pio status``, ``pio train`` (log line +
root span of the run's journal), the query server's ``GET /`` — so a run
on the wrong backend can never pass for a run on the chip: nothing here
is inferred from configuration, and a backend that fails to initialise
raises instead of degrading.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_compiles = {"programs": 0, "seconds": 0.0, "cacheHits": 0, "cacheWrites": 0}
_watching = False


def device_info() -> Dict:
    """``{"platform", "kind", "count"}`` of the default backend.  Raises
    (RuntimeError) when the backend cannot initialise."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory_bytes() -> List[Optional[int]]:
    """Per-device ``peak_bytes_in_use`` since process start; None for a
    device whose backend keeps no such statistic (the CPU backend).  On
    the TPU runtime it counts live buffers — arguments and results — and
    NOT a program's temporaries (PERF.md "Bring-up on TPU v5e"): the
    bound on what a program needs is its ``memory_analysis()``."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def watch_compiles() -> None:
    """Start counting this process's XLA compilations (idempotent).  JAX's
    monitoring listeners are process-global and cannot be removed one by
    one, so the tally is too."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    import time

    from jax import monitoring

    from predictionio_tpu.obs import spans

    # JAX records a cache hit on the compiling thread, inside the interval
    # whose duration it reports next on that thread
    hit = threading.local()

    def on_duration(event: str, duration: float, **kw) -> None:
        if event != _BACKEND_COMPILE:
            return
        with _lock:
            _compiles["programs"] += 1
            _compiles["seconds"] += duration
        cache_hit, hit.seen = getattr(hit, "seen", False), False
        # the compile as a span of the job and stage that paid for it
        collector = spans.active_collector()
        if collector is not None:
            attrs = {"seconds": round(duration, 6), "cache_hit": cache_hit}
            if kw.get("fun_name"):
                attrs["program"] = kw["fun_name"]
            collector.add_span("compile", time.time() - duration, duration,
                               parent=collector.open_span_id(), attrs=attrs)

    def on_event(event: str, **_kw) -> None:
        key = {_CACHE_HIT: "cacheHits", _CACHE_WRITE: "cacheWrites"}.get(event)
        if key is not None:
            if event == _CACHE_HIT:
                hit.seen = True
            with _lock:
                _compiles[key] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def compile_stats() -> Dict:
    """Compilations since ``watch_compiles()``: programs built or fetched
    and the seconds that took, how many came out of the persistent cache
    (``cacheHits``) and how many were written to it (``cacheWrites`` —
    only programs past JAX's minimum compile time are), plus the cache
    directory in effect."""
    import jax

    with _lock:
        out = dict(_compiles)
    out["seconds"] = round(out["seconds"], 3)
    out["cacheDir"] = jax.config.jax_compilation_cache_dir
    return out
