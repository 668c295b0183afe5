"""Tracing/profiling helpers.

The reference has no custom tracer (SURVEY.md §5) — it leans on the Spark UI.
The TPU-native equivalents: ``jax.profiler`` traces viewable in
xprof/tensorboard, and a lightweight wall-clock timer that feeds the
workflow logs and is a span of ``obs.spans.span`` (the run's journal or
the request's trace when one is active, and the profiler's trace).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
from typing import Iterator, Optional

log = logging.getLogger("pio.trace")


@contextlib.contextmanager
def profile_to(log_dir: str, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax.profiler trace into log_dir (view with xprof/tensorboard).

    ``host_tracer_level``: 0 = host tracing off, 1 = critical events,
    2 = info, 3 = verbose.

    When the trace stops, ``<log_dir>/stages.json`` is written beside it:
    ``utils.device.stage_maps()`` of the programs this process dispatched,
    which says of each operation the trace shows by the compiler's name
    (``fusion.83``) which stage of which program it is
    (``cco.count_matmul`` of ``jit__cco_chunked_all_tiles``).  Reading the
    map compiles each program once more (docs/operations.md)."""
    import jax

    from predictionio_tpu.utils.device import stage_maps

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        with open(os.path.join(log_dir, "stages.json"), "w") as f:
            json.dump(stage_maps(), f, indent=1, sort_keys=True)


@contextlib.contextmanager
def timed(name: str, sink: Optional[dict] = None) -> Iterator[None]:
    """Wall-clock span logged at INFO; optionally recorded into sink.

    ``sink[name]`` accumulates seconds across calls and
    ``sink[name + ".count"]`` the number of calls, so a sink consumer can
    tell one 10 s span from a thousand 10 ms ones.  The block is also a
    span of ``obs.spans.span``."""
    from predictionio_tpu.obs.spans import span

    t0 = time.perf_counter()
    try:
        with span(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        log.info("%s took %.3fs", name, dt)
        if sink is not None:
            sink[name] = sink.get(name, 0.0) + dt
            count_key = name + ".count"
            sink[count_key] = sink.get(count_key, 0) + 1
