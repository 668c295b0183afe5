"""Tracing/profiling helpers.

The reference has no custom tracer (SURVEY.md §5) — it leans on the Spark UI.
The TPU-native equivalents: ``jax.named_scope`` for XLA-visible annotation,
``jax.profiler`` traces viewable in xprof/tensorboard, and a lightweight
wall-clock timer that feeds the workflow logs — and, when a span journal
is active (``obs.spans``: ``pio train``/``pio eval`` activate one per
run), every ``timed()`` block also lands in the journal as a structured
span with parent/child links.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Iterator, Optional

log = logging.getLogger("pio.trace")


@contextlib.contextmanager
def named_scope(name: str) -> Iterator[None]:
    """XLA-visible scope (shows up in xprof timelines and HLO names)."""
    import jax

    with jax.named_scope(name):
        yield


@contextlib.contextmanager
def profile_to(log_dir: str, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax.profiler trace into log_dir (view with xprof/tensorboard).

    ``host_tracer_level``: 0 = host tracing off, 1 = critical events,
    2 = info, 3 = verbose."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def timed(name: str, sink: Optional[dict] = None) -> Iterator[None]:
    """Wall-clock span logged at INFO; optionally recorded into sink.

    ``sink[name]`` accumulates seconds across calls and
    ``sink[name + ".count"]`` the number of calls, so a sink consumer can
    tell one 10 s span from a thousand 10 ms ones.  When a span journal
    is active (obs.spans: train/eval runs), the block is also recorded
    there as a structured span (with parent/child nesting); otherwise,
    when a request trace is live (obs.tracing flight recorder), it lands
    in that trace's waterfall instead."""
    from predictionio_tpu.obs import spans as _spans
    from predictionio_tpu.obs import tracing as _tracing

    sink_obj = _spans.current_journal() or _tracing.current_trace()
    ctx = sink_obj.span(name) if sink_obj is not None else contextlib.nullcontext()
    t0 = time.perf_counter()
    try:
        with ctx:
            yield
    finally:
        dt = time.perf_counter() - t0
        log.info("%s took %.3fs", name, dt)
        if sink is not None:
            sink[name] = sink.get(name, 0.0) + dt
            count_key = name + ".count"
            sink[count_key] = sink.get(count_key, 0) + 1
