"""What this process runs on, as JAX itself reports it.

The entry points print this — ``pio status``, ``pio train`` (log line +
root span of the run's journal), the query server's ``GET /`` — so a run
on the wrong backend can never pass for a run on the chip: nothing here
is inferred from configuration, and a backend that fails to initialise
raises instead of degrading.
"""

from __future__ import annotations

import dataclasses
import logging
import re
import threading
from typing import Dict, List, Optional

log = logging.getLogger("pio.device")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"

# The bytes one compiled program's plan may take on a chip: three quarters
# of a 16 GB v5e, the rest for the allocator's slack and what the plans
# leave uncounted.  The ALS solve's blocks and the CCO step's shapes are
# derived from it.
PLAN_BYTES = 12 * 10**9

_lock = threading.Lock()
_compiles = {"programs": 0, "seconds": 0.0, "cacheHits": 0, "cacheWrites": 0}
_watching = False
# what the process dispatched, one entry a signature of a program: [jitted
# function, abstract arguments, abstract keyword arguments, its stage map
# once read], no array
_noted: Dict[tuple, list] = {}
_stages: set = set()        # the stage names the ops declared (``stage``)
_mapping = threading.local()   # set on the thread that runs stage_maps()


def device_info() -> Dict:
    """``{"platform", "kind", "count"}`` of the default backend.  Raises
    (RuntimeError) when the backend cannot initialise."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory_bytes() -> List[Optional[int]]:
    """Per-device peak since process start, ``peak_bytes_in_use +
    peak_bytes_reserved``; None for a device whose backend keeps no such
    statistic (the CPU backend).  The TPU runtime keeps two books: live
    buffers (arguments and results) and the scratch it set aside for the
    temporaries of the programs it ran; a program's plan
    (``memory_analysis()``) is their sum, and so is what
    ``benchmark/run.py`` reports as ``memory_peak_bytes`` (PERF.md
    section 4)."""
    import jax

    def peak(stats: Dict) -> Optional[int]:
        if "peak_bytes_in_use" not in stats:
            return None
        return int(stats["peak_bytes_in_use"]) + int(
            stats.get("peak_bytes_reserved", 0))

    return [peak(d.memory_stats() or {}) for d in jax.devices()]


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
        # the compile as a span of the job and stage that paid for it;
        # stage_maps() compiles for whoever reads a trace, not for a job
        collector = spans.active_collector()
        if collector is not None and not getattr(_mapping, "on", False):
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


# -- stages: the program's own names for what a device program does ---------


def stage(name: str):
    """A stage of a device program: ``jax.named_scope(name)``, and the
    declaration that ``name`` is a stage.  The ops open every stage with
    this, so what ``stage_maps`` recognises in a compiled module's
    ``op_name`` metadata is exactly what they declared."""
    import jax

    _stages.add(name)
    return jax.named_scope(name)


def _signature(a):
    """What of an argument decides the program, hashable and cheap: an
    array's shape, dtype, weak type and (where it is committed to one)
    sharding.  A Python float is a traced scalar at every dispatch site,
    so its value is no part of the program; anything else (int, bool, str,
    None, a Mesh) is a static argument and stands for itself."""
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return (a.shape, a.dtype, getattr(a, "weak_type", False),
                a.sharding if getattr(a, "committed", False) else None)
    return float if isinstance(a, float) else a


def _abstract(a):
    """An argument as ``lower`` takes it, with no array behind it."""
    import jax

    sig = _signature(a)
    if sig is float:
        return jax.ShapeDtypeStruct((), "float32", weak_type=True)
    if sig is a:
        return a
    shape, dtype, weak_type, sharding = sig
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding,
                                weak_type=weak_type)


def note_dispatch(fn, *args, **kwargs) -> None:
    """Record that the process is about to dispatch the jitted ``fn`` with
    these arguments: once a distinct signature, as abstract arguments (no
    array is kept alive); a later dispatch of the same signature is one
    dictionary look-up.  Nothing is lowered or compiled here:
    ``stage_maps`` does that for whoever reads a trace.  The table keeps
    ``fn`` for the life of the process: a module-level function, or one a
    cached builder returns, never a closure over arrays or made anew each
    call."""
    key = (fn, tuple(map(_signature, args)),
           tuple((k, _signature(v)) for k, v in sorted(kwargs.items())))
    if key not in _noted:
        with _lock:
            # the last entry: its stage map, once stage_maps() has read it
            _noted.setdefault(key, [
                fn, tuple(map(_abstract, args)),
                {k: _abstract(v) for k, v in kwargs.items()}, None])


def noted(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, the dispatch noted first: what the ops
    call inside a ``dispatch`` span in place of the jitted function."""
    note_dispatch(fn, *args, **kwargs)
    return fn(*args, **kwargs)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")
# the first word that opens a parenthesis after the result's type
_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"[(\s,]%([\w.\-]+)")
_NO_WORK = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"))


@dataclasses.dataclass
class _Instruction:
    """One line of an optimised module's text, as ``parse_stage_map``
    reads it."""
    name: str
    stage: Optional[str]       # by rule 1 at first, then by rules 2 and 3
    opcode: str
    calls: Optional[str]       # the computation a fusion calls
    operands: List[str]
    compilers_own: bool        # no ``op_name`` at all, or a ``copy``


def _stage_of(op_name: str, stages) -> Optional[str]:
    """The innermost component of an ``op_name`` path that is a stage."""
    for part in reversed(op_name.split("/")):
        if part in stages:
            return part
    return None


def _agreed(votes) -> Optional[str]:
    """The stage more than half of ``votes`` name, if any."""
    votes = [v for v in votes if v is not None]
    if not votes:
        return None
    best = max(set(votes), key=votes.count)
    return best if 2 * votes.count(best) > len(votes) else None


def parse_stage_map(text: str, stages) -> tuple:
    """``(module name, {instruction: stage or None})`` of one optimised
    HLO module as ``compiled.as_text()`` prints it.  Listed are the
    instructions a trace can show: those of a computation that no fusion
    calls and no reduction applies, less the ones that run nothing
    (``_NO_WORK``).  Three rules, in this order:

    1. an instruction's stage is the innermost component of its
       ``op_name`` that is in ``stages``;
    2. a fusion without one takes the stage that more than half of the
       staged instructions of the computation it calls agree on, the
       fusions nested in it voting by what they call in turn;
    3. an instruction with no ``op_name`` at all, or a ``copy`` whose
       ``op_name`` names no stage, is the compiler's own (a scatter's zero
       fill and the copy that re-lays its result, the sort of its indices,
       an asynchronous copy): it takes the stage that the instructions
       whose results it reads agree on, failing that the stage of those
       that read its result.

    Anything else (a loop's own counter, a ``while``) has none."""
    module, current = "", None
    computations: Dict[str, List[_Instruction]] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        found = _INSTRUCTION.match(line)
        if found is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                current = computations.setdefault(header.group(1), [])
            continue
        if current is None:
            continue
        op_name = _OP_NAME.search(line)
        opcode = _OPCODE.search(line)
        calls = _CALLS.search(line)
        current.append(_Instruction(
            name=found.group(1),
            stage=_stage_of(op_name.group(1), stages) if op_name else None,
            opcode=opcode.group(1) if opcode else "",
            calls=calls.group(1) if calls else None,
            operands=_OPERAND.findall(line[found.end():]),
            compilers_own=op_name is None
            or (opcode is not None and opcode.group(1) == "copy")))
    # what a trace never shows: the bodies of fusions and of reducers
    fused = {i.calls for rows in computations.values() for i in rows
             if i.opcode == "fusion" and i.calls} | set(_APPLIED.findall(text))

    def votes(computation: str) -> list:
        out = []
        for i in computations.get(computation, []):
            if i.stage is not None:
                out.append(i.stage)
            elif i.opcode == "fusion" and i.calls:
                out.extend(votes(i.calls))
        return out

    found = {}
    for name, rows in computations.items():
        if name in fused:
            continue
        by_name = {i.name: i for i in rows}
        for i in rows:                                     # rule 2
            if i.stage is None and i.opcode == "fusion" and i.calls:
                i.stage = _agreed(votes(i.calls))
        for i in rows:                  # rule 3, by what it reads: in order
            if i.stage is None and i.compilers_own:
                i.stage = _agreed([by_name[o].stage for o in i.operands
                                   if o in by_name])
        readers: Dict[str, list] = {}
        for i in rows:
            for o in i.operands:
                readers.setdefault(o, []).append(i)
        for i in reversed(rows):        # rule 3, by who reads it: backwards
            if i.stage is None and i.compilers_own:
                i.stage = _agreed([r.stage for r in readers.get(i.name, [])])
        found.update({i.name: i.stage for i in rows
                      if i.opcode not in _NO_WORK})
    return module, found


# An option that changes nothing in what XLA builds (its default), named so
# that ``compile`` passes by the executable JAX keeps in memory for the
# lowering: that one may have come out of the persistent cache.
_OWN_COMPILE = {"xla_embed_ir_in_executable": False}


def _compiled_text(fn, args, kwargs) -> str:
    """The optimised module of one noted signature, compiled for
    ``stage_maps`` (see there).  Where an executable that came out of the
    compile cache gives no text, that one call is compiled again past the
    cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    lowered = fn.lower(*args, **kwargs)
    text = lowered.compile(compiler_options=_OWN_COMPILE).as_text() or ""
    if "HloModule" in text:
        return text
    log.warning("no text from the cached executable of %s: compiling it "
                "past the cache", getattr(fn, "__name__", fn))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile(compiler_options=_OWN_COMPILE).as_text() or ""
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def stage_maps() -> Dict[str, Dict]:
    """``{module name as a trace prints it (jit__cco_resident_all_tiles):
    {"stages": {instruction name: stage}, "unstaged": [instruction names],
    "instructions": n}}`` of every program the process noted
    (``note_dispatch``): each signature lowered from its abstract
    arguments and compiled, and the optimised module's text read
    (``parse_stage_map``); a signature is compiled once a process.  Two
    signatures of one program (the two event types of a UR job) share a
    module name and are merged; an instruction name they put in different
    stages has none.

    Paid by whoever reads a trace, after the fact: no job and no server
    calls it.  The compiles count in ``compile_stats()`` and open no
    ``compile`` span.  JAX's compile-cache key leaves a module's metadata
    out, so the executable a job ran may have come out of the cache with
    the scopes of whoever compiled it first (its instructions are named
    the same: metadata moves nothing).  Here each signature is compiled
    under a key that holds the metadata, so that the map is of this
    source's stages: a read of the cache where an earlier ``stage_maps()``
    of the same source wrote, a compile otherwise (seconds for a small
    program, 20 for the largest of PERF.md's cells)."""
    import jax

    with _lock:
        noted, stages = list(_noted.values()), frozenset(_stages)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    _mapping.on = True
    jax.config.update(flag, True)
    try:
        for entry in noted:
            if entry[3] is None:
                try:
                    entry[3] = parse_stage_map(
                        _compiled_text(*entry[:3]), stages)
                except Exception:    # one program's map, not the reader
                    log.warning("no stage map of %s",
                                getattr(entry[0], "__name__", entry[0]),
                                exc_info=True)
    finally:
        jax.config.update(flag, before)
        _mapping.on = False
    merged: Dict[str, Dict] = {}      # module -> {instruction: stage or None}
    for module, found in (entry[3] for entry in noted if entry[3]):
        seen = merged.setdefault(module, {})
        for instr, stage_ in found.items():
            if seen.setdefault(instr, stage_) != stage_:
                seen[instr] = None
    return {module: {"stages": {k: v for k, v in seen.items() if v},
                     "unstaged": sorted(k for k, v in seen.items() if not v),
                     "instructions": len(seen)}
            for module, seen in merged.items()}
