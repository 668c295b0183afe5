"""From a profiler trace (`*.xplane.pb`) to numbers, with `jax.profiler.ProfileData` alone.

What a TPU trace holds (looked at by hand before this was written, see
PERF.md): one plane per chip, `/device:TPU:<n>`, whose line `XLA Modules`
has one event per executed program (`jit__cco_resident_all_tiles(<id>)`) and
whose line `XLA Ops` has the operations inside them, a `while` with its
body's operations nested under it; and host planes (`/host:CPU`) with one
line per thread, on which the benchmark's own `TraceAnnotation`s lie.  A CPU
trace (the rehearsal) has no device plane: XLA's operations are events with
an `hlo_module` statistic on the host threads that ran them, and those are
taken as the device's.

    reduce(trace_dir, annotation) -> {
      "window_s":   first annotation's start .. last annotation's end,
      "busy_s":     union of device-operation intervals inside it, mean over chips,
      "programs":   {name: {"count", "seconds"}}      (XLA Modules, id stripped)
      "ops":        {short name: {"count", "seconds", "detail"}}   (self time;
                    `detail` is the name as the trace prints it, cut to 300)
      "jobs":       [{"start_s", "end_s", "first_op_s", "last_op_s"}]  one per annotation
      "breakdown":  {"device_ops": [[name, s]], "idle_gaps": [[what, s]]}  10 each;
                    an idle entry is the window's total of one kind of gap
    }

Times are seconds from the window's start.  `python3 benchmark/trace_reduce.py
<dir> [annotation]` prints the same, and `--dump` the planes and lines as
they are, for the look by hand.
"""

from __future__ import annotations

import glob
import json
import re
import sys
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(trace_dir))


def _events(line) -> list:
    """[(start_ns, end_ns, name, stats)]"""
    out = []
    for e in line.events:
        out.append((float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
                    e.name, e))
    return out


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle stretches [(start, end)] of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events) -> list:
    """[(name, self_ns)] of properly nested events of ONE line: an event's
    own time is its duration less its children's."""
    out, stack = [], []       # stack of [end, index into out]
    for s, e, name, _ in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([name, e - s])
        stack.append([e, len(out) - 1])
    return [(n, max(t, 0.0)) for n, t in out]


_KIND = re.compile(r'custom_call_target="(\w+)"|kind=(\w+)')


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO line, `%fusion.31 =
    (f32[100000,50]...) fusion(...), kind=kCustom, calls=...`: keep the
    operation's own name, and what kind of thing it is.  Operands are
    dropped, so a pattern never matches an operation through its inputs."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    kind = _KIND.search(rest)
    return head.lstrip("%") + (
        f" ({kind.group(1) or kind.group(2)})" if kind else "")


def _device_lines(profile) -> list:
    """[(ops_events, module_events)] one pair per chip."""
    chips = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue          # a plane of the chip's other cores, no XLA ops
        chips.append((_events(lines[OPS_LINE]),
                      _events(lines[MODULES_LINE])
                      if MODULES_LINE in lines else []))
    if chips:
        return chips
    # no device plane: a CPU trace; XLA's operations lie on host threads
    ops, modules = [], {}
    for plane in profile.planes:
        for ln in plane.lines:
            for s, e, name, ev in _events(ln):
                stats = {k: v for k, v in ev.stats}
                if "hlo_module" in stats and e > s:
                    ops.append((s, e, name, ev))
                    # a program = the span of one run's operations
                    key = (stats["hlo_module"], stats.get("run_id"))
                    lo, hi = modules.get(key, (s, e))
                    modules[key] = (min(lo, s), max(hi, e))
    return [(ops, [(lo, hi, key[0], None)
                   for key, (lo, hi) in modules.items()])]


def _annotations(profile, annotation: str) -> list:
    found = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            for s, e, name, _ in _events(ln):
                if name == annotation or name.startswith(annotation + "#"):
                    found.append((s, e))
    return sorted(found)


def reduce(trace_dir, annotation: str) -> dict:
    profile = load(trace_dir)
    chips = _device_lines(profile)
    jobs = _annotations(profile, annotation)
    if not jobs:
        raise ValueError(f"the trace holds no {annotation!r} annotation")
    if not chips or not any(ops for ops, _ in chips):
        raise ValueError("the trace holds no device operation")
    lo, hi = jobs[0][0], jobs[-1][1]
    sec = lambda ns: (ns - lo) / 1e9   # noqa: E731

    busy = [union_seconds([(s, e) for s, e, _, _ in ops], lo, hi) / 1e9
            for ops, _ in chips]
    programs, ops_by_name = {}, {}
    for ops, modules in chips:
        for s, e, name, _ in modules:
            if e <= lo or s >= hi:
                continue
            p = programs.setdefault(_ID.sub("", name),
                                    {"count": 0, "seconds": 0.0})
            p["count"] += 1
            p["seconds"] += (min(e, hi) - max(s, lo)) / 1e9
        inside = [t for t in ops if t[1] > lo and t[0] < hi]
        for name, own in self_times(inside):
            o = ops_by_name.setdefault(
                short_name(name),
                {"count": 0, "seconds": 0.0, "detail": name[:300]})
            o["count"] += 1
            o["seconds"] += own / 1e9
    n = len(chips)
    for table in (programs, ops_by_name):
        for v in table.values():
            v["seconds"] /= n

    # per job, on the first chip: when its first and last operations ran
    first_ops = sorted((s, e) for s, e, _, _ in chips[0][0])
    per_job, idle = [], {}      # idle: seconds summed by what the host did
    for k, (js, je) in enumerate(jobs):
        mine = [(s, e) for s, e in first_ops if e > js and s < je]
        job = {"start_s": sec(js), "end_s": sec(je),
               "first_op_s": sec(mine[0][0]) if mine else None,
               "last_op_s": sec(max(e for _, e in mine)) if mine else None}
        per_job.append(job)
        for gs, ge in gaps(mine, js, je):
            where = ("lead_in" if mine and ge <= mine[0][0] + 1 else
                     "tail" if mine and gs >= max(e for _, e in mine) - 1
                     else "between_programs" if mine else "no_device_work")
            idle[f"{annotation}:{where}"] = idle.get(
                f"{annotation}:{where}", 0.0) + (ge - gs) / 1e9
        if k + 1 < len(jobs) and jobs[k + 1][0] > je:
            idle[f"between:{annotation}"] = idle.get(
                f"between:{annotation}", 0.0) + (jobs[k + 1][0] - je) / 1e9
    top_ops = sorted(((k, v["seconds"]) for k, v in ops_by_name.items()),
                     key=lambda t: -t[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "chips": n,
        "programs": programs,
        "ops": ops_by_name,
        "jobs": per_job,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in
                          sorted(idle.items(), key=lambda t: -t[1])[:10]]},
    }


def dump(trace_dir, limit: int = 12) -> None:
    profile = load(trace_dir)
    for plane in profile.planes:
        print("PLANE", plane.name)
        for ln in plane.lines:
            evs = _events(ln)
            if not evs:
                continue
            by = {}
            for s, e, name, _ in evs:
                t = by.setdefault(name, [0, 0.0])
                t[0] += 1
                t[1] += (e - s) / 1e9
            print(f"  LINE {ln.name!r}: {len(evs)} events, "
                  f"{min(s for s, *_ in evs) / 1e9:.3f}s .. "
                  f"{max(e for _, e, *_ in evs) / 1e9:.3f}s")
            for name, (c, secs) in sorted(
                    by.items(), key=lambda kv: -kv[1][1])[:limit]:
                print(f"    {secs:10.4f}s x{c:<6} {name[:200]!r}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    else:
        out = reduce(sys.argv[1], sys.argv[2] if len(sys.argv) > 2
                     else "bench:job")
        out["ops"] = dict(sorted(out["ops"].items(),
                                 key=lambda kv: -kv[1]["seconds"])[:25])
        print(json.dumps(out, indent=1))
