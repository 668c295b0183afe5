"""Host time, or a counter, of named spans of the program's own journals,
mean per job.  The program keeps the span lists of the last journals it
completed in memory (`predictionio_tpu.obs.spans.recent_runs`); taken are
the last `facts["jobs"]` of them whose root is a `train` span with no
`error`: the window's jobs, whose journal files are gone by now.
args: {"spans": [names], "take": "duration" | "self" | "attr:<name>",
"scale": number}.  `self` is a span's duration less the part of it that its
children cover; `attr:<name>` sums that attribute.  A program that keeps no
runs, no run, or no such span in any of them: nothing returned, never 0."""


def _take(span: dict, run: list, take: str):
    if take == "duration":
        return span["duration_s"]
    if take == "self":
        from trace_reduce import union_seconds   # run.py put benchmark/ on the path

        lo, hi = span["start"], span["start"] + span["duration_s"]
        kids = [(c["start"], c["start"] + c["duration_s"])
                for c in run if c.get("parent") == span["id"]]
        return span["duration_s"] - union_seconds(kids, lo, hi)
    return (span.get("attrs") or {}).get(take.partition(":")[2])


def read(args: dict, facts: dict):
    try:
        from predictionio_tpu.obs.spans import recent_runs
    except ImportError:          # a program from before it kept its runs
        return None
    jobs = int(facts.get("jobs") or 0)
    runs = [run for run in recent_runs()
            if any(s["name"] == "train" and s.get("parent") is None
                   and not s.get("error") for s in run)][-jobs:]
    if not jobs or not runs:
        return None
    got = [_take(s, run, args.get("take", "duration"))
           for run in runs for s in run if s["name"] in args["spans"]]
    got = [v for v in got if v is not None]
    if not got:
        return None
    return float(args.get("scale", 1)) * sum(got) / len(runs)
