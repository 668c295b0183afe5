"""A count the program writes as an attribute on its own spans, summed over
the spans that match and averaged over the window's jobs.  args: {"span":
name, "where": {attribute: value, ...}, "attr": name}: of the spans called
`span` whose attributes equal `where`, the sum of `attr`.  The jobs are
taken as `journal_span` takes them: the last `facts["jobs"]` journals the
program kept (`predictionio_tpu.obs.spans.recent_runs`) whose root is a
`train` span with no `error`.  A program that keeps no runs, or writes no
such span or attribute (this reader's parent commit): nothing returned,
never 0."""


def read(args: dict, facts: dict):
    try:
        from predictionio_tpu.obs.spans import recent_runs
    except ImportError:
        return None
    jobs = int(facts.get("jobs") or 0)
    runs = [run for run in recent_runs()
            if any(s["name"] == "train" and s.get("parent") is None
                   and not s.get("error") for s in run)][-jobs:]
    if not jobs or not runs:
        return None
    where = args.get("where", {})
    got = [(s.get("attrs") or {}).get(args["attr"])
           for run in runs for s in run if s["name"] == args["span"]
           and all((s.get("attrs") or {}).get(k) == v
                   for k, v in where.items())]
    got = [v for v in got if v is not None]
    if not got:
        return None
    return float(sum(got)) / len(runs)
