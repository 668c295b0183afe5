"""Device time of named programs, per job, from the trace's `XLA Modules`
line.  args: {"programs": [substrings of the program names as the trace
prints them]}.  Nothing matched: nothing returned."""


def read(args: dict, facts: dict):
    hit = [v["seconds"] for name, v in facts["reduced"]["programs"].items()
           if any(p in name for p in args["programs"])]
    if not hit or not facts["jobs"]:
        return None
    return 1e3 * sum(hit) / facts["jobs"]
