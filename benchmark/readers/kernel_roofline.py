"""A kernel's share of its roofline: the least time the chip could take for
the kernel's work in one job (`run.least_seconds`: the larger of operations
/ peak and bytes / bandwidth, from `roofline/<args.roofline>.py` and
`peaks.json`)
over the device time its operations took per job (self time on the trace's
`XLA Ops` line).  args: {"roofline": module, "ops": [substrings of the
operation's own name as the trace prints it]}.  No operation
matched (the kernel is off the path, or renamed): nothing returned, never
0."""


def read(args: dict, facts: dict):
    if not facts["least_job_s"] or not facts["jobs"]:
        return None
    took = sum(v["seconds"] for name, v in facts["reduced"]["ops"].items()
               if any(p in name for p in args["ops"]))
    if took <= 0:
        return None
    return 100.0 * facts["least_job_s"](args["roofline"]) * facts["jobs"] / took
