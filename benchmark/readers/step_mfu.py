"""The whole step's share of the chip's peak: the least time the chip could
take for the window's jobs (`run.least_seconds` of the configuration's
`roofline` module, per job) over the window's time on the host clock.  It bounds every kernel's roofline: a
change that takes a kernel off the path silences that kernel's metric and
still has to move this one."""


def read(args: dict, facts: dict):
    if not facts["least_job_s"] or not facts["jobs"]:
        return None
    return (100.0 * facts["least_job_s"](facts["roofline"]) * facts["jobs"]
            / facts["window_s"])
