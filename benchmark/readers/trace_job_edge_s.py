"""Host time at one edge of a job, mean over the window's jobs, from the
trace: `lead_in` is from the benchmark's annotation at the start of the call
to the first device operation inside it, `tail` from the last device
operation to the end of the call.  args: {"edge": "lead_in" | "tail"}."""


def read(args: dict, facts: dict):
    jobs = [j for j in facts["reduced"]["jobs"]
            if j["first_op_s"] is not None]
    if not jobs:
        return None
    if args["edge"] == "lead_in":
        spans = [j["first_op_s"] - j["start_s"] for j in jobs]
    else:
        spans = [j["end_s"] - j["last_op_s"] for j in jobs]
    return sum(spans) / len(spans)
