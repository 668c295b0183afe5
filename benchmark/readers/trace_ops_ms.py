"""Device time of named operations, per job, from the trace's `XLA Ops`
line: self time (an operation's duration less its children's), mean over
the chips, as `trace_reduce.reduce` gives it under `ops`.  args: {"ops":
[substrings of the operation's own name as the trace prints it], "opcodes":
[HLO opcodes: what stands before the operands in the operation's line, as
in `%x.1 = f32[8] reduce-scatter(...)`; an asynchronous pair
(`all-reduce-start`, `all-reduce-done`) matches its opcode too], "calls":
[names of computations a fusion calls, as in `fusion(...), kind=kCustom,
calls=%all-reduce-scatter.clone`: the form the TPU compiler gives a
reduce-scatter whose shards it pads]}.  An operation is taken if its name,
its opcode or what it calls matches.  Operands are not looked at, so an
operation never matches through its inputs.  Nothing matched (a program without such an operation: this
reader's parent commit, or one chip): nothing returned, never 0."""

import re

# the first word that opens a parenthesis after the result's type: a type's
# own parentheses follow `T`, `S` or a bracket, never a space
_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9\-]*)\(")


def opcode(detail: str) -> str:
    """`reduce-scatter` of `%rs.3 = f32[8,4]{1,0:T(8,128)} reduce-scatter(
    f32[32,4]{1,0} %p), ...`; an event the trace names otherwise (a CPU
    trace's thunks) gives ''."""
    found = _OPCODE.search(detail)
    return found.group(1) if found else ""


def read(args: dict, facts: dict):
    names, opcodes = args.get("ops", []), args.get("opcodes", [])
    calls = ["calls=%" + c for c in args.get("calls", [])]
    took = 0.0
    for name, v in facts["reduced"]["ops"].items():
        detail = v.get("detail", "")
        code = opcode(detail)
        if any(p in name for p in names) or any(
                code == c or code in (c + "-start", c + "-done")
                for c in opcodes) or any(c in detail for c in calls):
            took += v["seconds"]
    if took <= 0 or not facts["jobs"]:
        return None
    return 1e3 * took / facts["jobs"]
