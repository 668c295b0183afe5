"""The share of the device's time that no stage of the program claims:
100 x seconds of the operations with no stage, or with an ambiguous one
(`trace_stage_ms.seconds_by_stage`: a name two of the window's programs
stage differently, an operation of a program that keeps no map), over the
seconds of all operations in `reduced["ops"]`.  What is left are a loop's
own counters, the `while`s' self time (the gaps between a body's
operations) and what the compiler made that no neighbour names.  A program
that keeps no stage map (this reader's parent commit): nothing returned."""

from readers.trace_stage_ms import seconds_by_stage   # run.py put benchmark/ on the path


def read(args: dict, facts: dict):
    by_stage = seconds_by_stage(facts)
    if by_stage is None:
        return None
    total = sum(by_stage.values())
    if total <= 0:
        return None
    return 100.0 * by_stage.get(None, 0.0) / total
