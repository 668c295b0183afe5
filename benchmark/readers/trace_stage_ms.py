"""Device time of named stages of the program, per job: the trace's `XLA
Ops` self time (mean over the chips, `trace_reduce.reduce` under `ops`,
keyed `name (kind)`), each operation looked up by its name, the kind
stripped, in the program's own stage maps
(`predictionio_tpu.utils.device.stage_maps()`: what the ops declared with
`utils.device.stage`, read out of the compiled modules) of the programs
the window ran (`reduced["programs"]`).  args: {"stages": [stage names]}.
`reduced["ops"]` merges equal names of different programs: a name that two
of the window's programs put in different stages, or one of them in none,
is counted as unstaged, never guessed.  Nothing matched, or a program that
keeps no stage map (this reader's parent commit): nothing returned, never
0."""


def seconds_by_stage(facts: dict):
    """{stage, or None for the unstaged: seconds in the window}, or None
    where the program keeps no stage map of any program the window ran."""
    try:
        from predictionio_tpu.utils.device import stage_maps
    except ImportError:          # a program from before it kept the maps
        return None
    maps = stage_maps()
    mine = [maps[p] for p in facts["reduced"]["programs"] if p in maps]
    if not mine:
        return None
    out = {}
    for name, v in facts["reduced"]["ops"].items():
        name = name.partition(" (")[0]
        said = {m["stages"].get(name) for m in mine
                if name in m["stages"] or name in m.get("unstaged", ())}
        stage = said.pop() if len(said) == 1 else None
        out[stage] = out.get(stage, 0.0) + v["seconds"]
    return out


def read(args: dict, facts: dict):
    by_stage = seconds_by_stage(facts)
    if by_stage is None or not facts["jobs"]:
        return None
    took = sum(by_stage.get(s, 0.0) for s in args["stages"])
    if took <= 0:
        return None
    return 1e3 * took / facts["jobs"]
