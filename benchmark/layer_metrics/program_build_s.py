"""compile: the seconds the daemon had spent, when the window opened,
inside the first call of each GOP / step executable (trace, lower,
compile or load from the compile cache, until the call returns with
the program enqueued): the clock `program_build` of `stage_ms` at the
window's FIRST snapshot. The part of `setup_s` that is one executable's
to give; the warm-up job passes through it in every cell. Not
measured, never 0, where the program has no such clock."""


def read(ev):
    built_ms = ev["snapshot"]["before"].get("program_build")
    if built_ms is None:
        return None
    return float(built_ms) / 1e3
