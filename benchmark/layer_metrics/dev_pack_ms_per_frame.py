"""device program: self time of the ops filed under `tvt.pack` (the sparse
packs of the level vector) and `tvt.compact` (their fold into one
payload) in the traced job's profile / that job's frames, averaged over
the devices."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.pack", "tvt.compact")
