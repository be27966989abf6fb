"""device program: self time of the ops filed under `tvt.deblock` (the
in-loop filter of H.264 8.7) in the traced job's profile / that job's
frames, averaged over the devices. Part of `dev_residual_ms_per_frame`,
which keeps both stages."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.deblock")
