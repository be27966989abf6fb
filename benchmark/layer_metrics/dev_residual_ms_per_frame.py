"""device program: self time of the ops filed under `tvt.residual` (P-frame
transform, quant, recon) and `tvt.deblock` (the in-loop filter, off in
the library configurations) in the traced job's profile / that job's
frames, averaged over the devices."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.residual", "tvt.deblock")
