"""device program: self time of the ops filed under `tvt.halo` (the band
halo exchange and recon fix-up of the split-frame steps) in the traced
job's profile / that job's frames, averaged over the devices."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.halo")
