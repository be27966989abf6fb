"""device program: self time of the ops filed under `tvt.intra` (the IDR
frame: row scans, intra transform and recon) in the traced job's profile
/ that job's frames, averaged over the devices."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.intra")
