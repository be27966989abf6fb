"""device program: self time of the ops filed under `tvt.me_prep` (search
centres, padding, centre stacks: all that feeds the motion-search kernel)
and `tvt.me_median` (the frame's median MV) in the traced job's profile
/ that job's frames, averaged over the devices."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.stage_ms_per_frame(ev, "tvt.me_prep", "tvt.me_median")
