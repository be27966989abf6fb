"""Source frames of jobs that ended done / seconds from the first submit
of the window to the last completion the client saw. The denominator
ends on a completion, so no part of a job is counted or dropped. Per
host (PERF.md divides by chips where it speaks of fps per chip)."""

from tvtbench import evidence


def read(ev):
    frames = evidence.frames_done(ev)
    return frames / evidence.window_s(ev) if frames else None
