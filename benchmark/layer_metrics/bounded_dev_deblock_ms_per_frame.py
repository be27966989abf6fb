"""device program: `dev_deblock_ms_per_frame` (self time of the ops
under `tvt.deblock` in the traced job's profile / that job's frames)
where the filter runs inside the `while` of a cut-aligned GOP's bounded
P-frame loop: the accepted reader, called as it is. Its row in
`hd-serving-rd` is the same filter inside the `scan`. Not measured
where that reader finds nothing, or where no wave of the window had a
bound (the counter `pad_frames_skipped` did not move)."""

from tvtbench import evidence
from tvtbench.spec import load_module


def bounded(ev):
    return evidence.stage_delta(ev, "pad_frames_skipped") > 0


def read(ev):
    if not bounded(ev):
        return None
    return load_module("layer_metrics", "dev_deblock_ms_per_frame").read(ev)
