"""device program: `stage_ms.dense_retry` growth over the window / frames
(host thread time spent waiting for a GOP's dense re-encode and then
for its int16 levels to be on the host: `dense_reencode + dense_fetch`
where the program splits it)."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "dense_retry")
