"""ingest: `stage_ms.decode` growth over the window / frames (host
thread time pulling frames from the source; raw .y4m here)."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "decode")
