"""staging: `stage_ms.stage` growth over the window / frames (stack +
H2D upload, on the staging thread)."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "stage")
