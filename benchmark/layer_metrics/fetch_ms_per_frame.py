"""D2H: `stage_ms.fetch` growth over the window / frames (host thread
time in the bulk device-to-host fetch; threads overlap, so not a
critical path)."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "fetch")
