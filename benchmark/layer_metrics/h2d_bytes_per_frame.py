"""staging: `h2d_bytes` counter growth over the window / frames."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "h2d_bytes")
