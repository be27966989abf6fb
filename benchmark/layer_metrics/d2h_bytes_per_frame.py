"""D2H: `d2h_bytes` counter growth over the window / frames."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "d2h_bytes")
