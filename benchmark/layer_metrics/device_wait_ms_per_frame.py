"""device program: `stage_ms.device_wait` growth over the window /
frames. Host time blocked on the device, not device time."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "device_wait")
