"""device program: union of the device-op intervals in the traced job's
profile / that job's frames, averaged over the devices."""

from tvtbench import evidence


def read(ev):
    prof = ev["profile"]
    return evidence.profile_per_frame(ev, prof["busy_s"] if prof else None)
