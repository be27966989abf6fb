"""device program: union of the collective-permute / all-reduce events in
the traced job's profile / that job's frames, averaged over the devices."""

from tvtbench import evidence


def read(ev):
    prof = ev["profile"]
    if not prof or not prof["collectives"]["events"]:
        return None
    return evidence.profile_per_frame(ev, prof["collectives"]["seconds"])
