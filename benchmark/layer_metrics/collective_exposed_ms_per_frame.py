"""device program: the part of the collectives' time during which no
compute op ran on that device / the traced job's frames."""

from tvtbench import evidence


def read(ev):
    prof = ev["profile"]
    if not prof or not prof["collectives"]["events"]:
        return None
    return evidence.profile_per_frame(
        ev, prof["collectives"]["exposed_seconds"])
