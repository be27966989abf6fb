"""ME kernel: device time of the motion-search kernel's events in the
traced job's profile / device busy time. Not measured where the events
cannot be told from other custom calls."""


def read(ev):
    prof = ev["profile"]
    if not prof or not prof["me"] or not prof["busy_s"]:
        return None
    return 100.0 * prof["me"]["seconds"] / prof["busy_s"]
