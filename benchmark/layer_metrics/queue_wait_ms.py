"""admission: median `started_at - created_at` of the window's job records
(one host clock, the daemon's)."""

from tvtbench import evidence


def read(ev):
    waits = [(j["record"]["started_at"] - j["record"]["created_at"]) * 1e3
             for j in evidence.done_jobs(ev)]
    return evidence.median(waits)
