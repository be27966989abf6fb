"""device: 1 - busy / the extent of the annotation `tvt:encode_stage` in
the traced job's profile (first device plane): the device's idle share
of the encode stage alone, without the profiler's start and stop that
`device_idle_pct`'s window holds. Not measured where the profile holds
no such annotation."""

from tvtbench import host_reduce


def read(ev):
    got = host_reduce.host_of(ev)
    if got is None:
        return None
    lo, hi = got["stage_ps"]
    return 100.0 * (1.0 - got["busy_ps"] / (hi - lo))
