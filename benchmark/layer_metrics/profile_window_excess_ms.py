"""device: what of the profile's own window — the one `device_idle_pct`
divides by — is the profiler's and not the job's: from the profile's
start to the start of the executor's annotation `tvt:encode_stage`, plus
from its end to the profile's stop, on the profiler's clock
(tvtbench/host_reduce.py). Not measured where the traced job's profile
holds no such annotation."""

from tvtbench import host_reduce


def read(ev):
    return host_reduce.ms(ev, "excess_ps")
