"""executor: device-idle time inside `tvt:encode_stage` that no `tvt:*`
annotation of any host thread covers / the traced job's frames: what is
left of the idle gaps' "no host span" once the host's spans are on the
profiler's clock (tvtbench/host_reduce.py). Not measured where the
profile holds no `tvt:encode_stage`."""

from tvtbench import evidence, host_reduce


def read(ev):
    got = host_reduce.host_of(ev)
    if got is None:
        return None
    return evidence.profile_per_frame(
        ev, got["idle_by"][host_reduce.UNNAMED] * 1e-12)
