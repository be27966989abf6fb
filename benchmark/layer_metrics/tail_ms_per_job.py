"""executor: from the traced job's last device op to the end of the
annotation `tvt:encode_stage`, on the profiler's clock: the last wave's
fetch, unpack and pack. Not measured where the profile holds no such
annotation."""

from tvtbench import host_reduce


def read(ev):
    return host_reduce.ms(ev, "tail_ps")
