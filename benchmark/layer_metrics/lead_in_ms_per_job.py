"""executor: from the start of the annotation `tvt:encode_stage` to the
first device op of the traced job, on the profiler's clock: encoder
construction, the plan, the first wave's decode, staging and dispatch.
Not measured where the profile holds no such annotation."""

from tvtbench import host_reduce


def read(ev):
    return host_reduce.ms(ev, "lead_in_ps")
