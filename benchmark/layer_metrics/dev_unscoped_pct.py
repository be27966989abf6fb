"""device program: self time of the ops that no stage of the encode names
(no `tvt.*` component in their path) / device busy time, in the traced
job's profile. Not measured where no op carries a stage at all: the
executables were then built without the names."""

from tvtbench import scope_reduce


def read(ev):
    return scope_reduce.unscoped_pct(ev)
