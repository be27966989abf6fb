"""device program: self time of the ops filed under `tvt.p_intra` (the
intra / inter decision of every P macroblock and the Intra16x16
residual of the ones that go intra) in the traced job's profile / that
job's frames, averaged over the devices. Not measured where the
profile holds no op of that stage: a program from before the setting,
or one that runs it off."""

from tvtbench import scope_reduce

STAGE = "tvt.p_intra"


def read(ev):
    got = scope_reduce.scopes_of(ev)
    if got is None or STAGE not in got["scopes"]:
        return None
    return scope_reduce.stage_ms_per_frame(ev, STAGE)
