"""device program: self time of the ops filed under `tvt.intra4x4` (an
IDR picture's luma coded again, each macroblock Intra16x16 or
Intra4x4: the search over the nine modes of every block, the
sixteen-block residual and reconstruction, the decision) in the traced
job's profile / that job's frames, averaged over the devices; an IDR
costs gop_frames times it. Not measured where the profile holds no op
of that stage: a program from before the setting, or one that runs it
off."""

from tvtbench import scope_reduce

STAGE = "tvt.intra4x4"


def read(ev):
    got = scope_reduce.scopes_of(ev)
    if got is None or STAGE not in got["scopes"]:
        return None
    return scope_reduce.stage_ms_per_frame(ev, STAGE)
