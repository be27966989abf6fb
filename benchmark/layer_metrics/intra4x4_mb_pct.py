"""device program: the share of the IDR pictures' macroblocks coded
Intra4x4 (H.264 7.3.5, mb_type I_NxN): growth of the counter
`i_mbs_4x4` / growth of `i_mbs_coded` x 100 over the window. What the
Intra16x16 / Intra4x4 decision of `intra4x4` chose on the cell's
content. Not measured where the program has no such counters (a
program from before the setting, or one that runs it off, counts no
IDR macroblock's kind) or packed no IDR in the window."""

from tvtbench import evidence


def read(ev):
    if "i_mbs_4x4" not in ev["snapshot"]["after"]:
        return None
    coded = evidence.stage_delta(ev, "i_mbs_coded")
    if coded <= 0:
        return None
    return 100.0 * evidence.stage_delta(ev, "i_mbs_4x4") / coded
