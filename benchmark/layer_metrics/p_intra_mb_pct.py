"""device program: the share of the P pictures' macroblocks coded
Intra16x16 (H.264 7.3.5, mb_type 5..30 in a P slice): growth of the
counter `p_mbs_intra` / growth of `p_mbs_coded` x 100 over the window.
What the intra / inter decision of `p_intra` chose on the cell's
content. Not measured where the program has no such counters (a
program from before the setting, or one that runs it off, counts no
macroblock's kind) or packed no P macroblock in the window."""

from tvtbench import evidence


def read(ev):
    if "p_mbs_intra" not in ev["snapshot"]["after"]:
        return None
    coded = evidence.stage_delta(ev, "p_mbs_coded")
    if coded <= 0:
        return None
    return 100.0 * evidence.stage_delta(ev, "p_mbs_intra") / coded
