"""ME kernel: the share of the P macroblocks' motion vectors with an
odd quarter-sample component: growth of the counter `mvs_quarter` /
growth of `mvs_coded` x 100 over the window. 0 where the encoder
searches at half-sample precision (its vectors are even in quarter
units by construction); what the quarter classes of the kernel are
there for where it does not. Not measured where the program has no
such counter or packed no P macroblock in the window."""

from tvtbench import evidence


def read(ev):
    if "mvs_quarter" not in ev["snapshot"]["after"]:
        return None
    coded = evidence.stage_delta(ev, "mvs_coded")
    if coded <= 0:
        return None
    return 100.0 * evidence.stage_delta(ev, "mvs_quarter") / coded
