"""staging: `stage_copy_bytes` counter growth over the window / frames:
host bytes the staging thread copies between the decoder's planes and
the arrays a GOP wave uploads. Each frame written once reads the
planes' share of `h2d_bytes_per_frame`. Not measured where the program
has no such counter."""

from tvtbench import evidence


def read(ev):
    if "stage_copy_bytes" not in ev["snapshot"]["after"]:
        return None
    return evidence.per_frame(ev, "stage_copy_bytes")
