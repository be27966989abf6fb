"""staging: `stage_ms.upload` growth over the window / frames: the H2D
upload of a GOP wave's host arrays alone (`jnp.asarray` of the three
planes and the wave's small arrays, on the staging thread), a part of
`stage_ms_per_frame`. Not measured where the program has no such
clock."""

from tvtbench import evidence


def read(ev):
    if "upload" not in ev["snapshot"]["after"]:
        return None
    return evidence.per_frame(ev, "upload")
